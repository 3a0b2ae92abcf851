"""The plain reference as a float32 ``jax.numpy`` program on the chip.

``grayscott.step`` with ``xp = jax.numpy``, looped by ``lax.fori_loop``.
For a sharded cell the fields are laid over the reference's own mesh
of the same devices; XLA partitions the pad-and-slice stencil and the
position-keyed noise by itself. Nothing here imports the program.
"""

from __future__ import annotations

from functools import partial

import numpy as np

from . import grayscott as gs


class Reference:
    """Advances the reference from step 0 on ``devices``, laid out as
    ``mesh_shape`` (x, y, z) blocks."""

    def __init__(self, L, params, seed, devices, mesh_shape=(1, 1, 1)):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        self.L = L
        self.params = {k: float(v) for k, v in params.items()}
        self.key = gs.key_words(seed)
        self.dtype = jnp.float32  # the configurations' stated precision
        n = int(np.prod(mesh_shape))
        mesh = Mesh(np.array(devices[:n]).reshape(mesh_shape),
                    ("x", "y", "z"))
        self.sharding = NamedSharding(mesh, P("x", "y", "z"))
        self._advance = jax.jit(
            self._advance_impl, static_argnums=(3,),
            in_shardings=(self.sharding, self.sharding, None),
            out_shardings=(self.sharding, self.sharding),
            donate_argnums=(0, 1),
        )
        self._init = jax.jit(partial(gs.init, jnp, L, self.dtype),
                             out_shardings=(self.sharding, self.sharding))
        self.fields = None
        self.step = 0

    def _advance_impl(self, u, v, step0, nsteps):
        import jax.numpy as jnp
        from jax import lax

        def body(i, carry):
            return gs.step(jnp, *carry, step0 + i, self.params, self.key,
                           L=self.L)

        return lax.fori_loop(0, nsteps, body, (u, v))

    def start(self):
        self.fields = self._init()
        self.step = 0

    def advance(self, nsteps):
        import jax.numpy as jnp

        self.fields = self._advance(*self.fields, jnp.int32(self.step),
                                    int(nsteps))
        self.step += nsteps
