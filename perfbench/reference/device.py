"""A plain reference module as a ``jax.numpy`` program on the chip.

The module's ``step`` with ``xp = jax.numpy``, looped by
``lax.fori_loop``, in the configuration's precision (``"float32"`` or
``"float64"``; for float64 the reference turns on JAX's 64-bit mode
itself). For a sharded cell the fields are laid over the reference's
own mesh of the same devices; XLA partitions the pad-and-slice stencil
and the position-keyed noise by itself. Nothing here imports the
program.
"""

from __future__ import annotations

from functools import partial

import numpy as np

from . import grayscott as gs


class Reference:
    """Advances ``module`` (``FIELDS``, ``init``, ``step``) from step 0
    on ``devices``, laid out as ``mesh_shape`` (x, y, z) blocks, in
    ``dtype``."""

    def __init__(self, module, L, params, seed, devices, mesh_shape=(1, 1, 1),
                 dtype="float32"):
        import jax

        if dtype == "float64":
            jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        self.module = module
        self.L = L
        self.params = {k: float(v) for k, v in params.items()}
        # The program's noise key: every model draws from one stream.
        self.key = gs.key_words(seed, x64=dtype == "float64")
        self.dtype = jnp.dtype(dtype)
        n = int(np.prod(mesh_shape))
        mesh = Mesh(np.array(devices[:n]).reshape(mesh_shape),
                    ("x", "y", "z"))
        self.sharding = NamedSharding(mesh, P("x", "y", "z"))
        nf = len(module.FIELDS)
        fields = (self.sharding,) * nf
        self._advance = jax.jit(
            self._advance_impl, static_argnums=(nf + 1,),
            in_shardings=fields + (None,), out_shardings=fields,
            donate_argnums=tuple(range(nf)),
        )
        self._init = jax.jit(partial(module.init, jnp, L, self.dtype),
                             out_shardings=fields)
        self.fields = None
        self.step = 0

    def _advance_impl(self, *args):
        import jax.numpy as jnp
        from jax import lax

        *fields, step0, nsteps = args

        def body(i, carry):
            return self.module.step(jnp, *carry, step0 + i, self.params,
                                    self.key, L=self.L)

        return lax.fori_loop(0, nsteps, body, tuple(fields))

    def start(self):
        self.fields = self._init()
        self.step = 0

    def advance(self, nsteps):
        import jax.numpy as jnp

        self.fields = self._advance(*self.fields, jnp.int32(self.step),
                                    int(nsteps))
        self.step += nsteps
