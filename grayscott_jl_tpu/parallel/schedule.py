"""Chain schedules: how one runner advances ``nsteps`` steps on a block.

A runner trace (``Simulation._local_run``) asks :func:`select` for a
round function and its depth, binds the round to the trace's
:class:`Block`, and drives it with :func:`run_chain_rounds`. The table:

=========  =======  =======================  ==========================
language   sharded  round                    depth
=========  =======  =======================  ==========================
pallas     no       :func:`pallas_block_     ``min(fuse, nsteps)``
                    round` (``gs_chain``)
pallas     yes      :func:`pallas_sharded_   :func:`sharded_chain_depth`
                    round` (``xy_chain``)
xla        no       :func:`xla_step_round`   1
xla        yes      :func:`xla_window_       ``min(fuse * halo_depth,
                    round`                   nsteps, min(local))``
=========  =======  =======================  ==========================

A sharded XLA runner of fewer than 2 steps takes the single step too,
unless its window rounds would be split-phase.

Every round is ``round(blk, fields, step0, done, depth)``: ``depth``
steps from absolute step ``step0 + done``, returning the fields and
whether the round was built split-phase (docs/OVERLAP.md). The step is
handed over as its two terms so that each round adds them where its
program has always added them.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

import jax.numpy as jnp
from jax import lax

from . import halo, temporal
from .domain import AXIS_NAMES


class Block(NamedTuple):
    """What a round reads besides its fields and steps: one runner
    trace's values for one (local) block."""

    model: Any
    params: Any
    #: int32[2] base key of the position-keyed noise stream.
    key: Any
    #: int32[3] global origin of the block.
    offs: Any
    #: Global grid side L.
    row: int
    dims: Tuple[int, int, int]
    use_noise: bool
    #: ``unit_noise(step, origin, shape)``: the shared uniform [-1, 1)
    #: stream at global cells.
    unit_noise: Callable
    #: Re-pins a block's pad cells (non-divisible L) to the boundary.
    pin: Callable
    #: Accumulation dtype of the XLA paths (docs/PRECISION.md).
    compute_dtype: Any
    #: Split-phase exchange armed (``comm_overlap``).
    overlap: bool
    #: Non-divisible L: the block stores pad cells.
    padded: bool
    #: Pallas interpret mode allowed off TPU (not under shard_map or
    #: vmap, where the XLA fallback runs instead).
    allow_interpret: bool


def run_chain_rounds(chain, depth, nsteps, step0, fields):
    """``nsteps`` steps as full-``depth`` rounds in a ``fori_loop``,
    then one shallower remainder round — the one place a step count is
    divided by a depth. ``chain(fields, step0, done, depth)`` is a
    bound round. Returns the fields and whether any round was built
    split-phase."""
    split = []

    def one(fields, done, d):
        fields, s = chain(fields, step0, done, d)
        split.append(s)
        return fields

    rounds, rem = divmod(nsteps, depth)
    # At depth 1 the loop index is the step offset itself.
    fields = lax.fori_loop(
        0, rounds,
        lambda i, f: one(f, i if depth == 1 else depth * i, depth),
        fields,
    )
    if rem:
        fields = one(fields, depth * rounds, rem)
    return fields, any(split)


def select(language, sharded, *, nsteps, fuse, halo_depth, local, dims,
           dtype, n_fields, overlap):
    """The round function and depth of one runner (the module's
    table). ``fuse`` is the chain depth before any cap and
    ``halo_depth`` the s-step exchange depth (docs/TEMPORAL.md)."""
    if language == "pallas" and not sharded:
        return pallas_block_round, min(fuse, max(nsteps, 1))
    if language == "pallas":
        return pallas_sharded_round, sharded_chain_depth(
            local, dims, dtype, min(fuse * halo_depth, max(nsteps, 1)),
            n_fields,
        )
    if not sharded or (nsteps < 2 and not window_overlaps(overlap, dims)):
        return xla_step_round, 1
    return xla_window_round, min(fuse * halo_depth, nsteps, min(local))


def sharded_chain_depth(local, dims, dtype, depth, n_fields):
    """Deepest chain depth <= ``depth`` the sharded Pallas round serves
    on block ``local`` of mesh ``dims``
    (``pallas_stencil.max_feasible_chain_depth``), at least 1: depth 1
    is the 12-face single-step round. Warns once when the VMEM slab
    budget, not the block's geometry, sets the cap — an exchange wider
    than the kernel can chain would run every step on its XLA
    fallback."""
    from ..ops import pallas_stencil as ps

    want = ps.chain_depth_cap(local, dims, depth)
    got = ps.max_feasible_chain_depth(
        local, dims, jnp.dtype(dtype).itemsize, depth, _sublane(dtype),
        mid_itemsize=ps.mid_itemsize_for(dtype), n_fields=n_fields,
    )
    if got < want:
        ps._warn_once(
            f"sharded chain depth capped at {max(got, 1)} (depth {want} "
            f"does not fit VMEM for local grid {tuple(local)} on mesh "
            f"{tuple(dims)})"
        )
    return max(got, 1)


def window_overlaps(overlap, dims) -> bool:
    """Whether the XLA window rounds are split-phase: only on 1D
    x-sharded meshes. Band windows thin along the leading (x) axis are
    codegen-stable on XLA:CPU; shrinking a trailing extent is the shape
    change its FP-contraction decisions key on (measured: x-thin frame
    windows reproduce the full window chain bitwise through k=4, y- and
    z-thin ones drift 1 ulp at some shapes). Multi-axis meshes get
    their overlap through the Pallas chains, whose band recomputes share
    the kernel fallback's structure. docs/OVERLAP.md "Bitwise-identity
    guarantee"."""
    return overlap and dims[1] == 1 and dims[2] == 1


def _sublane(dtype) -> int:
    return 16 if dtype == jnp.bfloat16 else 8


def _seeds(key, step):
    """The kernel's int32[3] noise seeds: key lo, key hi, step."""
    return jnp.stack([key[0], key[1], jnp.asarray(step, jnp.int32)])


def _launch(blk, fields, step, faces, depth, offsets=None, y_halos=None):
    """One fused-kernel launch of ``depth`` steps from ``step``
    (``pallas_stencil.fused_step``; its XLA fallback off TPU)."""
    from ..ops import kernelgen, pallas_stencil

    return pallas_stencil.fused_step(
        fields, blk.params, _seeds(blk.key, step), faces,
        spec=kernelgen.get_spec(blk.model), use_noise=blk.use_noise,
        allow_interpret=blk.allow_interpret, fuse=depth,
        offsets=blk.offs if offsets is None else offsets, row=blk.row,
        y_halos=y_halos,
    )


def pallas_block_round(blk, fields, step0, done, depth):
    """One ``gs_chain`` launch on the whole grid: ``depth`` steps per
    HBM pass (the slab pipeline is DMA-bound on the v5e, so per-step
    time scales about 1/depth)."""
    return _launch(blk, fields, step0 + done, None, depth), False


def pallas_sharded_round(blk, fields, step0, done, depth):
    """``depth`` steps on a sharded block. Depth 1 is one launch on the
    12 exchanged faces. Deeper rounds are :func:`temporal.xy_chain`:
    one exchange of ``depth``-wide slabs feeds one in-kernel chain
    across the x and y shard boundaries (``gs_x_chain`` on an (n, 1, 1)
    mesh, ``gs_xy_chain`` else), with XLA band recomputes on sharded z
    sides — and, split-phase, on the x and y sides too."""
    step = step0 + done
    bvs = blk.model.boundaries
    if depth == 1:
        faces = halo.exchange_faces(fields, bvs, AXIS_NAMES, blk.dims)
        return blk.pin(_launch(blk, fields, step, faces, 1)), False
    from ..ops import kernelgen, pallas_stencil

    def chain_kernel(fields_k, y_halos, faces_k, stp, offs_k):
        return _launch(blk, fields_k, stp, faces_k, depth, offs_k, y_halos)

    def band_kernel(fields_b, faces_b, stp, offs_b):
        # The x-chain XLA reference — the SAME program structure as the
        # fused kernel's own fallback, which keeps recomputed bands
        # bitwise equal.
        return pallas_stencil._xla_xchain_fallback(
            fields_b, blk.params, _seeds(blk.key, stp), faces_b,
            spec=kernelgen.get_spec(blk.model), fuse=depth,
            use_noise=blk.use_noise, offsets=offs_b, row=blk.row,
        )

    split = blk.overlap and temporal.xy_overlap_feasible(
        fields[0].shape, blk.dims, depth
    )
    return blk.pin(temporal.xy_chain(
        fields, blk.params, blk.model, depth=depth, step=step,
        offs=blk.offs, chain_kernel=chain_kernel, use_noise=blk.use_noise,
        unit_noise=blk.unit_noise, row=blk.row, axis_names=AXIS_NAMES,
        axis_sizes=blk.dims, boundaries=bvs,
        sublane=_sublane(fields[0].dtype), overlap=split,
        band_kernel=band_kernel,
    )), split


def xla_step_round(blk, fields, step0, done, depth):
    """One XLA step (``depth`` is 1): ghost pad (exchanged faces where
    sharded), noise, reaction."""
    from ..ops import stencil

    fields_pad = halo.halo_pad(fields, blk.model.boundaries, AXIS_NAMES,
                               blk.dims)
    if blk.use_noise:
        nz = blk.params.noise * blk.unit_noise(
            step0 + done, blk.offs, fields[0].shape
        )
    else:
        nz = jnp.asarray(0.0, fields[0].dtype)
    return blk.pin(stencil.reaction_update(
        fields_pad, nz, blk.params, blk.model,
        compute_dtype=blk.compute_dtype,
    )), False


def xla_window_round(blk, fields, step0, done, depth):
    """``depth`` steps from ONE ``depth``-wide corner-propagated
    exchange: stage s recomputes step n+1+s on a window reaching
    (depth-1-s) cells beyond the block (neighbour-owned ring cells
    reproduce the owner's values bitwise), and the shrinking ring is
    the next stage's ghost shell — the exchange count per step falls
    by ``depth`` (the reference exchanges every step,
    ``communication.jl:138-199``). Split-phase (:func:`window_overlaps`)
    the same exchange is issued with no consumer on the interior
    chain, which runs on a frozen-constant frame; the sharded-face
    bands are then recomputed from the arrived frame and stitched."""
    step = step0 + done
    bvs = blk.model.boundaries
    chain = dict(
        depth=depth, step=step, row=blk.row, use_noise=blk.use_noise,
        unit_noise=blk.unit_noise, boundaries=bvs,
        compute_dtype=blk.compute_dtype,
    )
    if window_overlaps(blk.overlap, blk.dims):
        pending = halo.start_exchange(fields, bvs, AXIS_NAMES, blk.dims,
                                      depth)
        frozen = halo.frozen_frame(fields, bvs, depth)
        fields_i = temporal.window_chain(
            frozen, blk.params, blk.model, origin=blk.offs - depth,
            final_pin=blk.padded, **chain,
        )
        return temporal.stitch_bands_from_frame(
            fields_i, pending.finish(), blk.params, blk.model,
            offs=blk.offs, axis_sizes=blk.dims, **chain,
        ), True
    # Global-coordinate pinning per stage: ring cells outside the domain
    # and, for non-divisible L, pad cells inside the block. The final
    # stage has no ring, so divisible-L runs skip its all-true mask.
    fields_w = halo.halo_pad_wide(fields, bvs, AXIS_NAMES, blk.dims, depth)
    return temporal.window_chain(
        fields_w, blk.params, blk.model, origin=blk.offs - depth,
        final_pin=blk.padded, **chain,
    ), False
