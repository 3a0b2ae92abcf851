"""Cross-shard temporal blocking: the chain rounds that
``parallel/schedule.py`` drives on sharded blocks.

The fused Pallas kernel (``ops/pallas_stencil.py``) chains ``k``
timesteps per HBM pass by walking shrinking windows along its leading
(x) axis. Crossing shard boundaries with that chain needs k-deep halo
data on every sharded axis — and what that costs depends on which
*Mosaic tiling dimension* the axis lands on:

* **x** (untiled leading dim): free — the kernel's x-chain mode
  consumes k-wide exchanged x slabs directly, and on an (n, 1, 1) mesh
  that is all :func:`xy_chain` exchanges;
* **y** (sublane dim, 8/16-granularity): cheap — :func:`xy_chain`
  hands the kernel k-deep exchanged y halos (each rounded up to the
  sublane tile with boundary-constant filler rows), which it places
  above and below the owned rows in VMEM; the kernel's mid-stage
  global-coordinate pinning makes in-domain halo rows ring-recompute
  the y neighbor's values, so the in-kernel chain crosses y shard
  boundaries at a few percent of plane-area overhead, and the owned
  rows are updated in place;
* **z** (128-lane dim): expensive — a ±k z pad would round the lane
  extent up to the next 128 multiple (up to ~50% wasted vector work),
  so z shard boundaries are instead handled OUTSIDE the kernel:
  the kernel runs with frozen z edges, contaminating the outermost k
  z-cells per sharded z side (one cell per stage), and
  :func:`window_chain` recomputes those k-wide bands in XLA from a
  corner-propagated k-deep frame (``halo.halo_pad_wide``) — O(k * n^2)
  cells per side per round against the kernel's O(n^3).

Per ``k`` steps: ONE exchange round (4 ppermutes for an (n, m, 1)
mesh, 6 with z sharded — the per-step cost the reference pays in
``communication.jl:138-199``), one fused k-deep kernel pass, and — only
when z is sharded — two thin XLA band chains. Everything reproduces the
step-at-a-time trajectory exactly (position-keyed noise,
``ops/noise.py``), which the CPU-mesh bitwise tests assert.

This supersedes the round-3 design (single-step kernel launches with an
XLA-advanced ghost shell), which paid a measured 1.46x per-stage
penalty because in-kernel fusion stopped at every shard boundary.

Communication-avoiding s-step exchange (``halo_depth``, round 9,
docs/TEMPORAL.md): the XLA chain path generalizes the same machinery
into exchanging once per ``halo_depth`` chain rounds — a
(chain_depth x halo_depth)-deep corner-propagated frame
(``halo.halo_pad_wide``) feeds one :func:`window_chain` whose valid
region shrinks one cell per side per step until the next exchange
restores full width. Because :func:`window_chain` shrinks uniformly,
composing ``k`` depth-``d`` segments on the shared frame is the SAME
program as one depth-``k*d`` chain — the realization
``parallel/schedule.py`` uses — so ``halo_depth=k`` at chain depth
``d`` is bitwise identical to ``halo_depth=1`` at chain depth ``k*d``,
and the split-phase form
(:func:`stitch_bands_from_frame` after an interior pass on a frozen
frame) composes with it unchanged: the deeper transfer hides behind
proportionally more interior steps.
"""

from __future__ import annotations

from typing import Callable, Sequence, Tuple

import jax.numpy as jnp
from jax import lax

from . import halo


def pin_out_of_domain(arr, bv, origin, row):
    """Pin every cell whose GLOBAL coordinate falls outside ``[0, row)``
    on any axis to the frozen boundary value (the reference's
    ``MPI.PROC_NULL`` ghost semantics); ``origin`` (int32[3]) is the
    global coordinate of ``arr[0, 0, 0]``.

    Works on any offset sub-box of a shard, and — unlike a mesh-edge
    ring mask — also pins **pad cells inside the block** (non-divisible
    L stores a padded grid, ``parallel/domain.py``)."""
    origin = jnp.asarray(origin, jnp.int32)
    valid = None
    for dim in range(3):
        g = origin[dim] + jnp.arange(arr.shape[dim])
        vd = ((g >= 0) & (g < row)).reshape(
            tuple(arr.shape[dim] if d == dim else 1 for d in range(3))
        )
        valid = vd if valid is None else valid & vd
    return jnp.where(valid, arr, jnp.asarray(bv, arr.dtype))


def window_chain(
    fields_w, params, model, *, depth, step, origin, row, use_noise,
    unit_noise, boundaries: Sequence[float], final_pin: bool = True,
    compute_dtype=None,
):
    """``depth`` XLA steps on ghost-inclusive field windows, shrinking
    one cell per side per stage; returns the (shape - 2*depth) cores.

    Model-generic: ``fields_w`` is the model's field tuple in
    declaration order and the update is the shared n-field
    ``stencil.reaction_update`` with ``model``'s reaction.

    ``origin`` (int32[3]) is the global coordinate of each window's
    ``[0, 0, 0]``; after each stage, cells outside the global domain are
    pinned to the frozen per-field ``boundaries`` values by
    :func:`pin_out_of_domain`'s global-coordinate masks. Same op order
    and position-keyed noise as every other path — bitwise-exact
    against the stepwise trajectory, so a band it computes can be
    stitched next to kernel-computed cells seamlessly.

    ``final_pin=False`` skips the last stage's pin masks — legal only
    when the caller knows every output cell is in-domain (a divisible-L
    block-shaped result), where the pin is a provably-all-true mask.
    Mid-stage pins always run: the shrinking ring reads them back.

    ``compute_dtype`` widens each stage's accumulation (the
    ``bf16_f32acc`` posture, docs/PRECISION.md): fields stay in the
    storage dtype between stages — so the exchanged frame and the
    per-stage rounding match the stepwise path exactly — and each
    stage upcasts, accumulates, and rounds back inside
    ``stencil.reaction_update``."""
    from ..ops import stencil

    fields_w = tuple(fields_w)
    origin = jnp.asarray(origin, jnp.int32)
    for s in range(depth):
        shape = tuple(d - 2 for d in fields_w[0].shape)
        o = origin + (s + 1)
        if use_noise:
            nzf = params.noise * unit_noise(step + s, o, shape)
        else:
            nzf = jnp.asarray(0.0, fields_w[0].dtype)
        fields_w = stencil.reaction_update(
            fields_w, nzf, params, model, compute_dtype=compute_dtype
        )
        if s + 1 < depth or final_pin:
            fields_w = tuple(
                pin_out_of_domain(f, bv, o, row)
                for f, bv in zip(fields_w, boundaries)
            )
    return fields_w


def stitch_bands_from_frame(
    fields_i, fields_w, params, model, *, depth, step, offs, row,
    axis_sizes, use_noise, unit_noise, boundaries: Sequence[float],
    dims_to_stitch: Sequence[int] = (0, 1, 2), compute_dtype=None,
):
    """Overwrite the ``depth``-thick boundary bands of block-shaped
    results with :func:`window_chain` recomputes from the exchanged
    corner-propagated frames ``fields_w`` (``halo.halo_pad_wide``
    width ``depth``). Model-generic over the field tuple.

    The split-phase stitch: ``fields_i`` came from an interior pass
    that saw frozen-constant ghosts, so every cell within ``depth``
    cells of a sharded face is contaminated (one cell per stage). Each
    such band is recomputed from a 3k-deep frame window spanning the
    FULL frame extent on the other axes — so corner cells land in two
    (or three) bands, each recomputing bitwise-identical values from
    the same frame, and sequential overwrites are safe. Axes with a
    single shard (or excluded via ``dims_to_stitch``) are skipped:
    their frozen ghosts were already the truth.

    ``offs`` (int32[3]) is the block's global origin. Must be called
    inside ``shard_map``.
    """
    k = depth
    fields_i = tuple(fields_i)
    fields_w = tuple(fields_w)
    offs = jnp.asarray(offs, jnp.int32)
    base = offs - k  # global origin of the frame
    for dim in range(3):
        if axis_sizes[dim] == 1 or dim not in dims_to_stitch:
            continue
        n_d = fields_i[0].shape[dim]
        m = fields_w[0].shape[dim]  # n_d + 2k
        for d0, w0 in ((0, 0), (n_d - k, m - 3 * k)):
            sl = [slice(None)] * 3
            sl[dim] = slice(w0, w0 + 3 * k)
            sl = tuple(sl)
            bands = window_chain(
                tuple(f[sl] for f in fields_w), params, model,
                depth=k, step=step,
                origin=base.at[dim].add(w0), row=row,
                use_noise=use_noise, unit_noise=unit_noise,
                boundaries=boundaries, compute_dtype=compute_dtype,
            )
            pos = [0, 0, 0]
            pos[dim] = d0
            fields_i = tuple(
                lax.dynamic_update_slice(fi, b, tuple(pos))
                for fi, b in zip(fields_i, bands)
            )
    return fields_i


def xy_overlap_feasible(local, dims, depth) -> bool:
    """Whether the split-phase form of :func:`xy_chain` applies at this
    geometry. The z-sharded (frame) form always does — its bands come
    from one corner-propagated frame and may overlap-write identical
    values. The slab form (p == 1) builds band windows from 2k-deep
    owned slices, so every sharded slab axis must be >= 2k deep (a
    shallower block has no comm-independent interior anyway)."""
    if dims[2] > 1:
        return True
    k = depth
    return not any(dims[d] > 1 and local[d] < 2 * k for d in (0, 1))


def xy_chain(
    fields, params, model, *, depth, step, offs, chain_kernel: Callable,
    use_noise, unit_noise, row, axis_names, axis_sizes,
    boundaries: Sequence[float], sublane: int = 8,
    overlap: bool = False, band_kernel: Callable = None,
):
    """``depth`` fused steps on an (n, m, p) sharded block: in-kernel
    chain across x and y shard boundaries, XLA band correction on
    sharded z sides. See the module docstring for the design.
    Model-generic: ``fields`` is the model's field tuple in declaration
    order, and every faces tuple is field-major (lo, hi) pairs — the
    generated kernel's x-chain operand order
    (``ops/pallas_stencil.fused_step``). Where neither y nor z is
    sharded (an (n, 1, 1) mesh) the round exchanges no y slabs and the
    kernel takes no y halos: its plane is the block's own ``ny`` rows
    (the ``gs_x_chain`` launch), and the exchange is 2 ppermutes. The
    s-step exchange schedule
    (``halo_depth=k``, docs/TEMPORAL.md) reuses this round unchanged at
    ``depth = fuse*k`` — one k-times-deeper ``halo_pad_wide`` frame per
    round, the same 6 (z-sharded) or 4 collectives, amortized over k
    times the steps.

    ``chain_kernel(fields, y_halos, faces, step, offs)`` runs the fused
    kernel (or its bitwise XLA fallback) at ``fuse=depth`` on the owned
    blocks, in place: ``y_halos`` are the per-field (lo, hi) blocks of
    ``pallas_stencil.y_halo_rows(depth, sublane)`` rows each (the
    ``depth`` exchanged rows next to the owned ones, boundary-constant
    filler beyond), or None on an (n, 1, 1) mesh, and the x faces span
    the same rows as the kernel's VMEM plane (``fused_step``'s xy-chain
    form). So the round neither pads the fields in y nor slices the
    result.
    ``unit_noise(step_idx, origin, shape)`` draws from the shared
    position-keyed stream. Must be called inside ``shard_map``.

    ``overlap=True`` is the split-phase form (docs/OVERLAP.md): the
    SAME exchange is issued first, but the kernel consumes frozen
    boundary constants instead — so it has no data dependency on the
    ppermutes and XLA can hide the ICI transfer under it — and the
    exchanged slabs/frame feed only the k-thick x/y (and z) boundary
    bands recomputed afterwards and stitched in. x/y bands run
    ``band_kernel`` — the x-chain XLA reference program
    (``pallas_stencil._xla_xchain_fallback``) on a thin body — NOT a
    different chain formulation: structural identity with the fused
    kernel's own fallback is what keeps the recomputed band bitwise
    equal under XLA's shape-sensitive codegen (FMA contraction). z
    bands keep the fused path's :func:`window_chain` recompute, which
    is identical in both modes. Slab-mode (p == 1) overlap needs every
    sharded slab axis to be at least 2k deep (otherwise there is no
    interior to hide behind); shallower blocks silently take the fused
    round, which is bitwise identical anyway.
    """
    from ..ops.pallas_stencil import y_halo_rows

    fields = tuple(fields)
    bvs = tuple(boundaries)
    nx, ny, nz = fields[0].shape
    dtype = fields[0].dtype
    dims = axis_sizes
    k = depth
    z_sharded = dims[2] > 1
    # Rows of y halo above and below the owned rows in the kernel's
    # plane: ky exchanged rows, then fy rows of filler.
    hy = y_halo_rows(k, sublane) if dims[1] > 1 or z_sharded else 0
    ky = k if hy else 0
    fy = hy - ky
    if overlap and not xy_overlap_feasible(fields[0].shape, dims, k):
        overlap = False  # no comm-independent interior: fused round
    if overlap and band_kernel is None:
        raise ValueError("xy_chain overlap=True requires band_kernel")

    # (body_fields, faces, offsets, out_row_slice, position) jobs for
    # the split-phase x/y band recompute, built beside the exchange.
    band_jobs = []

    def interleave(los, his):
        """Field-major (lo, hi) faces tuple from per-field slabs."""
        return tuple(x for pair in zip(los, his) for x in pair)

    def fill_y(a, bv, lo_rows, hi_rows):
        """``a`` with boundary-constant filler rows below and above."""
        if not (lo_rows or hi_rows):
            return a
        return jnp.pad(a, ((0, 0), (lo_rows, hi_rows), (0, 0)),
                       constant_values=bv)

    if z_sharded:
        # One corner-propagated k-deep frame serves the kernel's y halos,
        # its x faces, AND the band windows (6 ppermutes total).
        fields_w = halo.halo_pad_wide(
            fields, bvs, axis_names, dims, k
        )
        if overlap:
            # Split phase: the kernel sees frozen constants everywhere,
            # so the frame has NO consumer on the kernel's dataflow
            # path; bands for every sharded axis are stitched after.
            m_y = ny + 2 * k

            def fr(x0, x1, ys):
                """Frame windows of the fields at frame x range
                [x0, x1) and y range ``ys``, z clipped to the owned
                planes."""
                return tuple(w[x0:x1, ys, k:k + nz] for w in fields_w)

            if dims[1] > 1:
                # y bands: body rows are the frame's [arrived y slab |
                # 2k owned rows]; x faces are the frame's x ghosts
                # clipped to the same rows (corner-propagated, so the
                # band's x corners carry real neighbor data exactly as
                # the fused kernel's do).
                for ys, o_y, d_y in (
                    (slice(0, 3 * k), -k, 0),
                    (slice(m_y - 3 * k, m_y), ny - 2 * k, ny - k),
                ):
                    band_jobs.append((
                        fr(k, k + nx, ys),
                        interleave(fr(0, k, ys),
                                   fr(k + nx, nx + 2 * k, ys)),
                        jnp.stack([offs[0], offs[1] + o_y, offs[2]]),
                        slice(k, 2 * k), (0, d_y, 0),
                    ))
            if dims[0] > 1:
                # x bands: a k-plane body whose x faces come from the
                # frame — the arrived x ghost on the outside, adjacent
                # owned planes on the inside; full frame y extent.
                ally = slice(None)
                for xs, fl, fh, o_x, d_x in (
                    (slice(k, 2 * k), slice(0, k), slice(2 * k, 3 * k),
                     0, 0),
                    (slice(nx, k + nx), slice(nx - k, nx),
                     slice(k + nx, nx + 2 * k), nx - k, nx - k),
                ):
                    band_jobs.append((
                        fr(xs.start, xs.stop, ally),
                        interleave(fr(fl.start, fl.stop, ally),
                                   fr(fh.start, fh.stop, ally)),
                        jnp.stack([offs[0] + o_x, offs[1] - k,
                                   offs[2]]),
                        slice(k, k + ny), (d_x, 0, 0),
                    ))
        else:
            y_pairs = [(w[k:k + nx, :k, k:k + nz],
                        w[k:k + nx, k + ny:, k:k + nz]) for w in fields_w]
            x_pairs = [(w[:k, :, k:k + nz], w[k + nx:, :, k:k + nz])
                       for w in fields_w]
    else:
        # Lean 4-ppermute build: k-wide y slabs first, then x slabs of
        # the y-padded fields so the x faces carry y corner data. The
        # launch overwrites the fields, so all else the round reads of
        # them — the slabs the exchange sends, the bands' rows and
        # planes — is cut first: c rows and c planes at each end, which
        # the barrier keeps ahead of the launch (else XLA keeps the
        # fields alive past it with a whole-field copy).
        c = 2 * k if overlap else k
        ye = tuple(jnp.concatenate([f[:, :c], f[:, ny - c:]], axis=1)
                   for f in fields) if hy else ()
        xe = tuple(jnp.concatenate([f[:c], f[nx - c:]]) for f in fields)
        ye, xe, fields = lax.optimization_barrier((ye, xe, fields))
        if hy:
            y_pairs = halo.exchange_slabs(
                list(ye), bvs, 1, axis_names[1], dims[1], k
            )
            # The x ends of the y-padded fields [y lo | field | y hi]:
            # the first and last c planes, (2c, ny + 2k, nz).
            xe = tuple(
                jnp.concatenate([jnp.concatenate([lo[:c], lo[nx - c:]]),
                                 e,
                                 jnp.concatenate([hi[:c], hi[nx - c:]])],
                                axis=1)
                for e, (lo, hi) in zip(xe, y_pairs)
            )
        x_pairs = halo.exchange_slabs(
            list(xe), bvs, 0, axis_names[0], dims[0], k
        )
        if overlap and dims[1] > 1:
            # y bands: body rows are [arrived y slab | 2k owned rows]
            # of the y-padded fields; the x faces are the arrived x
            # slabs clipped to the same rows, so the band's x corners
            # carry real neighbor data exactly as the fused kernel's do.
            m_y = ny + 2 * k
            for body, ys, o_y, d_y in (
                (tuple(jnp.concatenate([lo, e[:, :2 * k]], axis=1)
                       for e, (lo, _) in zip(ye, y_pairs)),
                 slice(0, 3 * k), -k, 0),
                (tuple(jnp.concatenate([e[:, 2 * k:], hi], axis=1)
                       for e, (_, hi) in zip(ye, y_pairs)),
                 slice(m_y - 3 * k, m_y), ny - 2 * k, ny - k),
            ):
                band_jobs.append((
                    body,
                    interleave(
                        tuple(lo[:, ys, :] for lo, _ in x_pairs),
                        tuple(hi[:, ys, :] for _, hi in x_pairs),
                    ),
                    jnp.stack([offs[0], offs[1] + o_y, offs[2]]),
                    slice(k, 2 * k), (0, d_y, 0),
                ))
        if overlap and dims[0] > 1:
            # x bands: a k-plane body whose x faces are the arrived
            # slab and the adjacent owned planes (both y-padded where
            # y is sharded).
            for body, faces_b, d_x in (
                (tuple(e[:k] for e in xe),
                 interleave(tuple(lo for lo, _ in x_pairs),
                            tuple(e[k:2 * k] for e in xe)),
                 0),
                (tuple(e[3 * k:] for e in xe),
                 interleave(tuple(e[2 * k:3 * k] for e in xe),
                            tuple(hi for _, hi in x_pairs)),
                 nx - k),
            ):
                band_jobs.append((
                    body, faces_b,
                    jnp.stack([offs[0] + d_x,
                               offs[1] - ky if ky else offs[1], offs[2]]),
                    slice(ky, ky + ny), (d_x, 0, 0),
                ))

    y_halos = None
    if overlap:
        if hy:
            y_halos = tuple(jnp.full((nx, hy, nz), bv, dtype)
                            for bv in bvs for _ in (0, 1))
        faces = tuple(
            jnp.full((k, ny + 2 * hy, nz), bv, dtype)
            for bv in bvs for _ in (0, 1)
        )
    else:
        # The exchanged rows next to the owned ones, boundary-constant
        # filler beyond them: every halo is hy rows, whole sublane
        # tiles, and the x faces span the kernel's whole plane.
        if hy:
            y_halos = tuple(
                h for (lo, hi), bv in zip(y_pairs, bvs)
                for h in (fill_y(lo, bv, fy, 0), fill_y(hi, bv, 0, fy))
            )
        faces = tuple(
            fill_y(f, bv, fy, fy)
            for pair, bv in zip(x_pairs, bvs) for f in pair
        )
    out = chain_kernel(fields, y_halos, faces, step, offs)

    # Split-phase x/y bands first (they reproduce the fused kernel's
    # values, including each other's corners), then the z bands, which
    # overwrite the z shell in BOTH modes with identical values.
    for body, faces_b, offs_b, out_rows, pos in band_jobs:
        band = band_kernel(body, faces_b, step, offs_b)
        out = tuple(
            lax.dynamic_update_slice(o, b[:, out_rows, :], pos)
            for o, b in zip(out, band)
        )

    if z_sharded:
        # The kernel ran with frozen z edges: its outermost k z-cells
        # are stale wherever a z neighbor exists (and exactly correct
        # on global z edges). Recompute both k-wide bands from the
        # frame — bitwise the same values, so overwriting
        # unconditionally is correct on edge shards too.
        out = stitch_bands_from_frame(
            out, fields_w, params, model, depth=k,
            step=step, offs=offs, row=row, axis_sizes=dims,
            use_noise=use_noise, unit_noise=unit_noise,
            boundaries=bvs, dims_to_stitch=(2,),
        )
    return out
