"""Six-face halo exchange over a 3D device mesh via ``lax.ppermute``.

TPU-native replacement for the reference's ghost-cell machinery
(``src/simulation/communication.jl:109-199``): where the reference commits
three MPI derived vector datatypes per field and issues 12 ``MPI.Sendrecv!``
per step, here each (axis, direction) is one ``lax.ppermute`` of a
boundary slab riding ICI — and u/v slabs are stacked so the whole exchange
is 6 collectives per step, fused by XLA into the surrounding computation.

Non-periodic boundaries: the reference's edge ranks have ``MPI.PROC_NULL``
neighbors, so their ghost layers stay frozen at the initial values (u=1,
v=0). ``ppermute`` with a partial permutation delivers zeros to edge shards;
we select the frozen boundary value there instead (``jnp.where`` on
``lax.axis_index``).

Corner/edge ghost cells are left at boundary values — the 7-point stencil
never reads them (the reference's sequential xy/xz/yz exchange also leaves
them unsynchronized in a different but equally-unread state).

Split-phase exchange (round 6, docs/OVERLAP.md): the fused helpers above
produce data the *whole* kernel pass depends on, which serializes
ppermute latency in front of the compute. :func:`start_exchange` /
:class:`PendingExchange` issue the same ppermutes with NO consumer on the
interior compute's dataflow path, and :func:`frozen_frame` builds the
constant stand-in the interior pass reads instead — so XLA's async
collective-permute + latency-hiding scheduler can run the ICI transfer
under the interior work, and the arrived halos
feed only the thin boundary-band recompute that is stitched afterwards
(``parallel/temporal.stitch_bands_from_frame``). Under JAX there is no
imperative wait: "start" means *issued with no dependency on the interior
pass*, and ``finish()`` means *first consumed by the band stitch*.

All functions here must be called *inside* ``shard_map``.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def _slab(x: jnp.ndarray, dim: int, index: int, width: int) -> jnp.ndarray:
    """Extract a ``width``-thick boundary slab along ``dim`` (kept 3-D);
    ``index`` 0 = first slab, -1 = last slab."""
    idx = [slice(None)] * x.ndim
    idx[dim] = slice(0, width) if index == 0 else slice(-width, None)
    return x[tuple(idx)]


def _exchange_dim(
    arrays: List[jnp.ndarray],
    boundary_values: Sequence[float],
    dim: int,
    ax: str,
    n: int,
    width: int = 1,
) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
    """Resolved (lo, hi) ``width``-thick ghost slabs along one mesh axis
    for each array.

    One ``ppermute`` per direction carries all arrays (stacked along the
    transfer axis); global-edge shards get the frozen boundary value.
    ``n == 1`` (single shard on the axis) short-circuits to constants.
    """
    if n == 1:
        out = []
        for a, bv in zip(arrays, boundary_values):
            shape = list(a.shape)
            shape[dim] = width
            f = jnp.full(shape, bv, a.dtype)
            out.append((f, f))
        return out

    n_arr = len(arrays)
    idx = lax.axis_index(ax)

    # Stack the last slabs of all arrays -> send "up" (coord+1);
    # stack the first slabs -> send "down" (coord-1).
    send_up = jnp.concatenate([_slab(a, dim, -1, width) for a in arrays],
                              dim)
    send_dn = jnp.concatenate([_slab(a, dim, 0, width) for a in arrays],
                              dim)

    up_perm = [(i, i + 1) for i in range(n - 1)]
    dn_perm = [(i + 1, i) for i in range(n - 1)]
    recv_from_lo = lax.ppermute(send_up, ax, up_perm)  # lower nbr's top
    recv_from_hi = lax.ppermute(send_dn, ax, dn_perm)  # upper nbr's bottom

    lo_faces = jnp.split(recv_from_lo, n_arr, axis=dim)
    hi_faces = jnp.split(recv_from_hi, n_arr, axis=dim)

    out = []
    for i, (a, bv) in enumerate(zip(arrays, boundary_values)):
        bvt = jnp.asarray(bv, a.dtype)
        lo = jnp.where(idx > 0, lo_faces[i], bvt)
        hi = jnp.where(idx < n - 1, hi_faces[i], bvt)
        out.append((lo, hi))
    return out


def halo_pad(
    arrays: Sequence[jnp.ndarray],
    boundary_values: Sequence[float],
    axis_names: Tuple[str, str, str],
    axis_sizes: Tuple[int, int, int],
) -> Tuple[jnp.ndarray, ...]:
    """Ghost-pad each local block, filling ghosts from mesh neighbors.

    ``arrays`` are interior-shaped local blocks (same shape); ghosts come
    from the adjacent shard along each mesh axis, or stay at the frozen
    ``boundary_values`` on the global edge. This is the XLA-kernel form;
    the Pallas kernel consumes :func:`exchange_faces` instead.
    """
    arrays = list(arrays)
    padded = [
        jnp.pad(a, 1, mode="constant", constant_values=bv)
        for a, bv in zip(arrays, boundary_values)
    ]

    for dim, (ax, n) in enumerate(zip(axis_names, axis_sizes)):
        if n == 1:
            continue  # single shard on this axis: ghosts stay frozen
        faces = _exchange_dim(arrays, boundary_values, dim, ax, n)
        for i, (lo, hi) in enumerate(faces):
            # Write interior-sized faces into the padded array; corners and
            # edges keep the boundary constant (never read by the stencil).
            start_lo = [1] * 3
            start_lo[dim] = 0
            start_hi = [1] * 3
            start_hi[dim] = padded[i].shape[dim] - 1
            padded[i] = lax.dynamic_update_slice(padded[i], lo, start_lo)
            padded[i] = lax.dynamic_update_slice(padded[i], hi, start_hi)

    return tuple(padded)


def halo_pad_wide(
    arrays: Sequence[jnp.ndarray],
    boundary_values: Sequence[float],
    axis_names: Tuple[str, str, str],
    axis_sizes: Tuple[int, int, int],
    width: int,
) -> Tuple[jnp.ndarray, ...]:
    """Ghost-pad each local block with a ``width``-deep halo, **including
    edge/corner ghosts**.

    Deep halos feed temporal blocking: a ``width=2`` halo lets a shard
    recompute step n+1 on a +1-cell-extended window locally, so two
    steps need one exchange (the reference's per-step ``exchange!``,
    ``communication.jl:138-199``, amortized). Unlike the 7-point
    single-step stencil, the extended-window computation reads edge and
    corner ghosts, so exchanges are *sequential by axis* and each slab
    spans the full padded extent of the axes exchanged before it — the
    classic corner-propagation ordering (the reference's xy/xz/yz
    sequence has the same structure).
    """
    arrays = list(arrays)
    w = width
    padded = [
        jnp.pad(a, w, mode="constant", constant_values=bv)
        for a, bv in zip(arrays, boundary_values)
    ]

    for dim, (ax, n) in enumerate(zip(axis_names, axis_sizes)):
        if n == 1:
            continue  # single shard on this axis: ghosts stay frozen
        m = padded[0].shape[dim]
        # One slab-exchange implementation (``_exchange_dim``) serves
        # both the 1-deep face paths and this corner-propagated frame:
        # trimming the exchange axis's own ghosts makes the outermost
        # OWNED slabs the "boundary slabs" _exchange_dim sends, while
        # the other axes keep their full padded extent — so ghosts
        # filled by earlier axes ride along and corners propagate (the
        # reference's sequential xy/xz/yz ordering).
        trim = [slice(None)] * 3
        trim[dim] = slice(w, m - w)
        trim = tuple(trim)
        pairs = _exchange_dim(
            [p[trim] for p in padded], boundary_values, dim, ax, n, w
        )
        for i, (lo, hi) in enumerate(pairs):
            start_lo = [0] * 3
            start_hi = [0] * 3
            start_hi[dim] = m - w
            p = lax.dynamic_update_slice(padded[i], lo, start_lo)
            padded[i] = lax.dynamic_update_slice(p, hi, start_hi)

    return tuple(padded)


def exchange_slabs(
    arrays: Sequence[jnp.ndarray],
    boundary_values: Sequence[float],
    dim: int,
    ax: str,
    n: int,
    width: int,
) -> List[Tuple[jnp.ndarray, jnp.ndarray]]:
    """``width``-wide (lo, hi) boundary slabs along any one mesh axis
    (one ppermute per direction carries all arrays; global-edge shards
    get the frozen boundary constant). The in-kernel temporal chain's
    exchange (``parallel/temporal.xy_chain``): one round of
    ``width``-wide slabs feeds ``width`` fused kernel steps — 2
    collectives per k steps on an (n, 1, 1) mesh, where the reference
    exchanges 6 faces every step (``communication.jl:138-199``). Where
    y is sharded it exchanges y halos first, then x slabs of the
    y-padded fields, so the x slabs carry the y corner data the
    in-kernel ring recompute needs. Must be called inside
    ``shard_map``."""
    return _exchange_dim(list(arrays), boundary_values, dim, ax, n, width)


def frozen_frame(
    arrays: Sequence[jnp.ndarray],
    boundary_values: Sequence[float],
    width: int,
) -> Tuple[jnp.ndarray, ...]:
    """Each array ghost-padded ``width`` deep with the frozen boundary
    constant on every side — the :func:`halo_pad_wide` stand-in for the
    split-phase interior pass (as if every shard were a global-edge
    shard on every axis)."""
    return tuple(
        jnp.pad(a, width, mode="constant", constant_values=bv)
        for a, bv in zip(arrays, boundary_values)
    )


class PendingExchange:
    """An in-flight corner-propagated wide halo exchange.

    Holds the exchanged frames (``halo_pad_wide`` results). In JAX's
    dataflow model the ppermutes are already issued — *pending* means no
    op on the interior-compute path consumes them, so the scheduler is
    free to run the transfer underneath; :meth:`finish` hands the frames
    to the boundary-band stitch, the only consumer.
    """

    def __init__(self, frames: Tuple[jnp.ndarray, ...], width: int):
        self.frames = frames
        self.width = width

    def finish(self) -> Tuple[jnp.ndarray, ...]:
        """The exchanged frames (first consumption point)."""
        return self.frames


def start_exchange(
    arrays: Sequence[jnp.ndarray],
    boundary_values: Sequence[float],
    axis_names: Tuple[str, str, str],
    axis_sizes: Tuple[int, int, int],
    width: int,
) -> PendingExchange:
    """Issue the corner-propagated ``width``-deep exchange of
    :func:`halo_pad_wide` without tying it into the caller's compute:
    the same ppermutes, in the same per-axis order (so the fused and
    split-phase lowerings carry the SAME collective count), returned as
    a :class:`PendingExchange` consumed only by the band stitch."""
    return PendingExchange(
        halo_pad_wide(arrays, boundary_values, axis_names, axis_sizes,
                      width),
        width,
    )


def exchange_faces(
    arrays: Sequence[jnp.ndarray],
    boundary_values: Sequence[float],
    axis_names: Tuple[str, str, str],
    axis_sizes: Tuple[int, int, int],
) -> Tuple[jnp.ndarray, ...]:
    """Resolved halo faces for each array, without building padded blocks.

    Same communication pattern as :func:`halo_pad`, but the result is the
    1-thick face slabs themselves — the form the fused Pallas kernel
    consumes (``ops/pallas_stencil.fused_step``), which repairs its
    boundary rows/columns in-register instead of reading ghost cells from
    memory.

    Returns, for axes x, y, z in order and per array, ``(lo, hi)`` faces:
    for 2 arrays (u, v) that is
    ``(u_xlo, u_xhi, v_xlo, v_xhi, u_ylo, ..., v_zhi)``. On a global
    edge (or an axis with a single shard) the face is the frozen
    boundary constant.
    """
    arrays = list(arrays)
    flat = []
    for dim, (ax, n) in enumerate(zip(axis_names, axis_sizes)):
        for lo_hi in _exchange_dim(arrays, boundary_values, dim, ax, n):
            flat.extend(lo_hi)
    return tuple(flat)


