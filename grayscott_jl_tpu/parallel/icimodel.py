"""Analytic ICI weak-scaling model + Auto kernel-language selection.

The cost model that used to live in ``benchmarks/ici_model.py`` (which
now imports from here — the CLI front-end keeps its interface), promoted
into the package so the framework can consult it at construction time:
``kernel_language = "Auto"`` resolves to the kernel the model projects
to be the right one for the actual (mesh, L, dtype, fabric) of the run
(VERDICT r4 item 3 — previously the XLA-vs-Pallas choice at pod scale
was operator knowledge buried in pod scripts).

Model (per step, per device): compute time from measured single-chip
µs/step anchors (``MEASURED_US``, BASELINE.md v5e table), halo bytes
from the face geometry of the chain mode, communication serialized at
the max-loaded ICI link plus hop latency, efficiency = compute /
(compute + exposed comm). Every assumption is stated and overridable;
the fabric parameters are public per-generation figures (v5p ~90 GB/s
per link per direction, ~1 µs hop; v5e ~45 GB/s, 2D torus).

The reference has no equivalent: its kernel choice (communication.jl /
CUDAExt.jl) is fixed per build, and its MPI halo exchange pays full
per-step cost at every scale.
"""

from __future__ import annotations

from typing import Optional

#: Single-chip fused-kernel cost at fuse=k relative to the k=5 optimum,
#: measured round-robin in one process at L=256 f32 noisy (k=1:
#: ab_r3_fuse1v5; k=4,5,6: ab_r3_deepfuse medians). k=2,3 are a+b/k
#: interpolations through the k=1 and k=4 anchors — marked so in the
#: emitted rows. ``benchmarks/update_fuse_ratio.py --apply`` rewrites
#: this literal from a measured artifact.
FUSE_COST_RATIO = {1: 1493.1 / 1023.9, 2: 1.174, 3: 1.079,
                   4: 1077.0 / 1044.0, 5: 1.0, 6: 1069.3 / 1044.0}

#: Measured single-chip f32 noisy µs/step by (kernel language, local
#: side) — BASELINE.md v5e table, fast-window best-of; the throttled
#: state scales compute and comm denominators together, so efficiency
#: is roughly state-invariant. The Pallas numbers are the FUSED
#: (in-kernel k=4/5) single-chip path — the honest baseline a 1-chip
#: user gets; its sharded stages pay STAGE_RATIO on top (see project).
MEASURED_US = {
    ("Pallas", 128): 396.0,
    ("Pallas", 256): 727.6,
    ("Pallas", 512): 3618.2,
    ("XLA", 128): 738.7,
    ("XLA", 256): 1828.3,
    ("XLA", 512): 16073.1,
}

#: Sharded per-stage cost over the fused single-chip step for the
#: Pallas language: fuse=1 vs fuse=5 measured round-robin in ONE
#: process (benchmarks/results/ab_r3_fuse1v5_2026-07-30.jsonl:
#: 1493.1 vs 1023.9 us/step best, medians agree). The XLA language is
#: stepwise on a single chip too, so its ratio is 1.0 by construction.
STAGE_RATIO = {"Pallas": FUSE_COST_RATIO[1], "XLA": 1.0}

#: Fraction of the *ideally hideable* communication the split-phase
#: exchange (GS_COMM_OVERLAP, docs/OVERLAP.md) actually hides behind
#: interior compute: realized_overlap = OVERLAP_EFFICIENCY *
#: min(1, interior_compute / comm). The ideal bound comes from dataflow
#: (comm can hide only under compute that does not consume it); the
#: efficiency discounts scheduler imperfection — async collective-
#: permute issue latency, band-stitch cost, LHS scheduling slack — and
#: is the default until ``benchmarks/update_overlap.py --apply``
#: rewrites this literal from a measured ``halo_bench.py --ab``
#: artifact (the same calibration loop as FUSE_COST_RATIO).
OVERLAP_EFFICIENCY = 0.85

#: Fraction of the *ideal* 1/k s-step latency amortization
#: (``halo_depth``, docs/TEMPORAL.md) the schedule actually realizes,
#: PER KERNEL LANGUAGE: exchanging a (d x k)-deep frame once per k
#: chain rounds removes (1 - 1/k) of the per-round hop latency in the
#: ideal model, but the wider frame costs serialization, cache
#: pressure, and ring-recompute growth the latency term does not see —
#: and the two languages pay it differently (the XLA chain re-windows
#: in HBM; the Pallas chains deepen the in-kernel VMEM-resident walk,
#: whose >6-deep cost has no measured FUSE_COST_RATIO entry, so this
#: literal absorbs it). The defaults are the analytic guesses until
#: ``benchmarks/update_halo_depth.py --apply`` rewrites each entry
#: from a measured language-tagged ``halo_bench.py --ab --halo-depths``
#: artifact (the same calibration loop as OVERLAP_EFFICIENCY). The
#: "xla" value is the PR 9 literal, unchanged.
HALO_DEPTH_EFFICIENCY = {
    "xla": 0.9,
    "pallas": 0.9,
}


#: Single-chip compute-cost ratio of the ``bf16_f32acc`` posture
#: (docs/PRECISION.md) vs the f32 baseline the ``MEASURED_US`` anchors
#: were captured at: the stencil is memory-bandwidth-bound (envelope
#: probe, BASELINE.md), and bf16 fields halve the HBM bytes per step
#: while the f32 accumulation keeps the VPU/MXU work roughly flat — so
#: the analytic guess sits between the 0.5 bandwidth bound and 1.0
#: flops-flat, leaning conservative. An ANALYTIC literal until the
#: precision A/B (``benchmarks/precision_bench.py``) measures it on
#: real hardware — the same calibration discipline as
#: OVERLAP_EFFICIENCY / HALO_DEPTH_EFFICIENCY.
BF16_COMPUTE_RATIO = 0.75


def precision_compute_ratio(compute_precision: str) -> float:
    """Anchor-cost multiplier for a compute-precision posture: 1.0 for
    f32/equality (the anchors' own posture), :data:`BF16_COMPUTE_RATIO`
    for ``bf16_f32acc``. The HALO side of the posture needs no factor
    here — callers price it through ``itemsize`` (2 for bf16 fields),
    which is what halves every ``halo_bytes_*`` figure."""
    return (BF16_COMPUTE_RATIO
            if compute_precision == "bf16_f32acc" else 1.0)


def sstep_amortization(halo_depth: int, efficiency: float = None,
                       lang: str = "xla") -> float:
    """Fraction of the per-chain-round exchange hop latency that
    REMAINS under s-step exchange at depth ``halo_depth`` — 1.0 at
    k=1 (every round exchanges), approaching ``1 - efficiency`` as k
    grows (the calibrated share of the ideal 1/k win). ``lang``
    selects the per-language calibrated efficiency
    (:data:`HALO_DEPTH_EFFICIENCY`) when ``efficiency`` is None."""
    k = max(1, int(halo_depth))
    if k == 1:
        return 1.0
    eff = HALO_DEPTH_EFFICIENCY[lang] if efficiency is None else efficiency
    return 1.0 - eff * (1.0 - 1.0 / k)


def overlap_fraction(compute_us: float, comm_us: float,
                     efficiency: float = None) -> float:
    """Calibrated overlap fraction for a config: the share of raw comm
    hidden behind ``compute_us`` of comm-independent interior work."""
    if comm_us <= 0 or compute_us <= 0:
        return 0.0
    eff = OVERLAP_EFFICIENCY if efficiency is None else efficiency
    return min(1.0, eff * compute_us / comm_us)


def _resolve_overlap(overlap, compute_us: float, raw_comm_us: float):
    """Projection-row overlap: an explicit fraction, or ``"auto"`` for
    the calibrated ``overlap_fraction`` of this config."""
    if overlap == "auto":
        return overlap_fraction(compute_us, raw_comm_us)
    return float(overlap)


def anchor_us(lang: str, L: int) -> float:
    """Single-chip µs/step for a full L^3 grid: the measured anchor with
    the closest side, rescaled throughput-flat (conservative — larger
    locals measure closer to roofline, BASELINE.md)."""
    sides = sorted(s for k, s in MEASURED_US if k == lang)
    side = min(sides, key=lambda s: abs(s - L))
    return MEASURED_US[(lang, side)] * (L / side) ** 3


def project(
    local: int,
    fuse: int,
    us_per_step: float,
    *,
    stage_ratio: float = 1.0,
    itemsize: int = 4,
    links: int = 6,
    link_gbps: float = 90.0,
    hop_us: float = 1.0,
    overlap: float = 0.0,
    halo_depth: int = 1,
    n_fields: int = 2,
) -> dict:
    """Weak-scaling efficiency projection for one cubic-local config.

    Efficiency is sharded-per-step time over the single-chip baseline
    ``us_per_step``, accounting for ALL three sharding overheads:

    * per-stage cost ratio — ``stage_ratio`` x the fused single-chip
      step (1.0 for the XLA language, which is stepwise on one chip
      too);
    * ring recompute — stage s computes a (local+2(k-1-s))-wide
      window (``parallel/temporal.py``), extra volume the single-chip
      measurement does not contain;
    * exposed communication (serialization at the max-loaded link +
      hop latency), amortized over the k steps per exchange round.

    ``halo_depth`` (s-step exchange, docs/TEMPORAL.md) multiplies the
    steps per exchange round: the frame deepens to
    ``fuse * halo_depth`` (pricing the wider slabs and the extra ring
    recompute exactly) while the hop-latency amortization beyond one
    chain round is discounted by the calibrated
    :data:`HALO_DEPTH_EFFICIENCY`.
    """
    sk = max(1, int(halo_depth))
    s_steps = fuse * sk  # steps per exchange round
    wide = local + 2 * s_steps  # corner-propagated exchange slab
    # Every exchanged face carries all of the model's fields.
    face_bytes = wide * wide * s_steps * itemsize * n_fields
    total_bytes = 6 * face_bytes
    # The exchange completes at the MAX-loaded link, not at aggregate
    # bandwidth: with 6 links each face rides its own (1 face/link);
    # with 4 (v5e 2D torus) the y/z-shared links carry 2 faces each.
    faces_per_link = -(-6 // links)  # ceil
    ser_us = faces_per_link * face_bytes / (link_gbps * 1e3) / s_steps
    # One exchange round per s_steps; the amortization beyond the
    # chain-round baseline is what s-step adds, discounted by the
    # calibrated efficiency.
    lat_us = 6 * hop_us / fuse * sstep_amortization(sk)
    raw_us = ser_us + lat_us
    recompute = sum(
        (local + 2 * (s_steps - 1 - s)) ** 3 for s in range(s_steps)
    ) / (s_steps * local**3)
    ov = _resolve_overlap(
        overlap, us_per_step * stage_ratio * recompute, raw_us
    )
    comm_us = raw_us * (1.0 - ov)
    eff = us_per_step / (us_per_step * stage_ratio * recompute + comm_us)
    return {
        "local": local,
        "fuse": fuse,
        "halo_depth": sk,
        "stage_ratio": stage_ratio,
        "compute_us_per_step": round(us_per_step, 1),
        "ring_recompute_ratio": round(recompute, 4),
        "halo_bytes_per_round": total_bytes,
        "halo_bytes_per_step": round(total_bytes / s_steps),
        "exchanges_per_step": round(1.0 / s_steps, 4),
        "comm_us_per_step_exposed": round(comm_us, 2),
        "comm_us_per_step_hidden": round(raw_us - comm_us, 2),
        "links": links,
        "link_gbps": link_gbps,
        "overlap": round(ov, 4),
        "projected_weak_scaling_eff": round(eff, 4),
    }


def best_fuse(local, us_per_step, *, kmax=8, **kw):
    """The fuse depth minimizing total sharding overhead for a config —
    recompute grows and comm shrinks with k, and ``GS_FUSE`` is a free
    knob at launch time, so the projection reports the swept optimum."""
    return max(
        (project(local, k, us_per_step, **kw) for k in range(1, kmax + 1)),
        key=lambda r: r["projected_weak_scaling_eff"],
    )


def pin_big_vmem() -> None:
    """Pin the v4/v5/v6 VMEM budget so feasibility checks never dial a
    device — for CLI/model use where no backend should be touched."""
    from ..ops import pallas_stencil as ps

    ps._VMEM_BUDGET = ps._VMEM_BUDGETS["TPU v5 lite"]


def _feasible_chain_depth(local, itemsize, kmax, sublane=8, ypad=True,
                          n_fields=2):
    """Deepest chain depth the real Mosaic VMEM feasibility check
    admits for this local shape (``pallas_stencil.max_feasible_fuse*``);
    ``ypad`` selects the xy-chain form (y-halo planes) vs the 1D
    x-chain; ``n_fields`` scales the per-slab VMEM bytes (every field
    rides the same slab pipeline)."""
    from ..ops import pallas_stencil as ps

    if ypad:
        return ps.max_feasible_fuse_ypad(*local, itemsize, kmax, sublane,
                                         n_fields=n_fields)
    return ps.max_feasible_fuse(*local, itemsize, kmax,
                                n_fields=n_fields)


def band_cells_per_round(local, k):
    """Output cells of the two z-side XLA band chains per k-step round
    (``parallel/temporal.window_chain``): stage s shrinks the
    (nx+2k, ny+2k, 3k) window by one cell per side."""
    nx, ny, nz = local
    cells = 0
    for s in range(k):
        cells += ((nx + 2 * (k - s) - 2) * (ny + 2 * (k - s) - 2)
                  * (3 * k - 2 * s - 2))
    return 2 * cells


def project_chain(
    dims,
    L: int,
    fuse: int,
    base_us_full: float,
    *,
    local=None,
    itemsize: int = 4,
    sublane: int = 8,
    links: int = 6,
    link_gbps: float = 90.0,
    hop_us: float = 1.0,
    overlap: float = 0.0,
    xla_us_per_cell: float = None,
    halo_depth: int = 1,
    n_fields: int = 2,
) -> dict:
    """Weak-scaling projection for the round-4 cross-shard fused chain
    (``parallel/temporal.xy_chain``) on an (n, m, p) mesh.

    ``halo_depth`` (s-step exchange, docs/TEMPORAL.md) multiplies the
    in-kernel steps per exchange round: the frame deepens to
    ``fuse * halo_depth`` (pricing the wider y planes, x ring, and z
    bands exactly) while the per-stage cost stays keyed on the BASE
    fuse's measured ratio and the hop-latency amortization beyond one
    chain round is discounted by the calibrated Pallas
    :data:`HALO_DEPTH_EFFICIENCY` — the same scheme as
    :func:`project_1d`, because the generated kernel realizes
    halo_depth=k at fuse=d as the fuse=k*d chain program.

    Every sharded stage runs IN-KERNEL at the fused schedule (the 1.46x
    single-step penalty of the retired round-3 design is gone); the
    overheads are:

    * ``FUSE_COST_RATIO[k]`` — in-kernel depth vs the k=5 optimum;
    * y-plane growth — the kernel's VMEM plane carries a k-deep y halo
      on each side, each rounded up to the sublane tile
      (``pallas_stencil.y_halo_rows``), so every plane computes
      (ny + 2*hy)/ny more rows;
    * x ring recompute — mid-stage windows extend (k-1-s) planes per
      side, 1 + (k-1)/nx extra volume (same as the 1D x-chain);
    * z bands (p > 1 only) — two k-wide bands per round recomputed in
      XLA at the measured big-grid XLA per-cell rate (conservative: the
      band working set can be VMEM-resident, which XLA fuses faster);
    * exposed comm — 4 slab ppermutes per round for (n, m, 1), 6 for
      z-sharded, each face on its own torus link, serialization at the
      largest face.

    ``base_us_full`` is the fused single-chip µs/step for the WHOLE L^3
    grid; per-shard compute is 1/(n*m*p) of it (throughput-flat,
    conservative for big locals). ``local`` overrides the per-shard
    block shape — callers with pad-and-mask storage (non-divisible L)
    pass their ceil blocks so the projection describes the block shape
    actually run, the one the feasibility gates were applied to;
    the default is exact floor division. ``links`` is the number of
    torus links the exchange can ride (``fabric_for``): with fewer
    links than faces the serialization completes at the max-loaded
    link carrying ceil(n_faces/links) faces, mirroring ``project()``'s
    ``faces_per_link`` — a v5e/v6e 2D torus (4 links) pays 2 faces on
    the shared links for a z-sharded chain.
    """
    n, m, p = dims
    if local is None:
        local = (L // n, L // m, L // p)
    nx, ny, nz = local
    us_base = base_us_full / (n * m * p)
    r = FUSE_COST_RATIO.get(fuse)
    if r is None:
        raise ValueError(f"no measured fuse-cost ratio for k={fuse}")
    k = fuse
    sk = max(1, int(halo_depth))
    s_steps = k * sk  # in-kernel steps per exchange round
    from ..ops import pallas_stencil as ps

    ny_ext = ny + 2 * ps.y_halo_rows(s_steps, sublane)
    y_over = ny_ext / ny if (m > 1 or p > 1) else 1.0
    x_ring = 1.0 + (s_steps - 1) / nx
    compute_us = us_base * r * y_over * x_ring

    if p > 1:
        if xla_us_per_cell is None:
            xla_us_per_cell = MEASURED_US[("XLA", 256)] / 256**3
        band_us = (band_cells_per_round(local, s_steps) * xla_us_per_cell
                   / s_steps)
        # Frame faces span the padded extents (corner propagation).
        zx, zy = nz + 2 * s_steps, ny + 2 * s_steps
        face_bytes = max(
            zy * zx, (nx + 2 * s_steps) * zx, (nx + 2 * s_steps) * zy
        ) * itemsize * n_fields
        n_faces = 6
    else:
        band_us = 0.0
        face_bytes = max(ny_ext * nz, nx * nz) * itemsize * n_fields
        n_faces = (2 if n > 1 else 0) + (2 if m > 1 else 0)
    # Depth-wide slabs every s_steps steps -> per-step bytes are
    # depth-independent; completion at the MAX-loaded link: with fewer
    # links than faces (v5e/v6e 2D torus) some links carry
    # ceil(n_faces/links) faces.
    faces_per_link = -(-n_faces // links) if n_faces else 0
    ser_us = faces_per_link * face_bytes / (link_gbps * 1e3)
    lat_us = n_faces * hop_us / k * sstep_amortization(sk, lang="pallas")
    raw_us = ser_us + lat_us
    # Only the kernel pass is comm-independent dataflow in the split-
    # phase round; the band recomputes consume the exchange, so they
    # are not part of the hiding budget.
    ov = _resolve_overlap(overlap, compute_us, raw_us)
    comm_us = raw_us * (1.0 - ov)

    eff = us_base / (compute_us + band_us + comm_us)
    return {
        "mesh": f"{n},{m},{p}",
        "local": list(local),
        "fuse": k,
        # s-step exchange depth: the generated kernel realizes it as a
        # (fuse x halo_depth)-deep in-kernel chain per exchange round
        # (simulation.py Pallas chain paths, docs/TEMPORAL.md).
        "halo_depth": sk,
        "fuse_cost_ratio": r,
        "fuse_cost_ratio_interpolated": k in (2, 3),
        "compute_us_per_step": round(us_base, 1),
        "halo_bytes_per_step": round(n_faces * face_bytes / s_steps),
        "exchanges_per_step": (round(1.0 / s_steps, 4)
                               if n_faces else 0.0),
        "y_plane_overhead": round(y_over, 4),
        "x_ring_recompute": round(x_ring, 4),
        "z_band_us_per_step": round(band_us, 2),
        "comm_us_per_step_exposed": round(comm_us, 2),
        "comm_us_per_step_hidden": round(raw_us - comm_us, 2),
        "links": links,
        "link_gbps": link_gbps,
        "overlap": round(ov, 4),
        "projected_weak_scaling_eff": round(eff, 4),
    }


def _mesh_candidates(n_devices: int, L: int):
    """All (n, m, p) ordered factorizations of ``n_devices`` whose dims
    divide L — the mixed-mesh sweep space."""
    out = []
    for n in range(1, n_devices + 1):
        if n_devices % n or L % n:
            continue
        rest = n_devices // n
        for m in range(1, rest + 1):
            if rest % m or L % m:
                continue
            p = rest // m
            if L % p:
                continue
            out.append((n, m, p))
    return out


def best_chain_depth(dims, L, base_us_full, *, local=None, itemsize=4,
                     kmin=2, kmax=8, n_fields=2, **kw):
    """Best feasible chain row for ONE mesh: routes (n,1,1) to the 1D
    x-chain model and everything else to the xy-chain model, applying
    the SAME feasibility gates the kernel dispatch applies (Mosaic's
    128-lane tiling on the local z extent, VMEM slab fit, measured
    fuse-ratio availability) so the model never promises a schedule
    the kernel would silently decline. ``None`` when no depth in
    [kmin, kmax] survives. ``local`` defaults to exact division;
    callers with pad-and-mask storage pass their ceil blocks."""
    from ..ops import pallas_stencil as ps

    n, m, p = dims
    if local is None:
        local = tuple(L // d for d in dims)
    if min(local) < 2 or ps.mosaic_gate_reason(local, itemsize):
        # Dispatch-level Mosaic gates (f64 fallback, 128-lane tiling)
        # shared with the kernel — no chain schedule exists to project.
        return None
    sublane = 16 if itemsize == 2 else 8
    if m == 1 and p == 1:
        cap = _feasible_chain_depth(
            local, itemsize, max(kmin, local[0]), ypad=False,
            n_fields=n_fields,
        )
        ks = [k for k in FUSE_COST_RATIO if kmin <= k <= min(cap, kmax)]
        # The projection must describe the SAME block shape the gates
        # above were applied to — pass ``local`` through instead of
        # letting the model recompute it with floor division.
        rows = [project_1d(n, L, k, base_us_full, local=local,
                           itemsize=itemsize, n_fields=n_fields, **kw)
                for k in ks]
    else:
        cap = min(kmax, local[0], local[1])
        if p > 1:
            cap = min(cap, local[2] // 2)
        cap = _feasible_chain_depth(local, itemsize, cap, sublane,
                                    n_fields=n_fields)
        ks = [k for k in FUSE_COST_RATIO if kmin <= k <= cap]
        rows = [project_chain(dims, L, k, base_us_full, local=local,
                              itemsize=itemsize, sublane=sublane,
                              n_fields=n_fields, **kw)
                for k in ks]
    if not rows:
        return None
    return max(rows, key=lambda r: r["projected_weak_scaling_eff"])


def best_chain(n_devices, L, base_us_full, *, itemsize=4, kmax=8, **kw):
    """Sweep mesh factorization x feasible chain depth for the round-4
    chain; returns the best row (the VERDICT-8 mixed-mesh sweep), or
    ``None`` when no factorization admits a feasible depth >= 2."""
    best = None
    for dims in _mesh_candidates(n_devices, L):
        r = best_chain_depth(dims, L, base_us_full, itemsize=itemsize,
                             kmax=kmax, **kw)
        if r is not None and (
            best is None
            or r["projected_weak_scaling_eff"]
            > best["projected_weak_scaling_eff"]
        ):
            best = r
    return best


def project_1d(
    n: int,
    L: int,
    fuse: int,
    base_us_per_step: float,
    *,
    local=None,
    itemsize: int = 4,
    links: int = 6,
    link_gbps: float = 90.0,
    hop_us: float = 1.0,
    overlap: float = 0.0,
    halo_depth: int = 1,
    n_fields: int = 2,
) -> dict:
    """Weak-scaling projection for the 1D x-sharded in-kernel fused
    chain (``GS_TPU_MESH_DIMS=n,1,1``): each shard owns an
    (L/n, L, L) slab, the only halo is a fuse-wide x-slab pair riding
    2 torus links, and the kernel runs its in-kernel chain ACROSS the
    shard boundary — so the per-stage cost is the fused single-chip
    schedule scaled by the measured fuse-depth ratio, not a per-stage
    single-step penalty.

    ``base_us_per_step`` is the fused single-chip time for the WHOLE
    L^3 grid (the 1-chip baseline); per-shard compute is 1/n of it
    (throughput-flat assumption, conservative: bigger blocks measure
    closer to roofline). ``local`` overrides the (nx, ny, nz) block
    shape (pad-and-mask ceil blocks for non-divisible L; default is
    floor division with full L x L slab faces); ``links`` caps how
    many torus links the 2-face exchange can ride.
    """
    if local is None:
        local = (L // n, L, L)
    nx, ny, nz = local
    us_base = base_us_per_step / n
    sk = max(1, int(halo_depth))
    s_steps = fuse * sk  # steps per exchange round (s-step exchange)
    recompute = 1.0 + (s_steps - 1) / nx  # ring grows only along x
    r = FUSE_COST_RATIO.get(fuse)
    if r is None:
        raise ValueError(f"no measured fuse-cost ratio for k={fuse}")
    # k-wide slab each direction every k steps => per-step bytes are
    # k-independent; with >= 2 usable links each face rides its own x
    # link, else they serialize on the shared one.
    faces_per_link = -(-2 // links)
    ser_us = (faces_per_link * ny * nz * itemsize * n_fields
              / (link_gbps * 1e3))
    lat_us = 2 * hop_us / fuse * sstep_amortization(sk, lang="pallas")
    raw_us = ser_us + lat_us
    ov = _resolve_overlap(overlap, us_base * r * recompute, raw_us)
    comm_us = raw_us * (1.0 - ov)
    eff = us_base / (us_base * r * recompute + comm_us)
    return {
        "mesh": f"{n},1,1",
        "local": nx,
        "fuse": fuse,
        "halo_depth": sk,
        "fuse_cost_ratio": r,
        "fuse_cost_ratio_interpolated": fuse in (2, 3),
        "compute_us_per_step": round(us_base, 1),
        "ring_recompute_ratio": round(recompute, 4),
        "halo_bytes_per_step": round(2 * ny * nz * itemsize * n_fields),
        "exchanges_per_step": round(1.0 / s_steps, 4),
        "comm_us_per_step_exposed": round(comm_us, 2),
        "comm_us_per_step_hidden": round(raw_us - comm_us, 2),
        "links": links,
        "link_gbps": link_gbps,
        "overlap": round(ov, 4),
        "projected_weak_scaling_eff": round(eff, 4),
    }


def best_fuse_1d(n, L, base_us, *, itemsize=4, **kw):
    """1D x-chain depth sweep including the depth-1 (unfused-exchange)
    row — the CLI's explicit 1D comparison rows; feasibility gates
    shared with the kernel dispatch via :func:`best_chain_depth`."""
    return best_chain_depth((n, 1, 1), L, base_us, itemsize=itemsize,
                            kmin=1, kmax=max(FUSE_COST_RATIO), **kw)


# --------------------------------------------------------- Auto dispatch

#: Fabric by ``device_kind`` (lower-cased): per-link GB/s per
#: direction, links usable by the 6-face exchange. v5e is a 2D torus
#: (z faces share links with y); v4/v5p/v6 are 3D tori. ``cpu`` is
#: the virtual-device entry (no real fabric; the generic 3D-torus
#: numbers keep CPU projections comparable). A TPU that is not listed
#: is an error, not a guess.
_FABRICS = {
    "tpu v4": (90.0, 6),
    "tpu v5": (90.0, 6),
    "tpu v5p": (90.0, 6),
    "tpu v5 lite": (45.0, 4),
    "tpu v5e": (45.0, 4),
    "tpu v6 lite": (90.0, 4),
    "tpu v6e": (90.0, 4),
    "cpu": (90.0, 6),
}


def fabric_for(device_kind: str):
    """(link_gbps, links) for a device-kind string (``""`` — a virtual
    mesh with no kind — is ``cpu``), env-overridable via
    ``GS_AUTO_LINK_GBPS`` / ``GS_AUTO_LINKS``. Raises for a kind that
    is not in :data:`_FABRICS`."""
    from ..config.env import env_float, env_int

    kind = (device_kind or "cpu").strip().lower()
    if kind not in _FABRICS:
        raise ValueError(
            f"no ICI fabric for device kind {device_kind!r}; add it to "
            f"icimodel._FABRICS (known: {sorted(_FABRICS)})"
        )
    gbps, links = _FABRICS[kind]
    gbps = env_float("GS_AUTO_LINK_GBPS", float(gbps))
    links = env_int("GS_AUTO_LINKS", int(links))
    return gbps, links


def select_kernel(
    dims,
    L: int,
    *,
    platform: str = "tpu",
    device_kind: str = "",
    itemsize: int = 4,
    fuse: int = 5,
    eff_target: float = 0.90,
    objective: str = None,
    overlap="auto",
    hop_us: float = 1.0,
    sweep_mesh: bool = False,
    n_fields: int = 2,
):
    """Resolve ``kernel_language = "Auto"`` for a concrete run config.

    Returns ``(lang, info)`` with ``lang`` in {"pallas", "xla"} and
    ``info`` a JSON-able record of the decision (projected rows, the
    objective, and who holds the >=90% weak-scaling bar). With
    ``sweep_mesh`` (the mesh was NOT operator-forced) the Pallas chain
    is projected at its best mesh factorization x feasible depth
    (``best_chain``) instead of at ``dims`` — the chosen mesh/depth
    come back in the winning row for the caller to adopt.

    Policy (documented in BASELINE.md "Auto dispatch"):

    * off-TPU -> XLA always: the Pallas path off-TPU is the interpret-
      mode correctness tool (~1000x, BASELINE.md) or the per-shard XLA
      fallback — never a performance win;
    * single device -> Pallas when the fused kernel is VMEM-feasible
      for this shape (measured 2.5x the XLA kernel single-chip), else
      XLA;
    * sharded -> project both languages with the ICI model for the
      ACTUAL mesh and pick by ``objective``:
      - "efficiency" (default): the BASELINE.json north-star target is
        weak-scaling >=90% at pod scale, so prefer the faster kernel
        AMONG those projected to meet ``eff_target``; when none meets
        it, fall back to fastest outright (and say so in ``info``);
      - "throughput" (``GS_AUTO_OBJECTIVE=throughput``): fastest
        projected absolute step time, efficiency be damned — the
        Pallas chain's single-chip base is 2.3-4.4x the XLA kernel's,
        so it can lose the efficiency race while winning wall-clock.

    ``overlap``: the comm-hiding assumption threaded into every
    projection row. The default ``"auto"`` applies the calibrated
    split-phase overlap (``overlap_fraction`` — the runtime default is
    split-phase ON for sharded runs); pass ``0.0`` when the run has
    ``GS_COMM_OVERLAP=off`` so the pick reflects fully-exposed comm,
    or any explicit fraction for sensitivity studies.
    """
    from ..config.env import env_str

    objective = objective or env_str(
        "GS_AUTO_OBJECTIVE", "efficiency"
    )
    if objective not in ("efficiency", "throughput"):
        raise ValueError(
            f"GS_AUTO_OBJECTIVE must be 'efficiency' or 'throughput', "
            f"got {objective!r}"
        )
    n, m, p = dims
    n_devices = n * m * p
    info = {
        "dims": list(dims), "L": L, "platform": platform,
        "objective": objective, "eff_target": eff_target,
    }

    if platform != "tpu":
        info["reason"] = (
            "off-TPU the Pallas path is the interpret-mode correctness "
            "tool or the per-shard XLA fallback; XLA is the compiled path"
        )
        return "xla", info

    if n_devices == 1:
        from ..ops import pallas_stencil as ps

        gate = ps.mosaic_gate_reason((L, L, L), itemsize)
        if gate is not None:
            # The kernel would silently run its XLA fallback at this
            # shape/dtype — pick XLA openly so the recorded language
            # matches what executes.
            info["reason"] = f"single chip: {gate}"
            return "xla", info
        feasible = _feasible_chain_depth(
            (L, L, L), itemsize, max(fuse, 1), ypad=False,
            n_fields=n_fields,
        )
        if feasible >= 1:
            info["reason"] = (
                f"single chip: fused Pallas kernel feasible (depth "
                f"{feasible}), measured ~2.5x the XLA kernel"
            )
            return "pallas", info
        info["reason"] = (
            "single chip: no VMEM-feasible slab layout for this shape"
        )
        return "xla", info

    link_gbps, links = fabric_for(device_kind)
    info["link_gbps"], info["links"] = link_gbps, links
    # ``links`` rides along to BOTH languages' projections: the chain
    # models share the serialization-at-the-max-loaded-link treatment
    # with project(), so Auto's cross-language pick no longer
    # underestimates z-sharded Pallas chain comm on 2D-torus fabrics
    # (v5e/v6e: 6 faces on 4 links).
    kw = dict(links=links, link_gbps=link_gbps, hop_us=hop_us,
              overlap=overlap, n_fields=n_fields)

    # XLA language on the actual mesh: locals may be non-cubic; use the
    # cubic-equivalent side (the model's project() is cubic) — face
    # geometry differences are second-order next to the language choice.
    local = tuple(-(-L // d) for d in dims)  # ceil: pad-and-mask storage
    side = round((local[0] * local[1] * local[2]) ** (1 / 3))
    xla_us = anchor_us("XLA", L) / n_devices
    xla_row = best_fuse(side, xla_us, itemsize=itemsize, **kw)
    xla_row["kernel"] = "xla"

    # Pallas chain: at the best swept mesh when the caller lets us pick
    # (sweep_mesh), else at the actual mesh — 1D x-sharded runs the
    # x-chain, anything else the xy-chain (+ z bands when p > 1), at
    # the deepest VMEM-feasible depth <= the configured fuse.
    base_full = anchor_us("Pallas", L)
    if fuse < 2:
        # GS_FUSE=1 pins the unfused exchange: no chain schedule is
        # available to the run, so projecting one would justify the
        # pick with a schedule that cannot execute.
        chain_row = None
    elif sweep_mesh:
        chain_row = best_chain(n_devices, L, base_full,
                               itemsize=itemsize, kmax=fuse, **kw)
    else:
        chain_row = best_chain_depth(dims, L, base_full, local=local,
                                     itemsize=itemsize, kmax=fuse, **kw)
    if chain_row is not None:
        chain_row["kernel"] = "pallas"

    # Absolute per-step time: efficiency is relative to each language's
    # OWN single-chip base, so cross-language comparison must go
    # through it (the Pallas base is 2.3-4.4x faster).
    def step_us(row, base):
        return base / row["projected_weak_scaling_eff"]

    rows = [(xla_row, xla_us)]
    if chain_row is not None:
        rows.append((chain_row, base_full / n_devices))
    for row, base in rows:
        row["projected_step_us"] = round(step_us(row, base), 1)
    info["rows"] = [r for r, _ in rows]
    meets = [(r, b) for r, b in rows
             if r["projected_weak_scaling_eff"] >= eff_target]
    info["eff_target_holders"] = [r["kernel"] for r, _ in meets]

    if objective == "efficiency" and meets:
        pick, base = min(meets, key=lambda rb: step_us(*rb))
        info["reason"] = (
            f"fastest among kernels projected >= {eff_target:.0%} "
            "weak-scaling"
        )
    else:
        pick, base = min(rows, key=lambda rb: step_us(*rb))
        if objective == "efficiency":
            info["reason"] = (
                f"no kernel projected >= {eff_target:.0%} at this "
                "config; fastest outright"
            )
        else:
            info["reason"] = "fastest projected absolute step time"
    return pick["kernel"], info


def projected_step_us(
    lang: str,
    dims,
    L: int,
    fuse: int,
    *,
    itemsize: int = 4,
    links: int = 6,
    link_gbps: float = 90.0,
    hop_us: float = 1.0,
    overlap="auto",
    local=None,
    halo_depth: int = 1,
    compute_precision: str = "f32",
    n_fields: int = 2,
) -> Optional[float]:
    """Model-projected µs/step for ONE concrete (language, mesh, depth)
    config — the scalar the measured autotuner (``tune/candidates``)
    ranks its shortlist by. ``compute_precision`` (docs/PRECISION.md)
    prices the ``bf16_f32acc`` posture: the single-chip anchor scales
    by :data:`BF16_COMPUTE_RATIO` and the caller passes the bf16
    ``itemsize`` (2), which halves the projected halo bytes — the two
    halves of why the posture wins on a bandwidth-bound mesh. Routes to the same projection the Auto
    dispatch uses for that shape (cubic :func:`project` for the XLA
    language, :func:`project_1d`/:func:`project_chain` for the Pallas
    chains, the single-chip anchors for one device) and converts
    efficiency back to absolute time against the language's own base.
    ``halo_depth`` prices the s-step exchange for BOTH languages —
    :func:`project` for XLA, :func:`project_1d`/:func:`project_chain`
    for the Pallas chains (whose generated kernel realizes k at fuse=d
    as the fuse=k*d chain program). ``None`` when the model has
    nothing to say (no measured fuse ratio, no chain at this depth) —
    unscored candidates rank last, they are not excluded."""
    n, m, p = dims
    ndev = n * m * p
    ratio = precision_compute_ratio(compute_precision)
    if local is None:
        local = tuple(-(-L // d) for d in dims)
    if lang == "xla":
        base = anchor_us("XLA", L) / ndev * ratio
        if ndev == 1:
            return base
        side = max(2, round((local[0] * local[1] * local[2]) ** (1 / 3)))
        row = project(side, max(1, fuse), base, itemsize=itemsize,
                      links=links, link_gbps=link_gbps, hop_us=hop_us,
                      overlap=overlap, halo_depth=halo_depth,
                      n_fields=n_fields)
        return base / row["projected_weak_scaling_eff"]
    base_full = anchor_us("Pallas", L) * ratio
    r = FUSE_COST_RATIO.get(fuse)
    if ndev == 1:
        # halo_depth is a no-op unsharded (no exchange to amortize).
        return None if r is None else base_full * r
    if fuse < 2 or r is None:
        return None
    kw = dict(local=local, itemsize=itemsize, links=links,
              link_gbps=link_gbps, hop_us=hop_us, overlap=overlap,
              halo_depth=halo_depth, n_fields=n_fields)
    try:
        if m == 1 and p == 1:
            row = project_1d(n, L, fuse, base_full, **kw)
        else:
            row = project_chain(dims, L, fuse, base_full,
                                sublane=16 if itemsize == 2 else 8, **kw)
    except ValueError:
        return None
    return (base_full / ndev) / row["projected_weak_scaling_eff"]


def comm_report(sim) -> dict:
    """Per-step communication budget of a constructed ``Simulation`` —
    the ``comm`` section of RunStats (``utils/profiler.py``), mirroring
    the ``io`` overlap section: how many µs/step of halo exchange the
    ICI model projects for this exact config, and how much of it the
    split-phase schedule hides vs exposes.

    This is a MODEL projection (single-chip anchors + fabric figures,
    same machinery as Auto dispatch), not a measurement — host wall
    clock cannot attribute device-side comm, and a CPU-mesh run has no
    ICI at all. The section says so (``"model"``) and records the
    knobs, so a stats reader can recompute or recalibrate
    (``benchmarks/update_overlap.py``).
    """
    import numpy as np

    if not sim.sharded:
        return {
            "model": "ici-projection",
            "mode": "single-device",
            "comm_us_per_step": 0.0,
            "hidden_us": 0.0,
            "exposed_us": 0.0,
            "overlap": 0.0,
            "halo_depth": 1,
            "exchanges_per_step": 0.0,
            "halo_bytes_per_step": 0,
        }
    dims = sim.domain.dims
    L = sim.settings.L
    itemsize = int(np.dtype(sim.dtype).itemsize)
    try:
        kind = sim.mesh.devices.flat[0].device_kind
    except Exception:  # noqa: BLE001 — virtual meshes have no kind
        kind = ""
    link_gbps, links = fabric_for(kind)
    overlap_on = bool(getattr(sim, "comm_overlap", False))
    ov_arg = "auto" if overlap_on else 0.0
    fuse = max(1, int(sim._fuse_base()))
    local = tuple(-(-L // d) for d in dims)
    lang = "Pallas" if sim.kernel_language == "pallas" else "XLA"
    kw = dict(itemsize=itemsize, links=links, link_gbps=link_gbps,
              overlap=ov_arg,
              n_fields=int(getattr(sim.model, "n_fields", 2)))
    row = None
    if lang == "Pallas" and fuse >= 2:
        k = min(fuse, max(FUSE_COST_RATIO))
        k = k if k in FUSE_COST_RATIO else max(
            f for f in FUSE_COST_RATIO if f <= k
        )
        base_full = anchor_us("Pallas", L)
        sk = max(1, int(getattr(sim, "halo_depth", 1)))
        try:
            if dims[1] == 1 and dims[2] == 1:
                row = project_1d(dims[0], L, k, base_full, local=local,
                                 halo_depth=sk, **kw)
            else:
                row = project_chain(dims, L, k, base_full, local=local,
                                    halo_depth=sk, **kw)
        except ValueError:
            row = None
    if row is None:
        side = max(2, round(
            (local[0] * local[1] * local[2]) ** (1 / 3)
        ))
        n_dev = dims[0] * dims[1] * dims[2]
        row = project(side, fuse, anchor_us("XLA", L) / n_dev,
                      halo_depth=getattr(sim, "halo_depth", 1), **kw)
    exposed = row["comm_us_per_step_exposed"]
    hidden = row.get("comm_us_per_step_hidden", 0.0)
    return {
        "model": "ici-projection",
        "mode": "overlap" if overlap_on else "fused",
        "device_kind": kind or None,
        "kernel": lang,
        "fuse": row.get("fuse", fuse),
        # s-step exchange visibility (docs/TEMPORAL.md): how often this
        # schedule actually exchanges, and how many ghost bytes each
        # step amortizes — the numbers that make a halo_depth win
        # legible in gs_report.py.
        "halo_depth": row.get("halo_depth",
                              getattr(sim, "halo_depth", 1)),
        "exchanges_per_step": row.get("exchanges_per_step", 0.0),
        "halo_bytes_per_step": row.get("halo_bytes_per_step", 0),
        "links": links,
        "link_gbps": link_gbps,
        "comm_us_per_step": round(exposed + hidden, 2),
        "hidden_us": hidden,
        "exposed_us": exposed,
        "overlap": row["overlap"],
    }
