"""Public simulation API: settings -> model -> devices -> fields -> step loop.

This is the TPU-native analog of the reference's ``Simulation`` module
(``src/simulation/public.jl`` + ``communication.jl:15-46``):

* ``initialization(args)``  -> parse config, select devices, build the
  domain decomposition, initialize fields (``communication.jl:15-33``).
* ``Simulation.iterate(n)`` -> advance n steps (``public.jl:45-71``); halo
  exchange + stencil update + "swap" all live inside one jitted
  ``lax.fori_loop`` so XLA fuses and overlaps them — there is no per-step
  host round-trip, unlike the reference which re-dispatches from strings
  every step (``public.jl:47``, SURVEY defect #9).
* ``Simulation.get_fields()`` -> host copies of the model's fields
  (``Simulation_CPU.jl:125-133``; ghost stripping is a no-op here because
  fields are stored interior-shaped).

Multi-model: the physics comes from a registered model declaration
(``models/``: named fields, per-field boundary constants, typed params,
pure reaction, init) selected by the ``[model]`` TOML table; Gray-Scott
is the default and flagship. ``self.fields`` is the model's field tuple
in declaration order (``self.u``/``self.v`` alias fields 0/1 for the
two-field models). Everything below the model boundary — halo exchange,
split-phase overlap, temporal blocking, autotune, snapshots, and the
fused Pallas kernel itself — is model-generic: ``ops/kernelgen``
trace-inlines the model's pure reaction into the slab pipeline, and
Pallas eligibility is a feasibility check on the reaction's jaxpr
(``kernelgen.generation_gate_reason``, recorded as the ``kernel_gate``
provenance in ``kernel_selection``), not a model-name gate.

Distribution: with >1 device of the selected platform, fields are sharded
``P('x','y','z')`` over a 3D ``jax.sharding.Mesh`` (the ``MPI.Cart_create``
analog) and the step runs under ``shard_map`` with ``lax.ppermute`` halos
(``parallel/halo.py``). With 1 device the ghost shell is a constant pad.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .config import settings as config
from .config.env import env_raw, env_str
from .config.settings import Settings
from .models import get_model
from .ops import noise as noise_ops
from .ops import validate_kernel_language
from .parallel import schedule, temporal
from .parallel.domain import AXIS_NAMES, CartDomain
from .obs.trace import get_tracer
from .utils.log import _is_primary


def _gs_step(fn):
    """``fn`` under the stable name ``gs_step``, so its jitted program
    is ``jit_gs_step`` in HLO dumps and profiler traces (a bare
    ``partial`` or ``shard_map`` would be ``jit__unknown``)."""

    def gs_step(*args):
        return fn(*args)

    return gs_step


def default_fuse() -> int:
    """Temporal-blocking depth for single-block Pallas runs.

    Deeper fusion cuts HBM passes per step ~1/k until stage compute
    fills the DMA envelope; after the round-3 op diet (mul-form
    Laplacian, 2D-amortized noise hash) the measured optimum on the v5e
    moved from k=4 to k=5 (`benchmarks/results/ab_r3_deepfuse_*`).
    ``GS_FUSE`` overrides; off-TPU the interpreter pays per-stage
    simulation cost, so tests keep the historical depth 2.
    """
    v = env_str("GS_FUSE", "")
    if v:
        try:
            return max(1, int(v))
        except ValueError as e:
            raise ValueError(
                f"GS_FUSE must be a positive integer, got {v!r}"
            ) from e
    return 5 if jax.default_backend() == "tpu" else 2


#: Cache dirs already pointed at jax's persistent compilation cache —
#: makes :func:`_enable_compile_cache` idempotent per path.
_compile_cache_armed: set = set()


def _enable_compile_cache(path: str) -> None:
    """Arm JAX's persistent compilation cache at ``path``.

    Armed at Simulation construction (before the first jit) when
    ``config.resolve_compile_cache`` yields a directory: a cold chip
    run spends minutes compiling the fused kernel, and restarts,
    repeated runs and bench invocations then load the executables from
    disk. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already
    taken that directory and it is left as it is; otherwise the
    directory is set here. The floors are dropped to zero so the small
    programs of tests and smoke runs are cached too (the cache key
    includes the full program, so correctness is unaffected).
    """
    import os

    from jax.experimental.compilation_cache import compilation_cache

    # Re-enabled even for an armed path: a CPU Simulation in the same
    # process may have turned the cache off since.
    jax.config.update("jax_enable_compilation_cache", True)
    if path in _compile_cache_armed:
        return
    os.makedirs(path, exist_ok=True)
    if jax.config.jax_compilation_cache_dir != path:
        compilation_cache.set_cache_dir(path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # The cache object initializes lazily at the FIRST compile and then
    # pins its directory; a process that already jitted anything must
    # reset it or the new directory never receives entries.
    compilation_cache.reset_cache()
    _compile_cache_armed.add(path)


def select_devices(platform: str):
    """Devices of the requested platform (reference backend dispatch analog).

    For CPU runs the platform list is pinned to "cpu" before the first
    device query, so a CPU run never initializes — and so never holds —
    an accelerator that another process needs. A platform with no
    devices is a clear error; there is no fallback to another platform.
    """
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    try:
        devices = jax.devices(platform)
    except RuntimeError as e:
        raise RuntimeError(
            f"Backend {platform!r} requested in config but no such JAX "
            f"devices are available: {e}"
        ) from e
    from .resilience.sdc import device_name, resolve_blocklist

    blocked = resolve_blocklist()
    if blocked:
        kept = [d for d in devices if device_name(d) not in blocked]
        if not kept:
            raise RuntimeError(
                f"all {len(devices)} {platform} devices are "
                "quarantined (GS_DEVICE_BLOCKLIST / fleet quarantine "
                "docs) — no compute inventory left"
            )
        devices = kept
    return devices


def mesh_for_topology(shape, devices, backend: str):
    """Device array for a mesh of ``shape`` over ``devices``.

    On TPU, maps the logical mesh onto the physical ICI topology
    (v4/v5p are 3D tori) so the 6-face ppermute halo exchange rides
    single-hop links — the TPU analog of MPI_Cart_create's
    reorder=true. Virtual/CPU meshes have no topology to exploit and
    use enumeration order. Shared by the 3D spatial mesh and the
    ensemble engine's 4D (member, x, y, z) mesh.
    """
    if backend == "tpu":
        try:
            from jax.experimental import mesh_utils

            return mesh_utils.create_device_mesh(shape, devices=devices)
        except (ValueError, NotImplementedError, AssertionError) as e:
            import sys

            print(
                "gray-scott: warning: topology-aware mesh failed "
                f"({e}); falling back to enumeration order — halo "
                "ppermutes may ride multi-hop ICI links",
                file=sys.stderr,
            )
    return np.array(devices).reshape(shape)


class FieldSnapshot:
    """A device-detached capture of the model's fields draining to the
    host.

    Produced by :meth:`Simulation.snapshot_async`: the fields are copied
    into fresh device buffers and every addressable shard has a
    non-blocking device-to-host transfer in flight by the time the
    constructor returns. :meth:`blocks` resolves (blocking only on the
    remaining transfer time) to the ``local_blocks()`` format —
    ``[(offsets, sizes, *field_blocks), ...]`` with one block per model
    field in declaration order (for Gray-Scott: ``(offsets, sizes,
    u_block, v_block)``) — so a background writer thread can
    serialize/write while the driver thread dispatches the next compute
    chunk (``io/async_writer.py``).

    Lifetime contract: the snapshot owns its device buffers outright —
    it stays valid across later ``iterate`` calls even though those
    donate (and thereby delete) the simulation's own field buffers.
    """

    def __init__(self, parts, step: int, health=None,
                 field_names=("u", "v"), numerics=None,
                 checksums=None, enc_parts=None, enc_meta=None):
        #: Simulation step the snapshot was taken at.
        self.step = step
        self._parts = parts  # [(offsets, true_sizes, *field_devs), ...]
        self._blocks = None
        #: Lossy-codec parts (docs/PRECISION.md): same per-shard shape
        #: as ``_parts`` but coded fields carry their uint payloads —
        #: the bytes in flight are the compressed ones. ``enc_meta``
        #: maps field index -> (bits, lo_dev, hi_dev, dtype_str).
        self._enc_parts = enc_parts
        self._enc_meta = enc_meta or {}
        #: Model field names, for the health report's attribution.
        self.field_names = tuple(field_names)
        #: Device scalars of the fused health probe
        #: (``resilience/health.device_probe``) when the snapshot was
        #: taken with ``health=True``; resolved by :meth:`health_report`.
        self._health = health
        #: Device scalars of the fused numerics probe
        #: (``obs/numerics.device_numerics_probe``) when taken with
        #: ``numerics=True``; resolved by :meth:`numerics_report`.
        self._numerics = numerics
        #: Device scalars of the fused per-field integrity checksum
        #: (``resilience/integrity.device_field_checksum``) when taken
        #: with ``checksum=True``; re-derived host-side from the very
        #: bytes bound for the stores in :meth:`blocks` — a mismatch
        #: raises before anything is written.
        self._checksums = checksums

    def health_report(self):
        """Resolved :class:`~.resilience.health.HealthReport` for this
        snapshot, or None when no probe was requested. Blocks only on
        the probe's few scalars — the block D2H stays in flight."""
        if self._health is None:
            return None
        from .resilience.health import HealthReport

        finite, *minmax = self._health
        return HealthReport(
            bool(finite), *(float(x) for x in minmax),
            names=self.field_names,
        )

    def numerics_report(self):
        """Resolved :class:`~.obs.numerics.NumericsReport` for this
        snapshot, or None when no numerics probe was requested. Blocks
        only on the probe's scalars — the block D2H stays in flight,
        like :meth:`health_report`."""
        if self._numerics is None:
            return None
        from .obs import numerics as obs_numerics

        return obs_numerics.resolve_report(
            self._numerics, self.field_names
        )

    def has_checksums(self) -> bool:
        return self._checksums is not None

    def checksum_report(self):
        """Resolved per-field device checksums ``{field: int}``, or
        None when the probe was not requested — the values the store
        writers record in the integrity sidecar."""
        if self._checksums is None:
            return None
        return {
            n: int(np.asarray(c))
            for n, c in zip(self.field_names, self._checksums)
        }

    def _host_checksums(self, host_parts):
        """Per-field checksums recomputed from the resolved host
        arrays (full shard storage, pads included — the same elements
        the device reduction covered)."""
        from .resilience.integrity import host_field_checksum

        totals = [0] * len(self.field_names)
        for part in host_parts:
            for fi, arr in enumerate(part[2:]):
                totals[fi] = (
                    totals[fi] + host_field_checksum(arr)
                ) % (1 << 32)
        return totals

    def _verify_checksums(self, host_parts) -> None:
        """Compare the in-graph device-side checksums against the
        host-side recomputation over the landed bytes; a mismatch is
        data that changed somewhere on the device-copy → D2H path and
        raises before the poisoned step can reach any store."""
        from .resilience.integrity import CorruptionError

        host = self._host_checksums(host_parts)
        for name, dev, got in zip(
            self.field_names, self._checksums, host
        ):
            want = int(np.asarray(dev))
            if got != want:
                raise CorruptionError(
                    f"device-side field checksum mismatch: device "
                    f"{want:#010x}, host {got:#010x} — snapshot bytes "
                    "were silently corrupted in flight",
                    step=self.step, var=name,
                )

    def blocks(self):
        """Host blocks ``[(offsets, sizes, *field_blocks), ...]``,
        clipped to the true domain; blocks until the in-flight D2H
        transfers land (idempotent — resolved once, then cached).
        Snapshots taken with ``checksum=True`` verify the landed bytes
        against the fused device-side checksum first
        (:class:`~.resilience.integrity.CorruptionError` on mismatch —
        classified ``corruption`` by the supervisor).

        Returns a :class:`~.io.codec.BoundaryBlocks` list: the exact
        blocks in the list body (empty when this boundary skipped the
        exact copy — a lossy-output-only boundary), with the codec
        form, when captured, on its ``encoded`` attribute (coded
        fields as :class:`~.io.codec.EncodedField`, uncoded ones as
        plain arrays). Plain-list consumers are unaffected."""
        if self._blocks is None:
            from .io.codec import BoundaryBlocks, EncodedField

            exact = []
            if self._parts is not None:
                host_parts = [
                    (offsets, true) + tuple(np.asarray(d) for d in devs)
                    for offsets, true, *devs in self._parts
                ]
                if self._checksums is not None:
                    self._verify_checksums(host_parts)
                for offsets, true, *hosts in host_parts:
                    sl = tuple(slice(0, t) for t in true)
                    exact.append(
                        (offsets, true) + tuple(h[sl] for h in hosts)
                    )
            out = BoundaryBlocks(exact)
            if self._enc_parts is not None:
                enc_blocks = []
                for offsets, true, *devs in self._enc_parts:
                    sl = tuple(slice(0, t) for t in true)
                    entries = []
                    for i, d in enumerate(devs):
                        h = np.asarray(d)[sl]
                        meta = self._enc_meta.get(i)
                        if meta is None:
                            entries.append(h)
                        else:
                            bits, lo, hi, dt = meta
                            entries.append(EncodedField(
                                h, float(np.asarray(lo)),
                                float(np.asarray(hi)), bits, dt,
                            ))
                    enc_blocks.append((offsets, true) + tuple(entries))
                out.encoded = enc_blocks
            self._blocks = out
            self._parts = None  # release the device buffers
            self._enc_parts = None
        return self._blocks


class Simulation:
    """A running simulation of one registered model bound to a set of
    devices (Gray-Scott by default; ``[model]`` TOML table selects)."""

    #: Snapshot container class — the ensemble engine substitutes a
    #: member-aware one (``ensemble/engine.EnsembleFieldSnapshot``).
    snapshot_cls = FieldSnapshot
    #: True on :class:`~.ensemble.engine.EnsembleSimulation`: the step
    #: body runs under ``vmap`` over a leading member axis, which
    #: changes a few per-shard decisions (e.g. interpret-mode Pallas is
    #: not vmapped on CPU — the XLA fallback is).
    is_ensemble = False

    def __init__(
        self,
        settings: Settings,
        *,
        n_devices: Optional[int] = None,
        seed: int = 0,
        mesh_dims: Optional[Tuple[int, int, int]] = None,
    ):
        self.settings = settings
        #: Programmatic mesh-dims override (docs/RESHARD.md): the live
        #: in-job reshape path builds the TARGET simulation with an
        #: explicit factorization instead of mutating GS_TPU_MESH_DIMS
        #: (process-global env is thread-unsafe under the serve worker
        #: fleet). Wins over the env override in ``_make_domain``, and
        #: pins the mesh against auto-kernel mesh adoption below.
        self._mesh_dims_override = (
            tuple(int(d) for d in mesh_dims)
            if mesh_dims is not None else None
        )
        #: The registered model declaration this run integrates —
        #: fields, boundaries, params, reaction (``models/``).
        self.model = get_model(
            getattr(settings, "model", "grayscott") or "grayscott"
        )
        backend, self.kernel_language = config.load_backend_and_lang(settings)
        # Validate eagerly so an unavailable kernel language fails at
        # construction, not at first iterate (the reference defers all
        # dispatch errors to runtime fallbacks, public.jl:31-32, 77-78).
        validate_kernel_language(self.kernel_language)
        from .ops import kernelgen

        #: Why the kernel generator cannot lower this model's reaction
        #: into the fused Pallas kernel, or None when it can
        #: (docs/KERNELGEN.md). ONE statement of the model-side gate:
        #: explicit-Pallas validation, the Auto branch, and the
        #: autotuner shortlist below all consult this same reason.
        self._kernel_gate_reason = kernelgen.generation_gate_reason(
            self.model
        )
        if (self.kernel_language == "pallas"
                and self._kernel_gate_reason is not None):
            raise ValueError(
                f"kernel_language = 'Pallas' cannot be generated for "
                f"model {self.model.name!r}: {self._kernel_gate_reason} "
                f"(use 'Plain'/'XLA' or 'Auto')"
            )
        self.dtype = config.resolve_precision(settings)
        self._base_dtype = self.dtype
        #: Mixed-precision compute posture (docs/PRECISION.md,
        #: GS_COMPUTE_PRECISION / compute_precision key): "f32"
        #: (default — today's compute, bitwise), "bf16_f32acc" (fields,
        #: halo slabs, and stores held in bfloat16; Laplacian +
        #: reaction + Euler update accumulated in float32), or
        #: "equality" (pinned f32 AND a loud refusal of any lossy
        #: snapshot codec — the operator escape hatch asserting byte
        #: identity with a pre-posture build). Under an authorizing
        #: posture the measured autotuner may adopt the per-config
        #: winner across the precision axis below.
        self.compute_precision = config.resolve_compute_precision(
            settings
        )
        #: Accumulation dtype for the XLA reaction/Laplacian paths —
        #: equals the storage dtype except under ``bf16_f32acc``, where
        #: storage drops to bf16 and accumulation stays f32 (the Pallas
        #: kernel's own ``_compute_dtype`` applies the same rule
        #: in-kernel for bf16 fields).
        self.compute_dtype = self.dtype
        if self.compute_precision == "bf16_f32acc":
            self.dtype = jnp.bfloat16
            self.compute_dtype = jnp.float32
        #: Lossy snapshot codec posture (docs/PRECISION.md,
        #: GS_SNAPSHOT_BITS / snapshot_bits key): resolved here so a
        #: misconfiguration (unknown field, equality + codec) fails at
        #: construction and the posture joins the tuning-cache key.
        from .io.codec import resolve_snapshot_codec

        self.snapshot_codec = resolve_snapshot_codec(
            settings, self.model.field_names
        )

        # Persistent compilation cache (docs/TUNING.md "Compile
        # cache"): on by default, JAX_COMPILATION_CACHE_DIR wins, else
        # the checkout's .jax_cache. Must be armed before the first jit
        # below. CPU is refused: this jax's CPU executable
        # serialization does not round-trip faithfully (measured: a
        # cache-loaded sharded runner corrupted 8 cells by O(1) and
        # tripped the NaN health guard on a supervised restart), and a
        # cache that can change a trajectory is worse than recompiling.
        # GS_COMPILE_CACHE_FORCE=1 overrides for cache-wiring tests.
        self.compile_cache_dir = config.resolve_compile_cache(settings)
        if backend == "cpu" and env_raw("GS_COMPILE_CACHE_FORCE") != "1":
            if self.compile_cache_dir and (
                    env_raw("GS_COMPILE_CACHE") or settings.compile_cache):
                # Explicitly requested — refuse loudly, not silently.
                import sys as _sys

                print(
                    "gray-scott: warning: persistent compilation cache "
                    "disabled on the CPU backend (executable "
                    "serialization does not round-trip bitwise on this "
                    "jax; set GS_COMPILE_CACHE_FORCE=1 to override)",
                    file=_sys.stderr,
                )
            self.compile_cache_dir = None
            if jax.config.jax_compilation_cache_dir:
                # JAX_COMPILATION_CACHE_DIR arms JAX's cache by itself.
                jax.config.update("jax_enable_compilation_cache", False)
        if self.compile_cache_dir:
            _enable_compile_cache(self.compile_cache_dir)

        devices = select_devices(backend)
        if n_devices is not None:
            if n_devices > len(devices):
                raise ValueError(
                    f"requested {n_devices} devices, only {len(devices)} "
                    f"{backend} devices available"
                )
            devices = devices[:n_devices]
        elif len(devices) > 1:
            from .resilience.sdc import resolve_blocklist

            if resolve_blocklist():
                # Quarantine shrank the inventory to a count that may
                # not decompose this L (7 devices cannot split a
                # 32-cube): trim to the largest feasible prefix rather
                # than failing a restart the quarantine itself forced.
                for k in range(len(devices), 0, -1):
                    try:
                        self._make_domain(devices[:k])
                    except ValueError:
                        continue
                    devices = devices[:k]
                    break

        self.domain = self._make_domain(devices)
        self.sharded = self.domain.n_blocks > 1
        #: Split-phase halo exchange (GS_COMM_OVERLAP / comm_overlap
        #: key; docs/OVERLAP.md): "auto" = on for sharded runs. The
        #: trajectory is bitwise identical either way — overlap only
        #: removes the data dependency between the exchange and the
        #: interior compute so XLA can hide the ICI transfer.
        self.comm_overlap = (
            self.sharded
            and config.resolve_comm_overlap(settings) != "off"
        )
        #: True once a runner trace actually built a split-phase round
        #: (degenerate geometries fall back to the fused round even
        #: with overlap armed) — introspection for tests and stats.
        self.overlap_applied = False
        #: Communication-avoiding s-step exchange depth (GS_HALO_DEPTH
        #: / halo_depth key; docs/TEMPORAL.md): each exchange round
        #: transfers a (chain_depth x halo_depth)-deep ghost frame once
        #: and the chain advances that many steps on progressively
        #: shrinking valid regions — the XLA window chain in HBM, the
        #: generated Pallas chains as a deepened VMEM-resident
        #: in-kernel walk. 1 = today's one-exchange-per-round
        #: schedule (byte-identical); resolved "auto" stays 1 unless
        #: the measured autotuner adopts a deeper k below.
        self._halo_depth_pinned, self.halo_depth = (
            config.resolve_halo_depth(settings)
        )
        #: Set when a requested halo_depth > 1 was degraded because
        #: the Pallas chain's deepened working set is geometry- or
        #: VMEM-infeasible for this local block (the slab ledger's
        #: numbers ride along) — provenance for stats/tests.
        self.halo_depth_gate = None
        self._auto_fuse = None
        if self.kernel_language == "auto":
            # Resolve via the ICI cost model for the ACTUAL run config
            # (mesh dims, L, dtype, device generation) — see
            # parallel/icimodel.select_kernel for the policy. When the
            # operator did not force a mesh, the chain is projected at
            # its best swept factorization and the winning mesh/depth
            # are adopted. The decision is logged once on process 0 and
            # recorded in ``self.kernel_selection`` for the stats echo.
            import os as _os

            from .parallel import icimodel

            try:
                kind = devices[0].device_kind
            except Exception:
                kind = ""
            mesh_forced = (
                bool(env_str("GS_TPU_MESH_DIMS", ""))
                or self._mesh_dims_override is not None
            )
            if self._kernel_gate_reason is not None:
                # Generator feasibility gate (docs/KERNELGEN.md): the
                # fused kernel is generated from the model's reaction,
                # so Auto resolves to XLA only when generation is
                # infeasible — an EXPLICIT decision recorded in the
                # provenance with the generator's reason, and the
                # autotuner below searches XLA candidates only.
                self.kernel_language = "xla"
                self.kernel_selection = {
                    "reason": (
                        f"no Pallas kernel can be generated for model "
                        f"'{self.model.name}' "
                        f"({self._kernel_gate_reason}); XLA path"
                    ),
                    "kernel_gate": {
                        "model": self.model.name,
                        "generated": False,
                        "reason": self._kernel_gate_reason,
                    },
                }
            else:
                self.kernel_language, self.kernel_selection = (
                    icimodel.select_kernel(
                        self.domain.dims, settings.L, platform=backend,
                        device_kind=kind,
                        itemsize=np.dtype(self.dtype).itemsize,
                        fuse=default_fuse(),
                        n_fields=self.model.n_fields,
                        sweep_mesh=self.sharded and not mesh_forced,
                        # Auto's pick must reflect the comm this run
                        # will actually expose: the calibrated overlap
                        # when the split-phase exchange is armed,
                        # fully-exposed otherwise.
                        overlap="auto" if self.comm_overlap else 0.0,
                    )
                )
            if self.sharded:
                row = next(
                    (r for r in self.kernel_selection.get("rows", [])
                     if r["kernel"] == self.kernel_language), None,
                )
                if row is not None:
                    if (self.kernel_language == "pallas"
                            and not mesh_forced):
                        picked = tuple(
                            int(x) for x in row["mesh"].split(",")
                        )
                        if picked != self.domain.dims:
                            self.domain = CartDomain(
                                L=settings.L, dims=picked
                            )
                            self.kernel_selection["adopted_mesh"] = (
                                list(picked)
                            )
                    if not env_str("GS_FUSE", ""):
                        # Honor the winning row's swept depth for BOTH
                        # languages — the projection that justified the
                        # pick assumed it (still capped by the runner's
                        # own feasibility checks).
                        self._auto_fuse = int(row["fuse"])
            # Measured autotuner (tune/, docs/TUNING.md), consulted
            # AFTER the analytic decision and mesh adoption settled so
            # the tuning-cache key describes the mesh this run actually
            # uses. Modes: off/cached leave the analytic pick untouched
            # (cached applies a prior measured winner on a cache hit,
            # with zero measurement); quick/full measure the model's
            # shortlist on the real step function here, within
            # GS_AUTOTUNE_BUDGET_S.
            from . import tune

            link_gbps, links = icimodel.fabric_for(kind)
            decision = tune.autotune(
                settings,
                dims=self.domain.dims, L=settings.L, platform=backend,
                device_kind=kind, dtype=str(np.dtype(self.dtype)),
                noise=float(settings.noise),
                itemsize=int(np.dtype(self.dtype).itemsize),
                n_devices=n_devices, seed=seed,
                analytic_kernel=self.kernel_language,
                analytic_fuse=max(1, int(self._fuse_base())),
                comm_overlap=self.comm_overlap,
                overlap_toggle=(
                    self.sharded
                    and config.resolve_comm_overlap(settings) == "auto"
                ),
                link_gbps=link_gbps, links=links,
                # The model joins the tuning-cache key (a Brusselator
                # run must never adopt a Gray-Scott-measured winner)
                # and gates the candidate space to what this model's
                # kernels can actually run.
                model=self.model.name,
                n_fields=self.model.n_fields,
                pallas_allowed=(self._kernel_gate_reason is None),
                # Generator-contract version (schema v7): winners are
                # measured against THIS generator's kernels; 0 when no
                # Pallas kernel can be generated (XLA-only shortlist).
                kernel_generator=(
                    kernelgen.GENERATOR_VERSION
                    if self._kernel_gate_reason is None else 0
                ),
                # A pinned s-step depth joins the tuning-cache key and
                # is respected, not searched; "auto" (0) lets the
                # tuner widen the shortlist across k.
                halo_depth=(self.halo_depth if self._halo_depth_pinned
                            else 0),
                # The ADOPTED placement joins the key (schema v5,
                # docs/RESHARD.md): an elastically resumed run is a
                # different placement, and a winner tuned on mesh A
                # (or another process count) must never be applied on
                # mesh B.
                procs=jax.process_count(),
                # Precision + codec postures join the key (schema v6,
                # docs/PRECISION.md): a bf16-measured winner must never
                # be adopted by an f32 run (different HBM/halo bytes,
                # different schedule), and the bf16_f32acc posture arms
                # the precision CANDIDATE AXIS — the tuner measures
                # both precisions and the winner below may adopt
                # either, per config.
                compute_precision=self.compute_precision,
                snapshot_codec=self.snapshot_codec.posture(),
                **self._tune_extras(),
            )
            self.kernel_selection["autotune"] = decision.provenance
            if decision.provenance.get("source") in ("cache", "measured"):
                self.kernel_language = decision.kernel
                if decision.fuse is not None and not env_str(
                        "GS_FUSE", ""):
                    self._auto_fuse = decision.fuse
                if (decision.comm_overlap is not None and self.sharded
                        and config.resolve_comm_overlap(settings)
                        == "auto"):
                    self.comm_overlap = decision.comm_overlap
                if (decision.halo_depth is not None
                        and not self._halo_depth_pinned):
                    self.halo_depth = max(1, int(decision.halo_depth))
                if (decision.compute_precision is not None
                        and self.compute_precision == "bf16_f32acc"
                        and decision.compute_precision
                        in config.COMPUTE_PRECISIONS):
                    # Per-config precision adoption (docs/PRECISION.md):
                    # only an authorizing bf16_f32acc posture searches
                    # the precision axis, and the measured winner may
                    # keep bf16 or fall back to f32 for THIS config.
                    # Params and fields are built after this block, so
                    # the adopted dtype is what the run materializes.
                    self.compute_precision = decision.compute_precision
                    if self.compute_precision == "bf16_f32acc":
                        self.dtype = jnp.bfloat16
                        self.compute_dtype = jnp.float32
                    else:
                        self.dtype = self._base_dtype
                        self.compute_dtype = self._base_dtype
                if decision.bx is not None and not env_str(
                        "GS_BX", ""):
                    # GS_BX is read at kernel-trace time; an env pin is
                    # the one channel that reaches it. Process-wide by
                    # nature — recorded in the provenance, and an
                    # operator's own GS_BX always wins.
                    _os.environ["GS_BX"] = str(decision.bx)
                    decision.provenance["bx_env_pinned"] = True
                self._apply_tune_extras(decision)
            if _is_primary():
                import sys as _sys

                _prov = decision.provenance
                print(
                    "gray-scott: kernel_language=Auto resolved to "
                    f"{self.kernel_language!r} "
                    f"({self.kernel_selection.get('reason', '')}; "
                    f"autotune {_prov['mode']}, "
                    f"{_prov.get('source', 'analytic')} pick)",
                    file=_sys.stderr,
                )
        else:
            self.kernel_selection = None
        if isinstance(self.kernel_selection, dict):
            # Adopted-precision provenance (docs/PRECISION.md): every
            # stats/bench consumer of kernel_selection sees which
            # posture the run actually materialized next to the
            # kernel/fuse decision it rode in on.
            self.kernel_selection["compute_precision"] = (
                self.compute_precision
            )
            self.kernel_selection["snapshot_codec"] = (
                self.snapshot_codec.posture()
            )
            if self.kernel_language == "pallas":
                # Generated-kernel provenance (docs/KERNELGEN.md): a
                # resolved Pallas pick is a generator product, and
                # artifacts must be able to tell generator eras apart
                # (gs_report --check validates these attrs).
                self.kernel_selection["generated"] = True
                self.kernel_selection["generator_version"] = (
                    kernelgen.GENERATOR_VERSION
                )
        if (self.kernel_language == "pallas" and self.halo_depth > 1
                and self.sharded):
            # The generated Pallas chains run a REAL s-step schedule
            # (docs/TEMPORAL.md): one (fuse x halo_depth)-deep exchange
            # round feeds fuse*halo_depth in-kernel Euler steps over
            # progressively shrinking VMEM-resident valid regions — no
            # HBM round-trip between the inner steps. Feasibility is
            # the chain dispatch geometry composed with the VMEM slab
            # ledger (``pallas_stencil.max_feasible_chain_depth``);
            # infeasible k degrades to the deepest feasible k' LOUDLY,
            # with the ledger numbers in the provenance, so a config
            # written against the old blanket degrade fails
            # loud-and-explained instead of silently changing schedule.
            from .ops import pallas_stencil as _ps

            local = tuple(int(x) for x in self.domain.local_shape)
            dims = self.domain.dims
            itemsize = int(jnp.dtype(self.dtype).itemsize)
            sublane = 16 if self.dtype == jnp.bfloat16 else 8
            mid = _ps.mid_itemsize_for(self.dtype)
            nf = self.model.n_fields
            path = ("x-chain" if dims[1] == 1 and dims[2] == 1
                    else "xy-chain")

            def _cap(depth):
                return _ps.max_feasible_chain_depth(
                    local, dims, itemsize, depth, sublane,
                    mid_itemsize=mid, n_fields=nf,
                )

            d = max(1, _cap(self._fuse_base()))
            applied = next(
                (k for k in range(self.halo_depth, 0, -1)
                 if _cap(d * k) == d * k), 1,
            )
            if applied < self.halo_depth:
                self.halo_depth_gate = {
                    "requested": self.halo_depth,
                    "applied": applied,
                    "kind": "geometry-infeasible",
                    "reason": (
                        f"halo_depth={self.halo_depth} needs a "
                        f"{d * self.halo_depth}-deep in-kernel chain "
                        f"(fuse base {d} x halo_depth) on the Pallas "
                        f"{path}, but local block {local} "
                        f"({itemsize}-byte fields x {nf}) serves at "
                        f"most depth {d * applied} under the chain "
                        "geometry caps and the "
                        f"{_ps._vmem_budget()}-byte VMEM slab budget; "
                        f"running halo_depth={applied}"
                    ),
                    "geometry": {
                        "path": path,
                        "local_shape": list(local),
                        "fuse_base": int(d),
                        "requested_depth": int(d * self.halo_depth),
                        "feasible_depth": int(d * applied),
                        "vmem_budget_bytes": int(_ps._vmem_budget()),
                        "itemsize": itemsize,
                        "n_fields": int(nf),
                    },
                }
                if isinstance(self.kernel_selection, dict):
                    self.kernel_selection["halo_depth_gate"] = (
                        self.halo_depth_gate
                    )
                if _is_primary():
                    import sys as _sys

                    print(
                        "gray-scott: warning: "
                        + self.halo_depth_gate["reason"],
                        file=_sys.stderr,
                    )
                self.halo_depth = applied
        if (self.sharded and self.halo_depth > 1
                and self.kernel_language != "pallas"):
            # The s-step frame is exchanged in ONE single-hop round:
            # every slab must consist of owned cells, so the effective
            # exchange depth (chain depth x k) cannot exceed the local
            # block's smallest extent. Refuse loudly at construction —
            # a silently-capped k would misreport the schedule every
            # artifact records.
            d = max(1, min(self._fuse_base(),
                           min(self.domain.local_shape)))
            deep = d * self.halo_depth
            cap = min(self.domain.local_shape)
            if deep > cap:
                raise config.SettingsError(
                    f"halo_depth={self.halo_depth} needs a {deep}-deep "
                    f"ghost exchange (chain depth {d} x halo_depth), "
                    f"but the local block {self.domain.local_shape} "
                    f"supports at most {cap}; lower halo_depth/GS_FUSE "
                    "or use fewer devices per axis"
                )
        self.params = self._make_params()
        self.use_noise = self._resolve_use_noise()
        self.base_key = self._make_base_key(seed)
        self.step = 0
        #: Elastic-restore provenance (docs/RESHARD.md): set by
        #: ``reshard.restore.restore_run`` to the plan's describe()
        #: when this run resumed a checkpoint written on a DIFFERENT
        #: layout; None for fresh runs and same-shape resumes. Echoed
        #: into the RunStats config by the driver.
        self.reshard = None
        #: Executable analytics (``obs/xstats.py``): armed by GS_XSTATS
        #: / the ``xstats`` key, or implicitly whenever the persistent
        #: compile cache is — its hit/miss story must be observable.
        #: Each instrumented runner compile appends its record here;
        #: the driver merges the list into the RunStats ``executables``
        #: section.
        from .obs import xstats as obs_xstats

        self.xstats_enabled = (
            obs_xstats.resolve_xstats(settings)
            or bool(self.compile_cache_dir)
        )
        self.executables: list = []
        self._runners: Dict[int, object] = {}
        self._snapshot_fns: Dict[Tuple[bool, bool], object] = {}
        #: Non-donating replay twins of the runners, keyed by
        #: (nsteps, device permutation) — the SDC screening seam
        #: (resilience/sdc.py). Separate cache: a donating runner would
        #: consume the retained anchor buffers it must preserve.
        self._replay_fns: Dict[tuple, tuple] = {}

        self._build_mesh(devices, backend)
        #: The model's field arrays, declaration order (a tuple — the
        #: state the runner advances; ``u``/``v`` alias fields 0/1).
        self.fields = self._init_fields()

    # ----------------------------------------------------- field aliases
    # Two-field models (Gray-Scott, Brusselator, FHN) read naturally as
    # (u, v); the canonical state is ``self.fields``.

    @property
    def u(self):
        return self.fields[0]

    @u.setter
    def u(self, value):
        self.fields = (value,) + tuple(self.fields[1:])

    @property
    def v(self):
        return self.fields[1]

    @v.setter
    def v(self, value):
        self.fields = (self.fields[0], value) + tuple(self.fields[2:])

    def _field_index(self, field) -> int:
        """Resolve a field reference — model field name, the legacy
        ``"u"``/``"v"`` aliases, or an integer index."""
        if isinstance(field, int):
            return field
        if field in self.model.field_names:
            return self.model.field_names.index(field)
        alias = {"u": 0, "v": 1}.get(field)
        if alias is not None and alias < self.model.n_fields:
            return alias
        raise ValueError(
            f"unknown field {field!r} for model {self.model.name!r} "
            f"(fields: {', '.join(self.model.field_names)})"
        )

    # ------------------------------------------------- construction hooks
    # Overridden by ensemble/engine.EnsembleSimulation, which threads a
    # leading member axis through every one of these while the step
    # body, halo exchange, autotune and I/O plumbing stay shared.

    def _make_domain(self, devices) -> CartDomain:
        """Spatial decomposition over the selected devices."""
        return CartDomain.create(
            len(devices), self.settings.L,
            dims=self._mesh_dims_override,
        )

    def _make_params(self):
        """Typed params pytree, routed through the model declaration
        (``[model]`` table > legacy flat keys > declared defaults).
        Params live at the COMPUTE dtype: identical to the storage
        dtype except under ``bf16_f32acc``, where the f32 params feed
        the f32 accumulation directly (docs/PRECISION.md)."""
        return self.model.make_params(self.settings, self.compute_dtype)

    def _resolve_use_noise(self) -> bool:
        return self.settings.noise != 0.0

    def _make_base_key(self, seed: int):
        return jax.random.PRNGKey(seed)

    def _tune_extras(self) -> dict:
        """Extra kwargs for ``tune.autotune`` (ensemble size etc.)."""
        return {}

    def _apply_tune_extras(self, decision) -> None:
        """Apply decision fields beyond kernel/fuse/overlap/bx."""

    def _probe_fn(self):
        """The device-side health probe fused into the snapshot copy."""
        from .resilience.health import device_probe

        return device_probe

    def _numerics_probe_fn(self):
        """The device-side numerics probe (per-field min/max/mean/L2/
        finite reductions) fused into the snapshot copy — or run alone
        per round under ``GS_NUMERICS=every_round``."""
        from .obs.numerics import device_numerics_probe

        return device_numerics_probe

    def _build_mesh(self, devices, backend: str) -> None:
        """Construct ``self.mesh`` / ``self.field_sharding`` (or pin
        ``self.device`` for the single-device case)."""
        if self.sharded:
            mesh_devices = mesh_for_topology(
                self.domain.dims, devices, backend
            )
            self.mesh = Mesh(mesh_devices, AXIS_NAMES)
            self.field_sharding = NamedSharding(self.mesh, P(*AXIS_NAMES))
        else:
            self.mesh = None
            self.field_sharding = None
            self.device = devices[0]

    def _fuse_base(self) -> int:
        """Chain/temporal-blocking depth before the runner's own caps:
        the Auto-swept depth when Auto adopted one (GS_FUSE unset),
        else ``default_fuse()`` (GS_FUSE or the platform default)."""
        if self._auto_fuse is not None:
            return self._auto_fuse
        return default_fuse()

    # ------------------------------------------------------------------ init

    def _init_fields(self) -> Tuple[jax.Array, ...]:
        """Sharded field construction: each device shard is built locally
        for its block (multi-host ready), mirroring the reference's
        per-rank ``init_fields`` (``Simulation_CPU.jl:14-72``). The
        initial condition is the model's declared ``init``."""
        L, dtype = self.settings.L, self.dtype
        if not self.sharded:
            return tuple(
                jax.device_put(f, self.device)
                for f in self.model.init(L, dtype)
            )

        dom = self.domain
        # Non-divisible L stores a padded grid (equal blocks, pad cells
        # at global coords >= L held at the boundary value — exactly
        # what the model's init produces for out-of-seed cells).
        gshape = dom.storage_shape

        def make(field_idx: int):
            def cb(index):
                offsets = tuple(s.start or 0 for s in index)
                sizes = tuple(
                    (s.stop or g) - (s.start or 0)
                    for s, g in zip(index, gshape)
                )
                return self.model.init(
                    L, dtype, offsets=offsets, sizes=sizes
                )[field_idx]

            return jax.make_array_from_callback(
                gshape, self.field_sharding, cb
            )

        return tuple(make(i) for i in range(self.model.n_fields))

    # ---------------------------------------------------------------- runner

    def _local_run(self, *args, nsteps: int):
        """``nsteps`` steps on one (local) block. Called directly on a
        single device, or per-shard under ``shard_map``.

        ``args`` is the model's field tuple (declaration order) followed
        by ``(base_key, step0, params)`` — the variadic field prefix is
        what makes the runner model-generic (one field for heat, two for
        Gray-Scott/Brusselator/FHN, n for anything registered).

        The chain schedule — which round, at what depth — is
        ``parallel/schedule.select``'s; this builds the block's offsets
        and its pin and noise closures, and runs the rounds.

        Noise everywhere comes from the position-keyed stream
        (``ops/noise.py``): one shared key, absolute step index, global
        cell coordinates — so the trajectory is invariant under step
        chunking, shard layout, and temporal fusion.
        """
        *fields, base_key, step0, params = args
        model, L = self.model, self.settings.L
        dtype = fields[0].dtype
        key = lax.bitcast_convert_type(base_key, jnp.int32)
        if self.sharded:
            offs = jnp.stack([
                lax.axis_index(ax) * jnp.int32(b)
                for ax, b in zip(AXIS_NAMES, self.domain.local_shape)
            ])
        else:
            offs = jnp.zeros((3,), jnp.int32)
        padded = self.sharded and self.domain.padded

        def pin(fields):
            """Re-pin each block's pad cells (global coords >= L) to the
            field's boundary value — required after every chain round
            with non-divisible L: the chain's final stage writes them
            unpinned, and the next round's stencil reads them as the
            frozen ghost shell."""
            if not padded:
                return tuple(fields)
            return tuple(
                temporal.pin_out_of_domain(f, bv, offs, L)
                for f, bv in zip(fields, model.boundaries)
            )

        def unit_noise(step_idx, offsets, shape):
            return noise_ops.uniform_pm1_block(
                key, step_idx, offsets, shape, L, dtype
            )

        sched = self._schedule(nsteps)
        blk = schedule.Block(
            model=model, params=params, key=key, offs=offs, row=L,
            dims=self.domain.dims, use_noise=self.use_noise,
            unit_noise=unit_noise, pin=pin,
            compute_dtype=self.compute_dtype, overlap=self.comm_overlap,
            padded=padded,
            # Concurrent interpret-mode kernels deadlock under shard_map
            # (global interpreter state), and under vmap interpret mode
            # re-interprets per member: both take the kernel's XLA
            # fallback, the same elementwise program, bitwise.
            allow_interpret=not self.sharded and not self.is_ensemble,
        )
        fields, split = schedule.run_chain_rounds(
            partial(sched.round, blk), sched.depth, nsteps, step0,
            tuple(fields), slab=sched.slab,
        )
        self.overlap_applied |= split
        return fields

    def _schedule(self, nsteps: int) -> schedule.Schedule:
        """The ``nsteps`` runner's chain schedule
        (``parallel/schedule.select``) on this block."""
        return schedule.select(
            self.kernel_language, self.sharded, nsteps=nsteps,
            fuse=self._fuse_base(), halo_depth=self.halo_depth,
            local=self.domain.local_shape, dims=self.domain.dims,
            dtype=self.dtype, n_fields=self.model.n_fields,
            overlap=self.comm_overlap,
        )

    def step_slabs(self, nsteps: int):
        """Slabs per XLA step of the ``nsteps`` runner (1 where the
        step is not blocked); None for a Pallas runner."""
        return self._schedule(nsteps).step_slabs

    def _compiler_options(self, nsteps: int):
        """The ``nsteps`` runner's compiler options: those of the
        blocked XLA step on TPU
        (``schedule.SLAB_STEP_TPU_COMPILER_OPTIONS``), else none."""
        device = (self.device if self.mesh is None
                  else self.mesh.devices.flat[0])
        if self._schedule(nsteps).slab is None or device.platform != "tpu":
            return None
        return dict(schedule.SLAB_STEP_TPU_COMPILER_OPTIONS)

    def _make_step_fn(self, nsteps: int, mesh=None):
        """The un-jitted ``nsteps``-step advance — one construction
        shared by :meth:`_runner` (jitted WITH field donation, the live
        path) and :meth:`replay_fields` (jitted without donation,
        optionally on a permuted ``mesh`` — the SDC screening path), so
        replay runs the very same traced program as the trajectory it
        checks."""
        local = partial(self._local_run, nsteps=nsteps)
        nf = self.model.n_fields
        if self.sharded:
            spec = P(*AXIS_NAMES)
            rep = P()
            return shard_map(
                local,
                mesh=self.mesh if mesh is None else mesh,
                in_specs=(spec,) * nf + (rep, rep, rep),
                out_specs=(spec,) * nf,
                # pallas_call outputs carry no varying-mesh-axes metadata;
                # skip the vma check (shardings are fully explicit here).
                check_vma=False,
            )
        return local

    def _runner(self, nsteps: int):
        """Compiled ``nsteps``-step advance, cached per nsteps."""
        fn = self._runners.get(nsteps)
        if fn is not None:
            return fn

        nf = self.model.n_fields
        fn = jax.jit(
            _gs_step(self._make_step_fn(nsteps)),
            donate_argnums=tuple(range(nf)),
            compiler_options=self._compiler_options(nsteps),
        )
        return self._register_runner(nsteps, fn)

    def _register_runner(self, nsteps: int, fn):
        """Cache a freshly-built runner — under executable analytics
        (``obs/xstats.py``) it is AOT-compiled here (the same
        ``lower().compile()`` path :meth:`compile_chunk` uses — the
        identical program, so trajectories are unchanged) with compile
        wall time, cost/memory analysis, collective counts, and the
        persistent-cache outcome captured per executable. Off means
        one boolean check; the jit wrapper is stored untouched."""
        if self.xstats_enabled:
            from .obs import xstats as obs_xstats

            fn = obs_xstats.instrument_compile(self, fn, nsteps)
        self._runners[nsteps] = fn
        return fn

    def compile_chunk(self, nsteps: int) -> None:
        """Ahead-of-time compile the ``nsteps`` runner without executing
        a single step.

        Launch support: pod jobs can pay the (20-60 s) compile before
        opening streams/checkpoints rather than inside the first
        ``iterate`` call, and a driver can compile-check a configuration
        without advancing the simulation. The compiled executable
        replaces the cached runner (same call signature), so ``iterate``
        uses it directly — compiling here and re-tracing on call would
        defeat the point. Note the first *execution* still pays a one-off
        device program-load (~tens of ms).
        """
        runner = self._runner(nsteps)
        if not hasattr(runner, "lower"):
            return  # already AOT-compiled
        with get_tracer().span("compile", step=self.step,
                               runner=f"runner[{nsteps}]"):
            compiled = runner.lower(
                *self.fields, self.base_key, jnp.int32(self.step),
                self.params,
            ).compile()
        self._runners[nsteps] = compiled

    # ------------------------------------------------------------- replay
    # The redundant-compute seam behind resilience/sdc.py: re-run rounds
    # from a retained anchor WITHOUT donating or advancing the live
    # state, optionally on a permuted device placement (shadow mode).

    def retain_fields(self) -> tuple:
        """Fresh non-donated device copies of the live fields — the SDC
        screener's boundary anchor. Same +0-copy idiom as
        :meth:`snapshot_async` (no D2H, no aliasing with the donated
        runner buffers), so retaining is bitwise-transparent to the
        trajectory."""
        return self._copy_fields(self.fields)

    def _copy_fields(self, fields) -> tuple:
        """Fresh non-donated device copies of a field tuple (sharding
        preserved)."""
        fn = getattr(self, "_retain_fn", None)
        if fn is None:

            def copy(*fields):
                return tuple(f + jnp.zeros((), f.dtype) for f in fields)

            fn = self._retain_fn = jax.jit(copy)
        return tuple(fn(*fields))

    def _replay_arg_shardings(self, mesh):
        """Shardings for (base_key, params) when the replay runs on an
        alternate mesh — both replicated for the spatial engine (the
        ensemble engine member-shards them)."""
        rep = NamedSharding(mesh, P())
        return rep, rep

    def replay_fields(
        self, fields, step0: int, nsteps: int, devices=None,
    ) -> tuple:
        """Recompute ``nsteps`` steps from ``fields`` (the state at
        absolute step ``step0``) and return the resulting field tuple,
        leaving the live state untouched.

        The replay jits the SAME step construction as :meth:`iterate`
        (:meth:`_make_step_fn`) *with the same donation signature*:
        XLA:CPU's FP-contraction decisions are donation-sensitive (a
        non-donating twin of the donating live runner drifts 1 ulp in
        the Pallas overlap bands), so the replay donates fresh copies
        of the anchor — never the caller's retained buffers — and the
        compiled program is the live executable bit for bit. With that,
        bitwise determinism — noise keyed by (key, absolute step,
        global cell), exchange schedule fixed — makes replay-vs-live an
        exact equality on any placement. ``devices`` optionally
        rebuilds the mesh over a permuted device assignment of the same
        shape (SDC shadow mode: a deterministic per-core fault cannot
        self-confirm); inputs are device_put onto the permuted sharding
        first."""
        if nsteps <= 0:
            return tuple(fields)
        if devices is None:
            # Same-placement (spot) replay IS the live runner: the one
            # compiled executable serves both, so spot screening pays
            # recompute only — no twin compile — and replay-equals-live
            # is the executable's own determinism, not a compiler
            # coincidence.
            fn, sharding, device = self._runner(nsteps), None, None
        else:
            key = (int(nsteps), tuple(d.id for d in devices))
            entry = self._replay_fns.get(key)
            if entry is None:
                mesh = None
                sharding = None
                device = None
                if self.mesh is not None:
                    mesh = Mesh(
                        np.array(devices).reshape(self.mesh.devices.shape),
                        self.mesh.axis_names,
                    )
                    sharding = NamedSharding(mesh, self.field_sharding.spec)
                else:
                    device = devices[0]
                # Donation mirrors the live runner: XLA:CPU codegen is
                # donation-sensitive (a non-donating twin drifts 1 ulp
                # in the Pallas overlap bands).
                fn = jax.jit(
                    _gs_step(self._make_step_fn(nsteps, mesh)),
                    donate_argnums=tuple(range(self.model.n_fields)),
                    compiler_options=self._compiler_options(nsteps),
                )
                entry = self._replay_fns[key] = (fn, sharding, device)
            fn, sharding, device = entry
        base_key, params = self.base_key, self.params
        # The donated field args must be fresh buffers: a bisection
        # replays from one anchor several times, and device_put onto an
        # unchanged sharding is an alias, not a copy.
        fields = self._copy_fields(fields)
        if sharding is not None:
            fields = tuple(jax.device_put(f, sharding) for f in fields)
            kck, pck = self._replay_arg_shardings(sharding.mesh)
            base_key = jax.device_put(base_key, kck)
            params = jax.device_put(params, pck)
        elif device is not None:
            fields = tuple(jax.device_put(f, device) for f in fields)
            base_key = jax.device_put(base_key, device)
            params = jax.device_put(params, device)
        return tuple(fn(*fields, base_key, jnp.int32(step0), params))

    # ---------------------------------------------------------------- public

    def iterate(self, nsteps: int = 1) -> None:
        """Advance the simulation ``nsteps`` steps (``public.jl:45-71``)."""
        if nsteps <= 0:
            return
        # Host side of one round: runner lookup (or build), argument
        # preparation, enqueue. The steps themselves run on the device.
        with get_tracer().span("iterate", step=self.step, nsteps=nsteps):
            runner = self._runner(nsteps)
            self.fields = tuple(runner(
                *self.fields, self.base_key, jnp.int32(self.step),
                self.params,
            ))
        self.step += nsteps

    def _shard_parts(self, *arrays):
        """Per-addressable-shard ``(offsets, true_sizes, *field_devs)``
        — the device-side half of the output path: each entry carries
        the shard's global (start, count) box clipped to the true
        domain (non-divisible L stores pad cells past L on the high
        edge of the last block per axis; framework internals that never
        leave the process) plus one single-device shard array per model
        field."""
        L = self.settings.L
        first = arrays[0]

        def box(index):
            # Slices are unhashable before py3.12, so shards are matched
            # across fields by their (start, count) box, not the raw
            # index.
            idx = index if isinstance(index, tuple) else (index,)
            offsets = tuple(sl.start or 0 for sl in idx)
            sizes = tuple(
                (sl.stop or g) - (sl.start or 0)
                for sl, g in zip(idx, first.shape)
            )
            return offsets, sizes

        other_shards = [
            {box(s.index): s for s in a.addressable_shards}
            for a in arrays[1:]
        ]
        parts = []
        for sh in first.addressable_shards:
            offsets, sizes = box(sh.index)
            true = tuple(min(L - o, s) for o, s in zip(offsets, sizes))
            parts.append(
                (offsets, true, sh.data)
                + tuple(m[(offsets, sizes)].data for m in other_shards)
            )
        return parts

    def snapshot_async(
        self, *, health: bool = False, numerics: bool = False,
        checksum: bool = False, bitflip=None, encode=None,
        exact: bool = True,
    ) -> FieldSnapshot:
        """Capture the current (u, v) for overlapped output: returns a
        :class:`FieldSnapshot` with non-blocking D2H transfers already
        in flight, so the caller can hand it to a background writer and
        immediately dispatch the next compute chunk.

        The fields are first copied into FRESH device buffers (one
        asynchronously dispatched device-side pass): the next donated
        ``iterate`` call aliases the current field buffers into its
        outputs and marks them deleted, which invalidates every shard
        view of them — holding a reference to the old arrays does NOT
        protect the data. The copy is storage the runner never sees, so
        the snapshot stays valid for as long as the consumer needs it.

        ``health=True`` additionally evaluates the fused
        ``isfinite``/range probe (``resilience/health.device_probe``)
        inside the SAME jitted program — the fields are read from HBM
        once for both copy and probe, and the five scalars ride the
        boundary's existing D2H (``FieldSnapshot.health_report``).
        ``numerics=True`` fuses the per-field min/max/mean/L2/finite
        reductions (``obs/numerics.device_numerics_probe``) into the
        same program the same way (``FieldSnapshot.numerics_report``).
        ``checksum=True`` (``GS_CKPT_VERIFY=full``) fuses the per-field
        integrity checksum
        (``resilience/integrity.device_field_checksum``) in next to
        them; ``FieldSnapshot.blocks`` re-derives it from the landed
        host bytes and refuses a mismatching boundary. ``bitflip``
        (chaos hook, the ``bitflip`` fault kind) flips one bit of the
        device-side COPY after the probes ran — silent write-path
        corruption, field/member-addressable, live trajectory
        untouched.

        ``encode`` (docs/PRECISION.md — the lossy snapshot codec) maps
        field indices to quantization bit widths: coded fields are
        additionally quantized to uint payloads INSIDE the same jitted
        program (``io/codec.device_quantize`` — the exact field is
        read from HBM once for copy, probes, and encode together) and
        only the compressed bytes ride the D2H for them.
        ``exact=False`` skips the exact copies entirely (a lossy-
        output-only boundary — the D2H volume win); at least one of
        ``exact``/``encode`` must be requested.
        """
        from .io import codec as io_codec

        enc_items = (
            tuple(sorted((int(i), int(b)) for i, b in encode.items()))
            if encode else None
        )
        if not exact and enc_items is None:
            raise ValueError(
                "snapshot_async(exact=False) needs an encode spec — "
                "a boundary with neither captures nothing"
            )
        key = (health, numerics, checksum, enc_items, exact)
        fn = self._snapshot_fns.get(key)
        if fn is None:
            # +0 forces a real output buffer (no donation, so XLA never
            # aliases inputs into outputs); sharding follows the inputs.
            device_probe = self._probe_fn() if health else None
            num_probe = self._numerics_probe_fn() if numerics else None
            ck_probe = self._checksum_probe_fn() if checksum else None
            spec = dict(enc_items) if enc_items else None

            def copy(*fields):
                res = {}
                copies = (
                    tuple(f + jnp.zeros((), f.dtype) for f in fields)
                    if exact else None
                )
                if copies is not None:
                    res["copies"] = copies
                if spec is not None:
                    entries, lohi = [], []
                    for i, f in enumerate(fields):
                        bits = spec.get(i)
                        if bits is None:
                            # Uncoded fields ride the codec set as
                            # exact copies (one buffer, shared with
                            # the exact set when both are captured).
                            entries.append(
                                copies[i] if copies is not None
                                else f + jnp.zeros((), f.dtype)
                            )
                        else:
                            q, lo, hi = io_codec.device_quantize(
                                f, bits
                            )
                            entries.append(q)
                            lohi.append((lo, hi))
                    res["enc"] = tuple(entries)
                    res["enc_lohi"] = tuple(lohi)
                if device_probe is not None:
                    res["health"] = device_probe(*fields)
                if num_probe is not None:
                    res["numerics"] = num_probe(*fields)
                if ck_probe is not None:
                    res["checksums"] = ck_probe(*fields)
                return res

            fn = self._snapshot_fns[key] = jax.jit(copy)
        res = fn(*self.fields)
        copies = res.get("copies")
        probe = res.get("health")
        nums = res.get("numerics")
        cksums = res.get("checksums")
        enc = res.get("enc")
        if bitflip is not None:
            if copies is not None:
                copies = self._apply_snapshot_bitflip(copies, bitflip)
            else:
                enc = self._apply_snapshot_bitflip(enc, bitflip)
        parts = self._shard_parts(*copies) if copies is not None else None
        enc_parts, enc_meta = None, None
        if enc is not None:
            enc_parts = self._shard_parts(*enc)
            enc_meta = {}
            for (i, bits), (lo, hi) in zip(enc_items, res["enc_lohi"]):
                enc_meta[i] = (
                    bits, lo, hi, str(np.dtype(self.dtype)),
                )
        for plist in (parts, enc_parts):
            for part in plist or ():
                for dev in part[2:]:
                    dev.copy_to_host_async()
        return self.snapshot_cls(
            parts, self.step, health=probe, numerics=nums,
            checksums=cksums, field_names=self.model.field_names,
            enc_parts=enc_parts, enc_meta=enc_meta,
        )

    def _checksum_probe_fn(self):
        from .resilience.integrity import device_field_checksum

        return device_field_checksum

    def _apply_snapshot_bitflip(self, copies, field="u"):
        """The ``bitflip`` fault body: XOR one bit of one field's
        snapshot COPY (after the checksum probe read the pristine
        fields) — exactly the silent write-path corruption the
        device-side checksum exists to catch. The live field buffers
        are untouched: the trajectory is unchanged, only this
        boundary's bytes are wrong."""
        from .resilience.integrity import apply_bitflip

        i = self._field_index(field if field is not True else "u")
        flipped = apply_bitflip(copies[i], (0,) * copies[i].ndim)
        return copies[:i] + (flipped,) + copies[i + 1:]

    def numerics_stats(self):
        """One probe-only numerics reduction over the live fields,
        resolved to a :class:`~.obs.numerics.NumericsReport` — the
        ``GS_NUMERICS=every_round`` path, for rounds that end at no
        write boundary (boundaries get the probe fused into the
        snapshot copy instead). A pure read of the field buffers: the
        trajectory is untouched."""
        fn = getattr(self, "_numerics_fn", None)
        if fn is None:
            probe = self._numerics_probe_fn()

            def run(*fields):
                return probe(*fields)

            fn = self._numerics_fn = jax.jit(run)
        return self._resolve_numerics_host(fn(*self.fields))

    def _resolve_numerics_host(self, raw):
        from .obs import numerics as obs_numerics

        return obs_numerics.resolve_report(raw, self.model.field_names)

    def poison_nan(self, field="u") -> None:
        """Chaos/testing hook (``resilience/faults.py`` kind ``nan``):
        set one cell of ``field`` (a model field name, the legacy
        ``"u"``/``"v"`` aliases, or an index) to NaN, modelling a
        numerical blow-up the health guard must catch at the next
        boundary. A scatter on the live buffers; sharding is
        preserved."""
        i = self._field_index(field)
        arr = self.fields[i]
        poisoned = arr.at[(0,) * arr.ndim].set(
            jnp.asarray(float("nan"), arr.dtype)
        )
        self.fields = (
            self.fields[:i] + (poisoned,) + self.fields[i + 1:]
        )

    def poison_drift(self, field="u", factor: float = 8.0) -> None:
        """Chaos/testing hook (``resilience/faults.py`` kind
        ``drift``): scale a small corner box of ``field`` by
        ``factor`` — a large but FINITE excursion, the numerical
        signature of a mixed-precision accumulation going wrong
        without blowing up. The corner sits outside the reaction seed
        (the activator field is zero there for every registered
        model's init), so the excursion decays diffusively instead of
        feeding the reaction: the health guard stays green
        (everything finite), while the field's max statistic jumps by
        ~``factor`` and the numerics drift signal
        (``obs/numerics.py``) must trip the
        :class:`~.resilience.health.DriftGate` per
        ``GS_DRIFT_POLICY``. A scatter on the live buffers; sharding
        is preserved."""
        i = self._field_index(field)
        arr = self.fields[i]
        box = tuple(slice(0, 2) for _ in range(arr.ndim))
        scaled = arr.at[box].multiply(
            jnp.asarray(factor, arr.dtype)
        )
        self.fields = (
            self.fields[:i] + (scaled,) + self.fields[i + 1:]
        )

    def _sdc_site(self, arr, device=None):
        """``(device_name, global index)`` for the ``sdc`` poison: the
        center cell of the target device's shard, so the flip lands
        squarely inside one device's block and diffusion keeps the
        divergence centered there over a short screening window —
        the attribution's blast-center rule sees a clean signal.
        Default target: the highest-id device owning a shard."""
        by_name = {}
        for sh in arr.addressable_shards:
            d = sh.device
            by_name[f"{d.platform}:{d.id}"] = sh
        if device is None:
            name = max(
                by_name,
                key=lambda n: (
                    n.rsplit(":", 1)[0], int(n.rsplit(":", 1)[1]),
                ),
            )
        else:
            name = device
            if name not in by_name:
                raise ValueError(
                    f"sdc fault device {name!r} owns no addressable "
                    f"shard (have: {', '.join(sorted(by_name))})"
                )
        sh = by_name[name]
        idx = sh.index if isinstance(sh.index, tuple) else (sh.index,)
        index = tuple(
            (sl.start or 0) + ((sl.stop or g) - (sl.start or 0)) // 2
            for sl, g in zip(idx, arr.shape)
        )
        return name, index

    def poison_sdc(self, device=None, field="u") -> str:
        """Chaos/testing hook (``resilience/faults.py`` kind ``sdc``):
        XOR the lowest bit of ONE LIVE cell in the shard owned by the
        named device, BEFORE the round runs — a compute-path fault
        model. The corrupted value is an *input* to the step program,
        so the trajectory diverges from a clean replay and SDC
        screening must detect it and attribute it back to this device.
        Contrast PR 14's snapshot-copy ``bitflip``, which corrupts
        write-path bytes only and must stay invisible to SDC checks
        (asserted in tier-1). Returns the poisoned device's name for
        the injection record.

        The flip hits the mantissa MSB of the storage word (bit 6 of a
        2-byte word, bit 22 of a 4-byte one): a lowest-bit flip at a
        flat-region cell is diffusively absorbed below one ulp within
        a round, while real SDC flips arbitrary bits — the screening
        contract targets persistent wrong answers. The flipped value
        stays finite (mantissa-only), so the health guard stays green
        and only screening can catch it."""
        from .resilience.integrity import apply_bitflip

        i = self._field_index(field)
        arr = self.fields[i]
        name, index = self._sdc_site(arr, device)
        bit = 6 if jnp.dtype(arr.dtype).itemsize == 2 else 22
        flipped = apply_bitflip(arr, index, bit=bit)
        if getattr(self, "field_sharding", None) is not None:
            # The scatter's jit can hand back a resharded (replicated)
            # output; the live state must keep the mesh sharding.
            flipped = jax.device_put(flipped, self.field_sharding)
        self.fields = (
            self.fields[:i] + (flipped,) + self.fields[i + 1:]
        )
        return name

    def local_blocks(self):
        """Per-addressable-shard ``(offsets, sizes, *field_blocks)``
        (for Gray-Scott: ``(offsets, sizes, u_block, v_block)``).

        The multi-host output path: each process writes only the blocks it
        owns, with their global (start, count) boxes — the ADIOS2
        per-rank-decomposition analog (``IO.jl:60-67``). Single device
        yields one whole-grid block.

        Synchronous form: reads the live field buffers directly (no
        device-side copy) and blocks until the values are on the host —
        callers must consume the result before the next ``iterate``.
        For output overlapped with compute use :meth:`snapshot_async`.
        """
        jax.block_until_ready(self.fields)
        return self.snapshot_cls(
            self._shard_parts(*self.fields), self.step,
            field_names=self.model.field_names,
        ).blocks()

    def restore_from_reader(self, reader, step_index: int, step: int) -> None:
        """Restore state with per-shard selection reads — each process
        pulls only its own blocks from the checkpoint store (scalable
        multi-host restart; no full-array gather). Store variables are
        the model's declared field names."""
        names = self.model.field_names
        if not self.sharded:
            self.restore_fields(
                tuple(
                    reader.get(name, step=step_index) for name in names
                ),
                step,
            )
            return

        storage = self.domain.storage_shape
        L = self.settings.L

        def make(name: str, bv: float):
            def cb(index):
                start = [s.start or 0 for s in index]
                count = [
                    (s.stop or g) - (s.start or 0)
                    for s, g in zip(index, storage)
                ]
                # The store holds the true L^3 domain; pad cells (only
                # present for non-divisible L) are reconstructed at the
                # boundary value, exactly as a fresh init would.
                true = [min(L - st, c) for st, c in zip(start, count)]
                block = reader.get(
                    name, step=step_index, start=start, count=true
                ).astype(self.dtype)
                if tuple(true) != tuple(count):
                    buf = np.full(count, bv, dtype=self.dtype)
                    buf[tuple(slice(0, t) for t in true)] = block
                    return buf
                return block

            return jax.make_array_from_callback(
                storage, self.field_sharding, cb
            )

        self.fields = tuple(
            make(name, bv)
            for name, bv in zip(names, self.model.boundaries)
        )
        self.step = int(step)

    def restore_fields(self, fields, step: int) -> None:
        """Restore state from full host field arrays (fixes the
        reference's hardcoded ``restart_step = 0``,
        ``src/GrayScott.jl:77-78``). ``fields`` follows the model's
        declaration order."""
        fields = tuple(jnp.asarray(f, self.dtype) for f in fields)
        if len(fields) != self.model.n_fields:
            raise ValueError(
                f"Checkpoint has {len(fields)} fields; model "
                f"{self.model.name!r} declares {self.model.n_fields}"
            )
        expected = (self.settings.L,) * 3
        for name, f in zip(self.model.field_names, fields):
            if f.shape != expected:
                raise ValueError(
                    f"Checkpoint shape {name}={f.shape} does not match "
                    f"L={self.settings.L}"
                )
        if self.sharded and self.domain.padded:
            # Rebuild the pad shell at the boundary value (the stored
            # arrays cover only the true domain).
            pads = [
                (0, g - self.settings.L)
                for g in self.domain.storage_shape
            ]
            fields = tuple(
                jnp.pad(f, pads, constant_values=bv)
                for f, bv in zip(fields, self.model.boundaries)
            )
        target = self.field_sharding if self.sharded else self.device
        self.fields = tuple(jax.device_put(f, target) for f in fields)
        self.step = int(step)

    def restore(self, u: np.ndarray, v: np.ndarray, step: int) -> None:
        """Two-field compatibility form of :meth:`restore_fields` (the
        historical Gray-Scott signature)."""
        self.restore_fields((u, v), step)

    def get_fields(self) -> Tuple[np.ndarray, ...]:
        """Host copies of the model's fields (declaration order),
        clipped to the true ``L^3`` domain — the ghost-strip + D->H
        analog (``Simulation_CPU.jl:125-133``, ``CUDAExt.jl:199-209``;
        the strip also removes the storage pad of a non-divisible
        sharded L)."""
        jax.block_until_ready(self.fields)
        L = self.settings.L
        return tuple(
            np.asarray(f)[:L, :L, :L] for f in self.fields
        )

    def block_until_ready(self) -> None:
        with get_tracer().span("sync", step=self.step):
            jax.block_until_ready(self.fields)

    def layout(self):
        """The :class:`~.reshard.plan.LayoutMeta` describing this run's
        adopted decomposition — what its checkpoints record, and the
        "new" side of an elastic restore plan (docs/RESHARD.md)."""
        from .reshard.restore import layout_of

        return layout_of(self)

    def metrics_labels(self) -> dict:
        """The label set every metric of this run carries
        (``obs/metrics.py``): model / mesh / resolved kernel, so one
        scrape endpoint distinguishes runs sharing a host. The ensemble
        engine extends it with the member count."""
        return {
            "model": self.model.name,
            "mesh": "x".join(str(d) for d in self.domain.dims),
            "kernel": self.kernel_language,
        }

    def device_memory_stats(self) -> list:
        """Per-local-device allocator stats for the metrics registry
        (``obs/metrics.py``): HBM in use / peak per device, the number
        an operator watches for creeping fragmentation on a week-long
        campaign. Backends without ``memory_stats`` (CPU) contribute
        nothing — the list is empty there, and callers treat that as
        "no data", not zero."""
        out = []
        for d in jax.local_devices():
            try:
                ms = d.memory_stats()
            except Exception:  # noqa: BLE001 — optional PJRT surface
                ms = None
            if not ms:
                continue
            out.append({
                "device": f"{d.platform}:{d.id}",
                "bytes_in_use": int(ms.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(ms.get("peak_bytes_in_use", 0)),
            })
        return out


def initialization(
    args, *, n_devices: Optional[int] = None, seed: int = 0
) -> Tuple[Settings, CartDomain, Simulation]:
    """Parse config and build a ready-to-run simulation
    (reference ``Simulation.initialization``, ``communication.jl:15-33``)."""
    settings = config.get_settings(list(args))
    sim = Simulation(settings, n_devices=n_devices, seed=seed)
    return settings, sim.domain, sim


def finalize() -> None:
    """Reference-parity no-op (``communication.jl:40-46``): JAX needs no
    explicit teardown; kept so driver code mirrors the reference flow."""
