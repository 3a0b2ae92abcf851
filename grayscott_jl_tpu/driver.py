"""Simulation driver: the reference's ``GrayScott.main`` step loop.

Flow (``src/GrayScott.jl:68-103``): initialization -> output stream init ->
step loop -> write every ``plotgap`` -> close -> finalize; plus what the
reference only declares (SURVEY defect #4): checkpoint every
``checkpoint_freq`` and restart from ``restart_input``.

Idiomatic-JAX difference: the loop advances in fused chunks — the number of
steps to the next output/checkpoint boundary runs as one jitted
``lax.fori_loop`` on device (halo exchange included), with host contact
only at the boundaries. The reference instead crosses the host boundary
every single step (``public.jl:45-71``).

Output is overlapped with compute: each boundary captures an async
:class:`~.simulation.FieldSnapshot` (non-blocking D2H) and submits it to
the bounded background writer (``io/async_writer.py``), so
serialization/VTK/disk for step N drain while steps N+1.. compute.
``GS_ASYNC_IO_DEPTH`` bounds the in-flight steps (0 = the reference's
synchronous flow); the pipeline preserves step order, applies
backpressure when full, surfaces writer errors on this thread, and is
drained before the run is declared complete.

Kernel scheduling (``tune/``, docs/TUNING.md): with
``kernel_language = "Auto"`` the Simulation constructor consults the
measured autotuner (``GS_AUTOTUNE`` / ``autotune`` TOML key) behind the
analytic ICI-model dispatch; the decision provenance (mode, cache
hit/miss, candidates timed, tuning seconds) lands in the RunStats
``kernel_selection`` section below, next to the supervisor's
degradation provenance.

Observability (``obs/``, docs/OBSERVABILITY.md): the loop below is the
instrumentation spine — every phase boundary is one watchdog heartbeat
which is one trace span edge (``GS_TRACE``), every ``RunStats`` phase
is a nested span, each fused round feeds the step-latency histogram
(``GS_METRICS``), and lifecycle/fault/recovery markers route through
the unified event stream (``GS_EVENTS``). ``GS_PROFILE=start:stop``
brackets a step range with a ``jax.profiler`` device capture. All of
it observes host-side control flow only: trajectories are bitwise
identical with observability on or off.

Resilience (``resilience/``): :func:`main` is split into the supervision
dispatch and :func:`run_once`, the single-attempt loop. ``GS_SUPERVISE``
routes through ``resilience.supervisor.supervise`` — failure
classification, backoff, checkpoint auto-resume, Pallas->XLA
degradation. ``run_once`` itself hosts the boundary-time hooks: the
deterministic fault plan (``GS_FAULTS``), the device-side health guard
on the snapshot path (``GS_HEALTH_POLICY``), and a close-on-any-exit
guarantee for the output/checkpoint stores (an async-writer re-raise
must not leak open stores or a half-written rollback sidecar).
"""

from __future__ import annotations

import copy
import os
import time
from typing import List, Optional

from .config.env import env_int, env_raw, env_str
from .config.settings import Settings, get_settings
from .simulation import Simulation, finalize
from .utils.log import Logger


def _next_boundary(step: int, period: int, limit: int) -> int:
    """Next multiple of ``period`` after ``step``, capped at ``limit``."""
    if period <= 0:
        return limit
    return min(limit, (step // period + 1) * period)


def _resolve_reshape_dims(req, sim):
    """Resolve a live-reshape request to concrete target mesh dims, or
    None for an infeasible / no-op request (docs/RESHARD.md).

    The serve side stays JAX-free, so its elastic policy sends scale
    HINTS (``{"scale": "grow"|"shrink"}``) and the driver — the layer
    that can see the device inventory — resolves them: grow doubles the
    spatial device count toward the idle chips, shrink halves it to
    donate the slice. An explicit ``{"mesh_dims": [x, y, z]}`` pins the
    target outright.
    """
    from .parallel.domain import CartDomain, dims_create

    if not isinstance(req, dict):
        return None
    member_shards = int(getattr(sim, "member_shards", 1))
    cur = sim.domain.n_blocks
    if req.get("mesh_dims"):
        dims = tuple(int(d) for d in req["mesh_dims"])
    else:
        scale = req.get("scale")
        if scale == "grow":
            n = cur * 2
        elif scale == "shrink":
            n = cur // 2
        else:
            return None
        if n < 1:
            return None
        dims = dims_create(n, 3)
    n = dims[0] * dims[1] * dims[2]
    from .resilience.sdc import usable_devices

    if n * member_shards > len(usable_devices()):
        return None  # not enough (non-quarantined) chips to grow into
    try:
        CartDomain.create(n, sim.settings.L, dims=dims)
    except ValueError:
        return None  # infeasible for this L — refuse the hint quietly
    if dims == tuple(sim.domain.dims):
        return None
    return dims


def maybe_initialize_distributed() -> None:
    """Multi-host bring-up (replaces the reference's ``MPI.Init``,
    ``communication.jl:20``).

    Activated by ``GS_TPU_COORDINATOR`` (host:port) +
    ``GS_TPU_NUM_PROCESSES`` + ``GS_TPU_PROCESS_ID`` for explicit launch
    (works on CPU for testing), or ``GS_TPU_DISTRIBUTED=auto`` for
    TPU-pod autodetection via ``jax.distributed.initialize()``.
    """
    import os

    import jax

    coord = env_raw("GS_TPU_COORDINATOR")
    if coord:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=env_int("GS_TPU_NUM_PROCESSES"),
            process_id=env_int("GS_TPU_PROCESS_ID"),
        )
    elif env_raw("GS_TPU_DISTRIBUTED") == "auto":
        jax.distributed.initialize()


def main(args: List[str], *, n_devices: Optional[int] = None, seed: int = 0):
    """Run a full simulation from CLI args (reference ``GrayScott.main``).

    With supervision armed (``GS_SUPERVISE`` / ``supervise`` TOML key)
    the run goes through the restart loop; otherwise a single open-loop
    :func:`run_once` — the reference's behavior, plus guaranteed store
    closure on failure.

    ``GS_SEED`` overrides the base PRNG seed (default 0) without an API
    call — e.g. to launch the solo-run equivalent of ensemble member k
    (seed ``base + k``; docs/ENSEMBLE.md).
    """
    settings = get_settings(list(args))
    env_seed = env_str("GS_SEED", "").strip()
    if env_seed:
        seed = int(env_seed)

    # Split-phase exchange support flags (async collective-permute +
    # latency-hiding scheduler) must reach libtpu before the backend
    # initializes; TPU-only flags, and pointless when the operator
    # pinned the fused exchange.
    from .config import settings as config_mod

    backend, _lang = config_mod.load_backend_and_lang(settings)
    if backend == "tpu" and config_mod.resolve_comm_overlap(settings) != "off":
        from .utils.benchmark import inject_overlap_tpu_flags

        inject_overlap_tpu_flags()

    maybe_initialize_distributed()

    from .resilience import supervisor

    if supervisor.supervision_enabled(settings):
        # Multi-host runs are supervised for real (the old per-process
        # refusal is gone): classified failures rendezvous on a quorum
        # restart step so all ranks restart together
        # (resilience/rendezvous.py, docs/RESILIENCE.md).
        return supervisor.supervise(settings, n_devices=n_devices, seed=seed)
    return run_once(settings, n_devices=n_devices, seed=seed)


def _close_quietly(store) -> None:
    """Best-effort close on the failure path: the store may hold an
    open step from a writer-thread death — a secondary close error must
    never mask the exception already in flight."""
    try:
        store.close()
    except Exception:  # noqa: BLE001 — deliberately swallowed
        pass


def _with_checksums(fn, checksums):
    """Bind the boundary's device-side field checksums onto a write
    target (``write_step``/``save`` grow an optional ``checksums``
    kwarg) — runs on the async writer's worker thread."""

    def wrapped(step, blocks):
        return fn(step, blocks, checksums=checksums)

    return wrapped


def _with_io_fault(plan, journal, fn):
    """Wrap an ``AsyncStepWriter`` target so a due ``io_error`` fault
    raises inside it — surfacing on the driver thread as a transient
    ``AsyncIOError``, exactly the path a real disk hiccup takes.
    Runs on the writer's worker thread; plan/journal are thread-safe.
    """
    from .resilience.faults import InjectedIOError

    def wrapped(step, blocks):
        fault = plan.take("io_error", step)
        if fault is not None:
            journal.record(
                event="injected", kind="io_error", step=step,
                planned_step=fault.step,
            )
            raise InjectedIOError(
                f"injected transient I/O error at step {step} "
                f"(planned step {fault.step})"
            )
        return fn(step, blocks)

    return wrapped


def run_once(
    settings: Settings,
    *,
    n_devices: Optional[int] = None,
    seed: int = 0,
    context=None,
    sim_factory=None,
    reshape_poll=None,
):
    """One supervised-or-not simulation attempt.

    ``context`` is the supervisor's
    :class:`~.resilience.supervisor.SupervisorContext` (shared fault
    plan + journal across attempts, degradation provenance); standalone
    runs build their own from the environment. Raises on failure —
    classification and recovery live in the supervisor, not here.

    ``sim_factory`` (optional) supplies the Simulation instead of the
    constructors below — the warm-ensemble seam the serve worker fleet
    uses (``serve/worker.py``): a factory can hand back an
    already-compiled :class:`~.ensemble.engine.EnsembleSimulation`
    rebound to this launch's members (``repack``), so a packed batch
    pays zero recompilation. Called as
    ``sim_factory(settings, n_devices=..., seed=...)``.

    ``reshape_poll`` (optional) is the between-rounds live-reshape hook
    (docs/RESHARD.md "In-job reshapes"): called at every round
    boundary; a truthy return — ``{"mesh_dims": [x, y, z]}`` or
    ``{"scale": "grow"|"shrink"}`` — moves the LIVE state onto the
    target mesh via ``reshard.restore.reshape_live`` (no checkpoint
    round-trip, continuation bitwise-identical) and the run keeps
    stepping on the new layout. The serve elastic policy
    (``serve/elastic.py``) feeds this hook.
    """
    from .resilience.faults import (
        FaultPlan,
        ShutdownListener,
        resolve_graceful_shutdown,
    )
    from .resilience.health import HealthGuard
    from .resilience.supervisor import FaultJournal
    from .resilience.watchdog import Watchdog, resolve_watchdog

    if context is not None:
        plan, journal = context.plan, context.journal
    else:
        plan = FaultPlan.from_env(settings)
        journal = FaultJournal.from_env(settings)
    guard = HealthGuard.from_env(settings)

    # Hang watchdog + graceful-shutdown listener bracket the whole
    # attempt: the watchdog's "compile" deadline must already be armed
    # while the Simulation constructor jits (and autotunes), and a
    # SIGTERM during compile should still exit through the graceful
    # path at the first boundary.
    from .obs import events as obs_events

    deadlines = resolve_watchdog(settings)
    wd = Watchdog(deadlines, journal=journal).start() if deadlines else None
    shutdown = ShutdownListener(
        enabled=resolve_graceful_shutdown(settings), watchdog=wd,
        # Live preemption notice on the unified event stream the moment
        # the signal lands (the boundary-time graceful_shutdown journal
        # marker follows later, possibly much later on a long round).
        on_request=lambda signum: obs_events.get_events().emit(
            "shutdown_requested", signum=signum
        ),
    ).install()
    try:
        return _run_once_inner(
            settings, n_devices=n_devices, seed=seed, context=context,
            plan=plan, journal=journal, guard=guard, wd=wd,
            shutdown=shutdown, sim_factory=sim_factory,
            reshape_poll=reshape_poll,
        )
    except BaseException as exc:
        # A watchdog expiry unwinds as KeyboardInterrupt (the monitor's
        # interrupt_main, possibly re-raised through the shutdown
        # listener); surface it as the classified hang it is.
        if (wd is not None and wd.expired is not None
                and isinstance(exc, KeyboardInterrupt)):
            wd.check()  # raises HangError with the expired phase/step
        raise
    finally:
        shutdown.uninstall()
        if wd is not None:
            wd.stop()
        # The trace file must be valid after EVERY attempt — a
        # supervised multi-restart run flushes here between attempts,
        # and the atomic rewrite keeps the on-disk JSON well-formed
        # even if the next attempt dies mid-span.
        from .obs.trace import get_tracer

        try:
            get_tracer().flush()
        except OSError as e:
            import sys

            print(f"gray-scott: warning: could not write trace "
                  f"({e})", file=sys.stderr)


def _run_once_inner(
    settings: Settings,
    *,
    n_devices: Optional[int],
    seed: int,
    context,
    plan,
    journal,
    guard,
    wd,
    shutdown,
    sim_factory=None,
    reshape_poll=None,
):
    import jax

    from .obs import events as obs_events
    from .obs import metrics as obs_metrics
    from .obs.trace import ProfileWindow, get_tracer
    from .resilience.faults import (
        GracefulShutdown,
        InjectedKernelError,
        PreemptionError,
        injected_hang_wait,
    )

    # Observability sinks (docs/OBSERVABILITY.md): process-wide
    # singletons, so a supervised run's restart attempts share one
    # trace, one event stream, and one metrics registry — the unified
    # timeline is the point. All of them are no-ops unless their env
    # knob (GS_TRACE / GS_EVENTS / GS_METRICS / GS_PROFILE) is set, and
    # none of them touch the jitted programs: trajectories are bitwise
    # identical obs on or off (asserted in tier-1).
    tracer = get_tracer()
    evs = obs_events.get_events()
    metrics = obs_metrics.get_metrics(settings)
    profile = ProfileWindow.from_env()
    attempt = context.attempt if context is not None else 0

    def _mark(phase, step=None):
        """One driver phase boundary: the watchdog heartbeat (which
        itself emits the trace span edge) or, on an unwatched run, the
        edge directly — same timeline either way."""
        if wd is not None:
            wd.heartbeat(phase, step)
        else:
            tracer.edge(phase, step)

    _mark("compile")
    ens = getattr(settings, "ensemble", None)
    if sim_factory is not None:
        # The serve worker's warm-ensemble seam: the factory may hand
        # back an already-compiled engine rebound to this launch.
        sim = sim_factory(settings, n_devices=n_devices, seed=seed)
    elif ens is not None:
        # Batched ensemble run (docs/ENSEMBLE.md): one compiled launch
        # advances every member; stores are member-indexed.
        from .ensemble.engine import EnsembleSimulation

        sim = EnsembleSimulation(settings, n_devices=n_devices, seed=seed)
    else:
        sim = Simulation(settings, n_devices=n_devices, seed=seed)
    log = Logger(verbose=settings.verbose)
    proc, nprocs = jax.process_index(), jax.process_count()

    restart_step = 0
    if settings.restart:
        # Elastic restore (docs/RESHARD.md): the checkpoint's recorded
        # layout is compared against the mesh THIS run adopted; a
        # mismatch reshards via per-new-shard selection reads (and, for
        # ensembles, grows/shrinks the member set) with a `reshard`
        # event on the journal and the unified stream.
        from .reshard.restore import restore_run

        restart_step, _plan = restore_run(
            sim, settings, log=log, journal=journal
        )
        if ens is not None:
            log.info(
                f"Restarted {ens.n} ensemble members from "
                f"{settings.restart_input} member stores at step "
                f"{restart_step}"
            )
        else:
            log.info(
                f"Restarted from {settings.restart_input} at step "
                f"{restart_step}"
            )

    # Lossy snapshot codec (docs/PRECISION.md): resolved at Simulation
    # construction (misconfigurations fail there); ensembles keep exact
    # output — per-member quantization ranges are a member-axis
    # reduction the fused probe family does not carry yet, and a codec
    # that silently changed meaning per member would be worse than
    # refusing. Loud, not silent.
    codec = sim.snapshot_codec
    if ens is not None and codec.enabled:
        log.warn(
            "snapshot_bits ignored for ensemble runs (member stores "
            "stay exact); lossy output is a solo-run codec"
        )
        from .io.codec import CodecConfig

        codec = CodecConfig({}, {})
    #: field-index -> bits spec for snapshot_async's fused encoder.
    enc_spec = {
        i: codec.output[n.lower()]
        for i, n in enumerate(sim.model.field_names)
        if n.lower() in codec.output
    }
    ckpt_lossy = bool(codec.ckpt)

    if ens is not None:
        from .ensemble.io import EnsembleCheckpointWriter, EnsembleStream

        stream_cls, ckpt_cls = EnsembleStream, EnsembleCheckpointWriter
    else:
        from .io.checkpoint import CheckpointWriter
        from .io.stream import SimStream

        stream_cls, ckpt_cls = SimStream, CheckpointWriter

    stream_kw = {"codec": codec.output or None} if ens is None else {}
    ckpt_kw = {"codec": codec.ckpt or None} if ens is None else {}
    stream = stream_cls(
        settings, sim.domain, sim.dtype, writer_id=proc, nwriters=nprocs,
        resume_step=restart_step if settings.restart else None,
        **stream_kw,
    )
    ckpt = (
        ckpt_cls(
            settings, sim.dtype, writer_id=proc, nwriters=nprocs,
            resume_step=restart_step if settings.restart else None,
            # Elastic-resume metadata (docs/RESHARD.md): fresh stores
            # record the writing run's layout so a future restore can
            # plan an old->new reshard.
            layout=sim.layout(),
            **ckpt_kw,
        )
        if settings.checkpoint
        else None
    )

    from .io.async_writer import AsyncStepWriter
    from .utils.profiler import RunStats, trace

    # Auto-dispatch provenance: which kernel the ICI model picked and
    # why (None for an explicitly pinned language); after a supervisor
    # degradation, also which language the run fell back FROM.
    selection = sim.kernel_selection
    if context is not None and context.degraded is not None:
        selection = {**(selection or {}), **context.degraded}
    from .config.settings import resolve_autotune

    stats = RunStats(settings.L, tracer=tracer, config={
        "attempt": attempt,
        "model": sim.model.name,
        "fields": list(sim.model.field_names),
        "mesh_dims": list(sim.domain.dims),
        "padded_storage": (
            list(sim.domain.storage_shape) if sim.sharded
            and sim.domain.padded else None
        ),
        "kernel_language": sim.kernel_language,
        "kernel_selection": selection,
        "precision": settings.precision,
        # Mixed-precision + codec postures (docs/PRECISION.md): what
        # the run actually materialized — the tuner may have adopted
        # bf16 under an authorizing posture, and every artifact reader
        # must be able to tell.
        "compute_precision": sim.compute_precision,
        "snapshot_codec": codec.describe(),
        "n_devices": sim.domain.n_blocks,
        "n_processes": nprocs,
        "comm_overlap": sim.comm_overlap,
        "halo_depth": sim.halo_depth,
        # Elastic-restore provenance: the old->new plan when this
        # attempt resumed a checkpoint written on a different layout
        # (mesh change, process-count change, ensemble grow); None
        # otherwise. docs/RESHARD.md.
        "reshard": sim.reshard,
        "compile_cache": sim.compile_cache_dir,
        # The resolved tuner mode rides in the config echo even for
        # explicitly-pinned kernel languages (where no tuning runs):
        # a stats reader can tell "not tuned" from "tuner off".
        "autotune_mode": resolve_autotune(settings),
        "process_index": proc,
        "ensemble": (
            {"members": ens.n, "member_shards": sim.member_shards}
            if ens is not None else None
        ),
    })
    if ens is not None:
        # Per-member section: params + resolved seeds up front; the
        # latest per-member health lands here at each probed boundary.
        stats.record_ensemble({
            **ens.describe(),
            "member_shards": sim.member_shards,
            "seeds": list(sim.member_seeds),
        })
    if context is not None:
        # Hand the live stats to the supervisor: a failing attempt's
        # phase accumulation becomes an attempt-tagged journal event
        # (``attempt_phases``) instead of dying with the attempt.
        context.stats = stats
    from .parallel import icimodel

    comm = icimodel.comm_report(sim)
    stats.record_comm(comm)
    stats.record_watchdog(
        {**wd.describe(), "attempt": attempt} if wd is not None
        else {"enabled": False}
    )

    # Metrics instruments, registered once per attempt (get-or-create:
    # restarted attempts find the same objects) and labeled by the
    # run's resolved config so one scrape distinguishes models/meshes/
    # kernels sharing a host. Off means the shared null instrument —
    # the loop below pays a no-op call, nothing else.
    mlabels = sim.metrics_labels()
    m_step_us = metrics.histogram("step_latency_us", **mlabels)
    m_rounds = metrics.counter("step_rounds", **mlabels)
    m_steps = metrics.counter("steps", **mlabels)
    # In-graph numerics telemetry (obs/numerics.py, GS_NUMERICS):
    # "boundary" fuses the per-field min/max/mean/L2/finite reductions
    # into the snapshot-copy jit at write boundaries; "every_round"
    # additionally probes after every fused round. Off builds nothing —
    # the loop below pays one `is not None` check.
    from .obs import numerics as obs_numerics
    from .resilience.health import DriftGate

    num_mode = obs_numerics.resolve_numerics(settings)
    num_recorder = (
        obs_numerics.NumericsRecorder(
            sim.model.field_names, metrics=metrics, events=evs,
            gate=DriftGate.from_env(settings), log=log, labels=mlabels,
            journal=journal,
        )
        if num_mode != "off" else None
    )
    stats.config["numerics"] = num_mode
    # Data-integrity layer (resilience/integrity.py,
    # docs/RESILIENCE.md): GS_CKPT_VERIFY=full fuses the device-side
    # field checksum into the snapshot-copy jit (verified host-side
    # before any store write; single-process only — the host can only
    # recompute over its local shards); GS_SCRUB arms the boundary
    # scrubber over every checkpoint replica.
    from .resilience import integrity as integ

    icfg = integ.resolve_config(settings)
    stats.config["integrity"] = dict(icfg)
    snapshot_checksum = icfg["verify"] == "full" and nprocs == 1
    scrubber = (
        integ.Scrubber(settings, journal=journal,
                       every=icfg["scrub_every"])
        if icfg["scrub"] and ckpt is not None else None
    )
    # Compute-path SDC screening (resilience/sdc.py, docs/RESILIENCE.md
    # "Silent data corruption"): GS_SDC_CHECK=spot|shadow replays the
    # rounds since the previous boundary from a retained anchor and
    # compares exact in-graph checksums — a mismatch is attributed to a
    # device and unwinds as SDCError before any store write.
    # Single-process only, like the snapshot checksum above (the
    # screener compares addressable shards for attribution).
    from .resilience import sdc as sdc_mod

    scfg = sdc_mod.resolve_sdc(settings)
    screener = (
        sdc_mod.Screener(
            sim, mode=scfg["mode"], every=scfg["every"],
            journal=journal, log=log.info,
        )
        if scfg["mode"] != "off" and nprocs == 1 else None
    )
    if screener is not None:
        screener.rearm(restart_step)
    stats.config["sdc"] = dict(scfg)
    m_sdc_checks = metrics.counter("sdc_checks", **mlabels)
    # s-step exchange visibility (docs/TEMPORAL.md): exchanges and
    # ghost bytes per step (schedule counts) make the halo_depth
    # amortization legible on the scrape.
    metrics.gauge("comm_exchanges_per_step", **mlabels).set(
        comm.get("exchanges_per_step")
    )
    metrics.gauge("comm_halo_bytes_per_step", **mlabels).set(
        comm.get("halo_bytes_per_step")
    )

    def _refresh_device_gauges():
        """Per-device allocator gauges; only refreshed when a metrics
        record is actually about to flush (the PJRT query is not
        boundary-cheap)."""
        for ms in sim.device_memory_stats():
            metrics.gauge(
                "device_bytes_in_use", device=ms["device"]
            ).set(ms["bytes_in_use"])
            metrics.gauge(
                "device_peak_bytes_in_use", device=ms["device"]
            ).set(ms["peak_bytes_in_use"])

    evs.emit(
        "run_start", step=restart_step, attempt=attempt,
        model=sim.model.name, L=settings.L, steps=settings.steps,
        kernel=sim.kernel_language, mesh=list(sim.domain.dims),
        restart=bool(settings.restart),
    )
    # The watchdog's drain heartbeat: while close() drains K queued
    # steps, each completed write re-arms the "drain" deadline (touch
    # only re-arms the currently armed phase, so mid-run worker writes
    # never mask a wedged driver).
    pipe = AsyncStepWriter(
        stats=stats, metrics=metrics,
        progress=(lambda s: wd.touch("drain", s)) if wd is not None else None,
    )
    stats.config["async_io_depth"] = pipe.depth
    step = restart_step
    first_round = True
    # Quarantine poll state: only a CHANGED blocklist pays the overlap
    # check + reshape attempt, so a refused move warns once, not every
    # round.
    quarantine_handled: frozenset = frozenset()

    def _graceful(at_step: int, ckpt_written: bool):
        """The preemption grace path: checkpoint NOW (off-schedule if
        needed), drain every accepted step durably, journal the resume
        marker, and exit via GracefulShutdown — the distinct
        EXIT_PREEMPTED code upstream tells the relauncher 'resume me'.
        """
        ckpt_step = None
        if ckpt is not None:
            if not ckpt_written:
                _mark("checkpoint", at_step)
                # A ckpt-lossy store's variables are uint — every save
                # (grace checkpoints included) must go through the
                # codec; the default exact store takes exact copies.
                snap = sim.snapshot_async(
                    encode=enc_spec if ckpt_lossy else None,
                    exact=not ckpt_lossy,
                )
                pipe.submit(at_step, snap, [("checkpoint", ckpt.save)])
                stats.count("checkpoints")
                log.info(
                    f"Graceful-shutdown checkpoint accepted at step {at_step}"
                )
            ckpt_step = at_step
        journal.record(
            event="graceful_shutdown", signal=shutdown.signum,
            step=at_step, checkpoint_step=ckpt_step,
        )
        _mark("drain", at_step)
        pipe.close()
        raise GracefulShutdown(shutdown.signum, at_step, ckpt_step)

    m_reshards = metrics.counter("reshards", **mlabels)
    m_reshard_wall = metrics.gauge("reshard_wall_s", **mlabels)

    def _apply_reshape(req) -> bool:
        """Between-rounds live reshape (docs/RESHARD.md "In-job
        reshapes"): move the LIVE state onto the target mesh with
        :func:`~.reshard.restore.reshape_live` — no kill, no checkpoint
        round-trip, continuation bitwise-identical — then swap in
        stores that append at the current step on the new layout."""
        nonlocal sim, stream, ckpt, first_round
        from .reshard.plan import ReshardError
        from .reshard.restore import reshape_live

        dims = _resolve_reshape_dims(req, sim)
        if dims is None:
            return False
        # The reshape pays a target compile plus the device-path move —
        # its own watchdog phase (GS_WATCHDOG_RESHAPE_S) so a wedged
        # move cannot hide under the looser compile budget forever.
        _mark("reshape", step)
        # Retire in-flight writes against the OLD stores before the
        # swap; the pipeline itself stays up.
        pipe.drain()
        try:
            new_sim, rplan = reshape_live(
                sim, mesh_dims=dims, seed=seed, log=log,
                journal=journal,
            )
        except ReshardError as e:
            log.warn(f"live reshape refused: {e}")
            return False
        if not rplan.changed:
            return False
        stream.close()
        if ckpt is not None:
            ckpt.close()
        sim = new_sim
        if screener is not None:
            # The screener's anchor/checksum closures are bound to the
            # old mesh; rebind and re-anchor on the adopted layout (the
            # move is bitwise-transparent, so the next replay segment
            # simply starts here).
            screener.rebind(sim)
            screener.rearm(step)
        # The rebuilt stores must APPEND at the current step: the
        # stores only open in append mode under settings.restart, and
        # a fresh (non-restarted) run that reshapes mid-life would
        # otherwise truncate every snapshot written before the move.
        # Per-step block boxes make mixed layouts in one store legal.
        resumed = copy.copy(settings)
        resumed.restart = True
        stream = stream_cls(
            resumed, sim.domain, sim.dtype, writer_id=proc,
            nwriters=nprocs, resume_step=step, **stream_kw,
        )
        if ckpt is not None:
            ckpt = ckpt_cls(
                resumed, sim.dtype, writer_id=proc, nwriters=nprocs,
                resume_step=step, layout=sim.layout(), **ckpt_kw,
            )
        # Config echo + comm model follow the adopted layout so every
        # artifact written after the move describes the mesh the run is
        # actually on; the reshard record carries the old one.
        stats.config["reshard"] = sim.reshard
        stats.config["mesh_dims"] = list(sim.domain.dims)
        stats.config["n_devices"] = sim.domain.n_blocks
        stats.record_comm(icimodel.comm_report(sim))
        m_reshards.inc()
        if sim.reshard is not None:
            m_reshard_wall.set(sim.reshard.get("wall_s"))
        first_round = True
        return True

    t0 = time.perf_counter()
    if profile is not None:
        profile.on_boundary(step)
    try:
        with trace(), pipe:
            while step < settings.steps:
                if reshape_poll is not None:
                    # Between-rounds elastic hook: the poll is cheap
                    # (a dict read under the serve scheduler's lock);
                    # only a truthy request pays the reshape.
                    req = reshape_poll()
                    if req:
                        _apply_reshape(req)
                # Quarantine poll (resilience/sdc.py): when a device
                # this run computes on lands in the blocklist — this
                # worker's own screener via the supervisor, a fleet
                # peer's quarantine doc, or an operator export — move
                # the live state onto the surviving inventory between
                # rounds, the live-path analog of the supervisor's
                # restart-with-exclusion.
                blocked = sdc_mod.resolve_blocklist()
                if blocked and blocked != quarantine_handled:
                    in_use = {
                        sdc_mod.device_name(d) for d in (
                            sim.mesh.devices.flat
                            if sim.mesh is not None else (sim.device,)
                        )
                    }
                    if blocked & in_use:
                        shards_per = max(
                            1, int(getattr(sim, "member_shards", 1))
                        )
                        dims = sdc_mod.feasible_dims(
                            len(sdc_mod.usable_devices()) // shards_per,
                            settings.L,
                        )
                        moved = dims is not None and _apply_reshape(
                            {"mesh_dims": dims}
                        )
                        if not moved:
                            log.warn(
                                "quarantined device(s) "
                                f"{sorted(blocked & in_use)} in use but "
                                "no feasible reshape target — continuing "
                                "on the current mesh"
                            )
                    quarantine_handled = blocked
                # The first round pays jit (and, under Auto, any
                # remaining autotune measurement) — its budget is
                # the compile deadline, every later round the much
                # tighter step_round one.
                _mark("compile" if first_round else "step_round", step)
                boundary = min(
                    _next_boundary(step, settings.plotgap, settings.steps),
                    _next_boundary(
                        step,
                        settings.checkpoint_freq if ckpt is not None else 0,
                        settings.steps,
                    ),
                )
                if sim.kernel_language == "pallas":
                    # Planned Mosaic runtime failure: armed only while
                    # Pallas is the resolved language (the supervisor's
                    # recovery degrades to XLA, where it cannot recur).
                    fault = plan.take("kernel", boundary)
                    if fault is not None:
                        journal.record(
                            event="injected", kind="kernel",
                            step=boundary, planned_step=fault.step,
                        )
                        raise InjectedKernelError(fault.step)
                fault = plan.take("sdc", boundary)
                if fault is not None:
                    # Compute-path corruption (faults.py kind catalog):
                    # flip one live cell on the named device BEFORE the
                    # round runs, so the corruption is an INPUT to the
                    # step program — unlike `bitflip`, which hits only
                    # the write-path snapshot copy. GS_SDC_CHECK replays
                    # from the pre-poison anchor and must diverge.
                    name = sim.poison_sdc(
                        device=sdc_mod.resolve_fault_device(settings)
                    )
                    journal.record(
                        event="injected", kind="sdc", step=step,
                        planned_step=fault.step, device=name,
                    )
                t_round = time.perf_counter()
                with stats.phase("compute", step=step):
                    sim.iterate(boundary - step)
                    # iterate() only dispatches; block so the phase
                    # measures device execution, not async enqueue time.
                    sim.block_until_ready()
                # Step-latency distribution: one sample per fused round
                # (per-step mean of the round — the host cannot see
                # individual steps inside the jitted chunk), feeding
                # the p50/p95/p99 the stats file and bench rows report.
                m_step_us.observe(
                    (time.perf_counter() - t_round)
                    / (boundary - step) * 1e6
                )
                m_rounds.inc()
                m_steps.inc(boundary - step)
                stats.count("steps", boundary - step)
                step = boundary
                first_round = False
                if num_recorder is not None and num_mode == "every_round":
                    # Probe-only reduction over the live fields: every
                    # fused round is covered, write boundaries
                    # included ("boundary" mode instead fuses the probe
                    # into the snapshot copy below — one HBM pass for
                    # copy, health, and numerics together).
                    num_recorder.observe(step, sim.numerics_stats())
                if profile is not None:
                    profile.on_boundary(step)

                if screener is not None:
                    # Screen BEFORE this boundary's poison faults (an
                    # injected nan/drift is a modeled failure the
                    # health/drift gates own, not compute-path SDC) and
                    # BEFORE any store write, so a mismatch unwinds as
                    # SDCError without persisting a single corrupt byte.
                    if screener.check(step):
                        m_sdc_checks.inc()
                fault = plan.take("nan", step)
                if fault is not None:
                    journal.record(
                        event="injected", kind="nan", step=step,
                        planned_step=fault.step,
                    )
                    sim.poison_nan()
                fault = plan.take("drift", step)
                if fault is not None:
                    # Finite-but-wrong excursion (docs/PRECISION.md):
                    # the health guard stays green, the numerics drift
                    # gate (GS_DRIFT_POLICY) must catch it.
                    journal.record(
                        event="injected", kind="drift", step=step,
                        planned_step=fault.step,
                    )
                    sim.poison_drift()
                fault = plan.take("preempt", step)
                if fault is not None:
                    # Fires BEFORE this boundary's writes: the
                    # SIGTERM-mid-compute shape. Steps already accepted
                    # by the pipeline still drain durably on the abort
                    # path (AsyncStepWriter.__exit__), like a
                    # grace-window shutdown.
                    journal.record(
                        event="injected", kind="preempt", step=step,
                        planned_step=fault.step,
                    )
                    raise PreemptionError(
                        f"injected preemption at step {step} "
                        f"(planned step {fault.step})"
                    )
                fault = plan.take("hang", step)
                if fault is not None:
                    # The wedged-collective shape: stall
                    # the driver thread at the boundary. Under an armed
                    # watchdog the step_round deadline expires
                    # mid-stall and the stall unwinds as HangError;
                    # unwatched, the bounded stall resolves and the run
                    # continues (faults change WHEN, never WHAT).
                    journal.record(
                        event="injected", kind="hang", step=step,
                        planned_step=fault.step,
                    )
                    injected_hang_wait(watchdog=wd, shutdown=shutdown)

                if screener is not None:
                    # Re-anchor every boundary (a device-side copy, no
                    # D2H) AFTER the poison takes above, so an injected
                    # nan/drift lands inside the anchor and the next
                    # replay segment reproduces it — faults change
                    # WHEN, never WHAT the screener compares.
                    screener.rearm(step)

                at_plot = (
                    settings.plotgap > 0 and step % settings.plotgap == 0
                )
                at_ckpt = (
                    ckpt is not None
                    and settings.checkpoint_freq > 0
                    and step % settings.checkpoint_freq == 0
                )
                if not (at_plot or at_ckpt):
                    if shutdown.requested:
                        _graceful(step, ckpt_written=False)
                    continue
                _mark("io", step)
                targets = []
                if at_plot:
                    log.info(
                        f"Simulation at step {step} writing output step "
                        f"{step // settings.plotgap}"
                    )
                    targets.append(("output", stream.write_step))
                if at_ckpt:
                    targets.append(("checkpoint", ckpt.save))
                if plan.pending("io_error"):
                    targets = [
                        (phase, _with_io_fault(plan, journal, fn))
                        for phase, fn in targets
                    ]
                # The bitflip fault corrupts THIS boundary's snapshot
                # copy on device (write-path silent corruption; the
                # live trajectory is untouched) — the device-side
                # checksum must catch it before anything is written.
                bitflip = None
                fault = plan.take("bitflip", step)
                if fault is not None:
                    journal.record(
                        event="injected", kind="bitflip", step=step,
                        planned_step=fault.step,
                    )
                    bitflip = True
                # Codec routing (docs/PRECISION.md): coded targets get
                # the fused device-side quantization; the exact copies
                # are captured only when some target needs them — a
                # lossy-output-only boundary moves ONLY the compressed
                # payload over D2H (the volume win).
                want_enc = bool(enc_spec) and (
                    at_plot or (at_ckpt and ckpt_lossy)
                )
                want_exact = (
                    (at_ckpt and not ckpt_lossy)
                    or (at_plot and not enc_spec)
                )
                with stats.phase("device_to_host", step=step):
                    snap = sim.snapshot_async(
                        health=guard.enabled,
                        numerics=num_mode == "boundary",
                        checksum=snapshot_checksum and want_exact,
                        bitflip=bitflip,
                        encode=enc_spec if want_enc else None,
                        exact=want_exact,
                    )
                    if pipe.synchronous:
                        # Depth 0 reproduces the reference's flow
                        # exactly: D2H resolves here, writes run inline
                        # in submit.
                        snap.blocks()
                if snap.has_checksums():
                    # Stamp the boundary's device checksums into the
                    # stores' integrity sidecars (per-step, per-field
                    # provenance next to the block CRCs).
                    cksums = snap.checksum_report()
                    targets = [
                        (phase, _with_checksums(fn, cksums))
                        for phase, fn in targets
                    ]
                if guard.enabled:
                    # Unhealthy + abort/rollback raises BEFORE the
                    # poisoned step is submitted — it never reaches the
                    # stores; warn records and writes anyway.
                    report = snap.health_report()
                    if ens is not None and report is not None:
                        stats.record_member_health(step, report)
                    try:
                        event = guard.check(step, report, log=log,
                                            metrics=metrics)
                    except Exception:
                        # Journal the failing report BEFORE unwinding:
                        # for ensembles this is where the non-finite
                        # member indices reach the FaultJournal.
                        journal.record(
                            event="health", kind="health", step=step,
                            policy=guard.policy, action=guard.policy,
                            **report.describe(),
                        )
                        raise
                    if event is not None:
                        journal.record(**event)
                gate_first = (
                    num_mode == "boundary"
                    and num_recorder.gate is not None
                    and getattr(num_recorder.gate, "raising", False)
                )
                if gate_first:
                    # A raising drift policy (abort/rollback,
                    # docs/PRECISION.md) mirrors the health guard: the
                    # DriftError must unwind BEFORE the drifted
                    # boundary is submitted, so the poisoned step
                    # never reaches the stores and the supervisor
                    # resumes from the last HEALTHY checkpoint.
                    num_recorder.observe(
                        step, snap.numerics_report(), boundary=True
                    )
                pipe.submit(step, snap, targets)
                if num_mode == "boundary" and not gate_first:
                    # After submit — the resolution blocks only on the
                    # probe's scalars, never delays the write pipeline.
                    num_recorder.observe(
                        step, snap.numerics_report(), boundary=True
                    )
                if at_plot:
                    stats.count("output_steps")
                    evs.emit("output", phase="io", step=step,
                             output_step=step // settings.plotgap)
                if at_ckpt:
                    stats.count("checkpoints")
                    evs.emit("checkpoint", phase="io", step=step)
                    log.info(f"Checkpoint accepted at step {step}")
                # The ckpt_corrupt fault flips one payload byte of the
                # latest DURABLE checkpoint entry in the primary store
                # (CRCs untouched — exactly the silent corruption the
                # verify/scrub/failover machinery exists to catch).
                fault = plan.take("ckpt_corrupt", step)
                if fault is not None and ckpt is not None:
                    info = integ.corrupt_store_byte(
                        integ.primary_checkpoint_path(settings)
                    )
                    journal.record(
                        event="injected", kind="ckpt_corrupt",
                        step=step, planned_step=fault.step,
                        **(info or {"corrupted": False}),
                    )
                if scrubber is not None and at_ckpt:
                    scrubber.maybe_scrub(step)
                # Interval metrics record (metrics_interval_s TOML /
                # GS_METRICS_INTERVAL_S): boundary-time only, with the
                # expensive device gauges refreshed just-in-time.
                metrics.maybe_flush(on_flush=_refresh_device_gauges)
                if shutdown.requested:
                    # After this boundary's scheduled writes so the
                    # resumed run reproduces the uninterrupted output
                    # stream byte-for-byte.
                    _graceful(step, ckpt_written=at_ckpt)

            # Drain INSIDE the timed region: the run is complete only
            # once every accepted step is durable (close re-raises a
            # writer failure with the failing step identified).
            _mark("drain", step)
            pipe.close()

        if screener is not None:
            # Echo what the screener actually did into the stats
            # artifact (boundaries seen, checks run, last verified
            # step) next to its resolved config.
            stats.config["sdc"].update(screener.describe())
        elapsed = time.perf_counter() - t0
        # Idle pack slots never count toward the work actually served
        # (docs/SERVICE.md): only ACTIVE members scale the aggregate.
        members = ens.active_n if ens is not None else 1
        cells = settings.L**3 * (settings.steps - restart_step) * members
        if ens is not None:
            log.info(
                f"Completed {settings.steps - restart_step} steps for "
                f"{members} ensemble members in {elapsed:.3f}s "
                f"({cells / max(elapsed, 1e-9):.3e} aggregate "
                "cell-updates/s)"
            )
        else:
            log.info(
                f"Completed {settings.steps - restart_step} steps in "
                f"{elapsed:.3f}s "
                f"({cells / max(elapsed, 1e-9):.3e} cell-updates/s)"
            )
        io_stats = pipe.overlap_stats()
        stats.record_io(io_stats)
        metrics.gauge("io_hidden_s").set(
            round(sum(io_stats["hidden_s"].values()), 6)
        )
        metrics.gauge("io_exposed_s").set(
            round(sum(io_stats["exposed_s"].values()), 6)
        )
        if wd is not None:
            # Re-record with the final heartbeat count (the pre-loop
            # record only captured the armed deadlines).
            stats.record_watchdog({**wd.describe(), "attempt": attempt})
        if scrubber is not None:
            # Scrub provenance next to the armed knobs: how many
            # audits ran and whether anything was quarantined.
            stats.config["integrity"].update(scrubber.describe())
        if journal.events:
            stats.record_faults(journal.events)
        if profile is not None:
            profile.finish()
        evs.emit(
            "run_complete", step=step, attempt=attempt,
            wall_s=round(elapsed, 3),
            steps=settings.steps - restart_step,
        )
        _refresh_device_gauges()
        metrics.maybe_flush(force=True)
        prom = env_raw("GS_METRICS_PROM")
        if prom:
            metrics.write_prometheus(prom)
        if metrics.enabled:
            stats.record_metrics(metrics.snapshot())
        if tracer.enabled or evs.enabled or metrics.enabled:
            stats.record_obs({
                "trace": tracer.describe(),
                "events": evs.describe(),
                "metrics": metrics.describe(),
            })
        if num_recorder is not None:
            stats.record_numerics(
                {"mode": num_mode, **num_recorder.describe()}
            )
        if sim.xstats_enabled:
            # Executable analytics (obs/xstats.py): the per-compile
            # records captured by the runner registrations.
            from .obs import xstats as obs_xstats

            xinfo = obs_xstats.summarize(sim.executables)
            xinfo["records"] = list(sim.executables)
            stats.record_executables(xinfo)
        stats.maybe_write()
        if settings.verbose:
            log.info(f"run stats: {stats.summary()}")

        stream.close()
        if ckpt is not None:
            ckpt.close()
    except BaseException as exc:
        # Failure path (async-writer re-raise, preemption, health trip,
        # injected kernel error, KeyboardInterrupt): the stores MUST
        # still be closed — an open store leaks file handles and, after
        # a rollback, leaves the sidecar marker pointing at steps that
        # were never committed. Best-effort: never mask the in-flight
        # exception with a secondary close error.
        if profile is not None:
            profile.finish()
        if not isinstance(exc, GracefulShutdown):
            # GracefulShutdown already journaled its own marker (which
            # the stream mirrors); everything else gets the live error
            # notice here. emit() is best-effort by contract.
            evs.emit(
                "run_error", step=step, attempt=attempt,
                error=f"{type(exc).__name__}: {exc}",
            )
        _close_quietly(stream)
        if ckpt is not None:
            _close_quietly(ckpt)
        raise
    finalize()
    return sim
