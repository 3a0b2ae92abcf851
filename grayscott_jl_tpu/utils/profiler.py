"""Tracing, timing, and run metrics.

The reference's entire observability story is one ``@time`` around the run
and commented-out PProf hooks (``gray-scott.jl:3-14``, SURVEY §5). Here:

* :class:`RunStats` — per-phase wall-clock accumulation (compute, output,
  checkpoint) with a structured JSON summary: cell-updates/s, per-phase
  totals, step counts. Written to ``GS_TPU_STATS`` (file path) and logged
  at verbose runs.
* :class:`trace` — ``jax.profiler`` device tracing, enabled with
  ``GS_TPU_PROFILE=<output-dir>``; view with TensorBoard/XProf or
  ``jax.profiler`` tooling.
"""

from __future__ import annotations

import contextlib
import json
import os

from ..config.env import env_raw
from ..obs.trace import NULL_TRACER
import time
from typing import Dict, Optional


class RunStats:
    """Accumulates per-phase timings and counters for one simulation run.

    Phases are host-side wall clock: JAX dispatch is asynchronous, so
    device compute launched in a "compute" phase may overlap and complete
    inside the next blocking phase (device_to_host / end-of-run sync).
    Total wall time and cell-updates/s are exact; use ``GS_TPU_PROFILE``
    device traces for per-op attribution.
    """

    def __init__(self, L: int, config: Optional[dict] = None,
                 tracer=None):
        self.L = L
        #: Span tracer (``obs/trace.py``): every :meth:`phase` context
        #: doubles as a trace span, so the timings RunStats was already
        #: measuring appear on the Chrome-trace timeline for free, and
        #: as a ``gs.<phase>`` profiler annotation — None (the null
        #: tracer) keeps only the annotation.
        self.tracer = tracer
        #: Static run configuration echoed into the summary (mesh dims,
        #: kernel language, chain depth, ...) so a pod operator can
        #: correlate a stats file with the layout that produced it
        #: without reconstructing the launch environment.
        self.config = dict(config or {})
        self.phases: Dict[str, float] = {}
        self.counters: Dict[str, int] = {}
        #: Async-output overlap accounting (``io/async_writer.py``):
        #: per-phase ``hidden_s`` (I/O that ran behind compute) vs
        #: ``exposed_s`` (driver-blocked), plus queue-depth high-water
        #: mark — how much I/O wall time the pipeline actually hid.
        self.io: Optional[dict] = None
        #: Fault/recovery events (``resilience/supervisor.FaultJournal``):
        #: every injected fault, health trip, and supervisor recovery
        #: action of the whole supervised run — the completing attempt
        #: merges the journal here, so one stats file tells the full
        #: story of how the run survived.
        self.faults: Optional[list] = None
        #: Hang-watchdog provenance (``resilience/watchdog.py``):
        #: armed/disabled, the per-phase deadlines in force, heartbeat
        #: count, and the expiry (phase/step) if the run hung — so a
        #: stats reader can tell "finished clean" from "finished after
        #: a watchdog-recovered wedge" without the journal.
        self.watchdog: Optional[dict] = None
        #: Halo-exchange budget (``parallel/icimodel.comm_report``):
        #: model-projected per-step ``hidden_us``/``exposed_us`` under
        #: the run's split-phase setting — the comm analog of the
        #: ``io`` overlap section (how much ICI time the split-phase
        #: exchange hides behind interior compute).
        self.comm: Optional[dict] = None
        #: Metrics snapshot (``obs/metrics.py``): the registered
        #: counters/gauges/histograms at run end — step-latency
        #: percentiles, queue depths, restart counts — so the stats
        #: file carries the same numbers a scraper would have seen.
        self.metrics: Optional[dict] = None
        #: Observability provenance (``obs/``): which sinks were armed
        #: (trace path + event/span counts, event-stream path, metrics
        #: path/interval) — a stats reader can find the companion files.
        self.obs: Optional[dict] = None
        #: Executable analytics (``obs/xstats.py``): per-compile cost /
        #: memory / collective-count records with compile wall time and
        #: the persistent-cache outcome, plus the model-vs-measured
        #: projection residual — the compiler's side of the run story.
        self.executables: Optional[dict] = None
        #: In-graph numerics telemetry (``obs/numerics.py``): probe
        #: count, the last per-field statistics, and each statistic's
        #: worst windowed drift — the baseline the precision policy
        #: (ROADMAP item 1) will gate against.
        self.numerics: Optional[dict] = None
        #: Per-member ensemble section (``ensemble/``, docs/ENSEMBLE.md):
        #: member params + seeds, the member-axis mesh split, and the
        #: latest per-member health probe — one stats file tells which
        #: member of a sweep did what. Also scales the
        #: ``cell_updates_per_s`` summary to the AGGREGATE across
        #: members (the number an ensemble run is judged by).
        self.ensemble: Optional[dict] = None
        self._t0 = time.perf_counter()

    @contextlib.contextmanager
    def phase(self, name: str, step: Optional[int] = None):
        tr = self.tracer if self.tracer is not None else NULL_TRACER
        with tr.span(name, phase=name, step=step):
            t = time.perf_counter()
            try:
                yield
            finally:
                self.phases[name] = self.phases.get(name, 0.0) + (
                    time.perf_counter() - t
                )

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def record_io(self, overlap: Optional[dict]) -> None:
        """Attach the async-writer overlap stats
        (``AsyncStepWriter.overlap_stats()``) to the summary."""
        self.io = dict(overlap) if overlap else None

    def record_faults(self, events: Optional[list]) -> None:
        """Attach the run's fault journal (injected faults, health
        trips, recovery actions) to the summary."""
        self.faults = [dict(e) for e in events] if events else None

    def record_watchdog(self, info: Optional[dict]) -> None:
        """Attach the hang-watchdog provenance
        (``Watchdog.describe()``, or ``{"enabled": False}``)."""
        self.watchdog = dict(info) if info else None

    def record_comm(self, report: Optional[dict]) -> None:
        """Attach the halo-exchange budget
        (``parallel/icimodel.comm_report``) to the summary."""
        self.comm = dict(report) if report else None

    def record_metrics(self, snapshot: Optional[dict]) -> None:
        """Attach the end-of-run metrics snapshot
        (``MetricsRegistry.snapshot()``) to the summary."""
        self.metrics = dict(snapshot) if snapshot else None

    def record_obs(self, info: Optional[dict]) -> None:
        """Attach the observability-sink provenance (trace / events /
        metrics ``describe()`` dicts) to the summary."""
        self.obs = dict(info) if info else None

    def record_executables(self, info: Optional[dict]) -> None:
        """Attach the executable-analytics section (``xstats.summarize``
        header + per-compile records + projection residual)."""
        self.executables = dict(info) if info else None

    def record_numerics(self, info: Optional[dict]) -> None:
        """Attach the numerics-telemetry section
        (``NumericsRecorder.describe()``)."""
        self.numerics = dict(info) if info else None

    def record_ensemble(self, info: Optional[dict]) -> None:
        """Attach the per-member ensemble section
        (``EnsembleSettings.describe()`` + resolved seeds)."""
        self.ensemble = dict(info) if info else None

    def record_member_health(self, step: int, report) -> None:
        """Record the latest per-member health probe (an
        ``EnsembleHealthReport``) into the ensemble section — the
        last-probed ranges plus which members (if any) went
        non-finite, keyed by the boundary step."""
        if self.ensemble is None:
            self.ensemble = {}
        self.ensemble["health"] = {
            "step": step,
            **report.describe(),
            "member_reports": [m.describe() for m in report.members],
        }

    def summary(self) -> dict:
        total = time.perf_counter() - self._t0
        steps = self.counters.get("steps", 0)
        compute = self.phases.get("compute", total)
        # ACTIVE members only: idle pack slots (docs/SERVICE.md) ride
        # in the vmapped launch but do no work anyone asked for — the
        # aggregate throughput must not credit padding.
        members = (
            int(self.ensemble.get(
                "active_members", self.ensemble.get("members", 1)
            ))
            if self.ensemble else 1
        )
        return {
            "L": self.L,
            # Nested under one key so caller-supplied names can never
            # collide with (and silently clobber) the built-in fields.
            "config": dict(self.config),
            "steps": steps,
            "wall_s": round(total, 6),
            "phases_s": {k: round(v, 6) for k, v in self.phases.items()},
            "io": self.io,
            "comm": self.comm,
            "watchdog": self.watchdog,
            "faults": self.faults,
            "metrics": self.metrics,
            "obs": self.obs,
            "executables": self.executables,
            "numerics": self.numerics,
            "ensemble": self.ensemble,
            "counters": dict(self.counters),
            # Aggregate across ensemble members (members == 1 solo).
            "cell_updates_per_s": (
                round(self.L**3 * steps * members / compute, 3)
                if compute > 0 else None
            ),
        }

    def maybe_write(self) -> Optional[str]:
        """Write the summary where ``GS_TPU_STATS`` points (if set).

        In a multi-process run each rank records its own local timings;
        the path gets a ``.rank<N>`` suffix so ranks don't clobber each
        other's file.
        """
        path = env_raw("GS_TPU_STATS")
        if not path:
            return None
        import jax

        if jax.process_count() > 1:
            path = f"{path}.rank{jax.process_index()}"
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.summary(), f)
            f.write("\n")
        return path


@contextlib.contextmanager
def trace():
    """``jax.profiler`` trace of the run when ``GS_TPU_PROFILE`` is set."""
    out = env_raw("GS_TPU_PROFILE")
    if not out:
        yield
        return
    import jax

    jax.profiler.start_trace(out)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
