"""Double-buffered asynchronous output pipeline.

The driver's reference-parity flow is fully synchronous: at every
``plotgap``/``checkpoint_freq`` boundary the device idles through D2H ->
serialization -> VTK assembly -> disk (``src/GrayScott.jl:68-103``; the
round-5 driver kept that shape). Here the driver instead *submits* a
:class:`~..simulation.FieldSnapshot` (D2H already in flight) and
immediately dispatches the next compute chunk; a single background
writer thread resolves the snapshot and runs the write targets
(``SimStream.write_step`` / ``CheckpointWriter.save``) off the driver
thread — the standard overlapped-output stage of distributed stencil
frameworks (arxiv 2309.10292, 2404.02218).

Guarantees:

* **strict step ordering** — one worker consuming a FIFO queue: steps
  hit the stores in submission order even when snapshots' D2H transfers
  land out of order;
* **bounded buffering with backpressure** — at most ``GS_ASYNC_IO_DEPTH``
  submitted-but-unwritten steps (default 2 — double buffering); a full
  pipeline blocks ``submit`` until the writer catches up, so device
  memory holds a bounded number of live snapshots;
* **synchronous fallback** — ``GS_ASYNC_IO_DEPTH=0`` runs every target
  inline on the driver thread (bitwise-identical stores either way;
  the writers are single-threaded in both modes, only *which* thread
  calls them changes);
* **first-error capture** — a target exception is recorded with its
  step and re-raised on the driver thread (as :class:`AsyncIOError`) at
  the next ``submit`` or at ``close``; later queued steps are discarded
  (writing past a failed step would corrupt store order);
* **draining close** — ``close()`` returns only after every accepted
  step is durably written (or the first error is surfaced).

Overlap accounting for benchmarks: the worker tracks busy seconds per
phase (``device_to_host`` resolution, ``output``, ``checkpoint``), and
the driver side tracks how long it was *blocked* on the pipeline
(backpressure + final drain). ``overlap_stats()`` splits each phase's
busy time into ``hidden_s`` (ran behind compute) and ``exposed_s``
(driver waited), attributing driver-blocked time across phases
pro-rata by busy time; in synchronous mode everything is exposed by
construction.
"""

from __future__ import annotations

import contextlib
import os
import queue
import threading
import time
from typing import Optional, Sequence, Tuple

__all__ = ["AsyncIOError", "AsyncStepWriter", "resolve_depth"]


class AsyncIOError(RuntimeError):
    """A background write failed; re-raised on the driver thread."""

    def __init__(self, step: int, original: BaseException):
        super().__init__(
            f"async I/O writer failed at step {step}: "
            f"{type(original).__name__}: {original}"
        )
        #: Simulation step whose write raised.
        self.step = step
        #: The exception raised by the write target.
        self.original = original

    @property
    def transient(self) -> bool:
        """Is the wrapped failure an OS-level I/O error — the class of
        failure a supervisor may retry (full/flaky disk, NFS hiccup,
        injected ``io_error`` fault)? Value/Key/Runtime errors out of a
        write target are programming or format errors: retrying those
        would re-fail or, worse, corrupt the store.

        Classified here, where the failing write's exception is still
        first-hand, so the supervisor (``resilience/supervisor.py``)
        never guesses from a formatted message. One ``RuntimeError``
        subclass gets its own taxonomy slot upstream: a
        :class:`~..resilience.integrity.CorruptionError` raised on
        this thread (snapshot checksum verify in ``blocks()``, the
        checkpoint read-back verify) is NOT transient-io — the
        supervisor unwraps ``original`` and classifies it
        ``corruption`` (restartable with replica failover, bounded to
        one retry per corrupt site).
        """
        return isinstance(self.original, OSError)


def resolve_depth(depth: Optional[int] = None) -> int:
    """Pipeline depth: the argument, else ``GS_ASYNC_IO_DEPTH``
    (default 2). ``0`` means synchronous; negatives are invalid."""
    if depth is None:
        raw = os.environ.get("GS_ASYNC_IO_DEPTH", "2")
        try:
            depth = int(raw)
        except ValueError as e:
            raise ValueError(
                f"GS_ASYNC_IO_DEPTH must be a non-negative integer, "
                f"got {raw!r}"
            ) from e
    if depth < 0:
        raise ValueError(
            f"async I/O depth must be non-negative, got {depth}"
        )
    return depth


_SENTINEL = object()

#: Phase name for snapshot-to-host resolution time in the busy ledger.
_D2H = "device_to_host"


class AsyncStepWriter:
    """Bounded-queue background writer for simulation output steps.

    ``submit(step, snapshot, targets)`` hands one output boundary to the
    pipeline; ``targets`` is a sequence of ``(phase_name, fn)`` where
    ``fn(step, blocks)`` performs the write (phase names feed the
    overlap accounting and, in synchronous mode, the driver's
    ``RunStats`` phases so depth=0 reproduces the old flow exactly).

    ``stats`` is an optional :class:`~..utils.profiler.RunStats`; when
    given, driver-side time is recorded under the target phase names
    (inline write time when synchronous, submit/backpressure time when
    async) and the drain under ``io_drain``.

    ``progress`` is an optional ``progress(step)`` callback invoked
    from the worker thread after each fully written step — the hang
    watchdog's drain heartbeat (``resilience/watchdog.Watchdog.touch``):
    a close() draining K queued steps is healthy as long as individual
    writes keep completing, and only a *stuck* write should trip the
    drain deadline. Exceptions from the callback are swallowed — a
    monitoring hook must never poison the store path.

    ``metrics`` is an optional :class:`~..obs.metrics.MetricsRegistry`;
    when given (and armed), the pipeline keeps a live
    ``async_io_queue_depth`` gauge and an ``io_steps_written`` counter —
    the queue-depth time series a stalled disk shows up in long before
    the backpressure reaches the driver. Disabled metrics hand back the
    shared null instrument, so the per-step cost is a no-op call.
    """

    def __init__(self, *, depth: Optional[int] = None, stats=None,
                 progress=None, metrics=None):
        if metrics is None:
            from ..obs.metrics import NULL_METRIC

            self._m_depth = self._m_written = NULL_METRIC
        else:
            self._m_depth = metrics.gauge("async_io_queue_depth")
            self._m_written = metrics.counter("io_steps_written")
        self.depth = resolve_depth(depth)
        self._stats = stats
        self._progress = progress
        self._busy: dict = {}
        self._busy_lock = threading.Lock()
        self._submit_wait = 0.0
        self._drain_wait = 0.0
        self._queue_hwm = 0
        self._accepted = 0
        self._written = 0
        self._error: Optional[Tuple[int, BaseException]] = None
        self._raised = False
        self._thread: Optional[threading.Thread] = None
        self._q: Optional[queue.Queue] = None
        if self.depth > 0:
            self._q = queue.Queue(maxsize=self.depth)
            self._thread = threading.Thread(
                target=self._run, name="gs-async-io", daemon=True
            )
            self._thread.start()

    # ---------------------------------------------------------- properties

    @property
    def synchronous(self) -> bool:
        return self.depth == 0

    @property
    def steps_written(self) -> int:
        """Steps fully written so far (monotone; == accepted after a
        clean ``close``)."""
        return self._written

    # ------------------------------------------------------------- worker

    def _add_busy(self, phase: str, seconds: float) -> None:
        with self._busy_lock:
            self._busy[phase] = self._busy.get(phase, 0.0) + seconds

    def _write_one(self, step, snapshot, targets) -> None:
        # The worker's writes under the driver's phase names, on the
        # profiler timeline (gs.<phase> on this thread's track).
        from ..obs.trace import annotation

        t = time.perf_counter()
        with annotation(_D2H, step=step):
            blocks = snapshot.blocks()
        self._add_busy(_D2H, time.perf_counter() - t)
        for phase, fn in targets:
            t = time.perf_counter()
            with annotation(phase, step=step):
                fn(step, blocks)
            self._add_busy(phase, time.perf_counter() - t)
        self._written += 1
        self._m_written.inc()
        self._m_depth.set(self._q.qsize() if self._q is not None else 0)
        if self._progress is not None:
            try:
                self._progress(step)
            except Exception:  # noqa: BLE001 — monitoring must not kill writes
                pass

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is _SENTINEL:
                return
            step, snapshot, targets = item
            # After a failure later steps are consumed but DISCARDED —
            # continuing to write would put steps after a hole — while
            # draining the queue keeps a backpressure-blocked submit
            # from deadlocking against a dead pipeline.
            if self._error is None:
                try:
                    self._write_one(step, snapshot, targets)
                except BaseException as e:  # noqa: BLE001 — must not die
                    self._error = (step, e)

    # ------------------------------------------------------------- driver

    def _raise_pending(self) -> None:
        if self._error is not None and not self._raised:
            self._raised = True
            step, exc = self._error
            raise AsyncIOError(step, exc) from exc

    def _phase_cm(self, name: str):
        if self._stats is None:
            return contextlib.nullcontext()
        return self._stats.phase(name)

    def submit(
        self, step: int, snapshot, targets: Sequence[Tuple[str, object]]
    ) -> None:
        """Hand one output step to the pipeline.

        Synchronous mode writes inline (under each target's stats
        phase). Async mode enqueues, blocking while the pipeline is at
        depth; a previously captured writer error re-raises here before
        anything new is accepted.
        """
        self._raise_pending()
        if self._raised:
            step0 = self._error[0] if self._error else "?"
            raise RuntimeError(
                f"async I/O writer already failed at step {step0}; "
                "no further steps are accepted"
            )
        targets = list(targets)
        if self.synchronous:
            blocks = snapshot.blocks()
            for phase, fn in targets:
                t = time.perf_counter()
                with self._phase_cm(phase):
                    fn(step, blocks)
                self._add_busy(phase, time.perf_counter() - t)
            self._written += 1
            self._m_written.inc()
            self._accepted += 1
            return
        with contextlib.ExitStack() as st:
            # Submit time (≈0 unless backpressured) lands in the same
            # stats phases the writes used to occupy, so phase output
            # keeps meaning "driver wall time spent on output".
            for phase, _ in targets:
                st.enter_context(self._phase_cm(phase))
            t = time.perf_counter()
            self._q.put((step, snapshot, targets))
            self._submit_wait += time.perf_counter() - t
        self._accepted += 1
        self._queue_hwm = max(self._queue_hwm, self._q.qsize())
        self._m_depth.set(self._q.qsize())

    def drain(self) -> None:
        """Block until every accepted step is durably written (or the
        first failure has surfaced), WITHOUT stopping the worker — the
        live-reshape path (docs/RESHARD.md) retires in-flight writes
        against the old stores here before swapping in the new ones."""
        if self._thread is not None:
            with self._phase_cm("io_drain"):
                t = time.perf_counter()
                while (self._error is None
                       and self._written < self._accepted):
                    time.sleep(0.002)
                self._drain_wait += time.perf_counter() - t
        self._raise_pending()

    def close(self) -> None:
        """Drain and stop the worker; re-raise a pending writer error.

        Returns only once every accepted step is durably written (or
        the first failure has been surfaced). Idempotent."""
        if self._thread is not None:
            with self._phase_cm("io_drain"):
                t = time.perf_counter()
                self._q.put(_SENTINEL)
                self._thread.join()
                self._drain_wait += time.perf_counter() - t
            self._thread = None
        self._raise_pending()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, *exc):
        if exc_type is None:
            self.close()
            return
        # Abort path: still drain (the worker must not outlive the
        # driver and a blocked peer must unwedge), but never let a
        # secondary writer error mask the in-flight exception.
        try:
            self.close()
        except AsyncIOError:
            pass

    # -------------------------------------------------------------- stats

    def overlap_stats(self) -> dict:
        """JSON-able overlap accounting for ``RunStats``.

        ``busy_s`` is worker (or inline) write time per phase;
        ``exposed_s`` splits the driver-blocked time (backpressure +
        drain; everything, in synchronous mode) across phases pro-rata
        by busy time, and ``hidden_s`` is the remainder — I/O that ran
        behind compute."""
        with self._busy_lock:
            busy = dict(self._busy)
        total_busy = sum(busy.values())
        if self.synchronous:
            exposed_total = total_busy
        else:
            exposed_total = min(
                self._submit_wait + self._drain_wait, total_busy
            )
        frac = exposed_total / total_busy if total_busy > 0 else 0.0
        exposed = {k: v * frac for k, v in busy.items()}
        hidden = {k: v - exposed[k] for k, v in busy.items()}
        rounded = lambda d: {k: round(v, 6) for k, v in d.items()}  # noqa: E731
        return {
            "depth": self.depth,
            "steps_accepted": self._accepted,
            "steps_written": self._written,
            "queue_depth_hwm": self._queue_hwm,
            "busy_s": rounded(busy),
            "hidden_s": rounded(hidden),
            "exposed_s": rounded(exposed),
            "submit_wait_s": round(self._submit_wait, 6),
            "drain_wait_s": round(self._drain_wait, 6),
        }
