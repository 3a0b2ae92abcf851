"""Host-side span tracer with Chrome trace-event export.

``GS_TRACE=path`` arms the process-wide tracer; the driver's phase
boundaries become spans with no new hot-path cost — every
``Watchdog.heartbeat`` already marks a phase transition, so the
heartbeat doubles as the span edge (``resilience/watchdog.py``), and
``RunStats.phase`` context managers (``utils/profiler.py``) emit the
nested timing spans they were already measuring. The export is the
Chrome trace-event JSON format (the ``traceEvents`` array of ``"X"``
complete events), directly loadable in Perfetto / ``chrome://tracing``;
``scripts/gs_report.py --check`` validates a file against
:func:`validate_trace`.

Design constraints:

* **stdlib only** — the watchdog must stay importable without JAX
  (``bench.py``'s parent process hooks in by design), so this module
  never imports jax at module level.
* **crash-consistent** — :meth:`SpanTracer.flush` rewrites the whole
  file atomically (tmp + rename), so the trace on disk is valid JSON
  after every attempt of a supervised multi-restart run, including one
  that dies between attempts.
* **bounded** — at most ``GS_TRACE_MAX_EVENTS`` (default 200000) events
  are retained; later spans are counted as dropped rather than growing
  host memory without bound on a long campaign.
* **balanced** — span nesting follows context-manager LIFO per thread
  and edge spans are closed before the next opens, so the exported
  intervals properly nest (asserted by :func:`validate_trace`).

Device-side timelines are a separate tool: ``GS_PROFILE=start:stop``
(:class:`ProfileWindow`) brackets a simulation-step range with
``jax.profiler.start_trace``/``stop_trace`` so the XLA timeline of
exactly the interesting rounds lands in ``GS_PROFILE_DIR`` without
paying profiler overhead for the whole run.

The two meet on the profiler's clock: every :meth:`SpanTracer.span`
(and the null tracer's, so with ``GS_TRACE`` unset too) is also a
``jax.profiler.TraceAnnotation`` named ``gs.<name>`` (:func:`annotation`).
Whenever a capture is open (``GS_PROFILE``, ``GS_TPU_PROFILE``, or any
caller's ``jax.profiler.start_trace``), the program's spans land in the
``.xplane.pb`` host plane beside the device ops; with none open a span
costs one TraceMe check. Heartbeat-fed :meth:`SpanTracer.edge` phases
mark the same boundaries as the nested spans and are not bridged.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
from typing import List, Optional

__all__ = [
    "NULL_TRACER",
    "ProfileWindow",
    "SpanTracer",
    "annotation",
    "get_tracer",
    "reset_tracer",
    "validate_trace",
]

#: tid of the driver-phase edge track (heartbeat-fed spans); real
#: threads are numbered from 1 in registration order.
EDGE_TID = 0

#: Prefix of the program's spans on the profiler timeline.
PROFILER_PREFIX = "gs."


def annotation(name, **attrs):
    """The profiler side of a span: a ``jax.profiler.TraceAnnotation``
    named ``gs.<name>`` with ``attrs`` as its metadata (None values
    left out). A no-op context until jax is imported by someone else —
    this module never imports it."""
    prof = sys.modules.get("jax.profiler")
    if prof is None:
        return contextlib.nullcontext()
    return prof.TraceAnnotation(
        PROFILER_PREFIX + str(name),
        **{k: v for k, v in attrs.items() if v is not None},
    )


@contextlib.contextmanager
def _annotated(name, step, attrs):
    """:func:`annotation` around the block. Yields ``attrs``: keys the
    block adds (an outcome known only at its end, such as a compile's
    cache hit or miss) are set on the annotation before it closes."""
    n = len(attrs)
    ann = annotation(name, step=step, **attrs)
    with ann:
        yield attrs
        if len(attrs) > n and hasattr(ann, "set_metadata"):
            ann.set_metadata(**{k: attrs[k] for k in list(attrs)[n:]})


def _proc_index() -> int:
    """The JAX process index, without ever forcing a backend init
    (mirrors ``FaultJournal.from_env``): 0 before/without jax."""
    if "jax" in sys.modules:
        try:
            import jax

            return jax.process_index()
        except Exception:  # noqa: BLE001 — pre-init / no backend
            return 0
    return 0


def rank_path(path: str) -> str:
    """``.rank<N>``-suffix a path in multi-process runs (mirrors
    ``GS_TPU_STATS`` / ``GS_FAULT_JOURNAL``) so ranks don't clobber
    each other's file."""
    if "jax" in sys.modules:
        try:
            import jax

            if jax.process_count() > 1:
                return f"{path}.rank{jax.process_index()}"
        except Exception:  # noqa: BLE001
            pass
    return path


class _NullTracer:
    """Shared no-op tracer: ``GS_TRACE`` unset records nothing; a span
    is only its profiler annotation (one TraceMe check with no capture
    open)."""

    enabled = False

    def span(self, name, phase=None, step=None, **attrs):
        return _annotated(name, step, attrs)

    def edge(self, phase, step=None) -> None:
        pass

    def instant(self, name, step=None, **attrs) -> None:
        pass

    def flush(self) -> Optional[str]:
        return None

    def describe(self) -> dict:
        return {"enabled": False}


NULL_TRACER = _NullTracer()


class SpanTracer:
    """Nestable host-side spans -> Chrome trace-event JSON.

    Span identity is ``(name, phase, step, attrs)``; timestamps are
    microseconds of ``time.perf_counter`` relative to tracer creation
    (``args.epoch`` in the file anchors them to wall clock for
    cross-file correlation with the event stream). Thread-safe: spans
    come from the driver thread, the async writer's worker, and the
    watchdog monitor.
    """

    enabled = True

    def __init__(self, path: str, proc: Optional[int] = None,
                 max_events: Optional[int] = None):
        self.path = path
        self.proc = _proc_index() if proc is None else proc
        if max_events is None:
            max_events = int(os.environ.get("GS_TRACE_MAX_EVENTS",
                                            "200000"))
        if max_events <= 0:
            raise ValueError(
                f"GS_TRACE_MAX_EVENTS must be > 0, got {max_events}"
            )
        self.max_events = max_events
        self.dropped = 0
        self._lock = threading.Lock()
        self._events: List[dict] = []
        self._t0 = time.perf_counter()
        self._epoch = time.time()
        #: Currently open heartbeat-fed phase span: (phase, step, t_us).
        self._edge = None
        self._tids = {}  # thread ident -> small tid
        self._meta = [{
            "ph": "M", "name": "process_name", "pid": self.proc,
            "tid": EDGE_TID,
            "args": {"name": f"gray-scott proc {self.proc}"},
        }, {
            "ph": "M", "name": "thread_name", "pid": self.proc,
            "tid": EDGE_TID, "args": {"name": "driver phases"},
        }]

    # ---------------------------------------------------------- plumbing

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = self._tids[ident] = len(self._tids) + 1
                self._meta.append({
                    "ph": "M", "name": "thread_name", "pid": self.proc,
                    "tid": tid,
                    "args": {"name": threading.current_thread().name},
                })
        return tid

    def _add(self, event: dict) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped += 1
                return
            self._events.append(event)

    def _complete(self, name, t0_us, dur_us, *, tid, phase=None,
                  step=None, attrs=None) -> None:
        args = {}
        if step is not None:
            args["step"] = step
        if attrs:
            args.update(attrs)
        self._add({
            "name": str(name),
            "cat": str(phase) if phase else "span",
            "ph": "X",
            "ts": round(t0_us, 3),
            "dur": round(max(dur_us, 0.0), 3),
            "pid": self.proc,
            "tid": tid,
            "args": args,
        })

    # -------------------------------------------------------------- spans

    @contextlib.contextmanager
    def span(self, name, phase=None, step=None, **attrs):
        """A nested timing span around a host-side block (LIFO per
        thread, so the exported intervals nest by construction), also
        a profiler annotation. Yields ``attrs``; keys the block adds
        are exported with the span."""
        t0 = self._now_us()
        try:
            with _annotated(name, step, attrs):
                yield attrs
        finally:
            self._complete(name, t0, self._now_us() - t0,
                           tid=self._tid(), phase=phase, step=step,
                           attrs=attrs)

    def edge(self, phase, step=None) -> None:
        """One driver phase boundary: close the open phase span, open
        the next. Fed by ``Watchdog.heartbeat`` — tracing the top-level
        phase timeline costs nothing the watchdog wasn't already
        paying."""
        now = self._now_us()
        with self._lock:
            prev, self._edge = self._edge, (str(phase), step, now)
        if prev is not None:
            self._complete(prev[0], prev[2], now - prev[2],
                           tid=EDGE_TID, phase=prev[0], step=prev[1])

    def instant(self, name, step=None, **attrs) -> None:
        """A zero-duration marker (fault injected, restart decided)."""
        args = dict(attrs)
        if step is not None:
            args["step"] = step
        self._add({
            "name": str(name), "cat": "event", "ph": "i", "s": "p",
            "ts": round(self._now_us(), 3), "pid": self.proc,
            "tid": self._tid(), "args": args,
        })

    # -------------------------------------------------------------- export

    def describe(self) -> dict:
        with self._lock:
            n = len(self._events)
        return {"enabled": True, "path": self.path, "events": n,
                "dropped": self.dropped}

    def flush(self) -> Optional[str]:
        """Atomically (re)write the whole trace file. The open edge
        span is exported as running-until-now without being closed, so
        flushing mid-run (every supervised attempt does) keeps the
        on-disk nesting balanced AND the in-memory edge alive."""
        now = self._now_us()
        with self._lock:
            events = list(self._meta) + list(self._events)
            edge = self._edge
        if edge is not None:
            args = {} if edge[1] is None else {"step": edge[1]}
            events.append({
                "name": edge[0], "cat": edge[0], "ph": "X",
                "ts": round(edge[2], 3),
                "dur": round(max(now - edge[2], 0.0), 3),
                "pid": self.proc, "tid": EDGE_TID, "args": args,
            })
        doc = {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "epoch_unix_s": round(self._epoch, 6),
                "proc": self.proc,
                "dropped_events": self.dropped,
            },
        }
        tmp = f"{self.path}.tmp{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
            f.write("\n")
        os.replace(tmp, self.path)
        return self.path


_tracer = None


def get_tracer():
    """The process-wide tracer: a :class:`SpanTracer` when ``GS_TRACE``
    names a path (``.rank<N>``-suffixed in multi-process runs), else
    the shared no-op. Resolved once — a supervised run's restart
    attempts all append to the same trace, which is the point: one
    timeline for the whole multi-attempt story."""
    global _tracer
    if _tracer is None:
        path = os.environ.get("GS_TRACE", "").strip()
        _tracer = SpanTracer(rank_path(path)) if path else NULL_TRACER
    return _tracer


def reset_tracer() -> None:
    """Drop the singleton (tests; re-resolves from env on next use)."""
    global _tracer
    _tracer = None


# --------------------------------------------------------------- validation


def validate_trace(doc) -> List[str]:
    """Problems with a Chrome trace-event document (empty list = valid).

    Checks the contract ``gs_report.py --check`` and the tier-1 tests
    enforce: a ``traceEvents`` array whose ``"X"`` events each carry
    numeric ``pid``/``tid``/``ts``/``dur`` and whose spans nest without
    partial overlap per ``(pid, tid)`` track.
    """
    problems: List[str] = []
    if isinstance(doc, dict):
        events = doc.get("traceEvents")
        if not isinstance(events, list):
            return ["no traceEvents array"]
    elif isinstance(doc, list):
        events = doc
    else:
        return ["document is neither an object nor an array"]

    spans = {}
    for i, e in enumerate(events):
        if not isinstance(e, dict) or "ph" not in e:
            problems.append(f"event {i}: not an object with a ph field")
            continue
        if e["ph"] != "X":
            continue
        bad = [k for k in ("pid", "tid", "ts", "dur")
               if not isinstance(e.get(k), (int, float))
               or isinstance(e.get(k), bool)]
        if not isinstance(e.get("name"), str) or not e.get("name"):
            bad.append("name")
        if bad:
            problems.append(
                f"event {i} ({e.get('name')!r}): missing/invalid "
                f"{', '.join(sorted(bad))}"
            )
            continue
        if e["dur"] < 0:
            problems.append(f"event {i} ({e['name']!r}): negative dur")
            continue
        spans.setdefault((e["pid"], e["tid"]), []).append(e)

    eps = 1e-3  # exported timestamps are rounded to 1e-3 us
    for track, evs in spans.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack: List[dict] = []
        for e in evs:
            while stack and stack[-1]["ts"] + stack[-1]["dur"] \
                    <= e["ts"] + eps:
                stack.pop()
            if stack:
                parent_end = stack[-1]["ts"] + stack[-1]["dur"]
                if e["ts"] + e["dur"] > parent_end + eps:
                    problems.append(
                        f"track {track}: span {e['name']!r} "
                        f"[{e['ts']}, {e['ts'] + e['dur']}] partially "
                        f"overlaps {stack[-1]['name']!r} ending at "
                        f"{parent_end} (nesting unbalanced)"
                    )
                    continue
            stack.append(e)
    return problems


# ------------------------------------------------------- profiler windows


class ProfileWindow:
    """``jax.profiler`` capture bracketing a simulation-step range.

    ``GS_PROFILE=start:stop`` (simulation steps) opens the capture at
    the first driver boundary with ``step >= start`` and closes it at
    the first with ``step >= stop``; the XLA device timeline lands in
    ``GS_PROFILE_DIR`` (default ``gs_profile``) for TensorBoard/XProf.
    Complements the host-side span trace: spans say which *round* was
    slow, the capture says which *op*. Profiler failures are reported
    and disable the window — a profiling misconfig must never kill a
    production run.
    """

    def __init__(self, start: int, stop: int, out_dir: str):
        if start < 0 or stop <= start:
            raise ValueError(
                f"profile window needs 0 <= start < stop, got "
                f"{start}:{stop}"
            )
        self.start = start
        self.stop = stop
        self.out_dir = out_dir
        self.active = False
        self._done = False

    @classmethod
    def from_env(cls) -> Optional["ProfileWindow"]:
        spec = os.environ.get("GS_PROFILE", "").strip()
        if not spec:
            return None
        parts = spec.split(":")
        if len(parts) != 2:
            raise ValueError(
                f"GS_PROFILE must be start:stop (steps), got {spec!r}"
            )
        try:
            start, stop = int(parts[0]), int(parts[1])
        except ValueError as e:
            raise ValueError(
                f"GS_PROFILE must be start:stop integers, got {spec!r}"
            ) from e
        return cls(start, stop,
                   os.environ.get("GS_PROFILE_DIR", "gs_profile"))

    def _fail(self, what: str, exc: Exception) -> None:
        print(f"gray-scott: warning: jax.profiler {what} failed "
              f"({exc}); profile window disabled", file=sys.stderr)
        self.active = False
        self._done = True

    def on_boundary(self, step: int) -> None:
        """Called at every driver boundary with the current step."""
        if self._done:
            return
        if self.active and step >= self.stop:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                self._fail("stop_trace", e)
                return
            self.active = False
            self._done = True
        elif not self.active and step >= self.start and step < self.stop:
            try:
                import jax

                jax.profiler.start_trace(self.out_dir)
            except Exception as e:  # noqa: BLE001
                self._fail("start_trace", e)
                return
            self.active = True

    def finish(self) -> None:
        """Close a still-open capture (run ended inside the window)."""
        if self.active:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001
                self._fail("stop_trace", e)
            self.active = False
            self._done = True
