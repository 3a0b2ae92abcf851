"""Reduce a JAX profiler trace of the window to what the metrics read.

``start(dir)`` opens the capture (Python tracer off: only the
harness's own ``perfbench.*`` spans and the runtime's events land on
the host side). ``load(dir, n_devices)`` reads the ``.xplane.pb`` with
``jax.profiler.ProfileData`` and returns a :class:`Reduced`:

* the device ops of each chip, with start, end, program (HLO module)
  and the bytes of their operands and results from the HLO shapes;
* the union of busy intervals per chip, inside the traced window;
* the idle gaps, each named by the innermost harness span it falls in;
* the program's own ``gs.*`` host spans (``stamps.collect_spans``), on
  ``program_spans``.

The traced window runs from the start of the first window span
(``perfbench.round`` or ``perfbench.run_once``) to the end of the last.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
WINDOW_SPANS = ("perfbench.round", "perfbench.run_once")
#: Ops that contain other ops (their events overlay their bodies).
CONTROL = {"while", "conditional", "call"}
COLLECTIVE = re.compile(r"^(collective-permute|all-reduce|all-gather"
                        r"|all-to-all|reduce-scatter|send|recv)")
COPY = {"copy", "copy-start", "copy-done"}
#: Ops whose event is the issue or the wait of a transfer that runs in
#: the background (its bytes move between the two events).
ASYNC = re.compile(r"-(start|done)$")
#: The opcode of an HLO instruction's text: the first lower-case word
#: followed by "(" after "=" (layouts such as {T(8,128)} are upper-case).
_OPCODE = re.compile(r"=.*?\s([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64"
                    r"|f64)\[([0-9,]*)\]")
#: An array in HLO text: dtype[dims], an optional layout (which may hold
#: parentheses, as TPU tilings do), and the operand's %name if any.
_ARRAY = re.compile(_SHAPE.pattern + r"(\{[^}]*\})?(?:\s+(%[\w.\-]+))?")
_VMEM = re.compile(r"S\([1-9]")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8}
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an
    error, not a default."""
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"perfbench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def start(trace_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def hlo_bytes(text: str) -> int:
    """HBM bytes of an HLO instruction's text: every distinct array among
    its result(s) and operands that lives in HBM (no memory-space
    annotation ``S(n)``, n > 0, as VMEM operands carry). Operands are
    written ``shape %name`` and counted once per name; attributes after
    the operand list (layout constraints) are not arrays moved. A
    dynamic-update-slice moves its update twice (it works in place), a
    slice or dynamic-slice its result twice."""
    m = _OPCODE.search(text or "")
    if not m:
        return 0
    results = text[text.index("=") + 1:m.start(1)]
    start = m.end(1)
    depth, end = 0, len(text)
    for i in range(start, len(text)):
        depth += {"(": 1, ")": -1}.get(text[i], 0)
        if depth == 0:
            end = i
            break
    res = [a for a in _ARRAY.finditer(results)
           if not _VMEM.search(a.group(3) or "")]
    ops = list(_ARRAY.finditer(text[start:end]))
    if m.group(1) == "dynamic-update-slice":
        # In place: the update is read and written, the buffer is not.
        return 2 * _array_bytes(ops[1]) if len(ops) > 1 else 0
    if m.group(1) in ("slice", "dynamic-slice"):
        # Only the part sliced out is read.
        return 2 * sum(_array_bytes(a) for a in res)
    total = sum(_array_bytes(a) for a in res)
    seen = set()
    for a in ops:
        name = a.group(4)
        if _VMEM.search(a.group(3) or "") or name in seen:
            continue
        seen.add(name)
        total += _array_bytes(a)
    return total


def _array_bytes(m) -> int:
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n * _ITEMSIZE[m.group(1)]


def union(intervals):
    """Merged, sorted, disjoint (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b):
    """Parts of union ``a`` not covered by union ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class Op:
    """One device op event. On the TPU the event's name is the HLO
    instruction's text, from which the name, opcode and bytes come."""

    __slots__ = ("text", "start", "end", "module", "name", "opcode",
                 "kind")

    def __init__(self, text, start, end, module=""):
        self.text, self.start, self.end, self.module = (
            text, start, end, module)
        self.name = text.split(" = ", 1)[0]
        m = _OPCODE.search(text)
        self.opcode = m.group(1) if m else self.name
        self.kind = ("control" if self.opcode in CONTROL
                     else "collective" if COLLECTIVE.match(self.opcode)
                     else "copy" if self.opcode in COPY
                     else "async" if ASYNC.search(self.opcode)
                     else "compute")

    @property
    def bytes(self) -> int:
        return hlo_bytes(self.text)


class Reduced:
    """Device ops per chip, host spans and the traced window (ns)."""

    def __init__(self, ops_by_device, spans, program_spans=()):
        self.spans = spans  # [(name, start, end)], host clock
        self.program_spans = list(program_spans)  # the same, gs.*
        wins = [(s, e) for n, s, e in spans if n in WINDOW_SPANS]
        if not wins:
            raise ValueError("trace holds no window span")
        self.lo = min(s for s, _ in wins)
        self.hi = max(e for _, e in wins)
        # Leaf ops only: a while loop's event overlays its body.
        self.ops = {d: [o for o in ops if o.end > self.lo
                        and o.start < self.hi and o.kind != "control"]
                    for d, ops in ops_by_device.items()}
        self.busy = {d: clip(union((o.start, o.end) for o in ops),
                             self.lo, self.hi)
                     for d, ops in self.ops.items()}

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the chips."""
        if not self.busy:
            return 0.0
        return sum(total(b) for b in self.busy.values()) * 1e-9 / len(
            self.busy)

    def step_module(self):
        """The program that holds the most device time: the runner."""
        t = {}
        for ops in self.ops.values():
            for o in ops:
                t[o.module] = t.get(o.module, 0) + (o.end - o.start)
        return max(t, key=t.get) if t else None

    def kernel_ops(self, device=None):
        """Compute ops (no collectives, no copies) of the step program."""
        mod = self.step_module()
        devs = [device] if device is not None else list(self.ops)
        return [o for d in devs for o in self.ops[d]
                if o.module == mod and o.kind == "compute"]

    def mosaic_ops(self):
        """The step program's Mosaic kernel launches."""
        return [o for o in self.kernel_ops()
                if 'custom_call_target="tpu_custom_call"' in o.text]

    def exposed_collective_ns(self, device) -> float:
        ops = self.ops[device]
        coll = union((o.start, o.end) for o in ops
                     if o.kind == "collective")
        comp = union((o.start, o.end) for o in ops
                     if o.kind != "collective")
        return total(clip(subtract(coll, comp), self.lo, self.hi))

    def idle_gaps(self, device):
        """Idle intervals of ``device`` inside the window."""
        return subtract([(self.lo, self.hi)], self.busy[device])

    def host_span_at(self, t):
        """Innermost harness span holding host time ``t``."""
        best = None
        for n, s, e in self.spans:
            if s <= t < e and (best is None or e - s < best[2] - best[1]):
                best = (n, s, e)
        return best[0] if best else "outside harness spans"

    def breakdown(self) -> dict:
        dev = sorted(self.ops)[0] if self.ops else None
        by_op = {}
        for ops in self.ops.values():
            for o in ops:
                by_op[o.name] = by_op.get(o.name, 0) + (o.end - o.start)
        n = max(1, len(self.ops))
        device_ops = sorted(((k, v * 1e-9 / n) for k, v in by_op.items()),
                            key=lambda kv: -kv[1])[:10]
        gaps = {}
        if dev is not None:
            for s, e in self.idle_gaps(dev):
                name = self.host_span_at((s + e) / 2)
                gaps[name] = gaps.get(name, 0) + (e - s)
        idle = sorted(((k, v * 1e-9) for k, v in gaps.items()),
                      key=lambda kv: -kv[1])[:10]
        return {"device_ops": [list(x) for x in device_ops],
                "idle_gaps": [list(x) for x in idle]}


def _module_of(modules, starts, t):
    """Name of the program (an "XLA Modules" event, sorted by start)
    running at ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i][2]:
        return modules[i][0]
    return ""


def from_profile(pd, n_devices=None) -> Reduced:
    from perfbench.stamps import collect_spans  # stamps imports this

    ops_by_device = {}
    spans = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if n_devices is not None and dev >= n_devices:
                continue
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            modules = sorted(((ev.name, ev.start_ns, ev.end_ns)
                              for ev in lines.get("XLA Modules", [])),
                             key=lambda x: x[1])
            starts = [x[1] for x in modules]
            ops_by_device[dev] = [
                Op(ev.name, ev.start_ns, ev.end_ns,
                   _module_of(modules, starts, ev.start_ns))
                for ev in lines.get("XLA Ops", [])]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("perfbench."):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    return Reduced(ops_by_device, spans, collect_spans(pd))


def load(trace_dir: str, n_devices=None) -> Reduced:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_profile(ProfileData.from_file(paths[-1]), n_devices)
