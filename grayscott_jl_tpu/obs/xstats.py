"""Executable analytics: what XLA actually built, per compile.

The runtime telemetry (spans, metrics, events — PR 8) sees the run
from the host; this module captures the *compiler's* side of the story
at the moment each step runner is compiled: ``cost_analysis()`` flops
and bytes, ``memory_analysis()`` buffer sizes, the HLO collective
census (how many collective-permutes/all-reduces the schedule really
carries — the number the HLO regression tests and the icimodel
calibration loop reason about), the whole-field copy census (loop-carry
copies an out-of-place kernel would force), the whole-field relayout
census (pads, slices and concatenates of field size around a launch),
the XLA step's slab count (``step_slabs``), compile wall time, and the
persistent compilation cache outcome (hit/miss) per executable.
Records land in three places at capture time: ``sim.executables``
(merged into the RunStats ``executables`` section by the driver), one
``executable`` record on the unified event stream, and the
``compiles``/``compile_cache_hits``/``compile_cache_misses`` counters
plus a ``compile_s_total`` gauge in the metrics registry.

Knob: ``GS_XSTATS`` env / ``xstats`` TOML key (on/off, default off).
Capture is also armed implicitly whenever the persistent compilation
cache is (on by default on TPU) — the cache's hit/miss story should
never be invisible just because nobody asked for full analytics.

Contract: armed capture routes the runner through the same
``lower().compile()`` AOT path ``Simulation.compile_chunk`` already
uses — the identical program, so trajectories and stores stay bitwise
identical (asserted in tier-1 for all four models). Off costs one
``if`` per runner construction, nothing on the step path. Every
analytics query is best-effort: a jax whose AOT surface drifted
degrades to a partial record, never a failed run.

Module is importable without JAX (it only touches the compiled objects
handed to it), like the rest of ``obs/``.
"""

from __future__ import annotations

import os
import re
import time
from typing import Optional

__all__ = [
    "capture",
    "collective_counts",
    "cache_listing",
    "field_copies",
    "field_relayouts",
    "cache_outcome",
    "instrument_compile",
    "publish",
    "resolve_xstats",
    "summarize",
]

_TRUTHY = ("1", "on", "true", "yes")
_FALSY = ("0", "off", "false", "no", "")


def resolve_xstats(settings=None) -> bool:
    """``GS_XSTATS`` env wins over the ``xstats`` TOML key; default
    off. Unknown values raise at startup."""
    raw = os.environ.get("GS_XSTATS")
    if raw is None and settings is not None:
        raw = getattr(settings, "xstats", "")
    raw = (raw or "").strip().lower()
    if raw in _TRUTHY:
        return True
    if raw in _FALSY:
        return False
    raise ValueError(
        f"GS_XSTATS / xstats must be on or off, got {raw!r}"
    )


#: HLO instruction names that move data between devices — the census
#: the collective-count regression tests (test_overlap) key on.
_COLLECTIVE_RE = re.compile(
    r"\b(collective-permute|all-reduce|all-gather|all-to-all|"
    r"reduce-scatter)\b"
)


def collective_counts(hlo_text: str) -> dict:
    """Occurrences of each collective op family in an HLO dump.
    ``-start``/``-done`` async pairs count under their family (the
    family name is a prefix of both halves)."""
    counts: dict = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


#: An array type in HLO text, ``f32[256,256,256]`` (layout not captured).
_ARRAY_TYPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([\d,]*)\]")

#: A ``copy`` or ``copy-start`` instruction and its result type (a
#: tuple for ``copy-start``: destination, source, context).
_COPY_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%\S+\s=\s(\(?\S+(?:,\s\S+)*)\s(copy|copy-start)\(",
    re.MULTILINE,
)


def _entry_param_types(hlo_text: str) -> list:
    """``(dtype, dims)`` of each ENTRY parameter, in order, from the
    ENTRY computation's signature line."""
    m = re.search(r"^ENTRY\s+%\S+\s+\((.*)\)\s*->", hlo_text, re.MULTILINE)
    if m is None:
        return []
    return [t.groups() for t in re.finditer(
        r"[\w.\-]+:\s" + _ARRAY_TYPE_RE.pattern, m.group(1))]


def field_copies(hlo_text: str, n_fields: int) -> int:
    """``copy`` and ``copy-start`` instructions whose result is a whole
    field block: the type of one of the first ``n_fields`` ENTRY
    parameters (a step runner takes its fields first). Each is a full
    read and write of a field that the step does not need — the loop
    carry copies an out-of-place kernel forces. Face-sized and scalar
    copies are not counted, nor ``copy-done`` (the other half of a
    ``copy-start``)."""
    fields = set(_entry_param_types(hlo_text)[:n_fields])
    count = 0
    for m in _COPY_RE.finditer(hlo_text):
        t = _ARRAY_TYPE_RE.search(m.group(1))
        if t is not None and t.groups() in fields:
            count += 1
    return count


#: An HLO instruction: optional ROOT, name, result type, opcode.
_INSTR_RE = re.compile(
    r"^\s*(ROOT\s+)?%\S+\s=\s(.+?)\s([a-z][a-z0-9\-]*)\(", re.MULTILINE
)

#: A computation header, ``%name (params) -> type {`` or ``ENTRY ...``.
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%(\S+)\s.*\{\s*$")

#: Opcodes that move a whole array into a new layout.
_RELAYOUT_OPS = ("pad", "slice", "concatenate")


def _elements(type_text: str) -> int:
    """Element count of an array result type; 0 for a tuple."""
    t = _ARRAY_TYPE_RE.match(type_text)
    if t is None:
        return 0
    n = 1
    for d in t.group(2).split(","):
        n *= int(d) if d else 1
    return n


def field_relayouts(hlo_text: str, n_fields: int) -> int:
    """``pad``, ``slice`` and ``concatenate`` results holding at least a
    field block's element count (the first ``n_fields`` ENTRY
    parameters), whether the op stands alone or is the root of a
    fusion: each reads and writes a whole field to move it into a new
    layout — the y pad before an xy-chain launch, the slice after it.
    In-place ``dynamic-update-slice`` and face-sized ops are not
    counted, nor ops inside a fusion other than its root."""
    sizes = [
        _elements(f"{dt}[{dims}]")
        for dt, dims in _entry_param_types(hlo_text)[:n_fields]
    ]
    if not sizes:
        return 0
    field = min(sizes)
    roots: dict = {}
    body: list = []  # (computation, opcode, type, called) of each op
    comp = None
    for line in hlo_text.splitlines():
        c = _COMP_RE.match(line)
        if c is not None:
            comp = c.group(1)
            continue
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        is_root, type_text, op = m.groups()
        called = re.search(r"\bcalls=%([\w.\-]+)", line)
        body.append((comp, op, type_text,
                     called.group(1) if called else None))
        if is_root:
            roots[comp] = op
    fused = {called for _, op, _, called in body if op == "fusion"}
    count = 0
    for comp, op, type_text, called in body:
        if comp in fused or _elements(type_text) < field:
            continue
        if op == "fusion":
            op = roots.get(called)
        if op in _RELAYOUT_OPS:
            count += 1
    return count


def cache_listing(path: Optional[str]) -> Optional[frozenset]:
    """Entries of the persistent compile cache directory, or None when
    no cache is armed / the directory is unreadable."""
    if not path:
        return None
    try:
        return frozenset(os.listdir(path))
    except OSError:
        return None


#: cost_analysis keys worth keeping — the raw dict carries hundreds of
#: per-operand ``bytes accessedN{}`` entries that would bloat every
#: stats file.
_COST_KEYS = ("flops", "bytes accessed", "transcendentals",
              "optimal_seconds", "utilization")

#: memory_analysis attributes present across the jax versions we care
#: about (each read defensively — absence is recorded as absence).
_MEMORY_ATTRS = (
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "temp_size_in_bytes",
    "alias_size_in_bytes",
    "generated_code_size_in_bytes",
)


def capture(compiled, *, name: str, compile_s: float,
            cache_dir: Optional[str] = None,
            cache_before: Optional[frozenset] = None,
            extra: Optional[dict] = None,
            n_fields: Optional[int] = None,
            step_slabs: Optional[int] = None) -> dict:
    """One executable's analytics record, from a ``jax`` AOT-compiled
    object. Every query is individually best-effort. ``n_fields``
    (the step runner's leading field arguments) arms the
    ``field_copies`` and ``field_relayouts`` censuses; ``step_slabs``
    (the XLA step's x-slabs, ``Simulation.step_slabs``; None on a
    Pallas runner) is recorded beside them."""
    rec = {"name": name, "compile_s": round(compile_s, 6)}
    if extra:
        rec.update(extra)
    if step_slabs is not None:
        rec["step_slabs"] = step_slabs

    try:
        cost = compiled.cost_analysis()
        # Older jax returns a one-dict list (per partition), newer the
        # dict itself.
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if isinstance(cost, dict):
            rec["cost"] = {
                k.replace(" ", "_"): round(float(cost[k]), 3)
                for k in _COST_KEYS if k in cost
            }
    except Exception:  # noqa: BLE001 — optional AOT surface
        pass

    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out = {}
            for attr in _MEMORY_ATTRS:
                v = getattr(mem, attr, None)
                if v is not None:
                    out[attr] = int(v)
            if out:
                # The operator-facing single number: everything the
                # executable holds live at once (args + outputs +
                # temps), the HBM envelope a capacity planner needs.
                peak = sum(
                    out.get(k, 0)
                    for k in ("argument_size_in_bytes",
                              "output_size_in_bytes",
                              "temp_size_in_bytes")
                )
                out["peak_bytes_estimate"] = peak
                rec["memory"] = out
    except Exception:  # noqa: BLE001
        pass

    try:
        hlo = compiled.as_text()
        rec["collectives"] = collective_counts(hlo)
        if n_fields:
            rec["field_copies"] = field_copies(hlo, n_fields)
            rec["field_relayouts"] = field_relayouts(hlo, n_fields)
        # Mosaic (Pallas-on-TPU) kernels in the program: 0 means the
        # step ran no fused kernel (XLA path, or interpret mode).
        rec["tpu_custom_calls"] = hlo.count(
            'custom_call_target="tpu_custom_call"'
        )
    except Exception:  # noqa: BLE001
        pass

    if cache_dir is not None:
        rec["cache"] = cache_outcome(cache_dir, cache_before)
    return rec


def cache_outcome(cache_dir: str, before: Optional[frozenset]) -> str:
    """``hit``, ``miss`` or ``unknown`` for the compile since
    ``before`` (:func:`cache_listing` of ``cache_dir``)."""
    after = cache_listing(cache_dir)
    if before is None or after is None:
        return "unknown"
    # A compile that wrote a new cache entry was a miss; one that left
    # the directory untouched was served from it.
    return "miss" if after - before else "hit"


def publish(rec: dict, *, metrics=None, events=None) -> None:
    """Mirror one capture into the metrics registry and the unified
    event stream (both no-ops when their sinks are off)."""
    if events is not None:
        events.emit("executable", phase="compile", **rec)
    if metrics is None:
        return
    metrics.counter("compiles").inc()
    g = metrics.gauge("compile_s_last")
    g.set(rec.get("compile_s"))
    if "step_slabs" in rec:
        metrics.gauge("step_slabs", runner=rec["name"]).set(
            rec["step_slabs"])
    cache = rec.get("cache")
    if cache == "hit":
        metrics.counter("compile_cache_hits").inc()
    elif cache == "miss":
        metrics.counter("compile_cache_misses").inc()


def summarize(records) -> dict:
    """Aggregate view of a run's capture list — the header of the
    RunStats ``executables`` section."""
    records = list(records)
    cache = [r.get("cache") for r in records]
    return {
        "compiles": len(records),
        "compile_s_total": round(
            sum(r.get("compile_s", 0.0) for r in records), 6
        ),
        "compile_cache_hits": cache.count("hit"),
        "compile_cache_misses": cache.count("miss"),
    }


def instrument_compile(sim, fn, nsteps: int):
    """AOT-compile a runner with analytics capture.

    Returns the compiled executable (stored by the caller in place of
    the jit wrapper, exactly like ``Simulation.compile_chunk``), or the
    wrapper unchanged if anything about the instrumented path fails —
    capture must never take a run down.
    """
    import jax.numpy as jnp

    from .trace import get_tracer

    cache_dir = sim.compile_cache_dir
    before = cache_listing(cache_dir)
    name = f"runner[{nsteps}]"
    # The "which step recompiled" span: lowering plus compile or cache
    # load, with the runner's name and the cache outcome.
    with get_tracer().span("compile", step=sim.step, runner=name) as attrs:
        try:
            t0 = time.perf_counter()
            lowered = fn.lower(
                *sim.fields, sim.base_key, jnp.int32(sim.step), sim.params
            )
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
        except Exception as e:  # noqa: BLE001 — never break the run
            import sys

            print(
                f"gray-scott: warning: executable analytics capture "
                f"failed for the {nsteps}-step runner ({e}); running "
                f"uninstrumented",
                file=sys.stderr,
            )
            return fn
        if cache_dir is not None:
            attrs["cache"] = cache_outcome(cache_dir, before)
    rec = capture(
        compiled, name=name, compile_s=compile_s,
        cache_dir=cache_dir, cache_before=before,
        n_fields=sim.model.n_fields,
        extra={"nsteps": nsteps,
               "kernel": sim.kernel_language,
               "model": sim.model.name},
        step_slabs=sim.step_slabs(nsteps),
    )
    sim.executables.append(rec)
    from .events import get_events
    from .metrics import get_metrics

    publish(rec, metrics=get_metrics(), events=get_events())
    return compiled
