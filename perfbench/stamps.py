"""What the program stamps on a profiler trace, for the readers that
attribute device time and idle time to the program's own work.

The program (``grayscott_jl_tpu``) jits its step runner as ``gs_step``
(XLA module ``jit_gs_step(<fingerprint>)``), stamps each Pallas launch
with ``{"gs_kernel": <variant>, "fuse": <depth>}`` in the custom call's
``kernel_metadata``, and opens ``gs.<name>`` profiler annotations around
its host work: ``gs.iterate`` (runner lookup, argument preparation,
enqueue), ``gs.sync`` (``block_until_ready``), ``gs.compile``, and the
driver's phases. In a trace without them (one recorded before the
program stamped its work) every function here finds nothing, and the
readers return None.

``trace_reduce.from_profile`` puts the program's ``gs.*`` host spans
(:func:`collect_spans`) on ``Reduced.program_spans``, where a reader of
idle time per span looks for them; :func:`load_spans` reads them alone
from a kept trace directory.
"""

from __future__ import annotations

import glob
import os
import re

from perfbench import trace_reduce as T

STEP_MODULE = "jit_gs_step"
SPAN_PREFIX = "gs."
#: A launch stamped by the program: ``gs_kernel`` in its kernel_metadata
#: (printed as a JSON object, one key per line on the TPU).
_LAUNCH = re.compile(r"kernel_metadata=[^}]*gs_kernel")


def is_launch(op) -> bool:
    return bool(_LAUNCH.search(op.text))


def step_ops(trace):
    """Per chip, the ``gs_step`` program's compute and copy ops (no
    collectives, no async transfer halves, no control ops)."""
    return {d: [o for o in ops if o.module.split("(", 1)[0] == STEP_MODULE
                and o.kind in ("compute", "copy")]
            for d, ops in trace.ops.items()}


def per_step_us(trace, record, keep) -> float | None:
    """Device time of the ``gs_step`` ops ``keep`` selects, per
    simulation step, averaged over the chips; None where the step
    program holds no stamped launch or the window no step."""
    by_dev = step_ops(trace)
    if not record.get("steps") or not any(
            is_launch(o) for ops in by_dev.values() for o in ops):
        return None
    ns = sum(o.end - o.start for ops in by_dev.values() for o in ops
             if keep(o)) / len(by_dev)
    return ns * 1e-3 / record["steps"]


def collect_spans(pd):
    """``[(name, start_ns, end_ns)]`` of the host events named ``gs.*``
    in a ``jax.profiler.ProfileData``. The annotation's attributes are
    event stats; a ``#key=value#`` suffix, where the name carries them,
    is cut off."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name.split("#", 1)[0], ev.start_ns,
                                  ev.end_ns))
    return spans


def load_spans(trace_dir: str):
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return collect_spans(ProfileData.from_file(paths[-1]))


def idle_us_per_round(trace, name: str) -> float | None:
    """Each chip's idle time in the window that overlaps the program's
    spans named ``name`` (``gs.iterate``, ``gs.sync``), summed, over the
    number of such spans in the window, averaged over the chips, in us.
    None where the trace has no such span in its window."""
    mine = T.clip([(s, e) for n, s, e in trace.program_spans if n == name],
                  trace.lo, trace.hi)
    if not mine or not trace.busy:
        return None
    cover = T.union(mine)
    idle = 0.0
    for d in trace.busy:
        gaps = trace.idle_gaps(d)
        idle += T.total(T.subtract(gaps, T.subtract(gaps, cover)))
    return idle / len(trace.busy) * 1e-3 / len(mine)
