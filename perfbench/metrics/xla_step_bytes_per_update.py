"""HBM bytes the XLA step program moves per cell-update: the bytes of
the ``jit_gs_step`` program's compute and copy ops (each op's HBM
operands and results from its HLO shapes, ``trace_reduce.hlo_bytes``;
an asynchronous copy once, see :func:`op_bytes`), summed over the
chips, over L^3 x steps. One pass per step that materialises nothing
moves 32 B in Float64 (u and v read once and written once); each
intermediate that XLA keeps in HBM between fusions adds to it (on v5e,
Float64 runs as pairs of float32 halves). Layer: kernel. Source: device
trace. Nothing to read where the step program holds a Mosaic launch
(the Pallas paths, which ``stencil_roofline`` reads) or the window no
step."""

from perfbench import stamps
from perfbench import trace_reduce as T

_MOSAIC = 'custom_call_target="tpu_custom_call"'


def op_bytes(op) -> int:
    """``op.bytes``, with an asynchronous copy (a prefetch into or an
    eviction from on-chip memory) counted once, on its ``copy-start``:
    the destination and the source, the two arrays of its result tuple,
    each where it lies in HBM. ``hlo_bytes`` of the whole text would
    count the source again as the operand, and the ``copy-done`` the
    destination again."""
    if op.opcode == "copy-done":
        return 0
    if op.opcode == "copy-start":
        return T.hlo_bytes(op.text.split(" copy-start(", 1)[0] + " tuple()")
    return op.bytes


def xla_step_ops(trace, record):
    """Per chip, the ``jit_gs_step`` program's compute and copy ops;
    None where it has none, where one of them is a Mosaic launch, or
    where the window holds no step."""
    by_dev = stamps.step_ops(trace)
    ops = [o for d in by_dev.values() for o in d]
    if not ops or not record.get("steps") or any(_MOSAIC in o.text
                                                 for o in ops):
        return None
    return by_dev


def read(trace, record):
    by_dev = xla_step_ops(trace, record)
    if by_dev is None:
        return None
    nbytes = sum(op_bytes(o) for ops in by_dev.values() for o in ops)
    return nbytes / (record["L"] ** 3 * record["steps"])
