"""Share of the roofline reached by the XLA step program: least time /
the device time of the ``jit_gs_step`` program's compute and copy ops,
per chip. Least time is the larger of flops / peak FLOP/s (the model's
flops per cell-update times the cell-updates) and bytes / peak HBM
bytes/s (the bytes ``xla_step_bytes_per_update`` counts). v5e publishes
no Float64 or VPU peak, only the MXU's bf16 one, so the HBM term binds.
Layer: kernel. Source: device trace. Nothing to read where the step
program holds a Mosaic launch (``stencil_roofline`` reads those)."""

from perfbench.metrics.xla_step_bytes_per_update import op_bytes, xla_step_ops


def read(trace, record):
    by_dev = xla_step_ops(trace, record)
    if by_dev is None:
        return None
    chips = len(by_dev)
    ops = [o for d in by_dev.values() for o in d]
    t = sum(o.end - o.start for o in ops) * 1e-9 / chips
    nbytes = sum(op_bytes(o) for o in ops) / chips
    flops = (record["flops_per_cell_update"] * record["L"] ** 3
             * record["steps"] / chips)
    peaks = record["peaks"]
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
