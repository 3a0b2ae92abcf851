"""Share of the roofline reached by the step program's Mosaic stencil
kernel: least time / its device time, per chip. Least time is the
larger of flops / peak FLOP/s (the model's flops per cell-update times
the cell-updates) and bytes / peak HBM bytes/s (each launch's HBM
operands and results from its HLO shapes, counted once: a Mosaic kernel
streams all of them). XLA fusions are left out: an in-place or slicing
fusion touches less than its shapes say. Layer: kernel. Source: device
trace. Nothing to read where no Mosaic kernel runs."""


def read(trace, record):
    ops = trace.mosaic_ops()
    if not ops:
        return None
    chips = len(trace.ops)
    t = sum(o.end - o.start for o in ops) * 1e-9 / chips
    nbytes = sum(o.bytes for o in ops) / chips
    flops = (record["flops_per_cell_update"] * record["L"] ** 3
             * record["steps"] / chips)
    peaks = record["peaks"]
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
