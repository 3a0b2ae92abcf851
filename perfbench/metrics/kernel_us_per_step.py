"""Device time of the step program's compute ops (the Mosaic kernel or
XLA's fusions; no collectives, no copies) per simulation step, averaged
over the chips. Layer: kernel. Source: device trace."""


def read(trace, record):
    ops = trace.kernel_ops()
    if not ops or not record["steps"]:
        return None
    ns = sum(o.end - o.start for o in ops) / len(trace.ops)
    return ns * 1e-3 / record["steps"]
