"""Collective device time during which no other op runs on the same
chip, per simulation step, on the chip where it is largest. Layer:
exchange (parallel/halo.py, parallel/temporal.py). Source: device
trace. Nothing to read on one chip."""


def read(trace, record):
    if len(trace.ops) < 2 or not record["steps"]:
        return None
    ns = max(trace.exposed_collective_ns(d) for d in trace.ops)
    return ns * 1e-3 / record["steps"]
