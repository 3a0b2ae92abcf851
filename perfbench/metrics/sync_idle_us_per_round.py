"""Device idle time while the host is inside
``Simulation.block_until_ready`` (the program's ``gs.sync`` span:
completion and launch latency), per round, averaged over the chips.
Layer: host dispatch. Source: device trace. Reads
``trace.program_spans``; nothing to read where the trace carries no
``gs.sync`` span."""

from perfbench import stamps


def read(trace, record):
    return stamps.idle_us_per_round(trace, "gs.sync")
