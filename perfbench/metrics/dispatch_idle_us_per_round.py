"""Device idle time while the host is inside ``Simulation.iterate``
(the program's ``gs.iterate`` span: runner lookup, argument
preparation, enqueue), per round, averaged over the chips. Layer: host
dispatch. Source: device trace. Reads ``trace.program_spans``; nothing
to read where the trace carries no ``gs.iterate`` span."""

from perfbench import stamps


def read(trace, record):
    return stamps.idle_us_per_round(trace, "gs.iterate")
