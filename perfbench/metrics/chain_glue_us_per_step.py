"""Device time of the ``gs_step`` program's compute and copy ops that
are not stamped kernel launches, per simulation step, averaged over the
chips: the XLA pads, slices, loop copies and face stitching around the
launches. Layer: chain schedule (simulation._local_run, the pad/slice
wrappers of ops/pallas_stencil.py). Source: device trace. Nothing to
read in a trace without the stamps."""

from perfbench import stamps


def read(trace, record):
    return stamps.per_step_us(trace, record,
                              lambda o: not stamps.is_launch(o))
