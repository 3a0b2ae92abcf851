"""Device time of the fused kernel's launches per simulation step,
averaged over the chips: the ops of the ``gs_step`` program whose
``kernel_metadata`` names a ``gs_kernel`` variant, as the program stamps
each Pallas launch (ops/pallas_stencil.py). Layer: kernel. Source:
device trace. Nothing to read in a trace without the stamps."""

from perfbench import stamps


def read(trace, record):
    return stamps.per_step_us(trace, record, stamps.is_launch)
