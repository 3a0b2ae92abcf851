"""1 - busy / window: the share of the traced window in which no
operation ran on the device, averaged over the chips. Layer: device.
Source: device trace."""


def read(trace, record):
    if trace.window_s <= 0 or not trace.busy:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
