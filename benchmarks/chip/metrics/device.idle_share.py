"""device.idle_share (device): 1 - (union of the device's op intervals
inside the window) / (the window), from the device trace, in %."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
