"""step.decode_ms (model step): device time of one jitted decode step,
mean over the decode steps that ran inside the window (device trace)."""


def read(run):
    t = run.trace
    if t is None or not t.decode_steps:
        return None
    return 1e3 * t.decode_s / t.decode_steps
