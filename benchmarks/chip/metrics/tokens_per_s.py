"""tokens_per_s: output tokens the client received inside the window, over
the window's seconds (host clock)."""


def read(run):
    n = sum(1 for s in run.sent for t in s.stamps if run.in_window(t))
    return n / run.seconds
