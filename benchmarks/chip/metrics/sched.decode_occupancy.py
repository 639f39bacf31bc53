"""sched.decode_occupancy (scheduler): slots decoded per decode tick over
the engine's slots, mean over the decode ticks inside the window, in %
(counted from the tokens each tick returned)."""


def read(run):
    ticks = [t for t in run.window_ticks() if t.decode_ctx]
    if not ticks:
        return None
    return 100.0 * sum(len(t.decode_ctx) for t in ticks) / (
        len(ticks) * run.batch_slots)
