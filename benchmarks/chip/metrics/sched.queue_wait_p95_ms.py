"""sched.queue_wait_p95_ms (scheduler): 95th percentile, over the requests
due inside the window, of the engine's admission time
(``RequestRecord.t_admit``, a program span) minus the due time. A request
never admitted counts at the moment the driver stopped."""
from chipbench.cell import percentile


def read(run):
    due = run.due_in_window()
    if not due:
        return None
    waits = []
    for s in due:
        t = run.t_admit.get(s.req.rid)
        waits.append((t if t is not None else run.window.closed) - s.due)
    return 1e3 * percentile(waits, 95)
