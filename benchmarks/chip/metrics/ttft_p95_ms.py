"""ttft_p95_ms: 95th percentile, over every request due inside the window,
of its first token's arrival minus its due time (host clock). A request
with no first token when the driver stopped counts at that moment: it is
not dropped."""
from chipbench.cell import percentile


def read(run):
    due = run.due_in_window()
    if not due:
        return None
    return 1e3 * percentile([(s.first if s.first is not None
                              else run.window.closed) - s.due for s in due],
                            95)
