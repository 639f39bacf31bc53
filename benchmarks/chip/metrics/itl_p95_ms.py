"""itl_p95_ms: 95th percentile of every gap between consecutive output
tokens of one request whose later token arrived inside the window, pooled
over all requests (host clock). Tokens that arrive in the same engine tick
are a gap of 0, as a streaming client sees them."""
from chipbench.cell import percentile


def read(run):
    gaps = [b - a for s in run.sent for a, b in zip(s.stamps, s.stamps[1:])
            if run.in_window(b)]
    return 1e3 * percentile(gaps, 95) if gaps else None
