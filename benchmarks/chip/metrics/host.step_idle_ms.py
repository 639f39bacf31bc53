"""host.step_idle_ms (engine host path): the device's idle time inside the
``serve.step`` spans that start in the window and hold a ``serve.decode``,
mean ms per such decode tick (program spans on the device trace's clock).
Prints on standard error the window's idle seconds charged to each span,
largest first; the p50 and maximum of idle per decode tick; and the spans
that hold each of the three longest idle gaps."""
import statistics
import sys

from chipbench import programspans


def read(run):
    spans = programspans.for_run(run)
    if spans is None:
        return None
    ticks = spans.decode_ticks()
    if not ticks:
        return None
    per_tick = [1e-6 * t.idle_ns for t in ticks]
    by_span = sorted(spans.idle_by_span.items(), key=lambda kv: -kv[1])
    print("idle by span (s): " + ", ".join(f"{k} {1e-9 * v:.6f}"
                                            for k, v in by_span),
          file=sys.stderr)
    print(f"idle per decode tick (ms): ticks {len(ticks)}, "
          f"p50 {statistics.median(per_tick):.3f}, max {max(per_tick):.3f}",
          file=sys.stderr)
    for g in spans.gaps[:3]:
        where = sorted(g.pieces.items(), key=lambda kv: -kv[1])
        print(f"idle gap {1e-6 * g.ns:.3f} ms at "
              f"{1e-9 * (g.start - spans.window[0]):.3f} s in the window: "
              + ", ".join(f"{k} {1e-6 * v:.3f} ms" for k, v in where),
              file=sys.stderr)
    return statistics.fmean(per_tick)
