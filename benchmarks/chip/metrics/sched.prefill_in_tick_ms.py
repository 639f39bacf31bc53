"""sched.prefill_in_tick_ms (scheduler): over the ``serve.step`` spans that
start in the window and hold both a ``serve.decode`` and a ``serve.prefill``
that ran at least one ``serve.prefill_chunk``, the mean duration of that
``serve.prefill`` in ms: how long the tick's decode waited on prefill
(program spans)."""
import statistics

from chipbench import programspans


def read(run):
    spans = programspans.for_run(run)
    if spans is None:
        return None
    waits = [p.ns for t in spans.decode_ticks()
             for p in t.named("serve.prefill") if p.has("serve.prefill_chunk")]
    return 1e-6 * statistics.fmean(waits) if waits else None
