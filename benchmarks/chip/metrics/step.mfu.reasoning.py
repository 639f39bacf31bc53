"""step.mfu.reasoning (model step): model FLOPs of the decode steps inside
the window, over their device time (device trace) times the chip's peak
bf16 FLOP/s, in %. Each decoded token counts the MLA projections and the
absorbed attention over its live context, the dense MLP or the router,
shared expert and routed experts at this chip's share, and the head
(``workcount_deepseek.token_flops``); the whole jitted step is timed, so
it bounds a later claim about any one kernel of the step."""
from chipbench import workcount_deepseek


def read(run):
    t = run.trace
    c = run.config["model"]
    if (t is None or run.peaks is None or t.decode_s <= 0
            or "kv_lora_rank" not in c):
        return None
    flops = sum(workcount_deepseek.token_flops(c, ctx, logits=True)
                for tick in run.window_ticks() for ctx in tick.decode_ctx)
    return 100.0 * flops / (t.decode_s * run.peaks["bf16_flops_per_s"])
