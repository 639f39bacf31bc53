"""step.mfu.decode (model step): model FLOPs of the decode steps inside the
window, over their device time (device trace) times the chip's peak bf16
FLOP/s, in %. Each decoded token counts every layer's weights, attention
over its live context and the unembedding; the whole jitted step is timed,
so it bounds the paged kernel's roofline when a later change moves work
out of the kernel."""
from chipbench import workcount


def read(run):
    t = run.trace
    if t is None or run.peaks is None or t.decode_s <= 0:
        return None
    c = run.config["model"]
    flops = sum(workcount.token_flops(c, ctx, logits=True)
                for tick in run.window_ticks() for ctx in tick.decode_ctx)
    return 100.0 * flops / (t.decode_s * run.peaks["bf16_flops_per_s"])
