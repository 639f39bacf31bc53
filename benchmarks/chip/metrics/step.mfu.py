"""step.mfu (model step): model FLOPs of the tokens computed inside the
window, over the window's seconds times the chip's peak bf16 FLOP/s, in %.
A decoded token counts every layer's weights, attention over its live
context and the unembedding; a prompt counts when its first token arrives,
with causal attention over itself and one row of logits."""
from chipbench import workcount


def read(run):
    if run.peaks is None:
        return None
    c = run.config["model"]
    flops = sum(workcount.token_flops(c, ctx, logits=True)
                for t in run.window_ticks() for ctx in t.decode_ctx)
    flops += sum(workcount.prompt_flops(c, len(s.arrival.prompt))
                 for s in run.sent if s.first is not None
                 and run.in_window(s.first))
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops_per_s"])
