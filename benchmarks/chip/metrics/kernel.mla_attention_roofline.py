"""kernel.mla_attention_roofline (kernels): the least time the chip could
take for the latent attention calls of the decode steps inside the window,
over their device time (device trace: the ``paged_decode`` calls inside
the decode steps), in %. The work is what the absorbed algorithm needs:
each live slot's context read once as (kv_lora + rope)-wide latent rows,
plus its absorbed queries and latent outputs, and 2 * H * (2 * kv_lora +
rope) FLOPs a key (``workcount_deepseek.latent_decode_work``); the least
time is the larger of FLOPs over peak FLOP/s and bytes over peak bytes/s.
Which of the two binds is printed on standard error."""
import sys

from chipbench import workcount, workcount_deepseek


def read(run):
    t = run.trace
    if t is None or run.peaks is None or t.paged_decode_s <= 0:
        return None
    c = run.config["model"]
    if "kv_lora_rank" not in c:
        return None
    flops = nbytes = 0
    for tick in run.window_ticks():
        if tick.decode_ctx:
            f, b = workcount_deepseek.latent_decode_work(c, tick.decode_ctx)
            flops += f
            nbytes += b
    least, bound = workcount.least_time(flops, nbytes, run.peaks)
    print(f"mla_attention decode: {flops} flops, {nbytes} bytes, bound by "
          f"{bound}, least {least:.6f} s against {t.paged_decode_s:.6f} s",
          file=sys.stderr)
    return 100.0 * least / t.paged_decode_s
