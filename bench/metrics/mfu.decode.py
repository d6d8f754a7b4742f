"""Model FLOPs utilisation of the serve loop: the model operations of all
the work done in the window (every token decoded at its real context and
every prompt prefilled at its real length, published widths) over the
window's seconds times the chips' peak bf16 FLOP/s."""
from bench import flops


def read(ctx):
    w = ctx.window
    work = (sum(flops.lm_decode_flops(ctx.model, c)
                for c in w.decode_contexts())
            + sum(flops.lm_prefill_flops(ctx.model, n)
                  for n in w.admitted_prompts()))
    if not work or w.seconds <= 0:
        return None
    return 100.0 * work / (w.seconds * ctx.chips
                           * ctx.peak["bf16_flops_per_s"])
