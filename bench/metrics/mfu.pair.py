"""Model FLOPs utilisation of Pairformer serving: the operations the
official dataflow needs (dense pair bias, no SVD; real residue counts) for
the refinement steps and admissions done in the window, over the window's
seconds times the chips' peak bf16 FLOP/s."""
from bench import flops


def read(ctx):
    w = ctx.window
    work = (sum(flops.pair_step_flops(ctx.model, n) for n in w.work("stepped"))
            + sum(flops.pair_admit_flops(ctx.model, n)
                  for n in w.work("admitted")))
    if not work or w.seconds <= 0:
        return None
    return 100.0 * work / (w.seconds * ctx.chips
                           * ctx.peak["bf16_flops_per_s"])
