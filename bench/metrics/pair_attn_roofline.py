"""Share of its roofline the factored-bias ragged flash-attention kernel
of the Pairformer reaches, in admission and refinement steps alike.

Needed work, per block, for every complex the window refined or admitted:
q k^T, p v and the rank-R factor product phi_q phi_k^T over its real n x n
residue pairs, reading q, k, v (bf16) and the two factors (f32) and writing
the output once. Queries and keys past n in the padded wave, and lanes
padded past the head size and the rank, are not needed work. Kernel time
is the summed device time of the Pallas kernel in every program: the
ragged kernel is the only one the Pairformer runs."""
from bench import trace as tr

BF16, F32 = 2, 4


def needed(m: dict, n: int):
    h, hd, r = m["n_heads"], m["head_dim"], m["bias_rank"]
    flops = 4 * n * n * h * hd + 2 * n * n * h * min(r, n)
    bytes_ = 4 * n * h * hd * BF16 + 2 * n * h * min(r, n) * F32
    return flops, bytes_


def read(ctx):
    if ctx.trace is None:
        return None
    t = tr.matching_s(ctx.trace, kernel=True)
    sizes = ctx.window.work("stepped") + ctx.window.work("admitted")
    if not t or not sizes:
        return None
    L = ctx.model["n_layers"]
    f = sum(needed(ctx.model, n)[0] for n in sizes) * L
    b = sum(needed(ctx.model, n)[1] for n in sizes) * L
    return ctx.roofline("pair_attn_roofline", f, b, t)
