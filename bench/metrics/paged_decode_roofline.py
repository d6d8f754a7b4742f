"""Share of its roofline the paged ALiBi decode kernel reaches.

Needed work, per layer, for every token decoded in the window: read the
keys and values of its context at the published widths (n_kv_heads x
head_dim, bf16) and its query and output, and compute q k^T and p v over
the context. Padding of heads (50 -> 64) and of the head size to 128 lanes
is not needed work. Kernel time is the summed device time of the Pallas
kernel in every program but prefill (``_pf``): the decode program's only
kernel is the paged decode kernel."""
from bench import trace as tr

PROGRAMS = r"^(?!.*_pf\b)"
BF16 = 2


def needed(m: dict, context: int):
    """(FLOP, bytes) of one token's decode attention in one layer."""
    h, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    flops = 4 * context * h * hd
    bytes_ = (2 * context * kv * hd + 2 * h * hd) * BF16
    return flops, bytes_


def read(ctx):
    if ctx.trace is None:
        return None
    t = tr.matching_s(ctx.trace, module=PROGRAMS, kernel=True)
    ctxs = ctx.window.decode_contexts()
    if not t or not ctxs:
        return None
    L = ctx.model["n_layers"]
    f = sum(needed(ctx.model, c)[0] for c in ctxs) * L
    b = sum(needed(ctx.model, c)[1] for c in ctxs) * L
    return ctx.roofline("paged_decode_roofline", f, b, t)
