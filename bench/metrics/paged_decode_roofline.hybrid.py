"""Share of its roofline the paged decode kernel reaches in the attention
layers of an interleaved Mamba2 / attention model (its bias-free mode).

Needed work, in each attention layer of ``layer_types``, for every token
decoded in the window: read the keys and values of its context at the
published widths (``num_key_value_heads`` x head size, bf16) and its query
and output, and compute q k^T and p v over the context for every query
head. The lanes past the head size (64 -> 128 in the pools) are not needed
work. Kernel time is the summed device time of the kernel named
``flash_decode_paged`` in the decode program ``jit_decode``."""
from bench import trace as tr

BF16 = 2
PROGRAM = r"^jit_decode\b"
KERNEL = r"flash_decode_paged"


def needed(m: dict, context: int):
    """(FLOP, bytes) of one token's decode attention in one layer."""
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m["hidden_size"] // h
    flops = 4 * context * h * hd
    bytes_ = (2 * context * kv * hd + 2 * h * hd) * BF16
    return flops, bytes_


def read(ctx):
    if ctx.trace is None:
        return None
    t = tr.matching_s(ctx.trace, KERNEL, module=PROGRAM, kernel=True)
    ctxs = ctx.window.decode_contexts()
    if not t or not ctxs:
        return None
    layers = sum(k == "attention" for k in ctx.cfg["layer_types"])
    f = sum(needed(ctx.cfg, c)[0] for c in ctxs) * layers
    b = sum(needed(ctx.cfg, c)[1] for c in ctxs) * layers
    return ctx.roofline("paged_decode_roofline.hybrid", f, b, t)
