"""Model FLOPs utilisation of the serve loop of an interleaved Mamba2 /
attention model: the model operations of all the work done in the window
(every token decoded at its real context and every prompt prefilled at its
real length, published widths) over the window's seconds times the chips'
peak bf16 FLOP/s.

Counted per token: two operations per weight of every matmul (the Mamba
``in_proj`` and ``out_proj``, attention q, k, v and o, the SwiGLU MLP) and
of the tied output head (each decoded token; a prompt's last position
only); per attention layer q k^T and p v over the keys a token attends
(its context in decode, the causal pairs in prefill); per Mamba layer the
causal conv (2 x width per channel) and the state: in decode the update
h exp(dt A) + dt x B^T (3 per state element) and the read-out h C (2), in
prefill the SSD chunk scan as ``ssd_prefill_roofline.needed`` counts it.
Norms, gates and activations are left out: under 0.1% of a layer."""
from bench import common

SSD = common.load_module("metrics", "ssd_prefill_roofline")


def _sizes(m: dict):
    d, f, v = m["hidden_size"], m["shared_intermediate_size"], m["vocab_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hm, p, s, w = (m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"],
                   m["mamba_d_conv"])
    return d, f, v, h, kv, d // h, hm, p, s, w, hm * p


def layer_flops(m: dict) -> int:
    """Matmul operations of one token through every layer (no head)."""
    d, f, _, h, kv, hd, hm, p, s, w, di = _sizes(m)
    mlp = 3 * d * f
    mamba = d * (2 * di + 2 * s + hm) + di * d
    attn = d * (h + 2 * kv) * hd + h * hd * d
    n_m = sum(k == "mamba" for k in m["layer_types"])
    n_a = len(m["layer_types"]) - n_m
    return 2 * (n_m * (mamba + mlp) + n_a * (attn + mlp))


def decode_flops(m: dict, context: int) -> int:
    """One generated token whose attention reads ``context`` keys."""
    d, _, v, h, _, hd, hm, p, s, w, di = _sizes(m)
    n_m = sum(k == "mamba" for k in m["layer_types"])
    n_a = len(m["layer_types"]) - n_m
    return (layer_flops(m) + 2 * v * d + n_a * 4 * context * h * hd
            + n_m * (5 * hm * p * s + 2 * w * (di + 2 * s)))


def prefill_flops(m: dict, n: int) -> int:
    """A prompt of ``n`` tokens, logits at its last position only."""
    d, _, v, h, _, hd, hm, p, s, w, di = _sizes(m)
    n_m = sum(k == "mamba" for k in m["layer_types"])
    n_a = len(m["layer_types"]) - n_m
    pairs = n * (n + 1) // 2
    return (n * layer_flops(m) + 2 * v * d + n_a * 4 * pairs * h * hd
            + n_m * (SSD.needed(m, n)[0] + 2 * w * (di + 2 * s) * n))


def read(ctx):
    w = ctx.window
    work = (sum(decode_flops(ctx.cfg, c) for c in w.decode_contexts())
            + sum(prefill_flops(ctx.cfg, n) for n in w.admitted_prompts()))
    if not work or w.seconds <= 0:
        return None
    return 100.0 * work / (w.seconds * ctx.chips
                           * ctx.peak["bf16_flops_per_s"])
