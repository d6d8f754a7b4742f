"""Model operations counted from the configuration's real sizes.

Counts follow the published dataflow at real widths (heads and head size
as published, real vocabulary, real prompt lengths and contexts, real
residue counts), never the padded shapes the program runs, so a change
that removes padding raises a utilisation without making the count stale.
One multiply-add is two operations.

The LM arithmetic extends ``repro.analysis.roofline.active_params`` /
``model_flops`` (dense branch, unpadded widths) with attention at the real
context; the Pairformer's follows the official dataflow: a dense pair bias
projected from the pair rep at every use, with no SVD.
"""
from __future__ import annotations


def lm_layer_params(m: dict) -> int:
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    return d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f


def lm_decode_flops(m: dict, context: int) -> float:
    """One generated token whose attention reads ``context`` keys."""
    layers = m["n_layers"] * (2 * lm_layer_params(m)
                              + 4 * context * m["n_heads"] * m["head_dim"])
    return float(layers + 2 * m["vocab"] * m["d_model"])


def lm_prefill_flops(m: dict, n: int) -> float:
    """A prompt of ``n`` tokens: causal attention counts the n(n+1)/2
    query-key pairs it needs; logits only at the last position."""
    pairs = n * (n + 1) // 2
    layers = m["n_layers"] * (2 * lm_layer_params(m) * n
                              + 4 * pairs * m["n_heads"] * m["head_dim"])
    return float(layers + 2 * m["vocab"] * m["d_model"])


def pair_step_flops(m: dict, n: int) -> float:
    """One refinement step of an n-residue complex: per block, the pair
    bias projected from z, single attention with it, the s transition."""
    d, h, hd, f, dp = (m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"],
                       m["d_pair"])
    per = (2 * n * n * dp * h            # bias = LN(z) @ W_b
           + 2 * n * d * 3 * h * hd      # q, k, v
           + 4 * n * n * h * hd          # q k^T and p v
           + 2 * n * h * hd * d          # output projection
           + 4 * n * d * f)              # transition
    return float(m["n_layers"] * per)


def pair_admit_flops(m: dict, n: int) -> float:
    """The admission trunk of an n-residue complex: per block the triangle
    update, the pair transition, and one attention step as above."""
    dp = m["d_pair"]
    tri = 4 * 2 * n * n * dp * dp + 2 * n * n * n * dp
    trans = 2 * 2 * n * n * dp * 4 * dp
    return float(m["n_layers"] * (tri + trans)) + pair_step_flops(m, n)
