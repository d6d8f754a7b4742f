"""Needed-work and model-FLOP functions against hand-worked numbers at
SMOKE widths, and the roofline share built on them."""
import pytest

from bench import common, flops

LM = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
      "head_dim": 16, "d_ff": 128, "vocab": 128}
PAIR = {"n_layers": 2, "d_model": 64, "n_heads": 2, "head_dim": 32,
        "d_ff": 128, "d_pair": 32, "bias_rank": 8}


def metric(name):
    return common.load_module("metrics", name)


def test_lm_model_flops():
    # q, k, v, o: 4 x 64 x 64; SwiGLU: 3 x 64 x 128
    assert flops.lm_layer_params(LM) == 40960
    # 2 layers x (2 x 40960 + 4 x 10 keys x 4 heads x 16) + head 2 x 128 x 64
    assert flops.lm_decode_flops(LM, 10) == 185344
    # 8 tokens, 36 causal pairs
    assert flops.lm_prefill_flops(LM, 8) == 1345536


def test_pair_model_flops():
    # per block: bias 12800, qkv 245760, attention 25600, out 81920,
    # transition 327680
    assert flops.pair_step_flops(PAIR, 10) == 1387520
    # plus triangle 819200 + 64000 and pair transition 1638400 per block
    assert flops.pair_admit_flops(PAIR, 10) == 6430720


def test_kernel_needed_work():
    assert metric("paged_decode_roofline").needed(LM, 10) == (2560, 2816)
    # q k^T + p v: 4 x 100 x 2 x 32; factors: 2 x 100 x 2 x 8;
    # bytes: q, k, v, o bf16 + two f32 factors
    assert metric("pair_attn_roofline").needed(PAIR, 10) == (28800, 6400)


def test_roofline_share_and_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = common.Context({}, {}, {}, peak, 1, None, 0.0)
    assert ctx.roofline("k", 197e12, 819e9 / 2, 2.0) == pytest.approx(50.0)
    assert "compute-bound" in ctx.notes[-1]
    assert ctx.roofline("k", 1.0, 819e9, 4.0) == pytest.approx(25.0)
    assert "memory-bound" in ctx.notes[-1]
    assert ctx.roofline("k", 1.0, 1.0, 0.0) is None
