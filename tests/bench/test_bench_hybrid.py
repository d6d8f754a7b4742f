"""The ``hybrid_lm`` family (interleaved Mamba2 / attention LMs) through the
harness at SMOKE size on the CPU, the look for a chip skipped: a run serves
and agrees with the plain reference while the fp8 control does not,
planted faults read not correct, the sample holds requests of distinct
slots, the readers' needed work against hand counts, and the traffic files
against their stated distributions."""
import math
import types

import numpy as np
import pytest

from bench import common, traffic
from conftest import load, run_smoke

# The smoke cell's init (in its file) is the reference's, scaled for width
# 64: no echo of the input, top-two logits about 0.007 apart. Over 80
# positions a reference with bfloat16 operands misses by 2e-4 at most and
# the fp8 control by 0.014-0.029 (CPU, three seeds); the limit sits between.
# Three requests of the four slots make the sample.
LIMITS = {"max_logit_gap": 0.004, "sample_requests": 3}
BENCH = {
    "end_to_end": [{"name": n, "unit": u} for n, u in (
        ("out_tok_per_s", "tokens/s"), ("itl_p99_ms", "ms"),
        ("setup_s", "s"))],
    "per_layer": [{"name": "slot_occupancy", "unit": "%",
                   "moves": "out_tok_per_s"}],
}


def _cell():
    return load("hybrid_smoke"), load("hybrid_smoke_mix")


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
def test_hybrid_cell_runs_and_the_control_fails(seed):
    cfg, mix = _cell()
    out = run_smoke(cfg, mix, LIMITS, seed=seed, seconds=3.0, control=True,
                    bench=BENCH)
    assert out["correct"] is True, out["checks"]
    assert out["control"]["correct"] is False, out["control"]["checks"]
    assert out["failed"] == 0 and out["attempted"] > mix["clients"]
    assert out["compiles_in_window"] == 0
    assert set(out["metrics"]) == {"out_tok_per_s", "itl_p99_ms", "setup_s"}


def _state_unchanged(monkeypatch):
    from repro.models import blocks
    orig = blocks.decode_step

    def stale(params, cache, tokens, cfg, **kw):
        logits, new = orig(params, cache, tokens, cfg, **kw)
        return logits, dict(new, ssm_h=cache["ssm_h"])
    monkeypatch.setattr(blocks, "decode_step", stale)


def _token_altered(monkeypatch):
    from repro.serve import backend
    orig = backend.sample_tokens_guarded

    def shifted(logits, temps, topks, keys, vocab):
        toks, new_keys, peak = orig(logits, temps, topks, keys, vocab)
        return (toks + 1) % vocab, new_keys, peak
    monkeypatch.setattr(backend, "sample_tokens_guarded", shifted)


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
def test_hybrid_fault_is_caught(monkeypatch, fault):
    fault(monkeypatch)
    cfg, mix = _cell()
    out = run_smoke(cfg, mix, LIMITS, seconds=2.0)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > LIMITS["max_logit_gap"]


def _record(prompt, output, start, end):
    return types.SimpleNamespace(spec={"prompt": prompt, "output": output},
                                 times=[start, end], finished=end)


@pytest.mark.parametrize("want", [1, 3, 8])
def test_sample_holds_requests_live_at_one_instant(want):
    fam = common.load_module("families", "hybrid_lm")
    longest = _record(900, 400, 5.0, 30.0)
    live = [_record(100, 50, 1.0 + i, 6.0 + i) for i in range(4)]
    # ended before, or started after, the longest one's first token
    apart = [_record(100, 50, 0.0, 4.0), _record(100, 50, 5.5, 9.0)]
    done = apart[:1] + live[:2] + [longest] + live[2:] + apart[1:]
    # live then too, but ended before the window: ok, not done
    ramp = _record(200, 60, 2.0, 5.0)
    pick = fam.co_resident(done, done + [ramp], want,
                           np.random.default_rng(7))
    assert pick[0] is longest
    assert len(pick) == min(want, 6)
    assert len({id(r) for r in pick}) == len(pick)
    assert all(r is longest or r in live + [ramp] for r in pick)


def test_readers_needed_work_by_hand():
    cfg = common.load_json(common.named_file("configs",
                                             "granite_4_0_h_micro"))
    ssd = common.load_module("metrics", "ssd_prefill_roofline")
    # 300 tokens: chunks of 256 and 44, causal pairs 32896 and 990;
    # C B^T 2 x pairs x 128; per head 2 x pairs x 64 + 4 x q x 128 x 64
    flops = (2 * 32896 * 128 + 64 * (2 * 32896 * 64 + 4 * 256 * 128 * 64)
             + 2 * 990 * 128 + 64 * (2 * 990 * 64 + 4 * 44 * 128 * 64))
    # x and y bf16, B and C bf16, dt f32, state in and out f32
    bytes_ = (2 * 300 * 4096 * 2 + 2 * 300 * 128 * 2 + 300 * 64 * 4
              + 2 * 64 * 64 * 128 * 4)
    assert ssd.needed(cfg, 300) == (flops, bytes_)

    hbm = common.load_module("metrics", "decode_hbm_roofline.hybrid")
    # the program's parameter count at published widths, in bf16
    assert hbm.weight_bytes(cfg) == 2 * 3_191_396_096
    # per slot: 36 x (64 x 64 x 128 f32 + 3 x (4096 + 256) bf16)
    assert hbm.state_bytes(cfg) == 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
    # state read and written; K and V of 101 positions in 4 layers
    assert hbm.token_bytes(cfg, 100) == (2 * hbm.state_bytes(cfg)
                                         + 4 * 2 * 8 * 64 * 2 * 101)

    paged = common.load_module("metrics", "paged_decode_roofline.hybrid")
    # 32 query heads of 64 over 1000 keys; K and V of 8 kv heads, q and o
    assert paged.needed(cfg, 1000) == (4 * 1000 * 32 * 64,
                                       (2 * 1000 * 8 * 64 + 2 * 32 * 64) * 2)

    mfu = common.load_module("metrics", "mfu.decode.hybrid")
    # matmul weights: Mamba in_proj 2048 x (2 x 4096 + 2 x 128 + 64) and
    # out_proj 4096 x 2048; attention q, k, v, o 2048 x (32 + 16) x 64 and
    # 2048 x 2048; the MLP 3 x 2048 x 8192 on all 40 layers
    mats = (36 * (2048 * 8512 + 4096 * 2048)
            + 4 * (2048 * 48 * 64 + 2048 * 2048) + 40 * 3 * 2048 * 8192)
    assert mats == 2_984_771_584
    head = 2 * 100352 * 2048
    conv = 2 * 4 * (4096 + 2 * 128)
    state = 5 * 64 * 64 * 128
    assert mfu.decode_flops(cfg, 1000) == (
        2 * mats + head + 4 * (4 * 1000 * 32 * 64) + 36 * (state + conv))
    pairs = 300 * 301 // 2
    assert mfu.prefill_flops(cfg, 300) == (
        2 * mats * 300 + head + 4 * (4 * pairs * 32 * 64)
        + 36 * (flops + conv * 300))


@pytest.mark.parametrize("mix,prompt,output", [
    ("doc_qa", (1024, 256, 2048), (384, 128, 1024)),
    ("decode_saturated", (160, 64, 256), (256, 128, 512)),
])
def test_new_cells_traffic(mix, prompt, output):
    m = common.load_json(common.named_file("traffic", mix))
    assert m["loop"] == "closed" and m["sampling"] == "greedy"
    for size, (median, lo, hi) in (("prompt", prompt), ("output", output)):
        spec = m["sizes"][size]
        assert (spec["median"], spec["min"], spec["max"]) == (median, lo, hi)
        vals = sorted(traffic.stratified(spec, 4001))
        assert vals[0] >= lo and vals[-1] <= hi
        assert abs(vals[2000] - median) <= 1
        # the lognormal's spread: its third quartile at median x exp(0.674 s)
        q3 = median * math.exp(0.6745 * spec["sigma"])
        assert abs(vals[3000] - q3) / q3 < 0.01
    assert (m["clients"], m["pool"]) == {"doc_qa": (64, 64),
                                         "decode_saturated": (16, 16)}[mix]
    assert len(traffic.requests(m, 2 ** 31 + 977)) == m["pool"]
