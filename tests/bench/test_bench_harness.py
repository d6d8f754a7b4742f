"""A whole run of each family at SMOKE size on the CPU through the
program's ServeEngine (Pallas in interpret mode): every metric reader
finds its numbers, nothing compiles in the window, and the served output
agrees with the plain reference."""
from conftest import run_smoke

LM_BENCH = {
    "end_to_end": [{"name": n, "unit": u} for n, u in (
        ("out_tok_per_s", "tokens/s"), ("itl_p99_ms", "ms"),
        ("setup_s", "s"))],
    "per_layer": [{"name": n, "unit": "%", "moves": "out_tok_per_s"}
                  for n in ("mfu.decode", "slot_occupancy")],
}
PAIR_BENCH = {
    "end_to_end": [{"name": n, "unit": u} for n, u in (
        ("complexes_per_s", "complexes/s"), ("setup_s", "s"))],
    "per_layer": [{"name": n, "unit": "%", "moves": "complexes_per_s"}
                  for n in ("mfu.pair",)],
}


def test_lm_cell_runs_and_agrees(smoke):
    cfg, mix = smoke("lm")
    # room for the 100 gaps a 99th percentile needs while other test
    # workers share the CPU
    out = run_smoke(cfg, mix, {"max_logit_gap": 0.05, "sample_tokens": 40},
                    seconds=8.0, bench=LM_BENCH)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > mix["clients"]
    assert out["compiles_in_window"] == 0
    assert set(out["metrics"]) == {"out_tok_per_s", "itl_p99_ms", "setup_s"}
    assert out["metrics"]["out_tok_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["max_logit_gap"]["value"] <= 0.05


def test_lm_cell_traced_reads_per_layer(smoke):
    cfg, mix = smoke("lm")
    out = run_smoke(cfg, mix, {"max_logit_gap": 0.05, "sample_tokens": 16},
                    traced=True, bench=LM_BENCH)
    assert set(out["metrics"]) == {"mfu.decode", "slot_occupancy"}
    assert 0 < out["metrics"]["slot_occupancy"]["value"] <= 100
    assert "breakdown" in out and "window_s" in out["device"]


def test_pair_cell_runs_and_agrees(smoke):
    cfg, mix = smoke("pairformer")
    out = run_smoke(cfg, mix, {"max_bias_rel_err": 0.03,
                               "max_trunk_rel_err": 0.05,
                               "max_step_rel_err": 0.02,
                               "sample_complexes": 2}, bench=PAIR_BENCH)
    assert out["correct"] is True
    assert out["compiles_in_window"] == 0
    assert out["metrics"]["complexes_per_s"]["value"] > 0
    assert out["checks"]["max_step_rel_err"]["value"] <= 0.02
    assert out["checks"]["max_trunk_rel_err"]["value"] <= 0.05
