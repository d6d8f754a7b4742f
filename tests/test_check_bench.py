"""scripts/check_bench.py — the CI benchmark-regression gate must pass on
healthy inputs, demonstrably FAIL on synthetic regressed inputs (a gate
that can't fail isn't one), and support --update-baseline."""
import importlib.util
import json
import os

import pytest

_SPEC = importlib.util.spec_from_file_location(
    "check_bench",
    os.path.join(os.path.dirname(__file__), "..", "scripts",
                 "check_bench.py"))
check_bench = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(check_bench)


def _write(path, payload):
    with open(path, "w") as f:
        json.dump(payload, f)
    return str(path)


def _healthy_kernels(speedup=1.0):
    return {"dense_vs_factored": {"speedup": speedup, "seq_len": 512},
            "dense_vs_factored_sweep": [
                {"speedup": 0.8, "seq_len": 128},
                {"speedup": speedup, "seq_len": 512},
            ]}


def _healthy_serve(decode=2000.0, ratio=1.0, chunked_ratio=2.4,
                   prefix_ratio=3.2, guard_ratio=1.0):
    return {
        "points": [
            {"occupancy": 1, "decode_tokens_per_s": decode / 2,
             "prefill_tokens_per_s": 1.0},
            {"occupancy": 4, "decode_tokens_per_s": decode,
             "prefill_tokens_per_s": 1.0},
        ],
        "lazy_vs_whole": {"occupancy": 4, "ratio": ratio},
        "chunked_prefill": {"long_prompt": 128, "chunk": 16, "steps": 24,
                            "rounds": 3, "whole_p99_step_ms": 24.0,
                            "chunked_p99_step_ms": 24.0 / chunked_ratio,
                            "ratio": chunked_ratio},
        "prefix_sharing": {"n_requests": 64, "shared_prefix": 512,
                           "page_size": 64, "hit_rate": 0.98,
                           "outputs_identical": True,
                           "ratio": prefix_ratio},
        "guard_overhead": {"occupancy": 4, "page_size": 16,
                           "outputs_identical": True,
                           "ratio": guard_ratio},
    }


def _healthy_neural(dense_us=2000.0, flash_us=2000.0):
    return {"rows": [
        {"name": "table6_infer_dense_pairbias", "us_per_call": dense_us},
        {"name": "table6_infer_flashbias_neural", "us_per_call": flash_us},
        {"name": "unrelated_row", "us_per_call": 1.0},
    ]}


def _healthy_pairformer(ratio=1.2, cached_ratio=1.0):
    point = {"n_res": 384, "ratio": ratio, "cached_ratio": cached_ratio,
             "factored_step_ms": 1.0, "dense_step_ms": ratio,
             "cached_bias_step_ms": cached_ratio}
    return {"points": [
        {"n_res": 128, "ratio": 0.5, "cached_ratio": 0.5},  # small-N decoy
        point,
    ], "factored_vs_dense": point}


@pytest.fixture
def files(tmp_path):
    bdir = tmp_path / "baselines"
    bdir.mkdir()
    _write(bdir / check_bench.KERNELS_BASELINE, {"speedup": 1.0})
    _write(bdir / check_bench.SERVE_BASELINE,
           {"occupancy": 4, "decode_tokens_per_s": 2000.0})
    _write(bdir / check_bench.NEURAL_BASELINE, {"speedup": 1.0})
    _write(bdir / check_bench.PAIRFORMER_BASELINE, {"cached_ratio": 1.0})
    kernels = _write(tmp_path / "k.json", _healthy_kernels())
    serve = _write(tmp_path / "s.json", _healthy_serve())
    return tmp_path, str(bdir), kernels, serve


def _run(bdir, kernels, serve, *extra):
    return check_bench.main(["--kernels", kernels, "--serve", serve,
                             "--baseline-dir", bdir, *extra])


def test_healthy_inputs_pass(files):
    tmp, bdir, kernels, serve = files
    assert _run(bdir, kernels, serve) == 0
    # a drop inside the band also passes (smoke-noise tolerant)
    k2 = _write(tmp / "k2.json", _healthy_kernels(speedup=0.8))
    s2 = _write(tmp / "s2.json", _healthy_serve(decode=1500.0, ratio=0.85))
    assert _run(bdir, k2, s2) == 0


def test_regressed_speedup_fails(files):
    tmp, bdir, _, serve = files
    bad = _write(tmp / "bad_k.json", _healthy_kernels(speedup=0.5))
    assert _run(bdir, bad, serve) == 1


def test_regressed_serve_decode_fails(files):
    tmp, bdir, kernels, _ = files
    bad = _write(tmp / "bad_s.json", _healthy_serve(decode=900.0))
    assert _run(bdir, kernels, bad) == 1


def test_regressed_lazy_ratio_fails(files):
    tmp, bdir, kernels, _ = files
    bad = _write(tmp / "bad_r.json", _healthy_serve(ratio=0.5))
    assert _run(bdir, kernels, bad) == 1
    # inside the band passes (noise-tolerant), and --tolerance narrows it
    near = _write(tmp / "near_r.json", _healthy_serve(ratio=0.85))
    assert _run(bdir, kernels, near) == 0
    assert _run(bdir, kernels, near, "--tolerance", "0.05") == 1


def test_regressed_chunked_prefill_ratio_fails(files):
    """ISSUE 7 gate: chunked prefill degenerating into a monolithic
    prefill stall (whole/chunked p99 ratio ~1.0) must fail CI. The floor
    is structural (1.2, fixed), NOT tolerance-scaled — widening
    --tolerance must not save it."""
    tmp, bdir, kernels, _ = files
    bad = _write(tmp / "bad_c.json", _healthy_serve(chunked_ratio=1.0))
    assert _run(bdir, kernels, bad) == 1
    assert _run(bdir, kernels, bad, "--tolerance", "0.90") == 1
    healthy = _write(tmp / "ok_c.json", _healthy_serve(chunked_ratio=1.3))
    assert _run(bdir, kernels, healthy) == 0


def test_regressed_prefix_sharing_ratio_fails(files):
    """ISSUE 9 gate: shared-prefix admission that stops matching (cached/
    uncached throughput ratio ~1.0) must fail CI. Structural floor (2.0,
    fixed), NOT tolerance-scaled — widening --tolerance must not save
    it."""
    tmp, bdir, kernels, _ = files
    bad = _write(tmp / "bad_pf.json", _healthy_serve(prefix_ratio=1.0))
    assert _run(bdir, kernels, bad) == 1
    assert _run(bdir, kernels, bad, "--tolerance", "0.90") == 1
    healthy = _write(tmp / "ok_pf.json", _healthy_serve(prefix_ratio=2.2))
    assert _run(bdir, kernels, healthy) == 0


def test_regressed_guard_overhead_ratio_fails(files):
    """ISSUE 10 gate: NaN/Inf guards costing more than 5% of decode
    throughput (guarded/unguarded ratio < 0.95) must fail CI. Structural
    floor (0.95, fixed), NOT tolerance-scaled — widening --tolerance
    must not save it."""
    tmp, bdir, kernels, _ = files
    bad = _write(tmp / "bad_g.json", _healthy_serve(guard_ratio=0.90))
    assert _run(bdir, kernels, bad) == 1
    assert _run(bdir, kernels, bad, "--tolerance", "0.90") == 1
    healthy = _write(tmp / "ok_g.json", _healthy_serve(guard_ratio=0.96))
    assert _run(bdir, kernels, healthy) == 0


def test_serve_only_skips_kernels_gate(files, tmp_path):
    """--serve-only (the mesh-serve CI job) gates BENCH_serve.json without
    a kernels artifact on disk — and still fails on serve regressions."""
    tmp, bdir, _, serve = files
    missing = str(tmp_path / "no_such_kernels.json")
    assert check_bench.main(["--kernels", missing, "--serve", serve,
                             "--baseline-dir", bdir, "--serve-only"]) == 0
    bad = _write(tmp / "bad_so.json", _healthy_serve(chunked_ratio=1.0))
    assert check_bench.main(["--kernels", missing, "--serve", bad,
                             "--baseline-dir", bdir, "--serve-only"]) == 1


def test_headline_is_sweep_point_not_small_n():
    """The gated kernels headline must be the paper-scale sweep point —
    a small-N artifact (where factored legitimately loses) would weaken
    the gate to meaninglessness. Runs bench_kernels' actual
    headline-selection logic on a sweep whose small-N point both LEADS
    the list and has the bigger speedup, so any regression to
    first/last/best-point selection is caught."""
    from benchmarks.bench_kernels import headline_point
    sweep = [
        {"seq_len": 128, "speedup": 2.0},     # small-N decoy, listed first
        {"seq_len": 2048, "speedup": 1.1},    # paper scale: the headline
        {"seq_len": 512, "speedup": 1.5},
    ]
    assert headline_point(sweep) == sweep[1]


def test_tolerance_flag_widens_band(files):
    tmp, bdir, kernels, _ = files
    near = _write(tmp / "near.json", _healthy_serve(decode=1500.0))
    assert _run(bdir, kernels, near, "--tolerance", "0.10") == 1
    assert _run(bdir, kernels, near, "--tolerance", "0.30") == 0


def test_update_baseline_roundtrip(files, tmp_path):
    tmp, _, kernels, serve = files
    new_dir = str(tmp_path / "fresh")
    assert _run(new_dir, kernels, serve, "--update-baseline") == 0
    with open(os.path.join(new_dir, check_bench.SERVE_BASELINE)) as f:
        sb = json.load(f)
    assert sb == {"occupancy": 4, "decode_tokens_per_s": 2000.0}
    assert _run(new_dir, kernels, serve) == 0


def test_occupancy_mismatch_with_baseline_fails(files):
    """A bench whose highest measured occupancy no longer matches the
    committed baseline's occupancy is not comparable — fail loudly instead
    of comparing different workloads."""
    tmp, bdir, kernels, _ = files
    shrunk = _healthy_serve()
    shrunk["points"] = shrunk["points"][:1]      # occ 1 only
    s = _write(tmp / "occ1.json", shrunk)
    assert _run(bdir, kernels, s) == 1


def test_gates_highest_occupancy_point(files):
    """The serve gate reads the HIGHEST-occupancy point, not list order."""
    tmp, bdir, kernels, _ = files
    shuffled = _healthy_serve()
    shuffled["points"] = shuffled["points"][::-1]
    s = _write(tmp / "shuf.json", shuffled)
    occ, tps = check_bench.serve_decode_point(json.load(open(s)))
    assert (occ, tps) == (4, 2000.0)
    assert _run(bdir, kernels, s) == 0


def test_neural_gate_opt_in(files):
    """--neural enables the Table 6 speedup gate: healthy passes, a
    regressed flash path fails, and omitting the flag skips the gate
    entirely (even with a regressed file on disk)."""
    tmp, bdir, kernels, serve = files
    good = _write(tmp / "n.json", _healthy_neural())
    assert _run(bdir, kernels, serve, "--neural", good) == 0
    bad = _write(tmp / "bad_n.json", _healthy_neural(flash_us=5000.0))
    assert _run(bdir, kernels, serve, "--neural", bad) == 1
    assert _run(bdir, kernels, serve) == 0  # flag absent -> gate skipped


def test_pairformer_headline_gate(files):
    """--pairformer gates the factored-vs-official-recompute ratio of the
    LARGEST-n_res point at >= 1 - tolerance; a factored path slower than
    the recompute dataflow fails CI."""
    tmp, bdir, kernels, serve = files
    good = _write(tmp / "p.json", _healthy_pairformer())
    assert _run(bdir, kernels, serve, "--pairformer", good) == 0
    bad = _write(tmp / "bad_p.json", _healthy_pairformer(ratio=0.5))
    assert _run(bdir, kernels, serve, "--pairformer", bad) == 1
    assert _run(bdir, kernels, serve) == 0  # flag absent -> gate skipped


def test_pairformer_cached_ratio_tripwire(files):
    """The cached_ratio gate compares against its committed baseline — a
    drop beyond tolerance (e.g. the factored step silently materializing
    the dense bias) fails even when the headline ratio stays healthy."""
    tmp, bdir, kernels, serve = files
    bad = _write(tmp / "trip.json", _healthy_pairformer(cached_ratio=0.5))
    assert _run(bdir, kernels, serve, "--pairformer", bad) == 1
    near = _write(tmp / "near_p.json", _healthy_pairformer(cached_ratio=0.8))
    assert _run(bdir, kernels, serve, "--pairformer", near) == 0
    assert _run(bdir, kernels, serve, "--pairformer", near,
                "--tolerance", "0.05") == 1


def test_pairformer_headline_is_largest_n_res():
    """The gated point is factored_vs_dense — bench_pairformer pins it to
    the largest-n_res sweep point, not the small-N decoy where the
    factored path legitimately loses on CPU."""
    head = check_bench.pairformer_headline(_healthy_pairformer())
    assert head["n_res"] == 384
    assert head["ratio"] == pytest.approx(1.2)


def test_schema_missing_gated_key_fails(files):
    """A bench that silently drops a gated key (here: lazy_vs_whole loses
    'ratio') must fail the schema gate loudly, not pass vacuously or die
    in a KeyError mid-check."""
    tmp, bdir, kernels, _ = files
    broken = _healthy_serve()
    del broken["lazy_vs_whole"]["ratio"]
    s = _write(tmp / "noratio.json", broken)
    assert _run(bdir, kernels, s) == 1
    errs = check_bench.schema_errors("serve", broken)
    assert errs == ["serve: missing required key path 'lazy_vs_whole.ratio'"]


def test_schema_empty_points_fails(files):
    """An empty sweep satisfies max()-free code paths nowhere — 'points[]'
    requires a non-empty list with the gated keys on every element."""
    tmp, bdir, kernels, _ = files
    broken = _healthy_serve()
    broken["points"] = []
    s = _write(tmp / "nopoints.json", broken)
    assert _run(bdir, kernels, s) == 1
    partial = _healthy_serve()
    del partial["points"][0]["decode_tokens_per_s"]
    s2 = _write(tmp / "partial.json", partial)
    assert _run(bdir, kernels, s2) == 1


def test_schema_named_row_missing_fails(files):
    """--neural schema pins the two Table 6 rows by NAME: a rename breaks
    the gate's row lookup, so it must fail at validation."""
    tmp, bdir, kernels, serve = files
    broken = _healthy_neural()
    broken["rows"][1]["name"] = "table6_infer_flashbias_renamed"
    n = _write(tmp / "renamed.json", broken)
    assert _run(bdir, kernels, serve, "--neural", n) == 1
    errs = check_bench.schema_errors("neural", broken)
    assert len(errs) == 1 and "table6_infer_flashbias_neural" in errs[0]


def test_schema_kernels_missing_sweep_fails(files):
    tmp, bdir, _, serve = files
    broken = _healthy_kernels()
    del broken["dense_vs_factored_sweep"]
    k = _write(tmp / "nosweep.json", broken)
    assert _run(bdir, k, serve) == 1


def test_schema_validates_before_update_baseline(files, tmp_path):
    """--update-baseline must not commit baselines read from a malformed
    bench file."""
    tmp, _, kernels, _ = files
    broken = _healthy_serve()
    del broken["chunked_prefill"]
    s = _write(tmp / "nochunk.json", broken)
    new_dir = str(tmp_path / "fresh_schema")
    assert _run(new_dir, kernels, s, "--update-baseline") == 1
    assert not os.path.exists(os.path.join(new_dir, check_bench.SERVE_BASELINE))


def test_schema_healthy_payloads_clean():
    for suite, payload in (
        ("kernels", _healthy_kernels()),
        ("serve", _healthy_serve()),
        ("neural", _healthy_neural()),
        ("pairformer", _healthy_pairformer()),
    ):
        assert check_bench.schema_errors(suite, payload) == []


def test_update_baseline_writes_opt_in_files(files, tmp_path):
    """--update-baseline with the opt-in flags also refreshes the neural
    and pairformer baselines; without the flags it leaves them unwritten."""
    tmp, _, kernels, serve = files
    new_dir = str(tmp_path / "fresh_opt")
    n = _write(tmp / "n_up.json", _healthy_neural(dense_us=3000.0))
    p = _write(tmp / "p_up.json", _healthy_pairformer(cached_ratio=0.9))
    assert _run(new_dir, kernels, serve, "--neural", n, "--pairformer", p,
                "--update-baseline") == 0
    with open(os.path.join(new_dir, check_bench.NEURAL_BASELINE)) as f:
        assert json.load(f) == {"speedup": 1.5}
    with open(os.path.join(new_dir, check_bench.PAIRFORMER_BASELINE)) as f:
        assert json.load(f) == {"cached_ratio": 0.9}
    assert _run(new_dir, kernels, serve, "--neural", n,
                "--pairformer", p) == 0
    bare_dir = str(tmp_path / "fresh_bare")
    assert _run(bare_dir, kernels, serve, "--update-baseline") == 0
    assert not os.path.exists(
        os.path.join(bare_dir, check_bench.NEURAL_BASELINE))
    assert not os.path.exists(
        os.path.join(bare_dir, check_bench.PAIRFORMER_BASELINE))
