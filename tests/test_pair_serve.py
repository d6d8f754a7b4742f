"""Batched Pairformer serving (ISSUE 6, paper Sec. 4.4): a request is one
complex, admission caches its pair-bias factors per slot, every step is one
refinement iteration over the padded slot batch. The contract under test:

- batched == solo, to f32 rounding: per-slot computation is batch-row
  independent and padding is pinned at max_len, so a complex's result is
  the same whether it shares the batch with strangers or runs alone (the
  two programs' reduction orders differ, so not bitwise);
- the factor cache is admission-frozen: steps reuse phi_q/phi_k untouched
  (the Pairformer analogue of the LM KV cache);
- the cached-dense and official-recompute dense dataflows are the same
  math (BENCH_pairformer's baselines measure representation cost only);
- priority classes order admission and pick preemption victims, and the
  all-default case is bit-identical to the classless engine.
"""
import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import get_model
from repro.models import pairformer as pf_mod
from repro.models.common import init_params, stack_layers
from repro.serve import FIFOScheduler, PairBatchBackend, Request, ServeEngine
from repro.serve.lifecycle import AdmissionRejected

MAX_LEN = 16      # pinned residue padding: results must not depend on wave
                  # composition, so the one wave-dependent shape is fixed


def _model(**overrides):
    cfg = smoke_config("pairformer_lite")
    if overrides:
        cfg = cfg.replace(**overrides)
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    return cfg, model, params


def _complexes(lens, f=64, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.standard_normal((n, f)).astype(np.float32) for n in lens]


# Batched and solo runs are different programs (other batch shapes, other
# reduction orders): on CPU their outputs (|x| ~0.7) differ by <= 1.5e-7,
# so results are held to 10x that, not bitwise.
BATCH_ATOL = 1.5e-6


def _alone(model, params, feats, budget, **kw):
    eng = ServeEngine(model, params, max_len=MAX_LEN, n_slots=1, **kw)
    rid = eng.submit(feats, budget)
    eng.run()
    return eng.result(rid)


def test_batched_matches_single_complex_runs():
    """5 variable-length complexes through 2 slots, arriving mid-flight and
    finishing at different steps (budgets differ). Every result must
    match the same complex served alone, to BATCH_ATOL."""
    cfg, model, params = _model()
    complexes = _complexes((12, 7, 16, 9, 5))
    budgets = [3, 5, 2, 4, 3]

    eng = ServeEngine(model, params, max_len=MAX_LEN, n_slots=2)
    r0 = eng.submit(complexes[0], budgets[0])
    r1 = eng.submit(complexes[1], budgets[1])
    eng.step()
    r2 = eng.submit(complexes[2], budgets[2])        # mid-flight arrivals
    eng.step()
    r3 = eng.submit(complexes[3], budgets[3])
    r4 = eng.submit(complexes[4], budgets[4])
    eng.run()
    assert eng.occupancy == 0 and eng.page_stats() == {}

    for i, rid in enumerate((r0, r1, r2, r3, r4)):
        assert eng.is_done(rid)
        got = eng.result(rid)
        assert got.shape == (complexes[i].shape[0], cfg.d_model)
        ref = _alone(model, params, complexes[i], budgets[i])
        np.testing.assert_allclose(got, ref, rtol=0, atol=BATCH_ATOL)


def test_factor_cache_frozen_across_steps():
    """Admission writes the per-layer SVD factors once; refinement steps
    reuse them bitwise-untouched while the single rep advances — the
    factor cache never recomputes (that IS the serving claim)."""
    _, model, params = _model()
    eng = ServeEngine(model, params, max_len=MAX_LEN, n_slots=2)
    for c in _complexes((11, 8)):
        eng.submit(c, 6)
    eng.admit()
    cache = eng.backend._cache
    assert "phi_q" in cache and "phi_k" in cache      # svd factor mode
    phi_q0 = np.asarray(cache["phi_q"]).copy()
    phi_k0 = np.asarray(cache["phi_k"]).copy()
    s_prev = np.asarray(cache["s"]).copy()
    for _ in range(3):
        eng.decode()
        cache = eng.backend._cache
        np.testing.assert_array_equal(np.asarray(cache["phi_q"]), phi_q0)
        np.testing.assert_array_equal(np.asarray(cache["phi_k"]), phi_k0)
        s_now = np.asarray(cache["s"])
        assert np.isfinite(s_now).all()
        assert not np.array_equal(s_now, s_prev)      # rep is refined
        s_prev = s_now.copy()


def test_dense_cached_and_recompute_paths_agree():
    """``bias_mode="dense"`` (bias cached at admission) and
    ``"dense_recompute"`` (the official AF3 dataflow: z cached, bias
    re-projected per step) are the same math in a different place — the
    bench's two dense baselines must price the SAME numbers."""
    _, model_c, params = _model(bias_mode="dense")
    _, model_r, _ = _model(bias_mode="dense_recompute")
    feats = _complexes((13,), seed=3)[0]
    got_c = _alone(model_c, params, feats, 4)
    got_r = _alone(model_r, params, feats, 4)
    np.testing.assert_array_equal(got_c, got_r)


def test_full_rank_svd_matches_dense_serve():
    """Sec. 4.3: with rank >= n_res the truncated SVD is exact, so the
    factored serve path reproduces the dense-bias serve path."""
    _, model_f, params = _model()                    # svd, bias_rank=8
    _, model_d, _ = _model(bias_mode="dense")
    feats = _complexes((7,), seed=4)[0]              # n_res 7 < rank 8
    got_f = _alone(model_f, params, feats, 3)
    got_d = _alone(model_d, params, feats, 3)
    np.testing.assert_allclose(got_f, got_d, atol=1e-4)


def test_factor_mlp_cache_serves_batched():
    """Eq. 5 factor-MLP mode: fitted (here randomly initialised — the
    contract is structural) factor params ride ``factors=`` into the
    engine; the cache holds MLP factors at the full configured rank and
    batched results still match solo, to BATCH_ATOL."""
    cfg, model, params = _model()
    fp = init_params(stack_layers(pf_mod.factor_mlp_template(cfg, hidden=16),
                                  cfg.n_layers), jax.random.PRNGKey(5))
    complexes = _complexes((10, 6), seed=6)
    eng = ServeEngine(model, params, max_len=MAX_LEN, n_slots=2, factors=fp)
    rids = [eng.submit(c, 3) for c in complexes]
    eng.run()
    assert eng.backend._cache["phi_q"].shape[-1] == cfg.bias_rank
    for c, rid in zip(complexes, rids):
        ref = _alone(model, params, c, 3, factors=fp)
        np.testing.assert_allclose(eng.result(rid), ref, rtol=0,
                                   atol=BATCH_ATOL)


def test_pair_request_validation():
    _, model, params = _model()
    eng = ServeEngine(model, params, max_len=MAX_LEN, n_slots=2)
    with pytest.raises(AdmissionRejected):           # int prompt payload
        eng.submit(np.arange(5, dtype=np.int32), 3)
    with pytest.raises(AdmissionRejected):           # exceeds max_len
        eng.submit(np.zeros((MAX_LEN + 1, 64), np.float32), 3)
    with pytest.raises(TypeError):                   # token-emitting API
        eng.generate([np.zeros((4, 64), np.float32)], 3)
    assert isinstance(eng.backend, PairBatchBackend)


def test_on_token_streams_per_refinement_step():
    """The pair backend emits no tokens, so ``submit(on_token=...)``
    drains the per-step (n_res, d_model) state instead: one callback per
    refinement iteration, and the final drained state IS the result."""
    cfg, model, params = _model()
    feats = _complexes((9,), seed=9)[0]
    eng = ServeEngine(model, params, max_len=MAX_LEN, n_slots=1)
    steps = []
    rid = eng.submit(feats, 4, on_token=steps.append)
    eng.run()
    assert len(steps) == 4                            # one per iteration
    assert all(s.shape == (9, cfg.d_model) for s in steps)
    assert not np.array_equal(steps[0], steps[-1])    # rep is refined
    np.testing.assert_array_equal(steps[-1], eng.result(rid))


def test_priority_classes_order_admission():
    """Higher class admits first regardless of arrival; within a class the
    policy is untouched FIFO — and with all-default priorities the order
    is bit-identical to the classless scheduler."""
    sched = FIFOScheduler()
    feats = np.zeros((4, 8), np.float32)
    for rid, pri in enumerate((0, 5, 0, 5, -1)):
        sched.add(Request(rid, feats, 1, priority=pri))
    assert [r.rid for r in sched.take(5)] == [1, 3, 0, 2, 4]

    sched = FIFOScheduler()                           # all-default: FIFO
    for rid in range(4):
        sched.add(Request(rid, feats, 1))
    assert [r.rid for r in sched.take(4)] == [0, 1, 2, 3]

    sched = FIFOScheduler(policy="spf")               # class outranks length
    sched.add(Request(0, np.zeros((2, 8), np.float32), 1, priority=0))
    sched.add(Request(1, np.zeros((9, 8), np.float32), 1, priority=1))
    sched.add(Request(2, np.zeros((4, 8), np.float32), 1, priority=1))
    assert [r.rid for r in sched.take(3)] == [2, 1, 0]


def test_add_front_orders_resumed_requests_by_class():
    """Preempted requests resume ahead of every arrival; within the front
    queue higher classes stay ahead and earlier rids break ties."""
    sched = FIFOScheduler()
    feats = np.zeros((4, 8), np.float32)
    sched.add(Request(9, feats, 1, priority=7))       # queued arrival
    sched.add_front(Request(2, feats, 1, priority=0))
    sched.add_front(Request(1, feats, 1, priority=3))
    sched.add_front(Request(3, feats, 1, priority=3))
    assert [r.rid for r in sched.take(4)] == [1, 3, 2, 9]


def test_preemption_victim_is_lowest_class_then_latest():
    """The engine evicts the lowest class first, latest arrival within it;
    the preempted complex restarts with its full budget and its final
    result still matches the solo run (nothing incremental was lost)."""
    _, model, params = _model()
    complexes = _complexes((9, 11, 6), seed=7)
    eng = ServeEngine(model, params, max_len=MAX_LEN, n_slots=3)
    rids = [eng.submit(c, 4, priority=p)
            for c, p in zip(complexes, (2, 0, 1))]
    eng.admit()
    eng.decode()
    assert eng.preempt() == rids[1]                   # class 0 evicts first
    assert eng.preempt() == rids[2]                   # then class 1
    assert eng.n_preemptions == 2 and eng.occupancy == 1
    eng.run()
    for c, rid in zip(complexes, rids):
        np.testing.assert_array_equal(eng.result(rid),
                                      _alone(model, params, c, 4))


def test_default_priority_victim_matches_pre_class_engine():
    """All-default priorities: the victim is the latest-arrived live
    request, exactly the pre-class policy."""
    _, model, params = _model()
    eng = ServeEngine(model, params, max_len=MAX_LEN, n_slots=2)
    rids = [eng.submit(c, 3) for c in _complexes((8, 5), seed=8)]
    eng.admit()
    assert eng.preempt() == rids[1]


def test_bf16_pair_rep_with_f32_weights():
    """The full config computes in bf16 while its weights may stay f32: the
    pair rep must keep its dtype across the layer scan (a carry promoted to
    f32 by the triangle update or pair transition fails to trace)."""
    cfg, model, params = _model(dtype="bfloat16")
    out = _alone(model, params, _complexes([12])[0], 2)
    assert out.status == "OK" and np.isfinite(np.asarray(out)).all()
