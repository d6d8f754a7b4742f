"""Fault tolerance (ISSUE 10): request lifecycle, guard + quarantine
containment, deterministic fault injection, crash-safe engine
checkpoint/restore, and randomized chaos drills.

The invariants under test: a fault never crashes the engine or leaks
pool pages; a quarantined request retries BIT-IDENTICALLY from its
preemption snapshot (greedy and sampled) up to ``max_retries`` and then
fails with a structured error; a poisoned slot's written prefix pages
leave the index; ``snapshot_engine``/``restore_engine`` round-trips the
whole host state through JSON and resumes every cache family
bit-identically.

The CI ``chaos`` job re-runs this file under a FAULT_SEED matrix; the
randomized drill below keys its plan and traffic off that seed.
"""
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import get_model
from repro.models.common import init_params
from repro.serve import (
    CANCELLED, FAILED, OK, QUEUED, REJECTED, RUNNING, TERMINAL_STATUSES,
    TIMED_OUT, AdmissionRejected, EngineStalled, FaultPlan, FaultSpec,
    RequestNotLive, RequestRecord, SamplingParams, ServeEngine)

FAULT_SEED = int(os.environ.get("FAULT_SEED", "0"))


def _model(arch):
    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab, (n,)).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# FaultPlan / FaultSpec units
# ---------------------------------------------------------------------------

class TestFaultPlan:
    def test_parse_grammar_and_roundtrip(self):
        plan = FaultPlan.parse("nan@12/0, alloc@5x3, step@20, delay@1/2x2")
        assert plan.specs == (FaultSpec("nan", 12, slot=0),
                              FaultSpec("alloc", 5, count=3),
                              FaultSpec("step", 20),
                              FaultSpec("delay", 1, slot=2, count=2))
        assert FaultPlan.parse(plan.spec_str()).specs == plan.specs
        assert FaultPlan.parse(None).specs == ()
        assert FaultPlan.parse("  ").specs == ()

    def test_bad_specs_rejected(self):
        with pytest.raises(ValueError, match="bad fault spec"):
            FaultPlan.parse("nan12")
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultPlan.parse("oom@3")
        with pytest.raises(ValueError, match="count >= 1"):
            FaultSpec("nan", 2, count=0)

    def test_queries_follow_tick_and_log_firings(self):
        plan = FaultPlan.parse("alloc@2x2,nan@3/1,delay@9")
        plan.tick(1)
        assert not plan.alloc_fails() and plan.nan_slots() == []
        plan.tick(2)
        assert plan.alloc_fails() and not plan.step_fails()
        plan.tick(3)
        assert plan.alloc_fails() and plan.nan_slots() == [1]
        plan.tick(4)
        assert not plan.alloc_fails()
        assert plan.fired == [(2, "alloc", -1), (3, "alloc", -1),
                              (3, "nan", 1)]

    def test_random_plans_are_seed_deterministic(self):
        a = FaultPlan.random(7, n_steps=40, n_slots=4)
        b = FaultPlan.random(7, n_steps=40, n_slots=4)
        assert a.specs == b.specs
        assert a.specs != FaultPlan.random(8, n_steps=40, n_slots=4).specs


def test_request_record_is_an_ndarray_with_status():
    rec = RequestRecord(np.arange(4, dtype=np.int32), status=FAILED,
                        error={"kind": "guard"})
    np.testing.assert_array_equal(rec, [0, 1, 2, 3])
    assert rec.status == FAILED and rec.error == {"kind": "guard"}
    assert rec.size == 4 and isinstance(rec.tokens, np.ndarray)
    ok = RequestRecord(np.zeros(2, np.int32))
    assert ok.status == OK and ok.error is None


# ---------------------------------------------------------------------------
# Request lifecycle: statuses, cancel, deadlines, rejection
# ---------------------------------------------------------------------------

class TestLifecycle:
    def test_status_walk_and_counts(self):
        cfg, model, params = _model("stablelm_12b")
        eng = ServeEngine(model, params, max_len=32, n_slots=2)
        rid = eng.submit(_prompts(cfg, (5,))[0], 4)
        assert eng.status(rid) == QUEUED
        eng.step()
        assert eng.status(rid) == RUNNING
        eng.run()
        rec = eng.result(rid)
        assert eng.status(rid) == OK and eng.is_done(rid)
        assert rec.status == OK and rec.error is None and rec.size == 4
        assert eng.status_counts() == {OK: 1}

    def test_unknown_rid_is_typed(self):
        _, model, params = _model("stablelm_12b")
        eng = ServeEngine(model, params, max_len=32, n_slots=1)
        for fn in (eng.result, eng.status, eng.is_done, eng.cancel):
            with pytest.raises(RequestNotLive, match="unknown request"):
                fn(99)

    def test_cancel_queued_and_live(self):
        cfg, model, params = _model("stablelm_12b")
        eng = ServeEngine(model, params, max_len=48, n_slots=1,
                          page_size=4, n_pages=12)
        a, b = (eng.submit(p, 20) for p in _prompts(cfg, (5, 6)))
        eng.step()                           # a live, b queued behind it
        assert eng.status(a) == RUNNING and eng.status(b) == QUEUED
        assert eng.cancel(b) is True         # queued cancel: just dequeue
        assert eng.status(b) == CANCELLED and eng.result(b).size == 0
        assert eng.cancel(a) is True         # live cancel: frees the slot
        assert eng.status(a) == CANCELLED and eng.occupancy == 0
        assert eng.result(a).size >= 1       # partial output retained
        assert eng.backend._pool.n_free == eng.n_pages   # pages drained
        assert eng.cancel(a) is False        # terminal: too late
        eng.run()                            # drains trivially
        assert eng.status_counts() == {CANCELLED: 2}

    def test_deadline_times_out_live_and_queued(self):
        cfg, model, params = _model("stablelm_12b")
        eng = ServeEngine(model, params, max_len=48, n_slots=1)
        pa, pb = _prompts(cfg, (5, 6))
        a = eng.submit(pa, 30, deadline_steps=3)
        b = eng.submit(pb, 4, deadline_steps=2)     # starves behind a
        eng.run()
        ra, rb = eng.result(a), eng.result(b)
        assert ra.status == TIMED_OUT and 1 <= ra.size < 30  # partial kept
        assert ra.error["kind"] == "deadline"
        assert rb.status == TIMED_OUT and rb.size == 0
        assert eng.occupancy == 0

    def test_rejection_strict_raises_lax_records(self):
        cfg, model, params = _model("stablelm_12b")
        eng = ServeEngine(model, params, max_len=16, n_slots=1)
        long = _prompts(cfg, (14,))[0]
        with pytest.raises(AdmissionRejected, match="contiguous mode"):
            eng.submit(long, 10)
        rid = eng.submit(long, 10, strict=False)
        rec = eng.result(rid)
        assert rec.status == REJECTED and rec.size == 0
        assert rec.error["kind"] == "admission"
        assert "contiguous mode" in rec.error["detail"]
        assert len(eng.scheduler) == 0       # never queued
        ok = eng.submit(long[:4], 3, strict=False)   # rid sequence intact
        assert ok == rid + 1
        eng.run()
        assert eng.result(ok).status == OK

    def test_submit_knob_validation(self):
        _, model, params = _model("stablelm_12b")
        eng = ServeEngine(model, params, max_len=16, n_slots=1)
        with pytest.raises(ValueError, match="deadline_steps"):
            eng.submit(np.arange(3), 2, deadline_steps=0)
        with pytest.raises(ValueError, match="max_retries"):
            eng.submit(np.arange(3), 2, max_retries=-1)
        with pytest.raises(ValueError, match="stall_limit"):
            ServeEngine(model, params, max_len=16, stall_limit=0)


def test_stall_guard_raises_with_diagnostics():
    cfg, model, params = _model("stablelm_12b")
    eng = ServeEngine(model, params, max_len=32, n_slots=1,
                      faults=FaultPlan.parse("delay@0x500"), stall_limit=5)
    eng.submit(_prompts(cfg, (5,))[0], 3)
    with pytest.raises(EngineStalled, match="no progress for 5"):
        eng.run()


def test_admission_delay_fault_only_defers():
    cfg, model, params = _model("stablelm_12b")
    plan = FaultPlan.parse("delay@0x3")
    eng = ServeEngine(model, params, max_len=32, n_slots=1, faults=plan)
    rid = eng.submit(_prompts(cfg, (5,))[0], 3)
    eng.run()
    assert eng.result(rid).status == OK
    assert [f[1] for f in plan.fired] == ["delay"] * 3


# ---------------------------------------------------------------------------
# Guard trips, quarantine, containment
# ---------------------------------------------------------------------------

def _paged(model, params, faults=None, **kw):
    kw.setdefault("max_len", 48)
    kw.setdefault("n_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("n_pages", 24)
    return ServeEngine(model, params, faults=faults, **kw)


class TestQuarantine:
    def test_nan_injection_retries_bit_identically(self):
        """One injected NaN step: the poisoned request quarantines, its
        snapshot resumes, and EVERY output — greedy and sampled — equals
        the fault-free run's."""
        cfg, model, params = _model("stablelm_12b")
        prompts = _prompts(cfg, (6, 9))
        samplings = [SamplingParams(0.0, 0, seed=0),
                     SamplingParams(1.0, 0, seed=1)]

        def run(faults):
            eng = _paged(model, params, faults=faults)
            rids = [eng.submit(p, 8, sampling=s)
                    for p, s in zip(prompts, samplings)]
            eng.run()
            return eng, [eng.result(r) for r in rids]

        ref_eng, ref = run(None)
        plan = FaultPlan.parse("nan@3/0")
        eng, got = run(plan)
        assert (3, "nan", 0) in plan.fired
        assert eng.n_quarantines == 1
        assert eng.page_stats()["quarantines"] == 1
        for g, w in zip(got, ref):
            assert g.status == OK
            np.testing.assert_array_equal(g, w)
        assert eng.backend._pool.n_free == eng.n_pages      # nothing leaked

    def test_retries_exhaust_to_failed_with_structured_error(self):
        cfg, model, params = _model("stablelm_12b")
        prompts = _prompts(cfg, (6, 9))
        # slot 0 is poisoned for many consecutive steps: every retry
        # re-faults until max_retries is spent
        plan = FaultPlan.parse("nan@2/0x30")
        eng = _paged(model, params, faults=plan)
        a = eng.submit(prompts[0], 8, max_retries=1)
        b = eng.submit(prompts[1], 8)
        eng.run()
        ra = eng.result(a)
        assert ra.status == FAILED
        assert ra.error["kind"] == "guard" and ra.error["retries"] == 1
        assert "non-finite" in ra.error["detail"]
        assert eng.result(b).status == OK           # neighbor unharmed
        assert eng.n_quarantines == 2               # initial trip + retry
        assert eng.backend._pool.n_free == eng.n_pages

    def test_zero_retries_fails_on_first_trip(self):
        cfg, model, params = _model("stablelm_12b")
        plan = FaultPlan.parse("nan@2/0")
        eng = _paged(model, params, faults=plan, n_slots=1)
        rid = eng.submit(_prompts(cfg, (6,))[0], 6, max_retries=0)
        eng.run()
        assert eng.result(rid).status == FAILED
        assert eng.n_quarantines == 1 and eng.n_preemptions == 0

    def test_guards_off_lets_poison_through(self):
        cfg, model, params = _model("stablelm_12b")
        plan = FaultPlan.parse("nan@2/0")
        eng = _paged(model, params, faults=plan, n_slots=1, guards=False)
        rid = eng.submit(_prompts(cfg, (6,))[0], 6)
        eng.run()
        rec = eng.result(rid)
        assert rec.status == OK and eng.n_quarantines == 0   # undetected

    def test_quarantine_invalidates_written_prefix_pages(self):
        """A poisoned slot's landed prompt pages must leave the index —
        a chain key commits to TOKENS, so a poisoned page would keep
        serving future matches forever if its entry survived."""
        cfg, model, params = _model("stablelm_12b")
        prompt = _prompts(cfg, (16,), seed=5)[0]     # 4 full pages
        # chunk 4: the prompt lands over steps 0-3, decode starts at 4 —
        # step 6 poisons mid-decode, and the window closes before the
        # clean resubmission below
        plan = FaultPlan.parse("nan@6/0x2")
        eng = ServeEngine(model, params, max_len=64, n_slots=1,
                          page_size=4, n_pages=24, prefill_chunk=4,
                          prefix_cache=True, faults=plan)
        rid = eng.submit(prompt, 6, max_retries=0)
        eng.run()
        assert eng.result(rid).status == FAILED
        stats = eng.page_stats()
        assert stats["prefix"]["invalidated"] >= 4   # the prompt chain
        assert stats["prefix"]["entries"] == 0
        assert eng.backend._pool.n_free == eng.n_pages   # index refs freed
        # the engine still serves: the same prompt re-lands cleanly
        rid2 = eng.submit(prompt, 6)
        eng.run()
        assert eng.result(rid2).status == OK
        assert eng.page_stats()["prefix"]["entries"] == 4


class TestContainment:
    def test_step_fault_contained_and_bit_identical(self):
        cfg, model, params = _model("stablelm_12b")
        prompts = _prompts(cfg, (6, 9))

        def run(faults):
            eng = _paged(model, params, faults=faults)
            rids = [eng.submit(p, 8) for p in prompts]
            eng.run()
            return eng, [eng.result(r) for r in rids]

        _, ref = run(None)
        plan = FaultPlan.parse("step@2x3")
        eng, got = run(plan)
        assert eng.n_faults_contained == 3
        assert [f[1] for f in plan.fired] == ["step"] * 3
        for g, w in zip(got, ref):
            assert g.status == OK
            np.testing.assert_array_equal(g, w)

    def test_alloc_fault_preempts_instead_of_crashing(self):
        cfg, model, params = _model("stablelm_12b")
        prompts = _prompts(cfg, (6, 7))

        def run(faults):
            eng = _paged(model, params, faults=faults, n_pages=16)
            rids = [eng.submit(p, 12) for p in prompts]
            eng.run()
            return eng, [eng.result(r) for r in rids]

        _, ref = run(None)
        # lazy growth first fires when a slot's length crosses its prompt
        # pages; blanket the window so the injection must hit one
        plan = FaultPlan.parse("alloc@1x8")
        eng, got = run(plan)
        assert any(k == "alloc" for _, k, _ in plan.fired)
        assert eng.n_faults_contained >= 1
        assert eng.n_preemptions >= 1
        for g, w in zip(got, ref):
            assert g.status == OK
            np.testing.assert_array_equal(g, w)
        assert eng.backend._pool.n_free == eng.n_pages


# ---------------------------------------------------------------------------
# Crash-safe checkpoint / restore
# ---------------------------------------------------------------------------

def _roundtrip(make_engine, submit_all, steps_before):
    """Reference run; crash a twin mid-flight at ``steps_before`` steps;
    restore its JSON snapshot onto a fresh engine; everything must finish
    OK and bit-identical. The snapshot must also be NON-mutating: the
    source engine keeps running to the same outputs."""
    ref = make_engine()
    rids = submit_all(ref)
    ref.run()
    want = [ref.result(r) for r in rids]

    src = make_engine()
    assert submit_all(src) == rids
    for _ in range(steps_before):
        src.step()
    state = json.loads(json.dumps(src.snapshot_engine()))

    dst = make_engine()
    dst.restore_engine(state)
    dst.run()
    for rid, w in zip(rids, want):
        got = dst.result(rid)
        assert got.status == OK
        np.testing.assert_array_equal(got, w)

    src.run()                                    # snapshot didn't perturb
    for rid, w in zip(rids, want):
        np.testing.assert_array_equal(src.result(rid), w)
    return state


def _mixed_submitter(prompts, budgets):
    """Alternating greedy / sampled submissions — one round trip proves
    both the deterministic path and the PRNG-chain path."""
    def go(eng):
        return [eng.submit(p, b, sampling=SamplingParams(
                    float(i % 2), 0, seed=i))
                for i, (p, b) in enumerate(zip(prompts, budgets))]
    return go


_CKPT_FAMILIES = [
    ("full", "stablelm_12b",
     dict(max_len=64, n_slots=2, prefill_len=12)),
    ("ring", "hymba_15b",
     dict(max_len=48, n_slots=2, prefill_len=12)),
    ("ssm", "mamba2_130m",
     dict(max_len=48, n_slots=2, prefill_len=12)),
]


@pytest.mark.parametrize("name,arch,kw", _CKPT_FAMILIES,
                         ids=[c[0] for c in _CKPT_FAMILIES])
def test_checkpoint_restore_bit_identical(name, arch, kw):
    cfg, model, params = _model(arch)
    prompts = _prompts(cfg, (5, 9, 12, 7), seed=3)
    _roundtrip(lambda: ServeEngine(model, params, **kw),
               _mixed_submitter(prompts, (6, 8, 5, 7)), steps_before=3)


def test_checkpoint_restore_paged_chunked_prefix_mid_plan():
    """The hardest token case in one drill: paged + chunked + prefix
    cache, snapshotted while one prompt is MID-ChunkPlan and two requests
    are actively sharing prefix pages."""
    cfg, model, params = _model("stablelm_12b")
    rng = np.random.RandomState(9)
    head = rng.randint(0, cfg.vocab, (16,)).astype(np.int32)
    prompts = [
        np.concatenate([head, rng.randint(0, cfg.vocab, (8,)).astype(np.int32)]),
        np.concatenate([head, rng.randint(0, cfg.vocab, (4,)).astype(np.int32)]),
        rng.randint(0, cfg.vocab, (26,)).astype(np.int32),   # mid-plan one
    ]
    state = _roundtrip(
        lambda: ServeEngine(model, params, max_len=64, n_slots=2,
                            page_size=4, n_pages=32, prefill_chunk=4,
                            prefix_cache=True),
        _mixed_submitter(prompts, (6, 7, 5)), steps_before=3)
    # the snapshot really did catch live work (prompts of 24+ tokens at
    # chunk 4 cannot have landed in 3 steps)
    assert len(state["live"]) + len(state["queue"]["front"]) \
        + len(state["queue"]["arrivals"]) >= 1


def test_checkpoint_restore_pairformer():
    cfg, model, params = _model("pairformer_lite")
    rng = np.random.RandomState(2)
    feats = [rng.standard_normal((n, 64)).astype(np.float32)
             for n in (12, 7, 9)]

    def submit_all(eng):
        return [eng.submit(f, b) for f, b in zip(feats, (3, 5, 4))]

    _roundtrip(lambda: ServeEngine(model, params, max_len=16, n_slots=2),
               submit_all, steps_before=2)


def test_checkpoint_preserves_lifecycle_records():
    cfg, model, params = _model("stablelm_12b")
    eng = ServeEngine(model, params, max_len=32, n_slots=1)
    prompts = _prompts(cfg, (5, 6, 30))
    done = eng.submit(prompts[0], 3)
    eng.run()
    live = eng.submit(prompts[1], 20)
    dead = eng.submit(prompts[2], 4, strict=False)   # REJECTED: 30+4 > 32
    cancelled = eng.submit(prompts[0], 5)
    eng.step()
    eng.cancel(cancelled)
    state = json.loads(json.dumps(eng.snapshot_engine()))

    dst = ServeEngine(model, params, max_len=32, n_slots=1)
    dst.restore_engine(state)
    assert dst.status(done) == OK
    np.testing.assert_array_equal(dst.result(done), eng.result(done))
    assert dst.status(dead) == REJECTED
    assert dst.result(dead).error["kind"] == "admission"
    assert dst.status(cancelled) == CANCELLED
    dst.run()
    assert dst.status(live) == OK and dst.result(live).size == 20
    # rid sequence continues where the snapshot left off
    assert dst.submit(prompts[0], 1) == cancelled + 1


def test_restore_refuses_mismatch_and_reuse():
    cfg, model, params = _model("stablelm_12b")
    eng = ServeEngine(model, params, max_len=32, n_slots=2)
    state = eng.snapshot_engine()
    assert state["version"] == 1

    other = ServeEngine(model, params, max_len=48, n_slots=2)
    with pytest.raises(ValueError, match="config mismatch.*max_len"):
        other.restore_engine(state)

    used = ServeEngine(model, params, max_len=32, n_slots=2)
    used.submit(_prompts(cfg, (4,))[0], 2)
    with pytest.raises(ValueError, match="fresh engine"):
        used.restore_engine(state)

    bad = dict(state, version=99)
    fresh = ServeEngine(model, params, max_len=32, n_slots=2)
    with pytest.raises(ValueError, match="snapshot version"):
        fresh.restore_engine(bad)


# ---------------------------------------------------------------------------
# Randomized chaos drill (seeded; CI re-runs under a FAULT_SEED matrix)
# ---------------------------------------------------------------------------

def test_randomized_chaos_conserves_pool_and_terminates():
    """~60 engine steps of seeded random traffic (mixed greedy/sampled,
    staggered arrivals) interleaved with random cancels, forced preempts
    and a random fault plan over every kind. Afterwards: every request
    reached a terminal status, no slot stayed occupied, and every pool
    page is accounted for — held only by the prefix index, refcount
    exactly 1 (refcounts drained, nothing leaked)."""
    cfg, model, params = _model("stablelm_12b")
    plan = FaultPlan.random(FAULT_SEED, n_steps=50, n_slots=3, n_faults=6)
    eng = ServeEngine(model, params, max_len=64, n_slots=3, page_size=4,
                      n_pages=28, prefill_chunk=4, prefix_cache=True,
                      faults=plan, stall_limit=300)
    rng = np.random.RandomState(FAULT_SEED + 1000)
    rids = []
    for _ in range(60):
        if len(rids) < 12 and rng.rand() < 0.4:
            prompt = rng.randint(0, cfg.vocab,
                                 (int(rng.randint(3, 20)),)).astype(np.int32)
            rids.append(eng.submit(
                prompt, int(rng.randint(2, 10)),
                sampling=SamplingParams(float(rng.rand() < 0.5), 0,
                                        seed=len(rids)),
                max_retries=2,
                deadline_steps=None if rng.rand() < 0.7 else 40))
        if rids and rng.rand() < 0.1:
            victim = rids[int(rng.randint(len(rids)))]
            if eng.status(victim) not in TERMINAL_STATUSES:
                assert eng.cancel(victim) is True
        if eng.occupancy and rng.rand() < 0.1:
            eng.preempt()
        eng.step()
    eng.run()

    assert len(rids) > 0 and eng.occupancy == 0
    counts = eng.status_counts()
    assert sum(counts.values()) == len(rids)
    assert set(counts) <= TERMINAL_STATUSES
    for rid in rids:
        rec = eng.result(rid)
        if rec.status == OK:
            assert rec.size >= 1
    # page conservation: the only remaining holders are index entries
    pool, prefix = eng.backend._pool, eng.backend._prefix
    assert not eng.backend._slot_pages
    index_pages = {e.page for e in prefix._entries.values()}
    assert pool.n_used == len(index_pages)
    for page in index_pages:
        assert pool.refcount(page) == 1
