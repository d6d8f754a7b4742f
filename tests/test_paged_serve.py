"""Paged KV serve engine (ISSUE 3): page-pool allocator unit tests,
paged-vs-contiguous greedy parity for every cache family under staggered
arrivals, OOM admission backpressure, the removal of the PR-2
``prompt + budget <= max_len`` bound, and the retired-slot freeze (stale
page tables must never scribble on reallocated pages)."""
import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import get_model
from repro.models.common import init_params
from repro.serve import PagePool, ServeEngine
from repro.serve.lifecycle import AdmissionRejected, PoolError

PF = 12


def _model(arch):
    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab, (n,)).astype(np.int32) for n in lens]


def _alone(model, params, prompt, budget, **kw):
    eng = ServeEngine(model, params, **kw)
    rid = eng.submit(prompt, budget)
    eng.run()
    return eng.result(rid)


# ---------------------------------------------------------------------------
# PagePool allocator
# ---------------------------------------------------------------------------

class TestPagePool:
    def test_alloc_free_accounting(self):
        pool = PagePool(8, 4)
        assert pool.n_free == 8
        a = pool.alloc(3)
        assert sorted(a) == [0, 1, 2] and pool.n_free == 5
        b = pool.alloc(2)
        assert sorted(b) == [3, 4] and pool.n_free == 3
        pool.free(a)
        assert pool.n_free == 6

    def test_fragmented_free_list_reuses_lowest_first(self):
        pool = PagePool(6, 4)
        a, b, c = pool.alloc(2), pool.alloc(2), pool.alloc(2)
        pool.free(a)            # holes at 0,1
        pool.free(c)            # holes at 4,5
        got = pool.alloc(3)     # spans both holes — pages need not be
        assert got == [0, 1, 4]  # contiguous, lowest indices first
        assert pool.n_free == 1
        pool.free(got + b)
        assert pool.n_free == 6

    def test_oom_raises_and_can_alloc_gates(self):
        pool = PagePool(4, 16)
        pool.alloc(3)
        assert pool.can_alloc(1) and not pool.can_alloc(2)
        with pytest.raises(MemoryError):
            pool.alloc(2)
        assert pool.n_free == 1       # failed alloc takes nothing

    def test_double_free_and_double_alloc_guards(self):
        pool = PagePool(4, 8)
        a = pool.alloc(2)
        pool.free(a)
        with pytest.raises(PoolError):
            pool.free(a)

    def test_pages_needed(self):
        pool = PagePool(8, 16)
        assert pool.pages_needed(1) == 1
        assert pool.pages_needed(16) == 1
        assert pool.pages_needed(17) == 2
        assert pool.pages_needed(0) == 1      # a slot always owns a page


# ---------------------------------------------------------------------------
# Paged engine vs contiguous engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["stablelm_12b", "hymba_15b", "mamba2_130m"])
def test_paged_matches_contiguous_staggered(arch):
    """Greedy outputs must be identical for every cache family. stablelm
    (dense full KV) actually pages; hymba (ring + SSM) and mamba2 (SSM)
    have constant-size caches, so ``page_size`` must be a no-op for them."""
    cfg, model, params = _model(arch)
    kw = {"max_len": 48, "n_slots": 2, "prefill_len": 11}
    prompts = _prompts(cfg, (4, 11, 7), seed=2)
    budgets = [7, 4, 6]

    def run(extra):
        eng = ServeEngine(model, params, **kw, **extra)
        rids = [eng.submit(prompts[0], budgets[0]),
                eng.submit(prompts[1], budgets[1])]
        eng.step()
        eng.step()
        rids.append(eng.submit(prompts[2], budgets[2]))   # mid-flight arrival
        eng.run()
        return eng, [eng.result(r) for r in rids]

    eng_c, out_c = run({})
    eng_p, out_p = run({"page_size": 16})
    assert eng_p.backend.paged == (arch == "stablelm_12b")
    for i, (c, p) in enumerate(zip(out_c, out_p)):
        np.testing.assert_array_equal(c, p, err_msg=f"{arch} request {i}")
    if eng_p.backend.paged:     # a drained engine returned every page
        assert eng_p.backend._pool.n_free == eng_p.n_pages


def test_hybrid_full_kv_pages_with_ssm_slot_leaves():
    """A window-less hybrid pages its KV while the SSM state / conv tails
    keep the slot discipline — both travel through one ``insert_paged``."""
    cfg, model, params = _model("hymba_15b")
    cfg = cfg.replace(window=0)
    model = get_model(cfg)
    prompts = _prompts(cfg, (5, 9), seed=9)
    kw = {"max_len": 32, "n_slots": 2, "prefill_len": 10}
    out_c = ServeEngine(model, params, **kw).generate(prompts, 5)
    eng_p = ServeEngine(model, params, page_size=8, **kw)
    assert eng_p.backend.paged
    assert "ssm_h" in eng_p.model.init_paged_cache(2, 8, 8)
    np.testing.assert_array_equal(out_c, eng_p.generate(prompts, 5))


def test_moe_paged_matches_contiguous():
    cfg, model, params = _model("granite_moe_3b_a800m")
    kw = {"max_len": 32, "n_slots": 2, "prefill_len": 8}
    prompts = _prompts(cfg, (5, 8), seed=3)
    eng_c = ServeEngine(model, params, **kw)
    eng_p = ServeEngine(model, params, page_size=8, **kw)
    assert eng_p.backend.paged
    np.testing.assert_array_equal(eng_c.generate(prompts, 4),
                                  eng_p.generate(prompts, 4))


def test_paged_accepts_request_beyond_max_len():
    """The PR-2 engine asserts on prompt + budget > max_len; the paged
    engine admits it as long as its pages fit the pool."""
    cfg, model, params = _model("stablelm_12b")
    prompt = _prompts(cfg, (40,), seed=4)[0]
    eng_c = ServeEngine(model, params, max_len=48, n_slots=2)
    with pytest.raises(AdmissionRejected):
        eng_c.submit(prompt, 40)                  # 40 + 40 > 48
    eng_p = ServeEngine(model, params, max_len=48, n_slots=2, page_size=16,
                        n_pages=8)
    rid = eng_p.submit(prompt, 40)                # needs 5 of 8 pages
    eng_p.run()
    assert eng_p.result(rid).size == 40
    assert eng_p.backend._pool.n_free == eng_p.n_pages

    # a request that can NEVER fit its page-table row is rejected up front
    with pytest.raises(AdmissionRejected):
        eng_p.submit(_prompts(cfg, (100,), seed=5)[0], 100)


def test_oom_admission_backpressure():
    """Pool sized for ~one request: free-page gating (and, under the
    default lazy reservation, mid-flight preemption when growth finds the
    pool dry) must keep the traffic within the pool, in FIFO order, and
    every request still completes with its alone-run output."""
    cfg, model, params = _model("stablelm_12b")
    kw = {"max_len": 32, "n_slots": 2, "prefill_len": 10, "page_size": 8, "n_pages": 3}
    prompts = _prompts(cfg, (7, 9, 5), seed=6)
    budget = 6                                    # ceil((9+6-1)/8) = 2 pages
    eng = ServeEngine(model, params, **kw)
    rids = [eng.submit(p, budget) for p in prompts]
    max_occ = 0
    while eng.occupancy or len(eng.scheduler):
        eng.step()
        max_occ = max(max_occ, eng.occupancy)
    # 3 pages can hold at most one 2-page footprint plus one 1-page
    # footprint at a time; lazy growth may overlap prompts but preemption
    # keeps concurrent footprints within the pool
    assert max_occ <= 2
    for rid, p in zip(rids, prompts):
        alone = _alone(model, params, p, budget, **kw)
        np.testing.assert_array_equal(eng.result(rid), alone)
    assert eng.backend._pool.n_free == eng.n_pages


# ---------------------------------------------------------------------------
# Retired-slot freeze (the idle-lane corruption class)
# ---------------------------------------------------------------------------

def test_retired_slot_is_frozen_and_reusable():
    """Regression (ISSUE 3): retired/free slots used to keep advancing
    ``cache["length"]`` and writing garbage KV on every engine step. Under
    paging the stale page table points at pages that get reallocated to
    other requests, so an unfrozen idle lane corrupts ANOTHER request's
    cache. Retire -> many steps -> reuse must leave every output equal to
    its alone run, and the freed slot's length must stay pinned at 0."""
    cfg, model, params = _model("stablelm_12b")
    kw = {"max_len": 64, "n_slots": 2, "prefill_len": PF, "page_size": 16}
    prompts = _prompts(cfg, (5, 9, 7), seed=7)

    eng = ServeEngine(model, params, **kw)
    r0 = eng.submit(prompts[0], 3)      # retires early
    r1 = eng.submit(prompts[1], 40)     # keeps decoding (> page_size steps,
    eng.step()                          # so an unfrozen idle lane would
    while not eng.is_done(r0):          # cross page boundaries)
        eng.step()
    free_slot = eng._free[0]
    for _ in range(20):                 # idle slot rides 20 full-batch steps
        eng.step()
        assert int(np.asarray(eng.backend._cache["length"])[free_slot]) == 0
    r2 = eng.submit(prompts[2], 6)      # reuses the slot (and r0's pages)
    eng.run()

    for rid, prompt, budget in ((r0, prompts[0], 3), (r1, prompts[1], 40),
                                (r2, prompts[2], 6)):
        alone = _alone(model, params, prompt, budget, **kw)
        np.testing.assert_array_equal(eng.result(rid), alone)


def test_retired_slot_frozen_contiguous_too():
    """The same freeze applies without paging: a freed slot's length stays
    0 (it used to grow without bound, walking scatter indices past the
    segment) and its KV rows stop changing between retire and reuse."""
    cfg, model, params = _model("stablelm_12b")
    eng = ServeEngine(model, params, max_len=48, n_slots=2, prefill_len=PF)
    r0 = eng.submit(_prompts(cfg, (5,), seed=8)[0], 2)
    r1 = eng.submit(_prompts(cfg, (9,), seed=8)[0], 30)
    while not eng.is_done(r0):
        eng.step()
    slot = eng._free[0]
    k_before = np.asarray(eng.backend._cache["k"][:, slot])
    for _ in range(10):
        eng.step()
        assert int(np.asarray(eng.backend._cache["length"])[slot]) == 0
    np.testing.assert_array_equal(np.asarray(eng.backend._cache["k"][:, slot]),
                                  k_before)


@pytest.mark.parametrize("reservation", ["whole", "lazy"])
def test_cache_programs_donate_the_pools(reservation):
    """Every backend program that takes the cache and returns its successor
    donates it: after an admission and after a decode step the page pools
    handed in are deleted (their buffers hold the successor), and a
    multi-request run, with lazy page growth or without, emits exactly the
    tokens of the same engine whose programs copy the cache instead."""
    cfg, model, params = _model("stablelm_12b")
    kw = {"max_len": 48, "n_slots": 2, "prefill_len": PF, "page_size": 4,
          "page_reservation": reservation}
    prompts = _prompts(cfg, (5, 9, 7), seed=5)
    budgets = [9, 6, 8]

    def run(donating):
        eng = ServeEngine(model, params, **kw)
        be = eng.backend
        if not donating:
            # the same programs as jitted before donation: each call
            # writes its successor into a fresh buffer
            be._insert_paged = jax.jit(model.insert_paged)
            be._grow_tables = jax.jit(model.grow_page_table)
            be._decode = jax.jit(model.decode, static_argnames="max_pages")
        rids = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
        be.ensure_state()
        pools = [be._cache["pages_k"], be._cache["pages_v"]]
        eng.admit()
        assert all(p.is_deleted() == donating for p in pools)
        pools = [be._cache["pages_k"], be._cache["pages_v"]]
        eng.decode()
        assert all(p.is_deleted() == donating for p in pools)
        eng.run()
        return [eng.result(r) for r in rids]

    for i, (got, want) in enumerate(zip(run(True), run(False))):
        np.testing.assert_array_equal(got, want, err_msg=f"request {i}")
