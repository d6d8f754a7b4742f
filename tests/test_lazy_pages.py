"""Lazy page growth + mid-flight preemption (ISSUE 4).

PagePool grow/watermark accounting, grow-on-boundary page-table growth,
free-list reuse after preemption, the scheduler's requeue-at-head and
shortest-prompt-first toggle, and the load-bearing determinism invariant:
a request preempted mid-decode (pages freed, re-queued, re-prefilled from
prompt + generated-so-far) produces bit-identical greedy output to the same
request run alone — for the full-KV (paged, auto-preempted on pool
exhaustion) and ring-KV (constant-size cache, explicitly preempted)
families."""
import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import get_model
from repro.models.common import init_params
from repro.serve import FIFOScheduler, PagePool, Request, SamplingParams, ServeEngine
from repro.serve.lifecycle import AdmissionRejected


def _model(arch):
    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab, (n,)).astype(np.int32) for n in lens]


def _alone(model, params, prompt, budget, sampling=None, **kw):
    eng = ServeEngine(model, params, **kw)
    rid = eng.submit(prompt, budget, sampling=sampling)
    eng.run()
    return eng.result(rid)


# ---------------------------------------------------------------------------
# PagePool growth accounting
# ---------------------------------------------------------------------------

class TestPagePoolGrowth:
    def test_grow_is_alloc_with_separate_accounting(self):
        pool = PagePool(6, 8)
        a = pool.alloc(2)
        g = pool.grow(1)
        assert a == [0, 1] and g == [2]
        assert pool.n_used == 3 and pool.n_grown == 1
        with pytest.raises(MemoryError):
            pool.grow(4)                   # grow gates like alloc
        assert pool.n_grown == 1           # failed grow accounts nothing

    def test_watermark_tracks_peak_not_current(self):
        pool = PagePool(8, 4)
        a = pool.alloc(3)
        assert pool.watermark == 3
        b = pool.grow(2)
        assert pool.watermark == 5
        pool.free(a + b)
        assert pool.n_used == 0 and pool.watermark == 5
        pool.alloc(2)
        assert pool.watermark == 5         # below peak: unchanged

    def test_freed_pages_reused_lowest_first_after_growth(self):
        pool = PagePool(6, 4)
        a = pool.alloc(2)                  # [0, 1]
        pool.alloc(2)                      # [2, 3]
        pool.free(a)
        assert pool.grow(3) == [0, 1, 4]   # holes first, then fresh


# ---------------------------------------------------------------------------
# Scheduler: requeue-at-head + shortest-prompt-first toggle
# ---------------------------------------------------------------------------

class TestScheduler:
    def _reqs(self, lens):
        return [Request(i, np.arange(1, n + 1), 4) for i, n in
                enumerate(lens)]

    def test_fifo_keeps_arrival_order(self):
        s = FIFOScheduler()
        for r in self._reqs((9, 3, 6)):
            s.add(r)
        assert [r.rid for r in s.take(3)] == [0, 1, 2]

    def test_spf_picks_shortest_prompt_stable(self):
        s = FIFOScheduler(policy="spf")
        for r in self._reqs((9, 3, 6, 3)):
            s.add(r)
        assert s.peek().rid == 1
        assert [r.rid for r in s.take(4)] == [1, 3, 2, 0]

    def test_requeued_resume_ahead_of_arrivals_in_rid_order(self):
        for policy in ("fifo", "spf"):
            s = FIFOScheduler(policy=policy)
            r0, r1, r2, r3 = self._reqs((9, 3, 6, 2))
            s.add(r2)
            s.add(r3)
            s.add_front(r1)                # preempted later arrival first..
            s.add_front(r0)                # ..then an earlier one
            assert s.peek().rid == 0       # arrival order within the front
            order = [r.rid for r in s.take(4)]
            assert order[:2] == [0, 1], (policy, order)


# ---------------------------------------------------------------------------
# Engine: grow-on-boundary, watermark, free-list reuse
# ---------------------------------------------------------------------------

def test_grow_on_boundary_allocates_one_page_per_crossing():
    cfg, model, params = _model("stablelm_12b")
    eng = ServeEngine(model, params, max_len=32, n_slots=2, prefill_len=6,
                      page_size=8)
    rid = eng.submit(_prompts(cfg, (5,), seed=1)[0], 20)
    eng.step()                             # admit: prompt pages only
    (slot,) = eng._live.keys()
    seen = {len(eng.backend._slot_pages[slot])}
    assert seen == {1}                     # ceil(5 / 8)
    while not eng.is_done(rid):
        eng.step()
        if slot in eng.backend._slot_pages:
            seen.add(len(eng.backend._slot_pages[slot]))
    # final length 5 + 20 - 1 = 24 -> three pages, grown one at a time
    assert seen == {1, 2, 3}
    assert eng.page_stats()["grown"] == 2
    assert eng.page_stats()["watermark"] == 3
    assert eng.backend._pool.n_free == eng.n_pages


def test_preemption_frees_pages_for_lowest_index_reuse():
    """r0 ([page 0]) grows while the pool is dry: r1 (pages [1, 2], later
    arrival = lower priority) is preempted, its pages return to the free
    list immediately, and r0's growth takes the lowest freed index."""
    cfg, model, params = _model("stablelm_12b")
    eng = ServeEngine(model, params, max_len=32, n_slots=2, prefill_len=10,
                      page_size=8, n_pages=3)
    p0, p1 = _prompts(cfg, (7, 9), seed=6)
    r0 = eng.submit(p0, 6)
    r1 = eng.submit(p1, 6)
    eng.step()                             # admit both: 1 + 2 pages, dry
    assert eng.backend._slot_pages == {0: [0], 1: [1, 2]}
    eng.step()                             # r0 crosses 8: grow -> preempt r1
    assert eng.n_preemptions == 1 and not eng.is_done(r1)
    assert eng.backend._slot_pages == {0: [0, 1]}  # lowest freed page reused
    eng.run()
    for rid, p in ((r0, p0), (r1, p1)):
        np.testing.assert_array_equal(
            eng.result(rid),
            _alone(model, params, p, 6, max_len=32, n_slots=2,
                   prefill_len=10, page_size=8, n_pages=3))
    assert eng.backend._pool.n_free == eng.n_pages


def test_whole_reservation_mode_never_grows_or_preempts():
    cfg, model, params = _model("stablelm_12b")
    kw = {"max_len": 32, "n_slots": 2, "prefill_len": 10, "page_size": 8}
    prompts = _prompts(cfg, (7, 9), seed=2)
    eng = ServeEngine(model, params, page_reservation="whole", **kw)
    out_whole = eng.generate(prompts, 8)
    assert eng.page_stats()["grown"] == 0
    assert eng.page_stats()["preemptions"] == 0
    lazy = ServeEngine(model, params, **kw)
    np.testing.assert_array_equal(out_whole, lazy.generate(prompts, 8))
    assert lazy.page_stats()["grown"] > 0


def test_lazy_admits_where_whole_reservation_starves():
    """Two requests whose full footprints (2 pages each) cannot coexist in
    a 3-page pool: whole-request reservation serializes them (occupancy
    never exceeds 1) while lazy growth runs them concurrently."""
    cfg, model, params = _model("stablelm_12b")
    kw = {"max_len": 32, "n_slots": 2, "prefill_len": 6, "page_size": 8, "n_pages": 3}
    prompts = _prompts(cfg, (4, 4), seed=3)

    def max_occ(reservation):
        eng = ServeEngine(model, params, page_reservation=reservation, **kw)
        rids = [eng.submit(p, 12) for p in prompts]
        occ = 0
        while eng.occupancy or len(eng.scheduler):
            eng.step()
            occ = max(occ, eng.occupancy)
        for rid, p in zip(rids, prompts):
            np.testing.assert_array_equal(
                eng.result(rid), _alone(model, params, p, 12, **kw))
        return occ

    assert max_occ("whole") == 1
    assert max_occ("lazy") == 2


# ---------------------------------------------------------------------------
# Preemption parity: preempted greedy output == single-request output
# ---------------------------------------------------------------------------

def test_preempted_equals_alone_full_kv_auto():
    """Full-KV family, pool-exhaustion path: the engine preempts on its own
    when growth finds the pool dry. Every staggered request — including a
    sampled one resuming from its PRNG key snapshot — must reproduce its
    alone-run output exactly, and the drained pool must be whole."""
    cfg, model, params = _model("stablelm_12b")
    kw = {"max_len": 32, "n_slots": 2, "prefill_len": 10, "page_size": 8, "n_pages": 3}
    prompts = _prompts(cfg, (7, 9, 5), seed=6)
    budgets = [6, 6, 8]
    samplings = [None, None, SamplingParams(temperature=0.7, top_k=5,
                                            seed=42)]
    eng = ServeEngine(model, params, **kw)
    rids = [eng.submit(prompts[0], budgets[0]),
            eng.submit(prompts[1], budgets[1])]
    eng.step()
    rids.append(eng.submit(prompts[2], budgets[2],
                           sampling=samplings[2]))   # mid-flight arrival
    eng.run()
    assert eng.n_preemptions >= 1          # the pool is too small not to
    for rid, p, b, sp in zip(rids, prompts, budgets, samplings):
        alone = _alone(model, params, p, b, sampling=sp, **kw)
        np.testing.assert_array_equal(eng.result(rid), alone)
    assert eng.backend._pool.n_free == eng.n_pages


@pytest.mark.parametrize("arch", ["stablelm_12b", "hymba_15b"])
def test_preempted_equals_alone_explicit(arch):
    """Explicit mid-flight preemption parity for the full-KV (stablelm)
    and ring-KV (hymba: sliding-window ring + SSM state) families. The
    ring/SSM caches hold no pages, so ``preempt`` is driven by hand —
    snapshotting, re-queuing and re-prefilling follow the same path."""
    cfg, model, params = _model(arch)
    kw = {"max_len": 48, "n_slots": 2, "prefill_len": 11}
    prompts = _prompts(cfg, (5, 9, 7), seed=3)
    budgets = [10, 8, 6]
    eng = ServeEngine(model, params, **kw)
    r0 = eng.submit(prompts[0], budgets[0])
    r1 = eng.submit(prompts[1], budgets[1])
    eng.step()
    eng.step()
    assert eng.preempt(r0) == r0           # victim by rid, mid-decode
    r2 = eng.submit(prompts[2], budgets[2])
    eng.step()
    assert eng.preempt() is not None       # default victim: highest rid
    eng.run()
    for rid, p, b in zip((r0, r1, r2), prompts, budgets):
        alone = _alone(model, params, p, b, **kw)
        np.testing.assert_array_equal(eng.result(rid), alone,
                                      err_msg=f"{arch} rid {rid}")


def test_resumed_overlength_prompt_rides_a_solo_wave():
    """A resumed prompt that outgrew the pinned ``prefill_len`` must not
    drag co-admitted requests onto its longer padding: padded prompt
    length feeds MoE expert capacity, so a mixed wave would break the
    wave-independence contract for the OTHER requests. The engine admits
    over-length resumes solo; a fresh arrival sharing the queue must still
    reproduce its alone-run output — checked on the MoE family, the one
    that can actually tell."""
    cfg, model, params = _model("granite_moe_3b_a800m")
    kw = {"max_len": 48, "n_slots": 2, "prefill_len": 8}
    prompts = _prompts(cfg, (5, 6), seed=11)
    eng = ServeEngine(model, params, **kw)
    r0 = eng.submit(prompts[0], 12)
    for _ in range(5):                     # r0 generates 5 tokens
        eng.step()
    assert eng.preempt(r0) == r0           # resumed prompt 10 > prefill_len
    r1 = eng.submit(prompts[1], 4)         # fresh arrival shares the queue
    eng.run()
    assert eng.scheduler.peek() is None
    np.testing.assert_array_equal(
        eng.result(r1), _alone(model, params, prompts[1], 4, **kw))
    assert eng.result(r0).size == 12


def test_submit_errors_state_their_actual_bound():
    """The contiguous admission error names the slot-segment bound (and
    the paged escape hatch); the paged error names the page-table/pool
    bound — not the removed PR-2 ``prompt + budget <= max_len`` contract."""
    cfg, model, params = _model("stablelm_12b")
    long_prompt = _prompts(cfg, (40,), seed=4)[0]
    eng_c = ServeEngine(model, params, max_len=48, n_slots=2)
    with pytest.raises(AdmissionRejected, match=r"contiguous mode.*max_len=48"):
        eng_c.submit(long_prompt, 40)
    eng_p = ServeEngine(model, params, max_len=48, n_slots=2, page_size=16,
                        n_pages=8)
    with pytest.raises(AdmissionRejected, match=r"paged mode.*page-table"):
        eng_p.submit(_prompts(cfg, (100,), seed=5)[0], 100)
