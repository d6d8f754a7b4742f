"""Prefix caching: content-hashed page sharing + copy-on-write (ISSUE 9).

The contract under test: with ``prefix_cache=True`` a request whose prompt
starts with an already-served prefix maps its page table onto the existing
pages (``PagePool.incref``) and prefills only the novel tail — and NOTHING
observable changes. Greedy and sampled outputs are bit-identical to the
unshared engine, including under eviction pressure and preemption/resume
of a request that is actively sharing pages.

Mechanics pinned here:

- ``PagePool`` refcounts: free is a decref, a page drains only at zero,
  double free and incref-of-free stay loud errors.
- ``PrefixCache``: hash-chain match/insert over full token blocks, LRU
  leaf-first eviction, and hash-collision safety — a colliding digest is
  rejected by the full token-block compare, never served.
- Retirement RETAINS the prompt's full pages in the index (refcount 1,
  evictable) — the vLLM-style cache-past-retirement behavior.
- Copy-on-write: a sharer that must re-run the span ``[done, matched)``
  (chunk-boundary alignment) copies those pages before writing.
- ``submit`` footprint errors account for shared-prefix hits while still
  matching the ``paged mode.*page-table`` shape tests/test_lazy_pages.py
  pins.
"""
import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import get_model
from repro.models.common import init_params
from repro.serve import PrefixCache, SamplingParams, ServeEngine
from repro.serve.lifecycle import AdmissionRejected, PoolError
from repro.serve.pages import PagePool


def _model(arch):
    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    return cfg, model, params


def _shared_prompts(cfg, common_len, tails, seed=11):
    rng = np.random.RandomState(seed)
    common = rng.randint(0, cfg.vocab, (common_len,)).astype(np.int32)
    return [np.concatenate([common, rng.randint(
        0, cfg.vocab, (n,)).astype(np.int32)]) for n in tails]


def _run(model, params, prompts, *, prefix_cache, n_pages=None, budget=14,
         temp=0.0, n_slots=2):
    kw = {} if n_pages is None else {"n_pages": n_pages}
    eng = ServeEngine(model, params, max_len=64, n_slots=n_slots,
                      page_size=4, pages_per_slot=16, prefill_chunk=4,
                      prefix_cache=prefix_cache, **kw)
    rids = [eng.submit(p, budget, sampling=SamplingParams(temp, 0, seed=i))
            for i, p in enumerate(prompts)]
    eng.run()
    return [eng.result(r) for r in rids], eng


# ---------------------------------------------------------------------------
# PagePool refcounts
# ---------------------------------------------------------------------------

def test_pool_refcount_units():
    pool = PagePool(4, page_size=4)
    a, b = pool.alloc(2)
    assert pool.refcount(a) == 1 and pool.refcount(b) == 1
    pool.incref([a])
    assert pool.refcount(a) == 2
    assert pool.free([a]) == []            # decref: still one holder
    assert pool.n_free == 2
    assert pool.free([a, b]) == [a, b]     # last holders -> both drain
    assert pool.n_free == 4


def test_pool_double_free_rejected():
    pool = PagePool(2, page_size=4)
    (p,) = pool.alloc(1)
    pool.free([p])
    with pytest.raises(PoolError, match="double free"):
        pool.free([p])


def test_pool_incref_of_free_page_rejected():
    pool = PagePool(2, page_size=4)
    with pytest.raises(PoolError, match="incref of free"):
        pool.incref([0])


# ---------------------------------------------------------------------------
# PrefixCache index
# ---------------------------------------------------------------------------

def test_index_match_walks_full_blocks_only():
    pool = PagePool(8, page_size=4)
    idx = PrefixCache(4)
    toks = np.arange(10, dtype=np.int32)        # 2 full blocks + partial
    pages = pool.alloc(3)
    assert idx.insert(toks, pages, pool) == 2   # partial page never indexed
    hit, matched = idx.match(toks)
    assert (hit, matched) == (pages[:2], 8)
    # a diverging second block matches only the first
    other = np.concatenate([toks[:4], toks[4:8][::-1]])
    hit, matched = idx.match(other)
    assert (hit, matched) == (pages[:1], 4)
    assert pool.refcount(pages[0]) == 2         # request + index
    assert pool.refcount(pages[2]) == 1         # partial page: request only


def test_index_rejects_hash_collisions_by_block_compare():
    """A degenerate digest maps EVERY block to one key: without the full
    token-block compare the index would serve page content for the wrong
    tokens. The compare must reject the hit (and count it)."""
    pool = PagePool(8, page_size=4)
    idx = PrefixCache(4, digest=lambda parent, block: b"collide")
    toks_a = np.arange(4, dtype=np.int32)
    toks_b = np.arange(4, 8, dtype=np.int32)
    idx.insert(toks_a, pool.alloc(1), pool)
    hit, matched = idx.match(toks_b)            # same key, different tokens
    assert (hit, matched) == ([], 0)
    assert idx.n_rejected == 1
    assert idx.match(toks_a)[1] == 4            # the real tokens still hit


def test_index_eviction_is_lru_leaf_first():
    pool = PagePool(8, page_size=2)
    idx = PrefixCache(2)
    toks = np.arange(6, dtype=np.int32)         # chain of 3 entries
    pages = pool.alloc(3)
    idx.insert(toks, pages, pool)
    pool.free(pages)                            # request retires: index-only
    assert idx.n_evictable(pool) == 3
    assert idx.evict(pool, 1) == 1              # deepest leaf goes first
    assert idx.match(toks) == (pages[:2], 4)
    assert idx.evict(pool, 5) == 2              # drains the rest, stops dry
    assert (len(idx), pool.n_free) == (0, 8)
    assert idx.n_evicted == 3


def test_index_evictable_excludes_chains_pinned_by_live_sharers():
    """An entry whose DESCENDANT has a live sharer can never become a
    leaf, so leaf-first eviction cannot drain it. n_evictable must not
    count such chains — the engine's preemption gate trusts it, and an
    overcount turns backpressure into pool exhaustion (the n_pages=9
    regression this PR fixed)."""
    pool = PagePool(8, page_size=2)
    idx = PrefixCache(2)
    toks = np.arange(6, dtype=np.int32)
    pages = pool.alloc(3)
    idx.insert(toks, pages, pool)
    pool.free(pages[:2])                        # ancestors: index-only
    assert pool.refcount(pages[2]) == 2         # leaf still shared
    assert idx.n_cached(pool) == 2              # retained, but...
    assert idx.n_evictable(pool) == 0           # ...pinned behind the leaf
    assert idx.evict(pool, 2) == 0              # and evict agrees
    pool.free([pages[2]])                       # sharer retires
    assert idx.n_evictable(pool) == 3


# ---------------------------------------------------------------------------
# Engine integration: retention, sharing, CoW
# ---------------------------------------------------------------------------

def test_retire_retains_prompt_pages_in_index():
    cfg, model, params = _model("stablelm_12b")
    prompts = _shared_prompts(cfg, 8, (2,))     # 2 full pages + partial
    outs, eng = _run(model, params, prompts, prefix_cache=True, budget=6)
    be = eng.backend
    assert len(be._prefix) == 2                 # full prompt pages indexed
    assert be._prefix.n_cached(be._pool) == 2   # retained past retirement
    assert be._pool.n_free == eng.n_pages - 2   # decode/partial pages drain
    # the retained pages are evictable on demand — nothing leaks
    assert be._prefix.evict(be._pool, 2) == 2
    assert be._pool.n_free == eng.n_pages


def test_second_request_hits_and_emits_identically():
    cfg, model, params = _model("stablelm_12b")
    p = _shared_prompts(cfg, 12, (5,))[0]
    prompts = [p, p]      # n_slots=1: the second arrives after the first
    off, _ = _run(model, params, prompts, prefix_cache=False, budget=8,
                  n_slots=1)                    # registers (same-wave
    on, eng = _run(model, params, prompts, prefix_cache=True, budget=8,
                   n_slots=1)                   # duplicates don't match)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)
    pf = eng.page_stats()["prefix"]
    assert pf["tokens_matched"] > 0 and pf["hit_rate"] > 0


def test_cow_on_page_aligned_hit():
    """A fully-cached page-aligned prompt must re-run its final chunk to
    produce the first sampled token (done is capped below prompt_len), so
    the deepest matched page is written by the sharer — copy-on-write
    copies it first, and the original entry keeps serving other
    requests."""
    cfg, model, params = _model("stablelm_12b")
    rng = np.random.RandomState(3)
    p = rng.randint(0, cfg.vocab, (8,)).astype(np.int32)   # exactly 2 pages
    prompts = [p, p, p]
    off, _ = _run(model, params, prompts, prefix_cache=False, budget=6)
    on, eng = _run(model, params, prompts, prefix_cache=True, budget=6)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)
    pf = eng.page_stats()["prefix"]
    assert pf["cow_copies"] >= 1
    assert pf["collisions_rejected"] == 0


@pytest.mark.parametrize("arch,temp", [
    ("stablelm_12b", 0.0),            # dense greedy
    ("stablelm_12b", 0.8),            # dense sampled (PRNG chain parity)
    ("granite_moe_3b_a800m", 0.8),    # MoE sampled (expert routing parity)
])
def test_shared_vs_unshared_bit_parity(arch, temp):
    cfg, model, params = _model(arch)
    prompts = _shared_prompts(cfg, 12, (5, 3, 7, 6))
    off, _ = _run(model, params, prompts, prefix_cache=False, temp=temp)
    on, eng = _run(model, params, prompts, prefix_cache=True, temp=temp)
    for i, (a, b) in enumerate(zip(off, on)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
    assert eng.page_stats()["prefix"]["hit_rate"] > 0


def test_parity_through_preemption_of_a_sharing_request():
    """Tight pool (n_pages=9): the prefix engine preempts requests that
    are actively sharing pages and evicts retained entries mid-run —
    preempt decrefs without invalidating other holders, resume re-matches
    the index, and outputs stay bit-identical to the unshared engine."""
    cfg, model, params = _model("stablelm_12b")
    prompts = _shared_prompts(cfg, 12, (5, 3, 7, 6))
    off, _ = _run(model, params, prompts, prefix_cache=False, n_pages=9)
    on, eng = _run(model, params, prompts, prefix_cache=True, n_pages=9)
    for i, (a, b) in enumerate(zip(off, on)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
    st = eng.page_stats()
    assert st["preemptions"] > 0            # sharing requests were evicted
    assert st["prefix"]["evictions"] > 0    # and the index gave pages back
    assert (eng.backend._pool.n_free + st["prefix"]["cached_pages"]
            == eng.n_pages)


def test_submit_error_accounts_for_shared_hits():
    """The paged footprint error must state what admission would actually
    reserve under sharing — and keep the `paged mode.*page-table` shape
    test_lazy_pages pins for the unshared engine."""
    cfg, model, params = _model("stablelm_12b")
    eng = ServeEngine(model, params, max_len=48, n_slots=2, page_size=16,
                      n_pages=8, prefill_chunk=16, prefix_cache=True)
    rng = np.random.RandomState(7)
    head = rng.randint(0, cfg.vocab, (32,)).astype(np.int32)
    eng.submit(head, 4)                     # lands 2 pages in the index
    eng.run()
    over = np.concatenate([head, rng.randint(
        0, cfg.vocab, (68,)).astype(np.int32)])
    with pytest.raises(AdmissionRejected,
                       match=r"paged mode.*shared via the prefix cache"
                             r".*page-table"):
        eng.submit(over, 100)
