"""Chunked prefill + streaming serve (ISSUE 7).

The chunked-prefill contract under test: admission becomes a host-side
planner (``ChunkPlan``) and prompts land ``prefill_chunk`` tokens per
engine step, interleaved with decode — and NOTHING observable changes.
Greedy and sampled outputs are bit-identical to the whole-prompt engine
(the PRNG chain is armed at plan time and only advances for decoding
slots), for contiguous, paged (factors ride ``pages_phi``) and ring-KV
(chunk clamped to the window, prefill wrapping the ring) cache families.

Streaming rides the same PR: ``submit(on_token=...)`` delivers each
emitted id as it is committed (token backends) or drains the per-step
state (pair backend), and the callback lives on the request descriptor so
preemption/resume keeps the stream attached.

Priority classes x paged preemption x chunked prefill: a lowest-class
victim caught MID-CHUNK returns its original request whole (zero tokens
generated -> nothing folded into the resumed prompt, partial chunk writes
are dead because the slot's committed length is still 0), its pages drain
back to the pool, and the resumed run is bit-identical to the
never-preempted engine.
"""
import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import get_model
from repro.models.common import init_params
from repro.serve import SamplingParams, ServeEngine
from repro.serve.scheduler import ChunkPlan, Request


def _model(arch):
    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = init_params(model.template(), jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lens, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab, (n,)).astype(np.int32) for n in lens]


def _traffic(engine, prompts, budget=6, temp=0.0, streams=None):
    """Staggered arrivals: half queue up front, the rest join one per
    step while earlier requests are mid-decode/mid-chunk."""
    rids = []

    def submit(i):
        cb = None if streams is None else streams.setdefault(i, []).append
        rids.append(engine.submit(
            prompts[i], budget,
            sampling=SamplingParams(temp, 0, seed=i), on_token=cb))

    for i in range(len(prompts) // 2):
        submit(i)
    i = len(prompts) // 2
    while len(engine.scheduler) or engine.occupancy or i < len(prompts):
        if i < len(prompts):
            submit(i)
            i += 1
        engine.step()
    return [engine.result(r) for r in rids]


# ---------------------------------------------------------------------------
# ChunkPlan: the host-side prompt cursor
# ---------------------------------------------------------------------------

def test_chunk_plan_walks_the_prompt():
    req = Request(0, np.arange(11, dtype=np.int32), 4)
    plan = ChunkPlan(req)
    assert plan.remaining == 11
    off, toks, last = plan.next_chunk(4)
    assert (off, last) == (0, False) and (toks == np.arange(4)).all()
    off, toks, last = plan.next_chunk(4)
    assert (off, last) == (4, False) and (toks == np.arange(4, 8)).all()
    off, toks, last = plan.next_chunk(4)        # ragged final chunk
    assert (off, last) == (8, True) and (toks == np.arange(8, 11)).all()
    assert plan.remaining == 0


# ---------------------------------------------------------------------------
# Bit-identity: chunked engine == whole-prompt engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,paged", [
    ("stablelm_12b", False),          # full-KV contiguous
    ("stablelm_12b", True),           # paged pools + page tables
    ("gpt2_alibi_15b", True),         # ALiBi factors ride pages_phi
])
def test_chunked_matches_whole_prompt_engine(arch, paged):
    cfg, model, params = _model(arch)
    kw = {"max_len": 48, "n_slots": 3}
    if paged:
        kw.update(page_size=4, pages_per_slot=12)
    prompts = _prompts(cfg, (13, 6, 17, 9, 5), seed=2)
    whole = _traffic(ServeEngine(model, params, **kw), prompts)
    streams = {}
    chunked = _traffic(
        ServeEngine(model, params, prefill_chunk=5, **kw), prompts,
        streams=streams)
    for i, (a, b) in enumerate(zip(whole, chunked)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
        # streaming delivered every committed token, in order
        np.testing.assert_array_equal(np.asarray(streams[i], np.int32), b)


def test_chunked_ring_kv_prompt_longer_than_window():
    """Ring-KV family (hymba: sliding-window ring + SSM state): the chunk
    is clamped to the window and a prompt LONGER than the window must
    wrap the ring mid-prefill exactly like the whole-prompt path."""
    cfg, model, params = _model("hymba_15b")
    assert cfg.window and cfg.window < 48     # ring is actually engaged
    kw = {"max_len": 48, "n_slots": 2}
    prompts = _prompts(cfg, (36, 10, 21), seed=5)   # 36 > window
    whole = _traffic(ServeEngine(model, params, **kw), prompts)
    chunked = _traffic(
        ServeEngine(model, params, prefill_chunk=8, **kw), prompts)
    for i, (a, b) in enumerate(zip(whole, chunked)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")


@pytest.mark.parametrize("paged", [False, True])
def test_chunked_sampled_prng_chain_parity(paged):
    """Sampled decode: keys are armed at PLAN time and committed only for
    decoding slots, so the per-request PRNG chain advances identically
    whether the prompt landed whole or in chunks."""
    cfg, model, params = _model("stablelm_12b")
    kw = {"max_len": 48, "n_slots": 3}
    if paged:
        kw.update(page_size=4, pages_per_slot=12)
    prompts = _prompts(cfg, (12, 7, 15, 6), seed=3)
    whole = _traffic(ServeEngine(model, params, **kw), prompts, temp=0.8)
    chunked = _traffic(
        ServeEngine(model, params, prefill_chunk=4, **kw), prompts,
        temp=0.8)
    for i, (a, b) in enumerate(zip(whole, chunked)):
        np.testing.assert_array_equal(a, b, err_msg=f"request {i}")


def test_unchunked_backend_reports_no_pending():
    """prefill_chunk=None keeps the legacy whole-prompt admission: no
    chunk planner, no pending slots, no prefill_step in the engine loop."""
    cfg, model, params = _model("stablelm_12b")
    eng = ServeEngine(model, params, max_len=32, n_slots=2)
    eng.submit(_prompts(cfg, (9,))[0], 4)
    eng.step()
    assert not eng.backend.prefill_pending()
    assert not list(eng.backend.pending_slots())
    eng.run()


# ---------------------------------------------------------------------------
# Priority classes x paged preemption x chunked prefill (mid-chunk victim)
# ---------------------------------------------------------------------------

def _priority_run(model, params, cfg, preempt_mid_chunk):
    """High-class request decoding, low-class long prompt admitted and
    caught mid-chunk; optionally preempt (default victim) right there."""
    eng = ServeEngine(model, params, max_len=48, n_slots=2,
                      prefill_chunk=4, page_size=4, pages_per_slot=12)
    p_hi, p_lo = _prompts(cfg, (6, 18), seed=9)
    streams = {"lo": []}
    r_hi = eng.submit(p_hi, 10, priority=1)
    for _ in range(3):                 # 2 chunks to land + 1 decode step
        eng.step()
    r_lo = eng.submit(p_lo, 6, priority=0,
                      on_token=streams["lo"].append)
    eng.step()                         # admit + first chunk, hi decodes
    if preempt_mid_chunk:
        (slot, plan), = eng.backend._pending.items()
        assert plan.req.rid == r_lo
        assert 0 < plan.done < plan.req.tokens.size      # mid-chunk
        assert eng.preempt() == r_lo   # lowest class wins the eviction
        assert eng.n_preemptions == 1
        # the victim generated nothing: its snapshot is the ORIGINAL
        # request, whole — nothing folded, full budget intact
        resumed = eng.scheduler.peek()
        assert resumed.rid == r_lo and resumed.max_new_tokens == 6
        np.testing.assert_array_equal(resumed.tokens, p_lo)
    eng.run()
    assert eng.backend._pool.n_free == eng.n_pages       # pages drained
    return eng.result(r_hi), eng.result(r_lo), streams["lo"]


def test_mid_chunk_preemption_resumes_bit_identical():
    cfg, model, params = _model("stablelm_12b")
    hi0, lo0, _ = _priority_run(model, params, cfg, preempt_mid_chunk=False)
    hi1, lo1, stream = _priority_run(model, params, cfg,
                                     preempt_mid_chunk=True)
    np.testing.assert_array_equal(hi0, hi1)
    np.testing.assert_array_equal(lo0, lo1)
    # the stream callback rode the descriptor through preemption: the
    # resumed request delivered every token exactly once
    np.testing.assert_array_equal(np.asarray(stream, np.int32), lo1)
