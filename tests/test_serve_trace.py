"""Tracing of the serve path: every jitted backend program and Pallas
kernel has a name the device trace shows, the engine's counters count the
prefill work its spans describe, and they survive snapshot and restore.
The spans themselves, read back from a profiler trace, are tested with the
benchmark's readers (tests/bench/test_bench_spans.py)."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import get_model
from repro.models.common import init_params
from repro.serve import ServeEngine
from repro.serve.trace import span, tracing

N_SLOTS, PREFILL_LEN, MAX_LEN = 2, 16, 32


@functools.lru_cache(maxsize=None)
def _model(arch):
    model = get_model(smoke_config(arch))
    return model, init_params(model.template(), jax.random.PRNGKey(0))


def _module(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _token_programs():
    """Module name of every program the token backend jits, lowered on the
    shapes a smoke engine runs: paged with chunked prefill and the prefix
    cache (every program but the contiguous insert), and contiguous."""
    model, params = _model("gpt2_alibi_15b")
    paged = ServeEngine(model, params, max_len=MAX_LEN, n_slots=N_SLOTS,
                        prefill_len=PREFILL_LEN, page_size=4,
                        pages_per_slot=8, prefill_chunk=4,
                        prefix_cache=True).backend
    flat = ServeEngine(model, params, max_len=MAX_LEN, n_slots=N_SLOTS,
                       prefill_len=PREFILL_LEN).backend
    names = {}
    for be in (paged, flat):
        be.ensure_state()
        pf_len = MAX_LEN if be is flat else PREFILL_LEN
        args = (be.params, _i32(N_SLOTS, PREFILL_LEN), None, _i32(N_SLOTS))
        names["_pf"] = _module(be._prefill.lower(*args, max_len=pf_len))
        wave = jax.eval_shape(functools.partial(be._prefill, max_len=pf_len),
                              *args)[1]
        if be is flat:
            names["insert"] = _module(be._insert.lower(
                be._cache, wave, _i32(N_SLOTS)))
            continue
        ids, table = _i32(N_SLOTS), _i32(N_SLOTS, be.pages_per_slot)
        names["decode"] = _module(be._decode.lower(
            be.params, be._cache, be._last_tok, max_pages=1))
        names["insert_paged"] = _module(be._insert_paged.lower(
            be._cache, wave, ids, table))
        names["grow_tables"] = _module(be._grow_tables.lower(
            be._cache, ids, table))
        names["copy_pages"] = _module(be._copy_pages.lower(
            be._cache, ids, ids))
        names["prefill_chunk"] = _module(be._chunk.lower(
            be.params, be._cache, _i32(N_SLOTS, 4), ids, ids, ids,
            max_pages=1))
    return names


def _pair_programs():
    model, params = _model("pairformer_lite")
    be = ServeEngine(model, params, max_len=MAX_LEN,
                     n_slots=N_SLOTS).backend
    be.ensure_state()
    args = (be.params, jax.ShapeDtypeStruct((N_SLOTS, MAX_LEN, 64),
                                            jnp.float32), _i32(N_SLOTS), None)
    wave = jax.eval_shape(functools.partial(be._prefill, max_len=MAX_LEN),
                          *args)[1]
    return {"_pf": _module(be._prefill.lower(*args, max_len=MAX_LEN)),
            "refine": _module(be._step.lower(be.params, be._cache)),
            "insert_factors": _module(be._insert.lower(
                be._cache, wave, _i32(N_SLOTS)))}


@pytest.mark.parametrize("family,programs", [
    ("token", _token_programs), ("pair", _pair_programs)])
def test_backend_programs_are_named(family, programs):
    names = programs()
    assert names == {k: f"jit_{k}" for k in names}
    assert len(names) == {"token": 7, "pair": 3}[family]
    # only admission's prefill program reads as ``_pf`` in the trace
    assert [k for k, v in names.items() if re.search(r"_pf\b", v)] == ["_pf"]


def _prompts(n, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 100, (int(k),)).astype(np.int32)
            for k in rng.randint(3, PREFILL_LEN + 1, n)]


def _engine(**kw):
    model, params = _model("gpt2_alibi_15b")
    return ServeEngine(model, params, max_len=MAX_LEN, n_slots=N_SLOTS,
                       prefill_len=PREFILL_LEN, page_size=4, **kw)


def test_counters_count_prefill_padding():
    eng = _engine()
    prompts = _prompts(5)
    for p in prompts:
        eng.submit(p, 4)
    eng.run()
    c = eng.counters()
    assert c["steps"] == eng.step_idx > 0
    assert c["waves"] >= -(-len(prompts) // N_SLOTS)
    # whole-prompt waves run every slot's row at the pinned prefill_len
    assert c["prefill_rows_x_len"] == c["waves"] * N_SLOTS * PREFILL_LEN
    assert c["prefill_real"] == sum(p.size for p in prompts)
    assert c["preemptions"] == c["quarantines"] == 0


def test_counters_count_chunks():
    eng = _engine(prefill_chunk=4)
    prompts = _prompts(3, seed=5)
    for p in prompts:
        eng.submit(p, 3)
    eng.run()
    c = eng.counters()
    assert c["prefill_real"] == sum(p.size for p in prompts)
    assert c["prefill_rows_x_len"] % (N_SLOTS * 4) == 0
    assert c["prefill_rows_x_len"] >= c["prefill_real"]


def test_pair_counters_count_padded_residues():
    model, params = _model("pairformer_lite")
    eng = ServeEngine(model, params, max_len=MAX_LEN, n_slots=N_SLOTS)
    sizes = [9, 20, 32]
    rng = np.random.RandomState(0)
    for n in sizes:
        eng.submit(rng.standard_normal((n, 64)).astype(np.float32), 2)
    eng.run()
    c = eng.counters()
    assert c["waves"] == 2
    assert c["prefill_rows_x_len"] == 2 * N_SLOTS * MAX_LEN
    assert c["prefill_real"] == sum(sizes)


@pytest.mark.parametrize("bias_mode", ["flashbias", "dense"])
def test_pair_counters_count_svd_solves(bias_mode):
    """Each admission wave factors every layer's (slots, heads) pair-bias
    matrices by SVD in one batched eigensolve; a dense bias cache runs no
    SVD."""
    model, params = _model("pairformer_lite")
    cfg = model.cfg.replace(bias_mode=bias_mode)
    eng = ServeEngine(get_model(cfg), params, max_len=MAX_LEN,
                      n_slots=N_SLOTS)
    rng = np.random.RandomState(1)
    for n in (9, 20, 32):
        eng.submit(rng.standard_normal((n, 64)).astype(np.float32), 1)
    eng.run()
    c = eng.counters()
    svd = bias_mode == "flashbias"
    assert c["waves"] == 2
    assert c["svd_matrices"] == svd * 2 * cfg.n_layers * N_SLOTS * cfg.n_heads
    assert c["svd_solves"] == svd * 2 * cfg.n_layers


def test_counters_survive_snapshot_and_restore():
    eng = _engine()
    for p in _prompts(6, seed=7):
        eng.submit(p, 6)
    for _ in range(5):
        eng.step()
    before = eng.counters()
    assert before["waves"] >= 1 and before["prefill_real"] > 0
    snap = eng.snapshot_engine()
    fresh = _engine()
    fresh.restore_engine(snap)
    assert fresh.counters() == before
    fresh.run()
    after = fresh.counters()
    assert all(after[k] >= before[k] for k in before)
    assert after["waves"] > before["waves"]


def test_spans_run_without_a_profiler():
    assert not tracing()
    with span("serve.test", step=1, live=2) as sp:
        sp.set_metadata(emitted=1)
