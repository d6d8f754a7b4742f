"""The interleaved Mamba2 / attention LM (granite_4_0_h_micro at SMOKE size)
against the plain reference (bench/reference/hybrid_lm.py) on seeded
random weights, on the CPU: prefill logits, paged decode after prefill
against the reference's full forward pass, chunked against whole prefill,
and preempt -> resume through the serve engine.

The program runs the configuration's float32 here with the Pallas kernels
in interpret mode (flash prefill, the SSD chunk scan, paged decode), so
the tolerances only have to cover float32 summation order: the kernels
and the reference add the same terms in different orders (chunked vs
sequential SSD scan, online vs whole softmax), 5 layers deep. Logits are
below 0.1 in size at the smoke init; float32 moves them by about 1e-7.
The tolerance is 2e-5, a hundred times that; the same program in bfloat16
(operands of 3 significant digits) misses it by 40-90 times (errors of
8e-4-2e-3 over four prompts), which the last test checks.
"""
import functools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import common  # noqa: E402
from repro.configs import smoke_config  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402

REF = common.load_module("reference", "hybrid_lm")
FAM = common.load_module("families", "hybrid_lm")
CFG = json.loads((ROOT / "tests" / "bench" / "data" /
                  "hybrid_smoke.json").read_text())
DIMS = REF.dims_of(CFG)
ATOL = 2e-5


def _program(dtype="float32", **kw):
    pcfg = smoke_config("granite_4_0_h_micro").replace(
        attn_impl="pallas_interpret", dtype=dtype, **kw)
    return get_model(pcfg), pcfg


@functools.lru_cache(maxsize=None)
def _weights(seed=0):
    return REF.init_weights(jax.random.PRNGKey(seed), DIMS, CFG["init"],
                            dtype=jnp.float32)


def _params(pcfg, seed=0):
    w = jax.tree.map(lambda a: a.astype(pcfg.dtype), _weights(seed))
    return FAM.to_program(w, pcfg)


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(0, DIMS["V"], n).astype(
        np.int32)


def _ref_logits(tokens):
    return np.asarray(REF.logits(_weights(), jnp.asarray(tokens), DIMS))


def test_layout_matches_the_program_template():
    model, pcfg = _program()
    from repro.models.common import is_pdef
    want = jax.tree.map(lambda p: p.shape, model.template(), is_leaf=is_pdef)
    assert jax.tree.map(lambda a: a.shape, _params(pcfg)) == want


def test_prefill_logits_match_reference():
    model, pcfg = _program()
    params = _params(pcfg)
    lengths = [21, 13]
    toks = np.zeros((2, 24), np.int32)
    rows = [_tokens(n, 10 + i) for i, n in enumerate(lengths)]
    for i, r in enumerate(rows):
        toks[i, :r.size] = r
    logits, _ = model.prefill(params, {"tokens": jnp.asarray(toks)},
                              max_len=32, lengths=jnp.asarray(lengths))
    for i, r in enumerate(rows):
        np.testing.assert_allclose(np.asarray(logits[i, 0]),
                                   _ref_logits(r)[-1], atol=ATOL)


@pytest.mark.parametrize("prompt_len", [7, 16])
def test_paged_decode_matches_reference_forward(prompt_len):
    """Prefill a prompt into the paged cache, then decode the rest of the
    sequence one token at a time: every step's logits against the
    reference's full forward pass at that position (the prompt ends
    mid-page and on a page boundary)."""
    model, pcfg = _program()
    params = _params(pcfg)
    seq = _tokens(30, 3)
    ref = _ref_logits(seq)
    cache = model.init_paged_cache(2, 8, 8, 4)
    _, wave = model.prefill(params, {"tokens": jnp.asarray(
        np.pad(seq[:prompt_len], (0, 16 - prompt_len))[None])},
        max_len=16, lengths=jnp.asarray([prompt_len]))
    cache = model.insert_paged(cache, wave, jnp.asarray([1]),
                               jnp.asarray([[5, 2, 7, 0]]))
    decode = jax.jit(model.decode)
    for t in range(prompt_len, 30):
        x = jnp.asarray([[0], [seq[t]]], jnp.int32)
        logits, cache = decode(params, cache, x)
        np.testing.assert_allclose(np.asarray(logits[1, 0]), ref[t],
                                   atol=ATOL)
    assert int(cache["length"][0]) == 0          # the idle slot stayed idle


def test_chunked_prefill_matches_whole_prefill():
    model, pcfg = _program()
    params = _params(pcfg)
    seq = _tokens(19, 4)
    ref = _ref_logits(seq)
    cache = model.init_paged_cache(2, 8, 8, 4)
    cache = model.grow_page_table(cache, jnp.asarray([0, 2]),
                                  jnp.asarray([[3, 1, 6, 8], [8] * 4]))
    chunk = jax.jit(model.prefill_chunk)
    for off in range(0, 19, 8):
        c = seq[off:off + 8]
        toks = np.zeros((2, 8), np.int32)
        toks[0, :c.size] = c
        final = off + 8 >= 19
        logits, cache = chunk(params, cache, jnp.asarray(toks),
                              jnp.asarray([off, 0]),
                              jnp.asarray([c.size, 0]),
                              jnp.asarray([19 if final else -1, -1]))
    np.testing.assert_allclose(np.asarray(logits[0, 0]), ref[18], atol=ATOL)
    _, cache = model.decode(params, cache, jnp.asarray([[seq[0]], [0]]))
    assert int(cache["length"][0]) == 20 and int(cache["length"][1]) == 0


def test_preempt_and_resume_serve_the_same_tokens():
    """A request preempted mid-decode resumes by re-prefilling its prompt
    and the tokens it had served, rebuilding its SSM state: it serves what
    an undisturbed run serves."""
    model, pcfg = _program()
    params = _params(pcfg)
    prompts = [_tokens(n, 20 + n) for n in (9, 14, 5)]

    def serve(preempt_at=None):
        eng = ServeEngine(model, params, max_len=64, n_slots=2,
                          prefill_len=32, page_size=8, n_pages=16,
                          pages_per_slot=8, page_reservation="whole",
                          prefill_rows=1)
        rids = [eng.submit(p, 10) for p in prompts]
        step = 0
        while eng.occupancy or len(eng.scheduler):
            eng.step()
            step += 1
            if step == preempt_at:
                assert eng.preempt(rids[0]) is not None
        return [list(eng.result(r)) for r in rids], eng.counters()

    plain, _ = serve()
    moved, counters = serve(preempt_at=5)
    assert counters["preemptions"] == 1
    assert moved == plain
    assert counters["state_bytes_per_slot"] > 0
    assert counters["paged_layers"] == 2


def test_bfloat16_misses_the_tolerance():
    model, pcfg = _program(dtype="bfloat16")
    params = _params(pcfg)
    seq = _tokens(21, 10)
    logits, _ = model.prefill(params, {"tokens": jnp.asarray(seq[None])},
                              max_len=24)
    err = np.abs(np.asarray(logits[0, 0], np.float32) - _ref_logits(seq)[-1])
    assert err.max() > 10 * ATOL
