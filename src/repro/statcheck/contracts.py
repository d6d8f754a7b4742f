"""Trace-time contract checker for the jitted serve programs.

``check_family`` builds the real serve backend for one cache family at
smoke scale, traces every jitted program it owns — prefill, chunked
prefill, decode step, cache insert, paged insert, page-table growth (LM
families); trunk prefill, refinement step, factor-cache insert
(pairformer) — and runs the :mod:`repro.statcheck.jaxpr_rules` walkers
over each closed jaxpr:

- ``no-pool-relayout`` on the decode programs (the ISSUE-5 tripwire:
  zero Θ(pool) transpose/convert/broadcast per decoded token) and on the
  ISSUE-9 prefix-cache ``copy_pages`` copy-on-write program (a page copy
  must stay a Θ(W·page) gather/scatter),
- ``no-host-callback`` on every program,
- ``eq3-fold`` on the pairformer refinement step when the factored-bias
  path is precision-free (FlashBias Eq. 3: ONE matmul of depth D + R),
- ``recompile-bound`` — an arithmetic audit of the engine's static-arg
  space: the pow2 ``max_pages`` rounding must produce at most
  ``log2(pages_per_slot) + 1`` distinct decode/chunk compile keys.

Tracing is abstract (``jax.jit(...).trace`` over ``ShapeDtypeStruct``
params), so the whole sweep runs in seconds on CPU with no kernels
executed. The default ``attn_impl="pallas_interpret"`` traces the Pallas
dispatch in ``kernels/ops.py`` — the exact decode jaxpr the accelerator
path runs — on CPU CI. ``verify_tripwire`` proves per run that the
relayout rule can still fire, on a synthetic decode-shaped program that
transposes a pool per layer (a tripwire that cannot fire is not a
tripwire).
"""
from __future__ import annotations

from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs import smoke_config
from repro.statcheck.jaxpr_rules import (
    Finding,
    eq3_fold_present,
    no_host_callback,
    no_pool_relayout,
    pool_threshold_for,
)

__all__ = ["FAMILIES", "check_family", "run_contracts", "verify_tripwire"]

# smoke-scale serve dimensions shared by every family check
MAX_LEN = 32
N_SLOTS = 4
PAGE_SIZE = 4
CHUNK = 4
RING_WINDOW = 8        # 0 < window < MAX_LEN -> ring KV
PAIR_MAX_LEN = 16
PAIR_FEATS = 64        # pairformer stub residue-feature width

# family -> smoke ArchConfig. "ring" is the dense arch with a sliding
# window (the ring cache is a cache mode, not a config family).
FAMILIES: Dict[str, Callable] = {
    "dense": lambda: smoke_config("stablelm_12b"),
    "moe": lambda: smoke_config("granite_moe_3b_a800m"),
    "ring": lambda: smoke_config("stablelm_12b").replace(window=RING_WINDOW),
    "ssm": lambda: smoke_config("mamba2_130m"),
    "pairformer": lambda: smoke_config("pairformer_lite"),
}


def _abstract_params(model):
    from repro.models.common import abstract_params
    return abstract_params(model.template())


def _token_backend(cfg):
    from repro.models import get_model
    from repro.serve.backend import TokenDecodeBackend
    model = get_model(cfg)
    params = _abstract_params(model)
    paged = (cfg.family in ("dense", "moe", "hybrid")
             and not (cfg.window and cfg.window < MAX_LEN)
             and model.init_paged_cache is not None)
    kwargs = {"page_size": PAGE_SIZE} if paged else {}
    if model.prefill_chunk is not None:
        kwargs["prefill_chunk"] = CHUNK
    if paged and "prefill_chunk" in kwargs and cfg.family in ("dense", "moe"):
        kwargs["prefix_cache"] = True    # ISSUE 9: trace the CoW program too
    be = TokenDecodeBackend(model, params, max_len=MAX_LEN,
                            n_slots=N_SLOTS, **kwargs)
    be.ensure_state()
    return be


def _decode_caps(be) -> List[Optional[int]]:
    """The static ``max_pages`` values worth tracing: the smallest and the
    largest the engine can ever pass (rules are monotone in between)."""
    if not be.paged:
        return [None]
    lo = be.page_cap({0: SimpleNamespace(length=0)})
    hi = be.page_cap({0: SimpleNamespace(
        length=be.pages_per_slot * be.page_size - 1)})
    return sorted({lo, hi})


def _audit_recompile_bound(be, family: str) -> List[Finding]:
    """Enumerate the REAL engine's static-arg space and assert the
    documented compile bound: the pow2 rounding in ``page_cap`` /
    ``_chunk_page_cap`` may produce at most ``log2(pages_per_slot) + 1``
    distinct keys each (serve/README.md §Cache layout contract)."""
    if not be.paged:
        return []
    bound = be.pages_per_slot.bit_length()
    findings = []
    decode_keys = {be.page_cap({0: SimpleNamespace(length=ln)})
                   for ln in range(be.pages_per_slot * be.page_size)}
    if len(decode_keys) > bound:
        findings.append(Finding(
            rule="recompile-bound", program=f"{family}/decode",
            message=(f"decode max_pages takes {len(decode_keys)} distinct "
                     f"values {sorted(decode_keys)} > documented bound "
                     f"{bound} (log2(pages_per_slot) + 1) — the pow2 "
                     "rounding discipline broke")))
    if be.chunk_size:
        saved = be._pending
        chunk_keys = set()
        try:
            for done in range(1, be.pages_per_slot * be.page_size + 1):
                be._pending = {0: SimpleNamespace(done=done)}
                chunk_keys.add(be._chunk_page_cap())
        finally:
            be._pending = saved
        if len(chunk_keys) > bound:
            findings.append(Finding(
                rule="recompile-bound", program=f"{family}/prefill_chunk",
                message=(f"chunk max_pages takes {len(chunk_keys)} "
                         f"distinct values > documented bound {bound}")))
    return findings


def _check_token_family(family: str, cfg) -> List[Finding]:
    be = _token_backend(cfg)
    cache, params = be._cache, be.params
    ns = be.n_slots
    thresh = pool_threshold_for(cache, cfg.n_layers)
    findings: List[Finding] = []

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    # prefill (wave path): padded prompt of one page / a few tokens
    pf_len = PAGE_SIZE * 2
    toks = sds((ns, pf_len), jnp.int32)
    lens = sds((ns,), jnp.int32)
    traced = {"prefill": be._prefill.trace(params, toks, None, lens,
                                           pf_len)}

    last = sds((ns, 1), jnp.int32)
    for cap in _decode_caps(be):
        traced[f"decode[max_pages={cap}]"] = be._decode.trace(
            params, cache, last, max_pages=cap)

    wave_cache = jax.eval_shape(lambda: be.model.init_cache(ns, MAX_LEN))
    slot_ids = sds((ns,), jnp.int32)
    if be.paged:
        wave_cache = jax.eval_shape(lambda: be.model.init_cache(ns, pf_len))
        tables = sds((ns, be.pages_per_slot), jnp.int32)
        traced["insert_paged"] = be._insert_paged.trace(
            cache, wave_cache, slot_ids, tables)
        traced["grow_tables"] = be._grow_tables.trace(cache, slot_ids,
                                                      tables)
    else:
        traced["insert"] = be._insert.trace(cache, wave_cache, slot_ids)

    if be.chunk_size:
        ctoks = sds((ns, be.chunk_size), jnp.int32)
        offs = sds((ns,), jnp.int32)
        cap = _decode_caps(be)[-1]
        traced["prefill_chunk"] = be._chunk.trace(
            params, cache, ctoks, offs, offs, offs, max_pages=cap)

    if getattr(be, "_prefix", None) is not None:
        ids = sds((ns,), jnp.int32)
        traced["copy_pages"] = be._copy_pages.trace(cache, ids, ids)

    for name, tr in traced.items():
        program = f"{family}/{name}"
        findings += no_host_callback(tr.jaxpr, program=program)
        # the relayout tripwire is a DECODE-step contract: per-token work
        # must be Θ(token), so zero pool-sized transposes. Prefill/chunk
        # programs legitimately transpose Θ(chunk) attention intermediates
        # and amortize them over the whole chunk. copy_pages (the ISSUE-9
        # copy-on-write primitive) is held to the decode standard: it runs
        # at admission inside the serve loop and must stay a Θ(W·page)
        # gather/scatter, never a pool relayout.
        if thresh and (name.startswith("decode") or name == "copy_pages"):
            findings += no_pool_relayout(tr.jaxpr, thresh, program=program)
    findings += _audit_recompile_bound(be, family)
    return findings


def _check_pair_family(family: str, cfg) -> List[Finding]:
    from repro.models import get_model
    from repro.serve.backend import PairBatchBackend
    model = get_model(cfg)
    params = _abstract_params(model)
    be = PairBatchBackend(model, params, max_len=PAIR_MAX_LEN,
                          n_slots=2)
    be.ensure_state()
    ns = be.n_slots

    feats = jax.ShapeDtypeStruct((ns, PAIR_MAX_LEN, PAIR_FEATS),
                                 jnp.float32)
    lens = jax.ShapeDtypeStruct((ns,), jnp.int32)
    slot_ids = jax.ShapeDtypeStruct((ns,), jnp.int32)
    traced = {
        "prefill": be._prefill.trace(params, feats, lens, None,
                                     PAIR_MAX_LEN),
        "step": be._step.trace(params, be._cache),
    }
    wave_cache = jax.eval_shape(
        lambda: model.init_cache(ns, PAIR_MAX_LEN, factors=None))
    traced["insert"] = be._insert.trace(be._cache, wave_cache, slot_ids)

    findings: List[Finding] = []
    for name, tr in traced.items():
        findings += no_host_callback(tr.jaxpr, program=f"{family}/{name}")
    # Eq. 3 fold: only asserted on the precision-free factored path — the
    # refinement step reads the frozen phi_q/phi_k factor cache, so its
    # attention must concat factors onto q/k and run ONE depth-(D+R)
    # matmul (core.attention.flashbias_concat_qk). The fold is an XLA-path
    # construct — the Pallas kernel folds in-kernel instead — so the step
    # is re-traced under attn_impl="xla" specifically for this rule.
    if cfg.bias_mode == "flashbias" and cfg.dtype == "float32":
        if cfg.attn_impl == "xla":
            step_xla = traced["step"]
        else:
            xla_cfg = cfg.replace(attn_impl="xla")
            xla_model = get_model(xla_cfg)
            xla_be = PairBatchBackend(xla_model, _abstract_params(xla_model),
                                      max_len=PAIR_MAX_LEN, n_slots=2)
            xla_be.ensure_state()
            step_xla = xla_be._step.trace(xla_be.params, xla_be._cache)
        head_dim = cfg.d_model // cfg.n_heads
        findings += eq3_fold_present(step_xla.jaxpr, head_dim,
                                     cfg.bias_rank,
                                     program=f"{family}/step[xla]")
    return findings


def check_family(family: str, *,
                 impl: str = "pallas_interpret") -> List[Finding]:
    """Trace every jitted serve program of ``family`` and return all rule
    violations (empty list = contracts hold)."""
    cfg = FAMILIES[family]().replace(attn_impl=impl)
    if cfg.family == "pairformer":
        return _check_pair_family(family, cfg)
    return _check_token_family(family, cfg)


def verify_tripwire(impl: str = "pallas_interpret") -> List[Finding]:
    """Built-in negative test: a decode-shaped program — a ``lax.scan``
    over the layers of the dense family's real page pool that transposes
    each layer's pool — MUST trip ``no-pool-relayout`` at the threshold
    the dense check uses. Returns a finding when it does not: a tripwire
    that cannot fire would pass every future regression too."""
    cfg = FAMILIES["dense"]().replace(attn_impl=impl)
    cache = _token_backend(cfg)._cache
    thresh = pool_threshold_for(cache, cfg.n_layers)
    pool = cache["pages_k"]                 # (L, KVH, n_pages, ps, hd)

    def relayout_step(pools, x):
        def body(x, layer_pool):            # (KVH, n_pages, ps, hd)
            return x + layer_pool.transpose(1, 2, 0, 3).sum(), None
        return jax.lax.scan(body, x, pools)[0]

    traced = jax.jit(relayout_step).trace(
        jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        jax.ShapeDtypeStruct((), pool.dtype))
    hits = [f for f in no_pool_relayout(traced.jaxpr, thresh,
                                        program="dense/decode[relayout]")
            if "transpose" in f.eqn]
    if hits:
        return []
    return [Finding(
        rule="tripwire-self-test", program="dense/decode[relayout]",
        message=("a per-layer pool transpose no longer trips the "
                 "decode-step relayout rule — the tripwire lost its teeth "
                 "(did the pool threshold calibration change?)"))]


def run_contracts(families, *, impl: str = "pallas_interpret",
                  self_test: bool = True) -> List[Finding]:
    """Check ``families`` under one impl; with ``self_test`` also prove
    the relayout tripwire still fires."""
    findings: List[Finding] = []
    for family in families:
        findings += check_family(family, impl=impl)
    if self_test:
        findings += verify_tripwire(impl=impl)
    return findings
