#!/usr/bin/env python3
"""Smoke run of the FlashBias serve path on TPU, through its entry points.

    python chip_smoke.py [--seed N]             # one chip
    python chip_smoke.py --chips 4 [--seed N]   # the sharded path only

Everything runs in one process, phase by phase, and the first failure ends
the run with a non-zero exit code. Earlier lines of standard output report
progress; they are smoke output, not benchmark metrics. The last line is the
JSON result ``{"ok": true, "device": {...}}``.

One chip:

1. device — the first JAX device must be a TPU. There is no CPU fallback.
2. lm-serve — ``gpt2_alibi_15b`` at its published widths (48 layers,
   d_model 1600, 50 -> 64 heads of 32, vocab 50257, exact rank-2 ALiBi)
   serves seeded requests through ``ServeEngine`` with paged KV. Every
   request must end OK; the engine's prefill and decode programs must
   contain the Pallas kernels (``tpu_custom_call``).
3. lm-check — one ALiBi causal attention call at the model's prefill shapes
   against float64 on the host; then one request's prefill logits and one
   paged decode step's logits under ``attn_impl="pallas"`` against
   ``attn_impl="xla"``.
4. pair — ``pairformer_lite`` at its config widths serves complexes through
   ``ServeEngine`` (the factored-bias ragged kernel), against the same
   complexes under ``attn_impl="xla"``.

``--chips 4``: ``codeqwen15_7b`` on a 1x4 ``(data, model)`` mesh — its bf16
weights alone exceed one chip's memory — serves requests with paged KV, and
one request's logits are checked against ``attn_impl="xla"`` on the same
mesh. The decode program must hold the kernel (inside ``shard_map``) and no
all-gather as large as a layer's pool shard.

The system under test always runs ``attn_impl="pallas"`` over the
kv-head-major KV caches in the config's bf16. The reference is
``attn_impl="xla"`` computing in f32 at matmul precision ``"highest"`` on
the same weights, and the bf16 ``attn_impl="xla"`` path, as served, measures
how far bf16 alone moves the output (the floor). No phase uses ``"auto"`` or
interpret mode. Weights are random, from ``--seed``, held in bf16 (the
compute dtype every matmul casts them to anyway).
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.bias import alibi_slopes  # noqa: E402
from repro.dist import Rules, make_mesh, use_mesh_rules  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.models.common import (  # noqa: E402
    cast_floats, init_params, param_shardings)
from repro.serve import ServeEngine  # noqa: E402
from repro.statcheck.mesh_rules import collective_sizes  # noqa: E402

SYSTEM, REFERENCE = "pallas", "xla"
# The runs compared, as (attn_impl, compute dtype, matmul precision); None
# keeps the config's dtype (bf16) and JAX's default precision.
RUNS = (
    (SYSTEM, None, None),                # the system, as served
    (REFERENCE, "float32", "highest"),   # the reference: XLA in f32
    (REFERENCE, None, None),             # XLA as served: the bf16 floor
)
# Tolerances, as relative L2 error against the reference.
# One attention call against float64: its output is rounded to bf16 once
# (2^-9 relative), so 2^-8 allows twice that.
KERNEL_TOL = 2.0 ** -8
# Model outputs: bf16 roundings compound over the layers of a random-weight
# model, so the bf16 XLA path itself is 1.2e-2 to 2.0e-2 from the f32
# reference on a v5e (gpt2_alibi_15b, pairformer_lite, codeqwen15_7b). The
# system may be NOISE_FACTOR times as far from the reference as that path.
NOISE_FACTOR = 2.0


def say(msg: str) -> None:
    print(msg, flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(name: str, ok: bool, detail: str) -> None:
    say(f"[{name}] {'ok' if ok else 'FAILED'}: {detail}")
    if not ok:
        raise SystemExit(f"chip_smoke: {name} failed: {detail}")


def check_close(name: str, what: str, got, want, floor) -> None:
    """The system's ``got`` against the reference ``want``, within
    NOISE_FACTOR times the bf16 XLA path's own error ``floor``."""
    err, noise = rel_err(got, want), rel_err(floor, want)
    tol = NOISE_FACTOR * noise
    check(name, err <= tol,
          f"{what} against the f32 xla reference: pallas rel L2 err "
          f"{err:.3e}, bf16 xla {noise:.3e} (tol {tol:.3e})")


def configured(cfg, impl: str, dtype):
    cfg = cfg.replace(attn_impl=impl)
    return cfg.replace(dtype=dtype) if dtype else cfg


def weights_in(params, dtype):
    """``params`` cast on the device for a run computing in ``dtype`` (None:
    as they are). Handing an f32 run bf16 weights would let XLA keep an f32
    copy of every layer's weights as a temporary, which one chip cannot hold
    for gpt2_alibi_15b's decode."""
    if dtype is None:
        return params
    return jax.jit(functools.partial(cast_floats, dtype=dtype))(params)


def precision(prec):
    return (jax.default_matmul_precision(prec) if prec
            else contextlib.nullcontext())


def device_info() -> dict:
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    say(f"[device] platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")
    check("device", info["platform"] == "tpu",
          f"first device is {info['platform']!r}; this smoke needs a TPU")
    return info


def init_weights(model, seed: int, dtype=jnp.bfloat16, mesh=None):
    """Random weights made on the device (sharded there under ``mesh``)."""
    tmpl = model.template()
    out = (None if mesh is None
           else param_shardings(tmpl, mesh, Rules()))
    make = jax.jit(lambda key: cast_floats(init_params(tmpl, key), dtype),
                   out_shardings=out)
    return jax.block_until_ready(make(jax.random.PRNGKey(seed)))


def lm_config(arch: str, impl: str = SYSTEM, dtype=None):
    return configured(get_config(arch), impl, dtype)


def seeded_prompts(rng, vocab: int, n: int, lo: int, hi: int):
    return [rng.integers(0, vocab, (int(k),)).astype(np.int32)
            for k in rng.integers(lo, hi + 1, n)]


def has_kernel(hlo: str) -> bool:
    return "tpu_custom_call" in hlo


def serve_lm(model, params, prompts, new_tokens: int, *, n_slots: int,
             page_size: int, prefill_len: int, mesh=None):
    """Serve ``prompts`` twice through one engine: the first pass compiles
    every program the traffic needs (set-up), the second is timed. Both
    must end every request OK with the same greedy tokens."""
    max_len = prefill_len + new_tokens
    engine = ServeEngine(model, params, max_len=max_len, n_slots=n_slots,
                         prefill_len=prefill_len, page_size=page_size,
                         pages_per_slot=-(-max_len // page_size), mesh=mesh)
    passes = []
    for _ in range(2):
        t0 = time.perf_counter()
        rids = [engine.submit(p, new_tokens) for p in prompts]
        engine.run()
        passes.append((time.perf_counter() - t0,
                       [engine.result(r) for r in rids]))
    (setup_s, first), (serve_s, second) = passes
    statuses = sorted({r.status for r in first + second})
    n_tok = sum(r.size for r in second)
    say(f"[lm-serve] {model.cfg.name}: {len(prompts)} requests (prompts "
        f"{min(p.size for p in prompts)}-{max(p.size for p in prompts)} "
        f"tokens, {new_tokens} greedy new tokens) over {n_slots} slots, "
        f"paged KV (page_size {page_size}); smoke output, not metrics: "
        f"set-up pass (compiles) {setup_s:.1f} s, timed pass {n_tok} "
        f"tokens in {serve_s:.2f} s")
    check("lm-serve", statuses == ["OK"] and n_tok == len(prompts)
          * new_tokens, f"statuses {statuses}, {n_tok} tokens served")
    same = all(np.array_equal(a, b) for a, b in zip(first, second))
    check("lm-serve", same, "second pass reproduced the first pass's "
          "greedy tokens" if same else "passes disagree")
    return engine


def engine_programs(engine, prompt) -> tuple:
    """HLO of the prefill and decode programs the engine ran."""
    be = engine.backend
    ns, pf_len = be.n_slots, -(-be.prefill_len // be.page_size) * be.page_size
    toks = jnp.zeros((ns, be.prefill_len), jnp.int32)
    lengths = jnp.full((ns,), prompt.size, jnp.int32)
    prefill = be._prefill.lower(be.params, toks, None, lengths,
                                pf_len).compile().as_text()
    decode = be._decode.lower(be.params, be._cache, be._last_tok,
                              max_pages=be.pages_per_slot).compile().as_text()
    return prefill, decode


def check_attention_kernel(heads: int, head_dim: int, n: int, seed: int):
    """One ALiBi causal attention call on bf16 inputs, the kernel and XLA
    each against float64 on the host."""
    rng = np.random.default_rng(seed)
    qkv = [jnp.asarray(rng.standard_normal((1, heads, n, head_dim)),
                       jnp.bfloat16) for _ in range(3)]
    slopes = alibi_slopes(heads)
    qf, kf, vf = (np.asarray(x, np.float64) for x in qkv)
    pos = np.arange(n)
    s = np.einsum("bhnd,bhmd->bhnm", qf, kf) / math.sqrt(head_dim)
    s += (np.asarray(slopes, np.float64)[:, None, None]
          * (pos[None, :] - pos[:, None]))
    s = np.where(pos[None, :] <= pos[:, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    exact = np.einsum("bhnm,bhmd->bhnd", p / p.sum(-1, keepdims=True), vf)
    errs = {}
    for impl in (SYSTEM, REFERENCE):
        fn = functools.partial(ops.flash_attention, slopes=slopes,
                               mask_kind="causal", impl=impl, layout="bhsd")
        errs[impl] = rel_err(jax.jit(fn)(*qkv), exact)
    check("lm-check", errs[SYSTEM] <= KERNEL_TOL,
          f"ALiBi causal attention ({heads} heads x {head_dim}, {n} tokens) "
          f"vs float64: pallas rel L2 err {errs[SYSTEM]:.3e}, xla "
          f"{errs[REFERENCE]:.3e} (tol {KERNEL_TOL:.3e})")


def lm_logits(model, params, prompt, page_size: int, next_tok=None):
    """(prefill logits, decode logits, prefill HLO, decode HLO) of one
    request through the model's entry points: ragged prefill, paged insert,
    one paged decode step feeding ``next_tok`` (default: the prefill's
    greedy token)."""
    n = prompt.size
    pf_len = -(-n // page_size) * page_size
    n_pages = pf_len // page_size + 1
    toks = jnp.asarray(prompt[None])
    lengths = jnp.asarray([n], jnp.int32)
    prefill = jax.jit(lambda p, t, l: model.prefill(
        p, {"tokens": t}, max_len=pf_len, lengths=l)).lower(
            params, toks, lengths).compile()
    logits0, wave = prefill(params, toks, lengths)
    cache = model.init_paged_cache(1, n_pages, page_size, n_pages)
    cache = jax.jit(model.insert_paged)(
        cache, wave, jnp.zeros((1,), jnp.int32),
        jnp.arange(n_pages, dtype=jnp.int32)[None])
    vocab = model.cfg.vocab
    if next_tok is None:
        next_tok = int(jnp.argmax(logits0[0, -1, :vocab]))
    tok = jnp.full((1, 1), next_tok, jnp.int32)
    decode = jax.jit(model.decode).lower(params, cache, tok).compile()
    logits1, _ = decode(params, cache, tok)
    return (np.asarray(logits0[0, -1, :vocab], np.float32),
            np.asarray(logits1[0, -1, :vocab], np.float32),
            prefill.as_text(), decode.as_text())


def check_lm_logits(arch: str, params, prompt, page_size: int, tag: str,
                    mesh=None) -> tuple:
    """System vs reference logits for one request; returns the system's
    (prefill HLO, decode HLO)."""
    results, tok = [], None
    for impl, dtype, prec in RUNS:   # each run decodes the system's token
        model = get_model(lm_config(arch, impl, dtype))
        with (use_mesh_rules(mesh, Rules()) if mesh is not None
              else contextlib.nullcontext()), precision(prec):
            results.append(lm_logits(model, weights_in(params, dtype),
                                     prompt, page_size, tok))
        if tok is None:
            tok = int(np.argmax(results[0][0]))
    system, ref, floor = results
    check(tag, not any(has_kernel(hlo) for hlo in ref[2:] + floor[2:]),
          "the xla reference programs hold no Pallas kernel")
    for i, name in enumerate(("prefill", "decode")):
        check_close(tag, f"{name} logits of a {prompt.size}-token request "
                    f"(argmax {int(np.argmax(system[i]))} vs "
                    f"{int(np.argmax(ref[i]))})", system[i], ref[i], floor[i])
    return system[2], system[3]


def phase_lm(seed: int) -> None:
    arch, page_size = "gpt2_alibi_15b", 128
    model = get_model(lm_config(arch, SYSTEM))
    t0 = time.perf_counter()
    params = init_weights(model, seed)
    say(f"[lm-serve] {arch}: {model.cfg.n_layers} layers, d_model "
        f"{model.cfg.d_model}, {model.cfg.heads_padded} heads x "
        f"{model.cfg.resolved_head_dim}, vocab {model.cfg.vocab}; bf16 "
        f"weights made on chip in {time.perf_counter() - t0:.1f} s")
    say("[lm-serve] note: whole prompts are admitted, so prefill attention "
        "runs the Pallas kernel; chunked prefill (prefill_chunk > 0) attends "
        "through XLA by design (ops.flash_chunk_attention) and is not run "
        "here")
    rng = np.random.default_rng(seed)
    prompts = seeded_prompts(rng, model.cfg.vocab, 8, 64, 256)
    engine = serve_lm(model, params, prompts, 32, n_slots=4,
                      page_size=page_size, prefill_len=256)
    prefill_hlo, decode_hlo = engine_programs(engine, prompts[0])
    check("lm-serve", has_kernel(prefill_hlo) and has_kernel(decode_hlo),
          "tpu_custom_call (the Pallas kernels) in the engine's prefill and "
          "decode programs")
    del engine
    check_attention_kernel(model.cfg.heads_padded,
                           model.cfg.resolved_head_dim, 256, seed)
    check_lm_logits(arch, params, prompts[0], page_size, "lm-check")


def phase_pair(seed: int) -> None:
    arch = "pairformer_lite"
    rng = np.random.default_rng(seed + 1)
    sizes = [int(n) for n in rng.integers(256, 385, 4)]
    feats = [rng.standard_normal((n, 64)).astype(np.float32) for n in sizes]
    steps = 2
    cfg = get_config(arch)
    params = init_weights(get_model(cfg), seed, dtype=jnp.float32)
    out = []
    for impl, dtype, prec in RUNS:
        model = get_model(configured(cfg, impl, dtype))
        t0 = time.perf_counter()
        with precision(prec):
            engine = ServeEngine(model, weights_in(params, dtype),
                                 max_len=384, n_slots=2)
            rids = [engine.submit(f, steps) for f in feats]
            engine.run()
        res = [engine.result(r) for r in rids]
        statuses = sorted({r.status for r in res})
        check("pair", statuses == ["OK"],
              f"{impl} in {model.cfg.dtype}: {len(res)} complexes (n_res "
              f"{sizes}) x {steps} refinement steps, statuses {statuses}, "
              f"{time.perf_counter() - t0:.1f} s with compiles (smoke "
              f"output, not a metric)")
        out.append(res)
    say(f"[pair] {arch}: {cfg.n_layers} blocks, d {cfg.d_model}, d_pair "
        f"{cfg.d_pair}, {cfg.n_heads} heads x {cfg.resolved_head_dim}, "
        f"rank {cfg.bias_rank}")
    for n, got, want, floor in zip(sizes, *out):
        check_close("pair", f"n_res {n}: single rep", got, want, floor)


def phase_sharded(seed: int) -> None:
    arch, page_size = "codeqwen15_7b", 128
    n = len(jax.devices())
    check("sharded", n >= 4, f"{n} devices; --chips 4 needs 4")
    mesh = make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])
    model = get_model(lm_config(arch, SYSTEM))
    cfg = model.cfg
    params = init_weights(model, seed, mesh=mesh)
    say(f"[sharded] {arch}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.heads_padded} heads x {cfg.resolved_head_dim}, vocab "
        f"{cfg.vocab}; bf16 weights sharded over a (data 1, model 4) mesh")
    rng = np.random.default_rng(seed)
    prompts = seeded_prompts(rng, cfg.vocab, 8, 64, 256)
    engine = serve_lm(model, params, prompts, 16, n_slots=4,
                      page_size=page_size, prefill_len=256, mesh=mesh)
    with use_mesh_rules(mesh, Rules()):
        prefill_hlo, decode_hlo = engine_programs(engine, prompts[0])
    pool = engine.backend._cache["pages_k"]
    shard = math.prod(pool.shape[1:]) // 4     # one layer's pool, per chip
    del engine
    found = collective_sizes(decode_hlo)
    say(f"[sharded] decode program collectives (largest result, elements): "
        f"{found or 'none'}; one layer's pool shard is {shard} elements")
    check("sharded", has_kernel(prefill_hlo) and has_kernel(decode_hlo),
          "tpu_custom_call in the sharded prefill and decode programs")
    gathered = found.get("all-gather", 0)
    check("sharded", gathered < shard,
          f"largest all-gather {gathered} elements < pool shard {shard}")
    check_lm_logits(arch, params, prompts[0], page_size, "sharded",
                    mesh=mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded codeqwen15_7b path")
    args = ap.parse_args(argv)
    info = device_info()
    say(f"[setup] compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        phase_sharded(args.seed)
    else:
        phase_lm(args.seed)
        phase_pair(args.seed)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
