"""Pairformer-lite: attention with pair-representation bias (AF3, Sec. 4.4).

Structure per block (a faithful-in-shape reduction of AF3's Pairformer):

1. triangle multiplicative update (outgoing) on the pair rep z (B,N,N,Dp),
2. single-rep attention whose logits take an additive bias PROJECTED FROM z
   — the dynamic, per-sample bias that motivates the paper's *neural
   decomposition* (Table 1 row c),
3. transition MLPs on both representations.

``bias_mode``:
- "dense"     — project z -> (B,H,N,N) bias and add to logits (official path),
- "flashbias" — token-wise factor MLPs phi_q/phi_k approximate the projected
  bias (Eq. 5); inputs are row/col summaries of z + the single rep, matching
  App. H Table 12 ("sum of row and column in pair representation" + single).

``fit_factor_mlps`` runs the paper's fine-tuning loop: freeze the trunk,
minimize || phi_q phi_k^T - bias ||^2 on sampled inputs.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import flags
from repro.configs.base import ArchConfig
from repro.core.decomp import svd_factors, svd_solves
from repro.dist.sharding import constrain
from repro.kernels import ops as kops
from repro.models.common import PDef, gelu_mlp, rmsnorm, stack_layers

__all__ = ["pairformer_template", "forward", "denoise_loss",
           "factor_mlp_template", "fit_factor_mlps",
           "init_serve_cache", "serve_prefill", "serve_step",
           "insert_serve_cache_at_slots"]


def pairformer_template(cfg: ArchConfig) -> dict:
    d, dp, h, f = cfg.d_model, cfg.d_pair, cfg.n_heads, cfg.d_ff
    hd = cfg.resolved_head_dim
    layer = {
        # triangle multiplicative update (outgoing)
        "tri_ln": PDef((dp,), (None,), ("zeros",)),
        "tri_a": PDef((dp, dp), (None, None)),
        "tri_b": PDef((dp, dp), (None, None)),
        "tri_g": PDef((dp, dp), (None, None)),
        "tri_o": PDef((dp, dp), (None, None)),
        # single attention with pair bias
        "ln1": PDef((d,), (None,), ("zeros",)),
        "wqkv": PDef((d, 3, h, hd), ("fsdp", None, "heads", None)),
        "wo": PDef((h, hd, d), ("heads", None, "fsdp")),
        "pair_bias_ln": PDef((dp,), (None,), ("zeros",)),
        "pair_bias_w": PDef((dp, h), (None, "heads")),
        # transitions
        "ln2": PDef((d,), (None,), ("zeros",)),
        "wi": PDef((d, f), ("fsdp", "mlp")),
        "wo_mlp": PDef((f, d), ("mlp", "fsdp")),
        "pair_ln": PDef((dp,), (None,), ("zeros",)),
        "pair_wi": PDef((dp, 4 * dp), (None, None)),
        "pair_wo": PDef((4 * dp, dp), (None, None)),
    }
    return {
        "single_in": PDef((64, d), (None, "fsdp")),   # residue-feature stub
        "pair_in": PDef((64, dp), (None, None)),
        "layers": stack_layers(layer, cfg.n_layers),
        "final_norm": PDef((d,), (None,), ("zeros",)),
        "out_head": PDef((d, 3), ("fsdp", None)),     # coordinate denoise stub
    }


def factor_mlp_template(cfg: ArchConfig, hidden: int = 256) -> dict:
    """Token-wise factor MLPs (App. H Table 12): 3 linear layers, tanh."""
    h, r = cfg.n_heads, cfg.bias_rank
    din = cfg.d_pair + cfg.d_model          # row/col pair summary + single
    def mlp():
        return {
            "w0": PDef((din, hidden), (None, None)),
            "b0": PDef((hidden,), (None,), ("zeros",)),
            "w1": PDef((hidden, hidden), (None, None)),
            "b1": PDef((hidden,), (None,), ("zeros",)),
            "w2": PDef((hidden, h * r), (None, None)),
            "b2": PDef((h * r,), (None,), ("zeros",)),
        }
    return {"q": mlp(), "k": mlp()}


def _factor_apply(fp: dict, x: jax.Array, heads: int, rank: int):
    y = jnp.tanh(x @ fp["w0"] + fp["b0"])
    y = jnp.tanh(y @ fp["w1"] + fp["b1"])
    y = y @ fp["w2"] + fp["b2"]
    return y.reshape(*y.shape[:-1], heads, rank)


def _triangle_update(lp, z):
    """Outgoing triangle multiplicative update: z_ij += sum_k a_ik * b_jk.
    Weights cast to z's dtype, so a bf16 pair rep stays bf16 across the
    layer scan."""
    dt = z.dtype
    zl = rmsnorm(z, lp["tri_ln"])
    a = (jax.nn.sigmoid(zl @ lp["tri_g"].astype(dt))
         * (zl @ lp["tri_a"].astype(dt)))
    b = zl @ lp["tri_b"].astype(dt)
    upd = jnp.einsum("bikc,bjkc->bijc", a, b) / float(np.sqrt(z.shape[2]))
    return z + upd @ lp["tri_o"].astype(dt)


def _pair_transition(lp, z):
    dt = z.dtype
    return z + gelu_mlp(rmsnorm(z, lp["pair_ln"]), lp["pair_wi"].astype(dt),
                        lp["pair_wo"].astype(dt))


def _pair_bias(lp, z, n_heads):
    """Project pair rep -> per-head additive bias (B, H, N, N)."""
    zb = rmsnorm(z, lp["pair_bias_ln"])
    return jnp.einsum("bijc,ch->bhij", zb, lp["pair_bias_w"])


def _factor_inputs(z, s):
    """Row/col pair summaries + single rep (App. H Table 12)."""
    row = z.mean(axis=2)           # (B,N,Dp)
    col = z.mean(axis=1)           # (B,N,Dp)
    return jnp.concatenate([row + col, s], axis=-1)


def _single_attention(lp, s, z, cfg: ArchConfig, factors_l=None):
    dt = s.dtype
    h = rmsnorm(s, lp["ln1"])
    qkv = jnp.einsum("bnd,dthe->tbnhe", h, lp["wqkv"].astype(dt))
    q, k, v = qkv[0], qkv[1], qkv[2]
    if cfg.bias_mode == "flashbias" and factors_l is not None:
        feats = _factor_inputs(z, h).astype(jnp.float32)
        pq = _factor_apply(factors_l["q"], feats, cfg.n_heads, cfg.bias_rank)
        pk = _factor_apply(factors_l["k"], feats, cfg.n_heads, cfg.bias_rank)
        o = kops.flash_attention(q, k, v, pq.astype(jnp.float32),
                                 pk.astype(jnp.float32), impl=cfg.attn_impl)
    else:
        from repro.core.attention import attention as core_attn
        bias = _pair_bias(lp, z, cfg.n_heads).astype(jnp.float32)
        o = core_attn(q, k, v, bias=bias, impl="chunked",
                      chunk_size=cfg.attn_chunk)
    return s + jnp.einsum("bnhe,hed->bnd", o, lp["wo"].astype(dt))


def forward(params, feats, cfg: ArchConfig, factors: Optional[dict] = None):
    """feats: (B, N, 64) residue features (stub). Returns (B, N, 3) coords."""
    dt = jnp.dtype(cfg.dtype)
    s = jnp.einsum("bnf,fd->bnd", feats.astype(dt), params["single_in"].astype(dt))
    z = jnp.einsum("bnf,fc->bnc", feats.astype(dt), params["pair_in"].astype(dt))
    z = z[:, :, None, :] + z[:, None, :, :]        # outer-sum init

    def body(carry, inp):
        s, z = carry
        lp, fl = inp if factors is not None else (inp, None)
        z = _triangle_update(lp, z)
        s = _single_attention(lp, s, z, cfg, fl)
        s = s + gelu_mlp(rmsnorm(s, lp["ln2"]), lp["wi"].astype(dt),
                         lp["wo_mlp"].astype(dt))
        z = _pair_transition(lp, z)
        return (s, z), None

    xs = (params["layers"], factors) if factors is not None else params["layers"]
    (s, z), _ = jax.lax.scan(body, (s, z), xs,
                         unroll=flags.scan_unroll(cfg.n_layers))
    s = rmsnorm(s, params["final_norm"])
    return jnp.einsum("bnd,dc->bnc", s, params["out_head"].astype(dt))


def denoise_loss(params, batch, cfg: ArchConfig, factors=None):
    pred = forward(params, batch["feats"], cfg, factors).astype(jnp.float32)
    return jnp.mean((pred - batch["coords"].astype(jnp.float32)) ** 2)


# ---------------------------------------------------------------------------
# Batched serve path (ISSUE 6): admission precomputes per-complex bias state
# ONCE; every refinement step reuses it from the slot cache.
#
# A serve "request" is one complex: its (n_res, 64) residue features. The
# admission trunk pass runs the full Pairformer once (triangle updates, pair
# transitions — z evolves exactly as in ``forward``) and captures, per layer,
# the attention bias STATE in one of three forms:
#
# - "mlp"   — factor MLP outputs phi_q/phi_k (L, B, N, H, R)  [Eq. 5],
# - "svd"   — truncated-SVD factors of the projected dense bias, same
#             shapes [Sec. 4.3; rank = cfg.bias_rank so the SVD jits],
# - "dense" — the projected bias itself (L, B, H, N, N)
#             [``bias_mode="dense"``] — the strongest dense baseline: one
#             projection amortized at admission, steps only stream it,
# - "pair"  — the per-layer pair rep itself (L, B, N, N, Dp)
#             [``bias_mode="dense_recompute"``] — the OFFICIAL dataflow
#             (the paper's Table 6 baseline): every step re-projects the
#             bias from z at use, exactly as AF3's pair-bias attention
#             does, trading Θ(N²·Dp·H) re-projection FLOPs + a Dp/H-times
#             larger cache for zero admission-time bias work.
#
# z is DISCARDED after admission (the memory win: Θ((N+M)R) per layer rides
# in the cache instead of Θ(N²) pair state + Θ(N²H) bias), and each serve
# step is one refinement iteration over the single representation: scan all
# L layers of pair-biased attention + transition with the frozen factors.
#
# Batching contract: every wave pads to the SAME n_res_max (the engine pins
# it to max_len), and every op here is batch-row independent, so a complex's
# trajectory is bit-identical whether it runs alone or packed with strangers
# — the Pairformer analogue of the LM path's pinned ``prefill_len``.
# Factor-MLP biases are nonzero at zero-padded residues (the MLPs carry
# biases), so attention masks keys at positions >= the slot's n_res via the
# ``lengths`` vector — exp(MASK - m) underflows to exactly 0.0 in f32, so
# padded keys contribute exact zero.
# ---------------------------------------------------------------------------


def _serve_mode(cfg: ArchConfig, factors) -> str:
    if cfg.bias_mode == "dense":
        return "dense"
    if cfg.bias_mode == "dense_recompute":
        return "pair"
    return "mlp" if factors is not None else "svd"


def _attend_cached(lp, s, bias_state, cfg: ArchConfig, lengths):
    """One pair-biased attention over the single rep from CACHED bias state
    (factor pair or dense bias) — shared verbatim by the admission trunk
    and the serve step, so the two can never diverge."""
    dt = s.dtype
    h = rmsnorm(s, lp["ln1"])
    qkv = jnp.einsum("bnd,dthe->tbnhe", h, lp["wqkv"].astype(dt))
    q, k, v = qkv[0], qkv[1], qkv[2]
    if isinstance(bias_state, tuple):
        pq, pk = bias_state                        # (B, N, H, R) f32
        o = kops.flash_attention(q, k, v, pq, pk, impl=cfg.attn_impl,
                                 lengths=lengths)
    else:
        from repro.core.attention import attention as core_attn
        o = core_attn(q, k, v, bias=bias_state, kv_length=lengths,
                      impl="chunked", chunk_size=cfg.attn_chunk)
    return s + jnp.einsum("bnhe,hed->bnd", o, lp["wo"].astype(dt))


def _serve_rank(cfg: ArchConfig, n: int, mode: str) -> int:
    """Factor width of the serve cache: the factor MLPs emit exactly
    ``bias_rank`` columns, but an SVD of an (n, n) bias has at most n."""
    return cfg.bias_rank if mode == "mlp" else min(cfg.bias_rank, n)


def admission_svds(cfg: ArchConfig, batch: int, max_len: int,
                   factors=None) -> tuple:
    """(matrices, eigensolver calls) of one admission wave's SVDs: each
    layer factors the (batch, heads, max_len, max_len) pair bias; (0, 0)
    unless the cache holds SVD factors."""
    if _serve_mode(cfg, factors) != "svd":
        return 0, 0
    shape = (batch, cfg.n_heads, max_len, max_len)
    return (cfg.n_layers * batch * cfg.n_heads,
            cfg.n_layers * svd_solves(shape))


def init_serve_cache(cfg: ArchConfig, batch: int, max_len: int,
                     factors=None) -> dict:
    """Zeroed pair slot cache. ``length`` doubles as the active mask
    (0 = retired slot, frozen by ``serve_step``). ``factors`` only selects
    the factor width (MLP factors are fixed-rank; SVD rank caps at
    ``max_len``) — the fitted params themselves are not read here."""
    dt = jnp.dtype(cfg.dtype)
    ln, h, d = cfg.n_layers, cfg.n_heads, cfg.d_model
    cache = {"s": jnp.zeros((batch, max_len, d), dt),
             "length": jnp.zeros((batch,), jnp.int32)}
    mode = _serve_mode(cfg, factors)
    if mode == "dense":
        cache["bias"] = jnp.zeros((ln, batch, h, max_len, max_len),
                                  jnp.float32)
    elif mode == "pair":
        cache["z"] = jnp.zeros((ln, batch, max_len, max_len, cfg.d_pair),
                               dt)
    else:
        r = _serve_rank(cfg, max_len, mode)
        cache["phi_q"] = jnp.zeros((ln, batch, max_len, h, r), jnp.float32)
        cache["phi_k"] = jnp.zeros((ln, batch, max_len, h, r), jnp.float32)
    return cache


def serve_prefill(params, batch, cfg: ArchConfig, factors=None, *,
                  max_len=None, lengths=None):
    """Admission trunk pass over a padded wave of complexes.

    batch: {"feats": (B, N_pad, 64)} with rows zero-padded past each
    complex's n_res; ``lengths`` (B,) the true n_res (0 for padding rows).
    Returns (None, wave_cache) — the wave cache rows scatter into the slot
    cache via ``insert_serve_cache_at_slots``.
    """
    feats = batch["feats"]
    b, n = feats.shape[0], feats.shape[1]
    dt = jnp.dtype(cfg.dtype)
    if lengths is None:
        lengths = jnp.full((b,), n, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    mode = _serve_mode(cfg, factors)

    valid = jnp.arange(n)[None, :] < lengths[:, None]          # (B, N)
    s = jnp.einsum("bnf,fd->bnd", feats.astype(dt),
                   params["single_in"].astype(dt))
    s = jnp.where(valid[..., None], s, 0)
    z = jnp.einsum("bnf,fc->bnc", feats.astype(dt),
                   params["pair_in"].astype(dt))
    z = z[:, :, None, :] + z[:, None, :, :]
    # zero the pair rep outside the valid n_res x n_res block: the outer
    # sum leaks z_i into (i, j_pad), and the triangle update contracts over
    # ALL k — unmasked, padded k would contaminate valid entries. Zeroed
    # once here it STAYS zero: rmsnorm(0) = 0 kills the triangle gates and
    # the pair transition has no biases.
    z = jnp.where((valid[:, :, None] & valid[:, None, :])[..., None], z, 0)

    def body(carry, inp):
        s, z = carry
        lp, fl = inp if mode == "mlp" else (inp, None)
        z = _triangle_update(lp, z)
        if mode == "mlp":
            h = rmsnorm(s, lp["ln1"])
            fx = _factor_inputs(z, h).astype(jnp.float32)
            state = (_factor_apply(fl["q"], fx, cfg.n_heads, cfg.bias_rank),
                     _factor_apply(fl["k"], fx, cfg.n_heads, cfg.bias_rank))
        elif mode == "svd":
            bias = _pair_bias(lp, z, cfg.n_heads).astype(jnp.float32)
            pq_h, pk_h = svd_factors(bias,
                                     rank=_serve_rank(cfg, n, mode))
            # (B, H, N, R) each -> residue-major (B, N, H, R)
            state = (pq_h.transpose(0, 2, 1, 3), pk_h.transpose(0, 2, 1, 3))
        elif mode == "pair":
            state = z                  # post-triangle z, as forward() uses
        else:
            state = _pair_bias(lp, z, cfg.n_heads).astype(jnp.float32)
        attn_state = (_pair_bias(lp, state, cfg.n_heads)
                      .astype(jnp.float32) if mode == "pair" else state)
        s = _attend_cached(lp, s, attn_state, cfg, lengths)
        s = s + gelu_mlp(rmsnorm(s, lp["ln2"]), lp["wi"].astype(dt),
                         lp["wo_mlp"].astype(dt))
        z = _pair_transition(lp, z)
        return (s, z), state

    xs = ((params["layers"], factors) if mode == "mlp"
          else params["layers"])
    (s, _), states = jax.lax.scan(body, (s, z), xs,
                                  unroll=flags.scan_unroll(cfg.n_layers))
    cache = {"s": s, "length": lengths}
    if mode == "dense":
        cache["bias"] = states
    elif mode == "pair":
        cache["z"] = states
    else:
        cache["phi_q"], cache["phi_k"] = states
    return None, cache


def serve_step(params, cache, cfg: ArchConfig):
    """One refinement iteration over every live slot: scan all L layers of
    single-rep attention with the CACHED per-layer bias state (no triangle
    update, no factor recompute — the per-complex factors were paid for
    once at admission). Retired slots (length 0) are frozen."""
    s0, lengths = cache["s"], cache["length"]
    dt = s0.dtype
    if "bias" in cache:
        states = cache["bias"]
    elif "z" in cache:
        states = cache["z"]            # official dataflow: project at use
    else:
        states = (cache["phi_q"], cache["phi_k"])

    pair_mode = "z" in cache

    def body(s, inp):
        lp, state = inp
        if pair_mode:
            # (B, N, N, Dp) pair rep -> re-project the bias at use
            state = _pair_bias(lp, state, cfg.n_heads).astype(jnp.float32)
        s = _attend_cached(lp, s, state, cfg, lengths)
        s = s + gelu_mlp(rmsnorm(s, lp["ln2"]), lp["wi"].astype(dt),
                         lp["wo_mlp"].astype(dt))
        return s, None

    s, _ = jax.lax.scan(body, s0, (params["layers"], states),
                        unroll=flags.scan_unroll(cfg.n_layers))
    active = (lengths > 0)[:, None, None]
    return dict(cache, s=jnp.where(active, s, s0))


def insert_serve_cache_at_slots(dst: dict, src: dict, slots) -> dict:
    """Scatter prefilled wave rows into the slot cache. ``s``/``length``
    lead with the batch axis; bias state leads with the layer axis (the
    slot axis is second). Out-of-range slot ids drop (padding rows)."""
    slots = jnp.asarray(slots, jnp.int32)
    out = {}
    for key, v in dst.items():
        if key in ("s", "length"):
            out[key] = v.at[slots].set(src[key].astype(v.dtype), mode="drop")
        else:
            out[key] = v.at[:, slots].set(src[key].astype(v.dtype),
                                          mode="drop")
    return out


def fit_factor_mlps(key, params, factor_params, sample_feats, cfg: ArchConfig,
                    *, steps: int = 300, lr: float = 1e-3):
    """Paper's fine-tuning (Eq. 5): match phi_q phi_k^T to the projected bias
    of every layer, trunk frozen. Returns (fitted factors, loss history)."""
    dt = jnp.dtype(cfg.dtype)

    def layer_ctx(feats):
        """Replay the trunk to collect (z, s) at each layer's attention."""
        s = jnp.einsum("bnf,fd->bnd", feats.astype(dt), params["single_in"].astype(dt))
        z = jnp.einsum("bnf,fc->bnc", feats.astype(dt), params["pair_in"].astype(dt))
        z = z[:, :, None, :] + z[:, None, :, :]
        ctxs = []
        n_layers = cfg.n_layers
        for i in range(n_layers):
            lp = jax.tree.map(lambda p: p[i], params["layers"])
            z = _triangle_update(lp, z)
            h = rmsnorm(s, lp["ln1"])
            ctxs.append((jnp.asarray(z), h, lp))
            s = _single_attention(lp, s, z, cfg, None)
            s = s + gelu_mlp(rmsnorm(s, lp["ln2"]), lp["wi"].astype(dt),
                             lp["wo_mlp"].astype(dt))
            z = _pair_transition(lp, z)
        return ctxs

    ctxs = layer_ctx(sample_feats)

    def loss_fn(fp):
        total = 0.0
        for i, (z, h, lp) in enumerate(ctxs):
            fl = jax.tree.map(lambda p: p[i], fp)
            target = _pair_bias(lp, z, cfg.n_heads)          # (B,H,N,N)
            feats_i = _factor_inputs(z, h).astype(jnp.float32)
            pq = _factor_apply(fl["q"], feats_i, cfg.n_heads, cfg.bias_rank)
            pk = _factor_apply(fl["k"], feats_i, cfg.n_heads, cfg.bias_rank)
            pred = jnp.einsum("bnhr,bmhr->bhnm", pq, pk)
            total = total + jnp.mean((pred - target) ** 2)
        return total / len(ctxs)

    # plain Adam
    b1, b2, eps = 0.9, 0.999, 1e-8
    mu = jax.tree.map(jnp.zeros_like, factor_params)
    nu = jax.tree.map(jnp.zeros_like, factor_params)

    @jax.jit
    def step(fp, mu, nu, t):
        loss, g = jax.value_and_grad(loss_fn)(fp)
        mu = jax.tree.map(lambda m, gg: b1 * m + (1 - b1) * gg, mu, g)
        nu = jax.tree.map(lambda n, gg: b2 * n + (1 - b2) * gg * gg, nu, g)
        def upd(p, m, n):
            mh = m / (1 - b1 ** t)
            nh = n / (1 - b2 ** t)
            return p - lr * mh / (jnp.sqrt(nh) + eps)
        return jax.tree.map(upd, fp, mu, nu), mu, nu, loss

    losses = []
    fp = factor_params
    for t in range(1, steps + 1):
        fp, mu, nu, loss = step(fp, mu, nu, jnp.asarray(t, jnp.float32))
        losses.append(float(loss))
    return fp, losses
