"""Plain reference of the interleaved Mamba2 / attention LM the benchmark
serves: IBM Granite 4.0-H (Hugging Face ``GraniteMoeHybridForCausalLM``
with no experts), written out in ``jax.numpy`` from its description, with
no kernel, cache, padding or batching.

    x = embed[tokens] * embedding_multiplier
    per layer (``layer_types``):
        x = x + residual_multiplier * mixer(rmsnorm(x) * w1)
        x = x + residual_multiplier * swiglu(rmsnorm(x) * w2)
    logits = (rmsnorm(x) * wf) @ embed^T / logits_scaling

- Mamba2 mixer: ``in_proj`` gives z, x, B, C and dt; a causal depthwise
  conv of width ``mamba_d_conv`` with bias over (x, B, C), then SiLU;
  dt = softplus(dt + dt_bias), A = -exp(A_log); the state recurrence
  h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t, run
  one position at a time; the gated RMSNorm norm(y * silu(z)) over all of
  d_inner (``mamba_n_groups`` 1); ``out_proj``.
- Attention mixer: causal, no position embedding, 32 query heads sharing 8
  key/value heads, softmax scale ``attention_multiplier``.
- RMSNorm: ``x / sqrt(mean(x^2) + rms_norm_eps) * w``.

Departures from the Hugging Face code: none in the mathematics. The HF
model runs the SSD recurrence chunk by chunk (``mamba_chunk_size``) and
this reference one position at a time; both are the same recurrence. The
HF code rounds activations to the weights' dtype between modules; this
reference keeps every activation in float32. Every matmul runs in float32
at ``highest`` precision on weights that are bfloat16 values (the type the
program serves). The weights stay bfloat16 in memory and each layer's are
upcast when it runs, so the whole model is never held in float32.

This module also owns the weights: ``init_weights`` draws them from a key
at the published shapes; the family adapter (``bench/families/
hybrid_lm.py``) lays them out as the program's parameters. Nothing here
imports the program.

``fp8=True`` is the control: every linear layer (projections, MLP, output
head) takes float8 e4m3 operands, weights scaled per output channel and
activations per token, the step below the bfloat16 the configuration
states.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


class Dims(dict):
    """Sizes and multipliers, hashable so the jitted layers take them as
    a static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def dims_of(cfg: dict) -> Dims:
    """Sizes from the configuration's published keys."""
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    return Dims({
        "L": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
        "H": cfg["num_attention_heads"], "KV": cfg["num_key_value_heads"],
        "hd": cfg["hidden_size"] // cfg["num_attention_heads"],
        "f": cfg["shared_intermediate_size"], "V": cfg["vocab_size"],
        "kinds": tuple(cfg["layer_types"]),
        "Hm": cfg["mamba_n_heads"], "P": cfg["mamba_d_head"],
        "N": cfg["mamba_d_state"], "W": cfg["mamba_d_conv"], "Di": di,
        "emb": float(cfg["embedding_multiplier"]),
        "res": float(cfg["residual_multiplier"]),
        "logit_div": float(cfg["logits_scaling"]),
        "att": float(cfg["attention_multiplier"]),
        "eps": float(cfg["rms_norm_eps"])})


# Init law (standard deviations; random weights from the seed). With a tied
# head, the current token's own logit carries 12 x |e|^2 from the scaled
# embedding in the residual stream; a larger embedding makes the model echo
# its input (at full width, 10 layers and an embedding at 0.1, 85% of
# positions predicted their own token on the CPU). An embedding at 0.02
# with projections at 0.04 and output projections at 0.08 lets the
# branches outweigh it: at 10 layers no echo (0.6%), top-two logits 0.021
# apart at the median, the widest gap 0.003-0.005 with bfloat16 operands
# and 0.16-0.19 with float8 (CPU, two seeds).
INIT = {"embed": 0.02, "proj": 0.04, "out": 0.08, "norm": 0.1, "conv": 0.3,
        "a_max": 16.0, "dt_min": 1e-3, "dt_max": 0.1}


def init_weights(key, dims: Dims, init: dict = None,
                 dtype=jnp.bfloat16) -> dict:
    """Weights at the published shapes, in the Hugging Face layout: one
    ``in_proj`` of columns [z, x, B, C, dt], a conv over [x, B, C], fused
    [gate, up] MLP input. A_log, dt_bias and D follow Mamba2's own init:
    A uniform in [1, a_max], dt log-uniform in [dt_min, dt_max] through the
    inverse softplus, D one; norm weights are 1 + N(0, norm)."""
    init = dict(INIT, **(init or {}))
    d, f, V = dims["d"], dims["f"], dims["V"]
    H, KV, hd = dims["H"], dims["KV"], dims["hd"]
    Hm, N, W, Di = dims["Hm"], dims["N"], dims["W"], dims["Di"]
    n_m = sum(k == "mamba" for k in dims["kinds"])
    n_a = sum(k == "attention" for k in dims["kinds"])
    ks = iter(jax.random.split(key, 32))

    def normal(shape, sd, mean=0.0):
        return (mean + sd * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    def mlp(n):
        return {"ln2": normal((n, d), init["norm"], 1.0),
                "w_in": normal((n, d, 2 * f), init["proj"]),
                "w_out": normal((n, f, d), init["out"])}

    a = jax.random.uniform(next(ks), (n_m, Hm), jnp.float32, 1.0,
                           init["a_max"])
    dt = jnp.exp(jax.random.uniform(
        next(ks), (n_m, Hm), jnp.float32, math.log(init["dt_min"]),
        math.log(init["dt_max"])))
    return {
        "embed": normal((V, d), init["embed"]),
        "final_norm": normal((d,), init["norm"], 1.0),
        "mamba": dict(mlp(n_m), **{
            "ln1": normal((n_m, d), init["norm"], 1.0),
            "in_proj": normal((n_m, d, 2 * Di + 2 * N + Hm), init["proj"]),
            "conv_w": normal((n_m, W, Di + 2 * N), init["conv"]),
            "conv_b": normal((n_m, Di + 2 * N), init["conv"]),
            "a_log": jnp.log(a).astype(dtype),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "d_skip": jnp.ones((n_m, Hm), dtype),
            "norm": normal((n_m, Di), init["norm"], 1.0),
            "out_proj": normal((n_m, Di, d), init["out"]),
        }),
        "attention": dict(mlp(n_a), **{
            "ln1": normal((n_a, d), init["norm"], 1.0),
            "wq": normal((n_a, d, H * hd), init["proj"]),
            "wk": normal((n_a, d, KV * hd), init["proj"]),
            "wv": normal((n_a, d, KV * hd), init["proj"]),
            "wo": normal((n_a, H * hd, d), init["out"]),
        }),
    }


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale along ``axis`` (the
    contraction axis, so one scale per row or output channel)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, fp8):
    """``x @ w`` in float32 (x (T, in), w (in, out))."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return x @ w


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _mlp(lp, x, dims, fp8):
    h = _rmsnorm(x, lp["ln2"], dims["eps"])
    gu = _linear(h, lp["w_in"], fp8)
    g, u = gu[:, :dims["f"]], gu[:, dims["f"]:]
    return x + dims["res"] * _linear(jax.nn.silu(g) * u, lp["w_out"], fp8)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def mamba_layer(lp: dict, x, dims, fp8: bool = False):
    """One Mamba2 layer with its MLP on the residual stream x (T, d)."""
    with jax.default_matmul_precision("highest"):
        Hm, P, N, W, Di = (dims[k] for k in ("Hm", "P", "N", "W", "Di"))
        t = x.shape[0]
        h = _rmsnorm(x, lp["ln1"], dims["eps"])
        proj = _linear(h, lp["in_proj"], fp8)
        z, xbc, dt = (proj[:, :Di], proj[:, Di:2 * Di + 2 * N],
                      proj[:, 2 * Di + 2 * N:])
        # causal depthwise conv: out_t = sum_i w_i xbc_{t-(W-1)+i} + b
        pad = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1])), xbc])
        cw = lp["conv_w"].astype(jnp.float32)
        xbc = sum(pad[i:i + t] * cw[i] for i in range(W)) \
            + lp["conv_b"].astype(jnp.float32)
        xbc = jax.nn.silu(xbc)
        xs = xbc[:, :Di].reshape(t, Hm, P)
        bm, cm = xbc[:, Di:Di + N], xbc[:, Di + N:]
        dt = jax.nn.softplus(dt + lp["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(lp["a_log"].astype(jnp.float32))

        def step(state, inp):
            x_t, dt_t, b_t, c_t = inp
            state = (state * jnp.exp(dt_t * a)[:, None, None]
                     + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None])
            return state, state @ c_t

        _, y = jax.lax.scan(step, jnp.zeros((Hm, P, N), jnp.float32),
                            (xs, dt, bm, cm))
        y = y + lp["d_skip"].astype(jnp.float32)[None, :, None] * xs
        y = y.reshape(t, Di) * jax.nn.silu(z)
        y = _rmsnorm(y, lp["norm"], dims["eps"])
        x = x + dims["res"] * _linear(y, lp["out_proj"], fp8)
        return _mlp(lp, x, dims, fp8)


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def attention_layer(lp: dict, x, dims, fp8: bool = False):
    """One attention layer with its MLP on the residual stream x (T, d)."""
    with jax.default_matmul_precision("highest"):
        H, KV, hd = dims["H"], dims["KV"], dims["hd"]
        t = x.shape[0]
        h = _rmsnorm(x, lp["ln1"], dims["eps"])
        q = _linear(h, lp["wq"], fp8).reshape(t, KV, H // KV, hd)
        k = _linear(h, lp["wk"], fp8).reshape(t, KV, hd)
        v = _linear(h, lp["wv"], fp8).reshape(t, KV, hd)
        s = jnp.einsum("tkge,uke->kgtu", q, k) * dims["att"]
        i = jnp.arange(t)
        s = jnp.where(i[None, :] <= i[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgtu,uke->tkge", p, v).reshape(t, H * hd)
        x = x + dims["res"] * _linear(o, lp["wo"], fp8)
        return _mlp(lp, x, dims, fp8)


def hidden(w: dict, tokens, dims: Dims, fp8: bool = False):
    """The final normalised hidden state at every position of ``tokens``
    (T,): (T, d) float32. Layers run one at a time, in published order."""
    layers = {"mamba": mamba_layer, "attention": attention_layer}
    x = w["embed"][tokens].astype(jnp.float32) * dims["emb"]
    seen = {"mamba": 0, "attention": 0}
    for kind in dims["kinds"]:
        lp = jax.tree.map(lambda a: a[seen[kind]], w[kind])
        seen[kind] += 1
        x = layers[kind](lp, x, dims, fp8)
    return _rmsnorm(x, w["final_norm"], dims["eps"])


@functools.partial(jax.jit, static_argnames=("dims", "fp8"))
def head(w_embed, hid, dims, fp8: bool = False):
    """Logits (T, V) of final hidden states (T, d)."""
    with jax.default_matmul_precision("highest"):
        return _linear(hid, w_embed.T, fp8) / dims["logit_div"]


def logits(w: dict, tokens, dims: Dims, fp8: bool = False):
    """Next-token logits at every position of ``tokens`` (T,): (T, V)."""
    return head(w["embed"], hidden(w, tokens, dims, fp8), dims, fp8)


def served_gaps(w: dict, seq, n_prompt: int, n_served: int, dims: Dims,
                window: int, fp8: bool = False):
    """Teacher-forced over ``seq`` = prompt + served tokens (padded on the
    right to a fixed length): for each served token, how far its reference
    logit lies below the reference's best at that position; with
    ``fp8=True`` the gap of the token the fp8 control ranks first there
    instead. Logits are taken over a ``window`` of positions from the one
    that predicts the first served token (``window`` >= ``n_served``), so
    the output head never holds every position's logits. Returns the
    gaps, (window,), zeros past the served tokens."""
    start = n_prompt - 1
    hid = jax.lax.dynamic_slice_in_dim(hidden(w, seq, dims), start, window)
    ref = head(w["embed"], hid, dims)
    if fp8:
        hq = jax.lax.dynamic_slice_in_dim(hidden(w, seq, dims, fp8=True),
                                          start, window)
        nxt = jnp.argmax(head(w["embed"], hq, dims, fp8=True), axis=-1)
    else:
        # position p predicts token p + 1
        nxt = jax.lax.dynamic_slice_in_dim(seq, start + 1, window)
    return _gaps(ref, nxt, n_served)


@jax.jit
def _gaps(ref, nxt, n_served):
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
    return jnp.where(jnp.arange(ref.shape[0]) < n_served, best - got, 0.0)
