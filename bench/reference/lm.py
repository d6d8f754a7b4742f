"""Plain reference of the decoder LM the benchmark serves.

The architecture is the one the program implements for its ``dense``
family, written out in ``jax.numpy`` from its description, with no
kernel, cache, padding or batching: token embedding scaled by sqrt(d),
then per layer a pre-norm causal attention with ALiBi (bias slope_h * (j -
i) on query i, key j), and a pre-norm SwiGLU MLP, each added to the
residual stream; a final RMSNorm and the tied (transposed) embedding as
the output head. RMSNorm is ``x / rms(x) * (1 + scale)`` with eps 1e-6.
Every matmul runs in float32 at ``highest`` precision on weights that are
bfloat16 values (the type the program serves).

This module also owns the weights: ``init_weights`` draws them from a key
at the published shapes (real heads and vocabulary, no padding), and the
family adapter pads them into the program's layout. Nothing here imports
the program.

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


def dims_of(cfg: dict) -> dict:
    m = cfg["model"]
    return {"L": m["n_layers"], "d": m["d_model"], "H": m["n_heads"],
            "hd": m["head_dim"], "f": m["d_ff"], "V": m["vocab"]}


def alibi_slopes(n_heads: int) -> jnp.ndarray:
    """ALiBi slopes (Press et al., 2022): the geometric sequence
    2^(-8/n) ... for n a power of two; otherwise that of the next lower
    power of two followed by every other slope of the one above."""
    def pow2(n):
        start = 2.0 ** (-8.0 / n)
        return [start ** (i + 1) for i in range(n)]
    p = 2 ** math.floor(math.log2(n_heads))
    s = pow2(p)
    if p < n_heads:
        s += pow2(2 * p)[0::2][:n_heads - p]
    return jnp.asarray(s, jnp.float32)


# Init law. Random weights at the usual 0.02 scale give a model that echoes
# its input: with a tied head the current token's own logit exceeds every
# other by several units, greedy decoding repeats it, and no rounding could
# ever change a served token (at full width and 6 layers on the CPU, every
# position echoed and float8 moved no argmax). Projections at 0.04 and
# output projections at 0.08 make each residual branch larger than the
# embedding it joins, so the next token depends on the whole context and
# top logits lie close together, where precision shows (12 layers: no echo,
# the widest gap 0.05 with bf16 operands and 1.30 with float8).
INIT = {"embed": 0.02, "proj": 0.04, "out": 0.08, "norm": 0.1}


def init_weights(key, dims: dict, init: dict = None,
                 dtype=jnp.bfloat16) -> dict:
    """Weights at the published shapes; ``init`` overrides entries of
    ``INIT`` (a configuration may state its own scales)."""
    init = dict(INIT, **(init or {}))
    L, d, H, hd, f, V = (dims[k] for k in ("L", "d", "H", "hd", "f", "V"))
    ks = iter(jax.random.split(key, 12))

    def normal(shape, sd):
        return (sd * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    return {
        "embed": normal((V, d), init["embed"]),
        "final_norm": normal((d,), init["norm"]),
        "layers": {
            "ln1": normal((L, d), init["norm"]),
            "wq": normal((L, d, H, hd), init["proj"]),
            "wk": normal((L, d, H, hd), init["proj"]),
            "wv": normal((L, d, H, hd), init["proj"]),
            "wo": normal((L, H, hd, d), init["out"]),
            "slopes": jnp.broadcast_to(alibi_slopes(H), (L, H)).astype(dtype),
            "ln2": normal((L, d), init["norm"]),
            "w_gate": normal((L, d, f), init["proj"]),
            "w_up": normal((L, d, f), init["proj"]),
            "w_down": normal((L, f, d), init["out"]),
        },
    }


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with an absmax scale along ``axis`` (the
    contraction axis, so one scale per row or output channel)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * (1.0 + scale.astype(jnp.float32))


def _linear(x, w, spec, fp8):
    """``einsum(spec, x, w)`` with x's last axis contracted against w's
    first."""
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum(spec, x, w)


@functools.partial(jax.jit, static_argnames=("fp8",))
def logits(w: dict, tokens, fp8: bool = False):
    """Next-token logits at every position of ``tokens`` (T,): (T, V) f32."""
    with jax.default_matmul_precision("highest"):
        d = w["embed"].shape[1]
        t = tokens.shape[0]
        x = w["embed"][tokens].astype(jnp.float32) * math.sqrt(d)
        i = jnp.arange(t)
        rel = (i[None, :] - i[:, None]).astype(jnp.float32)     # j - i
        causal = i[None, :] <= i[:, None]

        def layer(x, lp):
            h = _rmsnorm(x, lp["ln1"])
            q = _linear(h, lp["wq"], "td,dhe->the", fp8)
            k = _linear(h, lp["wk"], "td,dhe->the", fp8)
            v = _linear(h, lp["wv"], "td,dhe->the", fp8)
            s = jnp.einsum("the,uhe->htu", q, k) / math.sqrt(q.shape[-1])
            s = s + lp["slopes"].astype(jnp.float32)[:, None, None] * rel
            s = jnp.where(causal, s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("htu,uhe->the", p, v)
            x = x + _linear(o.reshape(t, -1), lp["wo"].reshape(-1, d),
                            "tc,cd->td", fp8)
            h = _rmsnorm(x, lp["ln2"])
            g = _linear(h, lp["w_gate"], "td,df->tf", fp8)
            u = _linear(h, lp["w_up"], "td,df->tf", fp8)
            x = x + _linear(jax.nn.silu(g) * u, lp["w_down"], "tf,fd->td",
                            fp8)
            return x, None

        x, _ = jax.lax.scan(layer, x, w["layers"])
        hid = _rmsnorm(x, w["final_norm"])
        return _linear(hid, w["embed"].T, "td,dv->tv", fp8)


@functools.partial(jax.jit, static_argnames=("fp8",))
def served_gaps(w: dict, seq, n_prompt, n_served, fp8: bool = False):
    """Teacher-forced over ``seq`` = prompt + served tokens (padded on the
    right to a fixed length): for each served token, how far its reference
    logit lies below the reference's best at that position; with
    ``fp8=True`` the gap of the token the fp8 control ranks first there
    instead. Returns the gaps, (T,) with zeros outside the served
    positions."""
    ref = logits(w, seq)
    pos = jnp.arange(seq.shape[0])
    # position p predicts token p + 1; served tokens sit at n_prompt ...
    nxt = (jnp.argmax(logits(w, seq, fp8=True), axis=-1) if fp8
           else jnp.roll(seq, -1))
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, nxt[:, None], axis=-1)[:, 0]
    mask = (pos >= n_prompt - 1) & (pos < n_prompt - 1 + n_served)
    return jnp.where(mask, best - got, 0.0)
