"""Plain reference of the Pairformer the benchmark serves.

The architecture is the program's ``pairformer`` family, written out in
``jax.numpy`` from its description. A complex of n residues with (n, 64)
features starts from a single rep s = feats @ W_s and a pair rep
z_ij = (feats @ W_z)_i + (feats @ W_z)_j. The admission trunk runs, per
block: an outgoing triangle multiplicative update of z (gate, a and b
projections of RMSNorm(z), z_ij += (sum_k a_ik b_jk / sqrt(N)) @ W_o, with N
the pinned residue padding of the serve wave, ``max_len``); the pair bias
b_h = RMSNorm(z) @ W_b, replaced by its best rank-R approximation
(truncated SVD, R = min(rank, N): FlashBias's approximation of the dense
pair bias, which the configuration states); single-rep attention with that
bias (softmax(q k^T / sqrt(hd) + b_h) v over the n residues, added to s);
a GELU transition of s; a GELU transition of z. Each refinement step then
runs every block's attention (with its frozen bias) and s transition.
RMSNorm is ``x / rms(x) * (1 + scale)`` with eps 1e-6; GELU is the tanh
form. The result of a complex is s after its last step.

Arrays keep the fixed shape N = ``max_len`` with every residue at or past
n masked (keys out of attention, z zero outside the n x n block), so one
compiled program serves every complex size. ``trunk`` is admission,
``step`` one refinement step from a given single rep. Every matmul runs in float32
at ``highest`` precision. ``fp8=True`` is the control: every value the
program keeps in bfloat16 (matmul operands, the s and z streams) is rounded
to float8 e4m3 with an absmax scale per row or channel. Nothing here
imports the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0
FEAT = 64


def dims_of(cfg: dict) -> dict:
    m = cfg["model"]
    return {"L": m["n_layers"], "d": m["d_model"], "H": m["n_heads"],
            "hd": m["head_dim"], "f": m["d_ff"], "dp": m["d_pair"],
            "R": m["bias_rank"], "N": cfg["engine"]["max_len"]}


INIT = {"proj": 0.02, "norm": 0.1}


def init_weights(key, dims: dict, init: dict = None,
                 dtype=jnp.float32) -> dict:
    """Weights at the program's shapes; ``init`` overrides entries of
    ``INIT`` (a configuration may state its own scales)."""
    init = dict(INIT, **(init or {}))
    L, d, H, hd, f, dp = (dims[k] for k in ("L", "d", "H", "hd", "f", "dp"))
    shapes = {
        "tri_ln": (dp,), "tri_a": (dp, dp), "tri_b": (dp, dp),
        "tri_g": (dp, dp), "tri_o": (dp, dp), "ln1": (d,),
        "wqkv": (d, 3, H, hd), "wo": (H, hd, d), "pair_bias_ln": (dp,),
        "pair_bias_w": (dp, H), "ln2": (d,), "wi": (d, f),
        "wo_mlp": (f, d), "pair_ln": (dp,), "pair_wi": (dp, 4 * dp),
        "pair_wo": (4 * dp, dp),
    }
    ks = iter(jax.random.split(key, len(shapes) + 4))

    def normal(shape, sd):
        return (sd * jax.random.normal(next(ks), shape, jnp.float32)
                ).astype(dtype)

    layers = {k: normal((L,) + s, init["norm" if len(s) == 1 else "proj"])
              for k, s in shapes.items()}
    return {"single_in": normal((FEAT, d), init["proj"]),
            "pair_in": normal((FEAT, dp), init["proj"]),
            "layers": layers,
            "final_norm": normal((d,), init["norm"]),
            "out_head": normal((d, 3), init["proj"])}


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


class _Prec:
    """What the control rounds: matmul operands and the streams."""

    def __init__(self, fp8: bool):
        self.fp8 = fp8

    def mm(self, spec, x, w):
        w = w.astype(jnp.float32)
        if self.fp8:
            x, w = _q8(x, -1), _q8(w, 0)
        return jnp.einsum(spec, x, w)

    def stream(self, x):
        return _q8(x, -1) if self.fp8 else x


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * (1.0 + scale)


def _gelu_mlp(pr, x, wi, wo):
    return pr.mm("...f,fd->...d", jax.nn.gelu(pr.mm("...d,df->...f", x, wi)),
                 wo)


def _truncate(bias, rank: int):
    """Best rank-``rank`` approximation of each head's (N, N) bias."""
    u, sv, vt = jnp.linalg.svd(bias, full_matrices=False)
    return jnp.einsum("hnr,hr,hrm->hnm", u[..., :rank], sv[..., :rank],
                      vt[..., :rank, :])


def _attend(pr, lp, s, bias, valid):
    h = _rmsnorm(s, lp["ln1"])
    qkv = pr.mm("nd,dthe->tnhe", h, lp["wqkv"])
    q, k, v = qkv[0], qkv[1], qkv[2]
    logit = jnp.einsum("nhe,mhe->hnm", q, k) / math.sqrt(q.shape[-1]) + bias
    logit = jnp.where(valid[None, None, :], logit, -jnp.inf)
    o = jnp.einsum("hnm,mhe->nhe", jax.nn.softmax(logit, axis=-1), v)
    s = pr.stream(s + pr.mm("nc,cd->nd", o.reshape(o.shape[0], -1),
                            lp["wo"].reshape(-1, lp["wo"].shape[-1])))
    return pr.stream(s + _gelu_mlp(pr, _rmsnorm(s, lp["ln2"]), lp["wi"],
                                   lp["wo_mlp"]))


@functools.partial(jax.jit, static_argnames=("rank", "fp8"))
def trunk(w: dict, feats, n, rank: int, fp8: bool = False):
    """Admission of one complex: (single rep after the trunk (N, d), each
    block's rank-``rank`` bias (L, H, N, N)); ``feats`` is (N, 64) with
    rows >= ``n`` ignored."""
    pr = _Prec(fp8)
    with jax.default_matmul_precision("highest"):
        big_n = feats.shape[0]
        valid = jnp.arange(big_n) < n
        pair_ok = (valid[:, None] & valid[None, :])[..., None]
        feats = jnp.where(valid[:, None], feats, 0.0)
        s = pr.stream(pr.mm("nf,fd->nd", feats, w["single_in"]))
        zi = pr.mm("nf,fc->nc", feats, w["pair_in"])
        z = pr.stream(jnp.where(pair_ok, zi[:, None] + zi[None, :], 0.0))

        def block(carry, lp):
            s, z = carry
            zl = _rmsnorm(z, lp["tri_ln"])
            a = (jax.nn.sigmoid(pr.mm("ijc,cd->ijd", zl, lp["tri_g"]))
                 * pr.mm("ijc,cd->ijd", zl, lp["tri_a"]))
            b = pr.mm("ijc,cd->ijd", zl, lp["tri_b"])
            upd = jnp.einsum("ikc,jkc->ijc", pr.stream(a), pr.stream(b)) \
                / math.sqrt(big_n)
            z = pr.stream(z + pr.mm("ijc,cd->ijd", upd, lp["tri_o"]))
            zb = _rmsnorm(z, lp["pair_bias_ln"])
            bias = jnp.einsum("ijc,ch->hij", zb,
                              lp["pair_bias_w"].astype(jnp.float32))
            bias = _truncate(bias, rank)
            s = _attend(pr, lp, s, bias, valid)
            z = pr.stream(z + _gelu_mlp(pr, _rmsnorm(z, lp["pair_ln"]),
                                        lp["pair_wi"], lp["pair_wo"]))
            return (s, z), bias

        (s, _), biases = jax.lax.scan(block, (s, z), w["layers"])
        return s, biases


@functools.partial(jax.jit, static_argnames=("fp8",))
def step(w: dict, s, biases, n, fp8: bool = False):
    """One refinement step of a complex from its single rep ``s`` (N, d):
    every block's attention with its frozen bias, and its transition."""
    pr = _Prec(fp8)
    with jax.default_matmul_precision("highest"):
        valid = jnp.arange(s.shape[0]) < n

        def block(s, inp):
            lp, bias = inp
            return _attend(pr, lp, s, bias, valid), None

        s, _ = jax.lax.scan(block, pr.stream(s), (w["layers"], biases))
        return s
