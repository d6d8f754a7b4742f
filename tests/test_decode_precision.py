"""The paged decode kernel's factored-bias product runs at full f32
precision.

At Mosaic's default precision the MXU rounds both operands of an f32 dot to
bf16. The ALiBi key factor is the row (1, pos): bf16 holds every integer
position only up to 256, so past it the bias errs by up to 2 x slope per
key (served logit gaps of 2.6-4.7 on the chip). Interpret mode computes f32
dots exactly, so a numerical test on the CPU cannot see the rounding: these
tests read the precision off the kernel body that Mosaic lowers.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_decode import flash_decode_paged_fwd


def _kernel_dots(fn, *args):
    """Precision of every dot_general inside the Pallas kernel bodies of
    ``fn(*args)``'s jaxpr."""
    out = []

    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general" and inside:
                out.append(eqn.params["precision"])
            for p in eqn.params.values():
                for sub in (p if isinstance(p, tuple) else (p,)):
                    sub = getattr(sub, "jaxpr", sub)       # ClosedJaxpr
                    if hasattr(sub, "eqns"):
                        walk(sub, inside
                             or eqn.primitive.name == "pallas_call")
    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return out


def _paged_args(bias: str):
    b, kvh, g, d, n_pages, ps = 2, 2, 8, 128, 4, 128
    q = jnp.zeros((b, kvh, g, d), jnp.bfloat16)
    pool = jnp.zeros((kvh, n_pages, ps, d), jnp.bfloat16)
    lengths = jnp.asarray([300, 500], jnp.int32)
    table = jnp.asarray([[0, 1, 2, 3], [3, 2, 1, 0]], jnp.int32)
    kw = {}
    if bias == "phi":
        kw = dict(phi_q=jnp.zeros((b, kvh, g, 128), jnp.float32),
                  phi_pages=jnp.zeros((1, n_pages, ps, 128), jnp.float32))
    return (q, pool, pool, lengths, table), kw


def test_factor_dot_is_highest_precision():
    args, kw = _paged_args("phi")
    dots = _kernel_dots(lambda *a: flash_decode_paged_fwd(*a, scale=0.125,
                                                          **kw), *args)
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    # q k^T, the factor product, p v: only the factor product needs f32
    assert len(dots) == 3
    assert dots.count(highest) == 1


def test_bias_free_kernel_keeps_default_precision():
    args, _ = _paged_args("none")
    dots = _kernel_dots(lambda *a: flash_decode_paged_fwd(*a, scale=0.125),
                        *args)
    assert len(dots) == 2 and all(p is None for p in dots)


@pytest.mark.parametrize("length", [257, 300, 700])
def test_alibi_phi_decode_past_256_matches_float64(length):
    """Paged phi-mode decode (the ALiBi serve path) against a float64
    softmax at positions where bf16 factors would be wrong."""
    rng = np.random.default_rng(length)
    h, d, ps = 4, 64, 128
    n_pages = -(-length // ps)
    q = rng.normal(size=(1, 1, h, d)).astype(np.float32)
    k = rng.normal(size=(length, h, d)).astype(np.float32)
    v = rng.normal(size=(length, h, d)).astype(np.float32)
    slopes = 2.0 ** (-8.0 * np.arange(1, h + 1) / h)
    pool_k = np.zeros((h, n_pages, ps, 128), np.float32)
    pool_v = np.zeros_like(pool_k)
    for j in range(length):
        pool_k[:, j // ps, j % ps, :d] = k[j]
        pool_v[:, j // ps, j % ps, :d] = v[j]
    pos = np.arange(n_pages * ps, dtype=np.float32)
    slab = np.zeros((n_pages, ps, 128), np.float32)
    slab[..., 0] = 1.0
    slab[..., 1] = pos.reshape(n_pages, ps)
    phi_q = np.zeros((1, 1, h, 2), np.float32)
    phi_q[..., 0] = -(length - 1) * slopes
    phi_q[..., 1] = slopes
    out = ops.flash_decode(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v),
        jnp.asarray([length], jnp.int32), phi_q=jnp.asarray(phi_q),
        phi_k=jnp.asarray(slab), impl="pallas_interpret",
        page_table=jnp.arange(n_pages, dtype=jnp.int32)[None])
    s = np.einsum("hd,thd->ht", q[0, 0].astype(np.float64),
                  k.astype(np.float64)) / np.sqrt(d)
    s += slopes[:, None] * (np.arange(length) - (length - 1))[None, :]
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("ht,thd->hd", p, v.astype(np.float64))
    np.testing.assert_allclose(np.asarray(out[0, 0, :, :d], np.float64), ref,
                               atol=2e-3)
