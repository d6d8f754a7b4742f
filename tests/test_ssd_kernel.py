"""SSD Pallas kernel (interpret mode) vs the pure-jnp ssd_scan oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ssd_scan import ssd_scan_fwd
from repro.models import ssd


def _mk(key, bsz, s, h, p, n):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (bsz, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    b = jax.random.normal(ks[3], (bsz, s, n))
    c = jax.random.normal(ks[4], (bsz, s, n))
    return x, dt, a, b, c


@pytest.mark.parametrize("s,chunk", [(16, 4), (32, 8), (64, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_oracle(s, chunk, dtype):
    x, dt, a, b, c = _mk(jax.random.PRNGKey(0), 2, s, 3, 8, 4)
    # compare against the oracle on the SAME quantized inputs, so bf16 cases
    # measure kernel error rather than input-quantization error
    xq = x.astype(dtype).astype(jnp.float32)
    dtq = dt.astype(dtype).astype(jnp.float32)
    bq = b.astype(dtype).astype(jnp.float32)
    cq = c.astype(dtype).astype(jnp.float32)
    y_ref, _ = ssd.ssd_scan(xq, dtq, a, bq, cq, chunk=chunk)

    y_k, _ = ssd_scan_fwd(
        x.transpose(0, 2, 1, 3).astype(dtype),       # (B,H,S,P)
        dt.transpose(0, 2, 1)[..., None].astype(dtype),
        a[:, None],
        b[:, None].astype(dtype),                    # (B,1,S,N)
        c[:, None].astype(dtype),
        chunk=chunk, interpret=True)
    y_k = y_k.transpose(0, 2, 1, 3)                  # back to (B,S,H,P)
    if dtype == jnp.bfloat16:
        # the kernel also WRITES y in bf16: quantize the oracle identically
        # so the comparison measures kernel error, not output rounding
        y_ref = y_ref.astype(jnp.bfloat16)
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_ref, np.float32),
                                   rtol=3e-2, atol=3e-2)
    else:
        np.testing.assert_allclose(np.asarray(y_k, np.float32),
                                   np.asarray(y_ref, np.float32), atol=2e-4)


def test_kernel_state_carries_across_chunks():
    """With multiple chunks the kernel's scratch state must thread exactly
    like the oracle's lax.scan carry (position > chunk sees history)."""
    x, dt, a, b, c = _mk(jax.random.PRNGKey(1), 1, 24, 2, 4, 3)
    y_ref, _ = ssd.ssd_scan(x, dt, a, b, c, chunk=8)
    y_k, _ = ssd_scan_fwd(x.transpose(0, 2, 1, 3),
                          dt.transpose(0, 2, 1)[..., None],
                          a[:, None], b[:, None], c[:, None],
                          chunk=8, interpret=True)
    y_k = y_k.transpose(0, 2, 1, 3)
    # the last chunk depends on the full 24-token history
    np.testing.assert_allclose(y_k[:, -8:], y_ref[:, -8:], atol=2e-4)


@pytest.mark.parametrize("s,chunk", [(20, 8), (32, 16)])
def test_kernel_initial_state_and_ragged_lengths(s, chunk):
    """ops.ssd_scan through the kernel: a batch whose rows end at their own
    lengths (dt = 0 past them) and start from a carried state, against the
    jnp scan on each row's real prefix. S off a chunk multiple exercises
    the zero-width padding."""
    bsz, h, p, n = 3, 2, 8, 4
    x, dt, a, b, c = _mk(jax.random.PRNGKey(2), bsz, s, h, p, n)
    h0 = jax.random.normal(jax.random.PRNGKey(3), (bsz, h, p, n))
    lengths = [s, s - 5, 3]
    keep = jnp.arange(s)[None, :] < jnp.asarray(lengths)[:, None]
    dt = jnp.where(keep[..., None], dt, 0.0)
    y_k, h_k = ops.ssd_scan(x, dt, a, b, c, chunk=chunk, h0=h0,
                            impl="pallas_interpret")
    for i, n_i in enumerate(lengths):
        y_r, h_r = ssd.ssd_scan(x[i:i + 1, :n_i], dt[i:i + 1, :n_i], a,
                                b[i:i + 1, :n_i], c[i:i + 1, :n_i],
                                chunk=chunk, h0=h0[i:i + 1])
        np.testing.assert_allclose(y_k[i, :n_i], y_r[0], atol=2e-4)
        np.testing.assert_allclose(h_k[i], h_r[0], atol=2e-4)


def test_ops_xla_impl_is_the_jnp_scan():
    x, dt, a, b, c = _mk(jax.random.PRNGKey(4), 1, 16, 2, 4, 3)
    y, hf = ops.ssd_scan(x, dt, a, b, c, chunk=8, impl="xla")
    y_r, h_r = ssd.ssd_scan(x, dt, a, b, c, chunk=8)
    assert (np.asarray(y) == np.asarray(y_r)).all()
    assert (np.asarray(hf) == np.asarray(h_r)).all()
