"""Pallas kernel sweeps (interpret=True on CPU) vs the pure-jnp oracle.

Per the assignment: for each kernel, sweep shapes/dtypes and assert_allclose
against ref.py.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.bias as bias_mod
from repro.kernels import ops, ref

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 5e-2}


def _mk(key, b, n, m, h, kvh, d, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, n, h, d), dtype)
    k = jax.random.normal(ks[1], (b, m, kvh, d), dtype)
    v = jax.random.normal(ks[2], (b, m, kvh, d), dtype)
    return q, k, v


class TestFlashBiasAttnKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,n,h,kvh,d", [
        (1, 32, 4, 4, 16),     # MHA aligned
        (2, 48, 8, 2, 24),     # GQA, unaligned seq + channel
        (1, 17, 2, 1, 8),      # ragged seq (padding path)
        (1, 64, 6, 3, 160),    # head_dim > 128 (stablelm-style)
    ])
    def test_phi_causal(self, dtype, b, n, h, kvh, d):
        q, k, v = _mk(jax.random.PRNGKey(0), b, n, n, h, kvh, d, dtype)
        pq, pk = bias_mod.alibi_factors(n, n, h, dtype=jnp.float32)
        pq4 = bias_mod.broadcast_factors(pq, b, n, h)
        pk4 = bias_mod.broadcast_factors(pk, b, n, h)
        out = ops.flash_attention(q, k, v, pq4, pk4, mask_kind="causal",
                                  impl="pallas_interpret",
                                  block_q=16, block_k=16)
        want = ref.mha_reference(q, k, v, phi_q=pq4, phi_k=pk4,
                                 mask_kind="causal")
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=ATOL[dtype])

    @pytest.mark.parametrize("mask", ["none", "causal", "local"])
    def test_masks_match(self, mask):
        q, k, v = _mk(jax.random.PRNGKey(1), 1, 48, 48, 4, 4, 16, jnp.float32)
        out = ops.flash_attention(q, k, v, mask_kind=mask, window=16,
                                  impl="pallas_interpret",
                                  block_q=16, block_k=16)
        want = ref.mha_reference(q, k, v, mask_kind=mask, window=16)
        np.testing.assert_allclose(out, want, atol=2e-5)

    def test_alibi_in_kernel_jit_generation(self):
        """App. C: slopes-only mode generates the rank-2 bias in-kernel."""
        h = 8
        q, k, v = _mk(jax.random.PRNGKey(2), 2, 32, 32, h, 4, 16, jnp.float32)
        slopes = bias_mod.alibi_slopes(h)
        out = ops.flash_attention(q, k, v, slopes=slopes, mask_kind="causal",
                                  impl="pallas_interpret",
                                  block_q=16, block_k=16)
        pq, pk = bias_mod.alibi_factors(32, 32, h)
        want = ref.mha_reference(
            q, k, v, phi_q=bias_mod.broadcast_factors(pq, 2, 32, h),
            phi_k=bias_mod.broadcast_factors(pk, 2, 32, h),
            mask_kind="causal")
        np.testing.assert_allclose(out, want, atol=2e-5)

    def test_gradients_match_reference(self):
        q, k, v = _mk(jax.random.PRNGKey(3), 1, 32, 32, 4, 2, 16, jnp.float32)
        pq, pk = bias_mod.alibi_factors(32, 32, 4)
        pq4 = bias_mod.broadcast_factors(pq, 1, 32, 4)
        pk4 = bias_mod.broadcast_factors(pk, 1, 32, 4)

        def f_kernel(q, pq4):
            return ops.flash_attention(q, k, v, pq4, pk4, mask_kind="causal",
                                       impl="pallas_interpret", block_q=16,
                                       block_k=16).sum()

        def f_ref(q, pq4):
            return ref.mha_reference(q, k, v, phi_q=pq4, phi_k=pk4,
                                     mask_kind="causal").sum()

        g1 = jax.grad(f_kernel, argnums=(0, 1))(q, pq4)
        g2 = jax.grad(f_ref, argnums=(0, 1))(q, pq4)
        np.testing.assert_allclose(g1[0], g2[0], atol=5e-5)
        np.testing.assert_allclose(g1[1], g2[1], atol=5e-4)

    def test_xla_and_kernel_paths_agree(self):
        q, k, v = _mk(jax.random.PRNGKey(4), 1, 40, 40, 4, 4, 16, jnp.float32)
        slopes = bias_mod.alibi_slopes(4)
        a = ops.flash_attention(q, k, v, slopes=slopes, mask_kind="causal",
                                impl="xla")
        b = ops.flash_attention(q, k, v, slopes=slopes, mask_kind="causal",
                                impl="pallas_interpret", block_q=8, block_k=8)
        np.testing.assert_allclose(a, b, atol=2e-5)


def _head_major(x):
    """(B, S, KVH, E) -> the caches' kv-head-major (B, KVH, S, E)."""
    return x.transpose(0, 2, 1, 3)


class TestFlashDecodeKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("b,s,h,kvh,d,blk", [
        (2, 64, 8, 4, 16, 16),
        (1, 96, 4, 1, 32, 32),
        (3, 40, 6, 2, 24, 8),    # ragged cache length
    ])
    def test_alibi_decode(self, dtype, b, s, h, kvh, d, blk):
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b, 1, h, d), dtype)
        kc = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, d), dtype)
        vc = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, d), dtype)
        lengths = jnp.asarray(
            np.random.RandomState(0).randint(1, s + 1, (b,)), jnp.int32)
        slopes = bias_mod.alibi_slopes(h)
        out = ops.flash_decode(q, _head_major(kc), _head_major(vc), lengths,
                               slopes=slopes, impl="pallas_interpret",
                               block_k=blk)
        want = ref.decode_reference(q, kc, vc, lengths, slopes=slopes)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=ATOL[dtype])

    def test_phi_decode(self):
        b, s, h, kvh, d, r = 2, 48, 4, 2, 16, 5
        q = jax.random.normal(jax.random.PRNGKey(0), (b, 1, h, d))
        kc = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, d))
        vc = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, d))
        pq = jax.random.normal(jax.random.PRNGKey(3), (b, 1, h, r))
        pk = jax.random.normal(jax.random.PRNGKey(4), (b, s, 1, r))
        lengths = jnp.array([31, 48], jnp.int32)
        pk_hm = jnp.broadcast_to(_head_major(pk), (b, kvh, s, r))
        out = ops.flash_decode(q, _head_major(kc), _head_major(vc), lengths,
                               pq, pk_hm, impl="pallas_interpret",
                               block_k=16)
        want = ref.decode_reference(q, kc, vc, lengths, phi_q=pq, phi_k=pk)
        np.testing.assert_allclose(out, want, atol=2e-5)

    def test_xla_decode_matches_oracle(self):
        b, s, h, kvh, d = 2, 64, 8, 4, 16
        q = jax.random.normal(jax.random.PRNGKey(0), (b, 1, h, d))
        kc = jax.random.normal(jax.random.PRNGKey(1), (b, s, kvh, d))
        vc = jax.random.normal(jax.random.PRNGKey(2), (b, s, kvh, d))
        lengths = jnp.array([10, 64], jnp.int32)
        slopes = bias_mod.alibi_slopes(h)
        out = ops.flash_decode(q, _head_major(kc), _head_major(vc), lengths,
                               slopes=slopes, impl="xla", block_k=16)
        want = ref.decode_reference(q, kc, vc, lengths, slopes=slopes)
        np.testing.assert_allclose(out, want, atol=2e-5)


MESH_PARITY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist import Rules, make_mesh, use_mesh_rules
    from repro.kernels import ops

    k = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    b, h, kvh, hd, s, ps, n_pages = 4, 8, 4, 32, 256, 128, 8
    rnd = lambda *sh: jax.random.normal(next(k), sh, jnp.float32)
    slopes = jnp.linspace(0.5, 0.01, h)
    q, kk, vv = rnd(b, h, s, hd), rnd(b, kvh, s, hd), rnd(b, kvh, s, hd)
    qd = rnd(b, 1, h, hd)
    pool_k, pool_v = rnd(kvh, n_pages, ps, 128), rnd(kvh, n_pages, ps, 128)
    lengths = jnp.asarray([200, 17, 256, 129], jnp.int32)
    table = jnp.asarray(np.random.default_rng(0).permutation(n_pages)
                        .reshape(b, 2), jnp.int32)
    calls = {
        "prefill": lambda: ops.flash_attention(
            q, kk, vv, slopes=slopes, mask_kind="causal",
            impl="pallas_interpret", layout="bhsd"),
        "decode": lambda: ops.flash_decode(
            qd, kk, vv, lengths, slopes=slopes, impl="pallas_interpret"),
        "paged": lambda: ops.flash_decode(
            qd, pool_k, pool_v, lengths, slopes=slopes,
            impl="pallas_interpret", page_table=table, max_pages=2),
    }
    mesh = make_mesh((2, 2), ("data", "model"))
    for name, call in calls.items():
        want = jax.jit(call)()
        # a fresh function: JAX's trace cache cannot see the ambient mesh
        sharded = jax.jit(lambda: call())
        with use_mesh_rules(mesh, Rules()):
            hlo = sharded.lower().as_text()
            got = sharded()
        assert "sdy.manual_computation" in hlo, name      # the shard_map
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, err_msg=name)
    print("ok")
""")


def test_kernels_under_mesh_match_unsharded():
    """Under a (data 2, model 2) mesh the Pallas calls run per shard in
    shard_map (kv heads over model, batch over data) and give the unsharded
    result. Subprocess: the device count is fixed before jax starts."""
    out = subprocess.run(
        [sys.executable, "-c", MESH_PARITY], capture_output=True, text=True,
        timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1] == "ok"
