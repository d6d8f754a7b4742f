"""SVD + neural decompositions (Table 1 rows b, c)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.decomp as dc
from repro.core.bias import sqdist_dense


class TestSVD:
    def test_full_rank_is_exact(self):
        t = jax.random.normal(jax.random.PRNGKey(0), (3, 24, 24))
        pq, pk = dc.svd_factors(t, rank=24)
        assert dc.reconstruction_error(t, pq, pk) < 1e-5

    def test_truncation_is_eckart_young_optimal(self):
        """Rank-r SVD error == sqrt(sum of discarded sigma^2)."""
        t = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
        s = jnp.linalg.svd(t, compute_uv=False)
        r = 4
        pq, pk = dc.svd_factors(t, rank=r)
        want = float(jnp.sqrt((s[r:] ** 2).sum()) / jnp.linalg.norm(t))
        got = dc.reconstruction_error(t, pq, pk)
        np.testing.assert_allclose(got, want, rtol=1e-4)

    def test_energy_rank_selection(self):
        u = jax.random.normal(jax.random.PRNGKey(2), (32, 5))
        t = u @ u.T          # exact rank 5
        pq, pk = dc.svd_factors(t, rank=None, energy=0.999)
        assert pq.shape[-1] <= 5
        assert dc.reconstruction_error(t, pq, pk) < 1e-3

    def test_per_head_batched(self):
        t = jax.random.normal(jax.random.PRNGKey(3), (4, 12, 12))
        pq, pk = dc.svd_factors(t, rank=12)
        assert pq.shape == (4, 12, 12) and pk.shape == (4, 12, 12)

    @pytest.mark.parametrize("shape", [(20, 12), (12, 20), (3, 20, 12),
                                       (3, 16, 16), (2, 3, 16, 16)])
    def test_factor_shapes(self, shape):
        t = jax.random.normal(jax.random.PRNGKey(4), shape)
        pq, pk = dc.svd_factors(t, rank=8)
        assert pq.shape == (*shape[:-1], 8)
        assert pk.shape == (*shape[:-2], shape[-1], 8)

    @pytest.mark.parametrize("shape,solves", [
        ((4, 16, 384, 384), 1), ((16, 24, 24), 1), ((24, 24), 1),
        ((3, 20, 12), 1), ((3, 300, 260), 3), ((2, 3, 300, 260), 6)])
    def test_svd_solves(self, shape, solves):
        assert dc.svd_solves(shape) == solves


def _padded_blocks(n_res, shape=(2, 4, 384, 384)):
    """Random blocks zero outside their leading n_res x n_res, as the
    Pairformer's pair bias is past a complex's residues."""
    a = jax.random.normal(jax.random.PRNGKey(n_res), shape)
    valid = jnp.arange(shape[-1]) < n_res
    return jnp.where(valid[:, None] & valid[None, :], a, 0.0)


class TestPolarEighSVD:
    """svd_factors' path for batched square tables on TPU: QDWH polar
    decomposition, then one batched eigensolve (here the default eigh in
    place of the chip's Jacobi)."""

    @pytest.mark.parametrize("n_res", [162, 384])
    def test_rank_96_matches_svd(self, n_res):
        a = _padded_blocks(n_res)
        u, s, v = jax.jit(functools.partial(dc._polar_eigh_svd, r=96))(a)
        assert u.shape == v.shape == (2, 4, 384, 96)
        got = (u * s[..., None, :]) @ jnp.swapaxes(v, -1, -2)
        uu, ss, vt = jnp.linalg.svd(a, full_matrices=False)
        want = (uu[..., :96] * ss[..., None, :96]) @ vt[..., :96, :]
        err = jnp.linalg.norm(got - want) / jnp.linalg.norm(want)
        assert float(err) <= 1e-5
        np.testing.assert_allclose(s, ss[..., :96], rtol=1e-5)
        # padded residues: factor rows exactly zero, not merely small
        assert not jnp.any(u[..., n_res:, :]) and not jnp.any(v[..., n_res:, :])


class TestNeuralDecomposition:
    def test_fit_recovers_low_rank_bias(self):
        """Eq. 5 training drives reconstruction loss down on sqdist bias
        (App. G-style target)."""
        key = jax.random.PRNGKey(0)
        params = dc.neural_decomp_init(key, 2, 2, hidden=32, heads=1, rank=8)

        def sample(k):
            xq = jax.random.uniform(k, (24, 2))
            target = sqdist_dense(xq, xq)[None]      # (1, N, N)
            return xq, xq, target

        fitted, losses = dc.fit_neural_decomposition(
            key, params, sample, steps=150, lr=3e-3)
        assert float(losses[-1]) < 0.3 * float(losses[:10].mean())

    def test_factors_are_tokenwise(self):
        """Remark 3.6: phi depends only on its own token's features — a
        permutation of inputs permutes outputs identically."""
        key = jax.random.PRNGKey(1)
        params = dc.neural_decomp_init(key, 3, 3, hidden=16, heads=2, rank=4)
        x = jax.random.normal(key, (10, 3))
        pq, _ = dc.neural_decomp_apply(params, x, x)
        perm = jnp.array([3, 1, 4, 0, 2, 9, 8, 7, 5, 6])
        pq_p, _ = dc.neural_decomp_apply(params, x[perm], x[perm])
        np.testing.assert_allclose(pq[perm], pq_p, atol=1e-6)
