"""SVD and neural decompositions of attention biases (Table 1, rows b & c).

- ``svd_factors``: offline truncated SVD of a *learnable-parameter* bias table
  (SwinV2 relative-position tables, Pangu-Weather). Run once after training;
  the factors then ride with q/k at inference (Sec. 4.3).
- ``NeuralDecomposition``: token-wise factor MLPs ``phi_hat_q, phi_hat_k``
  trained with Eq. (5) ``min || phi_q(x_q) phi_k(x_k)^T - f(x_q, x_k) ||^2``
  for dynamic, data-dependent biases (AlphaFold pair bias, App. G gravity /
  spherical-distance biases). Three linear layers with tanh in between,
  matching App. H Table 12.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import lowrank

__all__ = ["svd_factors", "svd_solves", "NeuralDecompParams",
           "neural_decomp_init", "neural_decomp_apply",
           "fit_neural_decomposition", "reconstruction_error"]


# ---------------------------------------------------------------------------
# SVD decomposition
# ---------------------------------------------------------------------------

def svd_factors(table: jax.Array, rank: Optional[int] = None,
                energy: float = 0.99) -> Tuple[jax.Array, jax.Array]:
    """Truncated-SVD factors of a (possibly per-head) dense bias table.

    table: (N, M) or (..., N, M). Returns (phi_q, phi_k) with shapes
    (..., N, R) and (..., M, R) such that phi_q @ phi_k^T is the best
    rank-R approximation (Eckart–Young). If ``rank`` is None it is chosen
    per ``energy`` (Remark 3.8: R maintaining e.g. 99% of sigma^2 mass),
    taking the max over heads so every slice meets the target.

    Singular values are split evenly (sqrt) between the two factors to keep
    their magnitudes balanced — this matters for bf16 kernels downstream.

    A batch of square tables takes ``_polar_eigh_svd`` on TPU (one
    batched eigensolve per call, ``svd_solves``); everything else, and
    every table off TPU, ``jnp.linalg.svd``, whose LAPACK kernel factors
    each matrix alone, so a matrix rounds the same in any batch.
    """
    mat = table.astype(jnp.float32)
    if rank is None:
        rank = lowrank.rank_for_energy(mat, energy)
    r = int(min(rank, *mat.shape[-2:]))
    if _batched_square(mat.shape):
        u, s, v = lax.platform_dependent(
            mat, tpu=functools.partial(_polar_eigh_svd, r=r),
            default=functools.partial(_svd, r=r))
    else:
        u, s, v = _svd(mat, r)
    sq = jnp.sqrt(s)
    return u * sq[..., None, :], v * sq[..., None, :]


def _svd(mat: jax.Array, r: int):
    """Top-``r`` singular triplets (u, s, v) by ``jnp.linalg.svd``."""
    u, s, vt = jnp.linalg.svd(mat, full_matrices=False)
    return u[..., :, :r], s[..., :r], jnp.swapaxes(vt[..., :r, :], -1, -2)


def _batched_square(shape) -> bool:
    return len(shape) > 2 and shape[-1] == shape[-2]


def svd_solves(shape) -> int:
    """Eigensolver calls ``svd_factors`` makes on TPU to factor a table
    of ``shape``: one for a batch of square tables. ``jnp.linalg.svd``,
    which takes the rest, solves a batch in one call up to 256 x 256 and
    maps its eigensolve over the batch one matrix at a time above."""
    if _batched_square(shape) or min(shape[-2:]) <= 256:
        return 1
    return math.prod(shape[:-2])


def _polar_eigh_svd(mat: jax.Array, r: int):
    """Top-``r`` singular triplets (u, s, v) of a batch of square matrices.

    The composition of jax's TPU ``svd`` (polar decomposition A = U_p H by
    QDWH, then H = V diag(s) V^T, so A = (U_p V) diag(s) V^T), with one
    Jacobi ``eigh`` of H over the whole batch. jax's own TPU ``eigh`` maps
    over the batch one matrix at a time and, above n = 256, divides each
    spectrum by further QDWH iterations down to 256 x 256 Jacobi leaves;
    on a TPU v5e that costs more than Jacobi on the whole matrix for the
    Pairformer's zero-padded 384 x 384 pair biases. (Jacobi's time grows
    with the batch: batching saves the serial loop, not the solves.)
    Jacobi is TPU-only; elsewhere the default ``eigh``, so the composition
    runs anywhere. Rows that are zero in ``mat`` stay exactly zero in u
    and v. Matmuls run at f32 precision, as in jax's ``svd``: the TPU
    default rounds through bf16.
    """
    with jax.default_matmul_precision("float32"):
        polar = functools.partial(lax.linalg.qdwh, max_iterations=10)
        for _ in range(mat.ndim - 2):
            polar = jax.vmap(polar)
        u_p, h, _, _ = polar(mat)
        eigh = functools.partial(lax.linalg.eigh, sort_eigenvalues=False)
        v, w = lax.platform_dependent(
            h, default=eigh, tpu=functools.partial(
                eigh, implementation=lax.linalg.EighImplementation.JACOBI))
        # eigh may return tiny negative eigenvalues of the PSD factor H
        s = jnp.maximum(w, 0.0)
        top = jnp.argsort(s, axis=-1, descending=True)[..., :r]
        s = jnp.take_along_axis(s, top, axis=-1)
        v = jnp.take_along_axis(v, top[..., None, :], axis=-1)
        return u_p @ v, s, v


def reconstruction_error(table: jax.Array, phi_q: jax.Array,
                         phi_k: jax.Array) -> float:
    """Relative Frobenius error of the factored reconstruction."""
    approx = phi_q @ jnp.swapaxes(phi_k, -1, -2)
    num = jnp.linalg.norm((approx - table).reshape(-1))
    den = jnp.linalg.norm(table.reshape(-1))
    return float(num / jnp.maximum(den, 1e-30))


# ---------------------------------------------------------------------------
# Neural decomposition (Eq. 5) — token-wise factor MLPs
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class NeuralDecompParams:
    """Two 3-layer tanh MLPs: R^{C'} -> R^{H*R} (App. H Table 12)."""
    q_layers: tuple  # tuple of (w, b)
    k_layers: tuple
    heads: int = dataclasses.field(metadata={"static": True}, default=1)
    rank: int = dataclasses.field(metadata={"static": True}, default=8)

    def tree_flatten(self):
        return (self.q_layers, self.k_layers), (self.heads, self.rank)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], heads=aux[0], rank=aux[1])


def _mlp_init(key, dims):
    layers = []
    for din, dout in zip(dims[:-1], dims[1:]):
        key, sub = jax.random.split(key)
        w = jax.random.normal(sub, (din, dout), jnp.float32) / np.sqrt(din)
        layers.append((w, jnp.zeros((dout,), jnp.float32)))
    return tuple(layers)


def _mlp_apply(layers, x):
    for i, (w, b) in enumerate(layers):
        x = x @ w + b
        if i < len(layers) - 1:
            x = jnp.tanh(x)
    return x


def neural_decomp_init(key, in_dim_q: int, in_dim_k: int, *, hidden: int = 256,
                       heads: int = 1, rank: int = 8) -> NeuralDecompParams:
    kq, kk = jax.random.split(key)
    return NeuralDecompParams(
        q_layers=_mlp_init(kq, (in_dim_q, hidden, hidden, heads * rank)),
        k_layers=_mlp_init(kk, (in_dim_k, hidden, hidden, heads * rank)),
        heads=heads, rank=rank)


def neural_decomp_apply(params: NeuralDecompParams, x_q: jax.Array,
                        x_k: jax.Array):
    """Factor tensors from source features.

    x_q: (..., N, C'_q), x_k: (..., M, C'_k) ->
    phi_q: (..., N, H, R), phi_k: (..., M, H, R).
    """
    def reshape(out):
        return out.reshape(*out.shape[:-1], params.heads, params.rank)
    return (reshape(_mlp_apply(params.q_layers, x_q)),
            reshape(_mlp_apply(params.k_layers, x_k)))


def predicted_bias(params: NeuralDecompParams, x_q, x_k):
    """(..., H, N, M) reconstruction phi_q phi_k^T."""
    pq, pk = neural_decomp_apply(params, x_q, x_k)
    return jnp.einsum("...nhr,...mhr->...hnm", pq, pk)


def fit_neural_decomposition(
    key: jax.Array,
    params: NeuralDecompParams,
    sample_fn: Callable[[jax.Array], Tuple[jax.Array, jax.Array, jax.Array]],
    *,
    steps: int = 1000,
    lr: float = 1e-3,
    lr_decay: float = 0.95,
    lr_decay_every: int = 50,
) -> Tuple[NeuralDecompParams, jax.Array]:
    """Optimize Eq. (5) with Adam on minibatches drawn by ``sample_fn``.

    sample_fn(key) -> (x_q (N, C'), x_k (M, C'), target_bias (H, N, M)).
    Mirrors App. H Table 12's schedule: Adam, lr decayed by ``lr_decay``
    every ``lr_decay_every`` steps. Returns (fitted params, loss history).
    """
    def loss_fn(p, xq, xk, target):
        pred = predicted_bias(p, xq, xk)
        return jnp.mean((pred - target) ** 2)

    b1, b2, eps = 0.9, 0.999, 1e-8
    zeros = jax.tree.map(jnp.zeros_like, params)
    state = (params, zeros, jax.tree.map(jnp.zeros_like, params))

    @jax.jit
    def step(state, key, i):
        p, mu, nu = state
        xq, xk, target = sample_fn(key)
        loss, g = jax.value_and_grad(loss_fn)(p, xq, xk, target)
        cur_lr = lr * (lr_decay ** (i // lr_decay_every))
        mu = jax.tree.map(lambda m, gg: b1 * m + (1 - b1) * gg, mu, g)
        nu = jax.tree.map(lambda n, gg: b2 * n + (1 - b2) * gg * gg, nu, g)
        t = i + 1.0
        def upd(pp, m, n):
            mhat = m / (1 - b1 ** t)
            nhat = n / (1 - b2 ** t)
            return pp - cur_lr * mhat / (jnp.sqrt(nhat) + eps)
        p = jax.tree.map(upd, p, mu, nu)
        return (p, mu, nu), loss

    losses = []
    for i in range(steps):
        key, sub = jax.random.split(key)
        state, loss = step(state, sub, jnp.asarray(i, jnp.float32))
        losses.append(loss)
    return state[0], jnp.stack(losses)
