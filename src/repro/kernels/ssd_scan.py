"""Pallas TPU kernel: fused Mamba2 SSD chunk scan.

Identified in EXPERIMENTS.md §Perf (hymba/mamba cells) as the remaining
memory hot spot: the XLA SSD path materializes the (B, nc, Q, Q, H) decay /
weight tensors to HBM-visible buffers; this kernel keeps the whole
intra-chunk pipeline in VMEM and carries the (P, N) state in scratch across
the sequential chunk axis — the same revisiting-accumulator pattern as the
flash attention kernel.

Per (batch, head, chunk) tile:

    cum   = cumsum(dt * a)                 (Q,)     VMEM
    L     = tril(exp(cum_i - cum_j))       (Q, Q)   VMEM, never HBM
    w     = (c b^T) . L . dt_j             (Q, Q)
    y     = w @ x  +  exp(cum) c @ h       (Q, P)   two MXU calls
    h     = exp(cum_Q) h + (dt*sdec*b)^T @ x        state update in scratch

HBM traffic: read x, dt, b, c and the initial state once; write y and the
final state once; between chunks h never leaves VMEM. The initial state is
what chunked prefill carries in; ragged prompts end early by dt = 0 past
their length (a zero-width step leaves the state unchanged), so the final
state is the state after each row's last real position.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_fwd"]


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
                y_ref, h_ref,
                h_scr,
                *, chunk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_scr[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (Q, 1) column
    a = a_ref[pl.program_id(1)]                  # scalar decay rate (<0)
    bm = b_ref[0, 0].astype(jnp.float32)         # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)         # (Q, N)

    # Mosaic lowers no cumsum and no (Q, 1) -> (1, Q) transpose, so the
    # inclusive prefix sum and the column/row views come from masked
    # (Q, Q) reductions on the VPU — exact f32 sums, no MXU rounding.
    q = x.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    da = dt * a                                                   # (Q, 1)
    cum_row = jnp.sum(jnp.where(ii <= jj, da, 0.0), axis=0,
                      keepdims=True)                              # (1, Q)
    cum = jnp.sum(jnp.where(ii == jj, cum_row, 0.0), axis=1,
                  keepdims=True)                                  # (Q, 1)
    dt_row = jnp.sum(jnp.where(ii == jj, dt, 0.0), axis=0,
                     keepdims=True)                               # (1, Q)
    cum_last = jnp.sum(da, axis=0, keepdims=True)                 # (1, 1)
    decay = jnp.where(ii >= jj, jnp.exp(cum - cum_row), 0.0)     # (Q, Q)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    w = cb * decay * dt_row
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q, P)

    # inter-chunk: y += exp(cum) * (c @ h^T);  h: (P, N)
    h = h_scr[...]
    ch = jax.lax.dot_general(cm, h, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, P)
    y = y + jnp.exp(cum) * ch

    # state update: h' = exp(cum_Q) h + sum_j sdec_j dt_j x_j b_j^T
    sdec = jnp.exp(cum_last - cum) * dt                           # (Q, 1)
    xw = x * sdec                                                 # (Q, P)
    upd = jax.lax.dot_general(xw, bm, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    h_new = jnp.exp(cum_last) * h + upd
    h_scr[...] = h_new

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(j == pl.num_programs(2) - 1)
    def _final():
        h_ref[0, 0] = h_new.astype(h_ref.dtype)


def ssd_scan_fwd(x: jax.Array,      # (B, H, S, P)
                 dt: jax.Array,     # (B, H, S, 1)  (softplus'd, > 0)
                 a: jax.Array,      # (H,) or (H, 1) negative decay rates (SMEM)
                 b: jax.Array,      # (B, 1|H, S, N)
                 c: jax.Array,      # (B, 1|H, S, N)
                 h0: Optional[jax.Array] = None,   # (B, H, P, N) f32
                 *,
                 chunk: int = 256,
                 interpret: bool = False):
    """Raw kernel entry — S must be a multiple of ``chunk`` (pad upstream
    with dt = 0: a step of zero width leaves the state as it was).

    Returns ``(y (B, H, S, P), h_final (B, H, P, N) f32)``; the scan
    starts from ``h0`` (zeros when None). b/c with a singleton head dim
    are shared by every head (ngroups=1, the assigned configs' setting):
    their index map reads head 0, so no per-head copy is ever made.
    """
    bsz, h, s, p = x.shape
    n = b.shape[-1]
    assert s % chunk == 0, (s, chunk)
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)
    nc = s // chunk

    def bc_map(b_, h_, j):
        return (b_, 0, j, 0)

    bc_idx = bc_map if b.shape[1] == 1 else (
        lambda b_, h_, j: (b_, h_, j, 0))
    state = pl.BlockSpec((1, 1, p, n), lambda b_, h_, j: (b_, h_, 0, 0))
    grid = (bsz, h, nc)
    y, h_fin = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, n), bc_idx),
            pl.BlockSpec((1, 1, chunk, n), bc_idx),
            state,
        ],
        out_specs=[pl.BlockSpec((1, 1, chunk, p),
                                lambda b_, h_, j: (b_, h_, j, 0)),
                   state],
        out_shape=[jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
                   jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(x, dt, a.reshape(h).astype(jnp.float32), b, c,
      h0.astype(jnp.float32))
    return y, h_fin
