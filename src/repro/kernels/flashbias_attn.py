"""Pallas TPU kernel: fused flash attention with low-rank (FlashBias) bias.

TPU adaptation of the paper's Triton kernel (Sec. 4.1 "Implementation
choices"), re-derived for the TPU memory hierarchy:

- The logits tile is computed as **two MXU contractions per tile**:
  ``s = (q @ k^T) * scale + phi_q @ phi_k^T`` — the factor tensors live in
  their own VMEM tiles instead of being concatenated onto q/k in HBM
  (which would re-write (N+M)(C+R) bytes and disturb existing layouts).
- Online softmax state (m, l, acc) is carried in VMEM scratch across the
  innermost (kv) grid axis; TPU grids are sequential so the revisiting
  accumulation pattern is well-defined.
- Masks (causal / sliding window) are *computed* from ``broadcasted_iota``
  — never read from HBM — and fully-masked kv blocks skip all compute via
  ``pl.when`` (the TPU analogue of mask-block pruning).
- ``bias_mode="alibi"`` additionally generates the rank-2 ALiBi bias
  *in-kernel* from per-head slopes (App. C's JIT trick): zero factor IO.
  The (H,) slope vector sits whole in SMEM and the body reads one scalar
  per head — a (1, 1) VMEM block of an (H, 1) array is not a legal TPU
  tile (the last two block dims must be (8, 128)-divisible or full).

Block shapes are (block_q x D) / (block_k x D) with D, R padded to the
128-lane boundary by the ``ops.py`` wrapper; block_q/block_k default to 128
(= MXU systolic dim), giving a VMEM working set of
``(2*block_q + 2*block_k)*(D+R)*4`` bytes ≪ 128 MiB v5e VMEM.

The head-major (B, H, N, D) layout this kernel reads is the repo-wide
cache/compute layout contract (ops.py module docstring): since ISSUE 5 the
models project q/k/v head-major directly (``flash_attention(layout=
"bhsd")``), so no transpose stands between the projections and these
blocks.

Forward-only: training uses the XLA chunked path (mirroring the paper, which
uses the Triton kernel for inference and SDPA for training). ``ops.py`` wires
this kernel as the forward of a ``jax.custom_vjp`` whose backward is the
chunked path's VJP.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.attention import DEFAULT_MASK_VALUE

__all__ = ["flashbias_attention_fwd"]


def _attn_kernel(
    # refs (inputs in BlockSpec order, then outputs, then scratch)
    q_ref, k_ref, v_ref, phi_q_ref, phi_k_ref, slopes_ref,
    o_ref,
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    block_q: int,
    block_k: int,
    mask_kind: str,
    window: int,
    kv_len: int,
    bias_mode: str,
):
    h = pl.program_id(1)          # q head index
    i = pl.program_id(2)          # q block index
    j = pl.program_id(3)          # kv block index
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * block_q
    k_start = j * block_k

    # ---- whole-block mask pruning (computed, not loaded) ----------------
    if mask_kind == "causal":
        run_block = k_start <= q_start + block_q - 1
    elif mask_kind == "local":
        run_block = jnp.logical_and(
            k_start <= q_start + block_q - 1,                 # causal side
            k_start + block_k - 1 >= q_start - (window - 1),  # window side
        )
    else:
        run_block = k_start < kv_len

    @pl.when(run_block)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (bq, bk)

        if bias_mode == "phi":
            pq = phi_q_ref[0, 0].astype(jnp.float32)  # (bq, R)
            pk = phi_k_ref[0, 0].astype(jnp.float32)  # (bk, R)
            s += jax.lax.dot_general(
                pq, pk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

        if bias_mode == "alibi":
            slope = slopes_ref[h]
            s += slope * (k_pos - q_pos).astype(jnp.float32)

        allowed = k_pos < kv_len
        if mask_kind == "causal":
            allowed = jnp.logical_and(allowed, q_pos >= k_pos)
        elif mask_kind == "local":
            allowed = jnp.logical_and(allowed, q_pos >= k_pos)
            allowed = jnp.logical_and(allowed, q_pos - k_pos < window)
        s = jnp.where(allowed, s, DEFAULT_MASK_VALUE)

        m_prev = m_scr[...]                            # (bq, 1)... stored (bq, 128) lanes
        m_cur = jnp.max(s, axis=1, keepdims=True)      # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)                 # (bq, 1)
        p = jnp.exp(s - m_new)                         # (bq, bk)
        l_new = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, Dv)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, Dv)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _attn_kernel_ragged(lengths_ref, *refs, **kw):
    """Per-batch-length variant: ``lengths`` arrives via scalar prefetch
    (SMEM, same idiom as flash_decode) and replaces the static ``kv_len``
    bound — each batch row masks (and block-skips) at its OWN length, which
    is what the serve engine's padded wave of variable-n_res complexes
    needs. The body is the static kernel verbatim with a traced bound."""
    kw.pop("kv_len", None)
    _attn_kernel(*refs, kv_len=lengths_ref[pl.program_id(0)], **kw)


def flashbias_attention_fwd(
    q: jax.Array,            # (B, H, N, D)
    k: jax.Array,            # (B, K, M, D)
    v: jax.Array,            # (B, K, M, Dv)
    phi_q: Optional[jax.Array] = None,   # (B, H, N, R)
    phi_k: Optional[jax.Array] = None,   # (B, H, M, R)
    slopes: Optional[jax.Array] = None,  # (H,) for bias_mode="alibi"
    *,
    scale: float,
    mask_kind: str = "none",
    window: int = 0,
    kv_len: Optional[int] = None,
    lengths: Optional[jax.Array] = None,  # (B,) int32 per-batch kv bound
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Raw kernel entry — shapes must already be tile-aligned (see ops.py)."""
    b, h, n, d = q.shape
    _, kvh, m, _ = k.shape
    dv = v.shape[-1]
    group = h // kvh
    kv_len = m if kv_len is None else kv_len
    bias_mode = "phi" if phi_q is not None else ("alibi" if slopes is not None else "none")

    grid = (b, h, n // block_q, m // block_k)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
        pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j, *_: (b_, h_ // group, j, 0)),
        pl.BlockSpec((1, 1, block_k, dv), lambda b_, h_, i, j, *_: (b_, h_ // group, j, 0)),
    ]
    args = [q, k, v]
    if bias_mode == "phi":
        r = phi_q.shape[-1]
        in_specs += [
            pl.BlockSpec((1, 1, block_q, r), lambda b_, h_, i, j, *_: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, r), lambda b_, h_, i, j, *_: (b_, h_, j, 0)),
        ]
        args += [phi_q, phi_k]
    else:
        in_specs += [None, None]
        args += [None, None]
    if bias_mode == "alibi":
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(slopes.reshape(h).astype(jnp.float32))   # to SMEM
    else:
        in_specs.append(None)
        args.append(None)

    static = {"scale": scale, "block_q": block_q, "block_k": block_k,
              "mask_kind": mask_kind, "window": window,
              "bias_mode": bias_mode}
    out_spec = pl.BlockSpec((1, 1, block_q, dv),
                            lambda b_, h_, i, j, *_: (b_, h_, i, 0))
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, dv), jnp.float32),
    ]
    out_shape = jax.ShapeDtypeStruct((b, h, n, dv), q.dtype)

    if lengths is not None:
        kernel = functools.partial(_attn_kernel_ragged, **static)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch)
        return pl.pallas_call(kernel, grid_spec=grid_spec,
                              out_shape=out_shape, interpret=interpret,
                              name="flashbias_attn_ragged")(
            lengths.astype(jnp.int32), *args)

    kernel = functools.partial(_attn_kernel, kv_len=kv_len, **static)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flashbias_attn",
    )(*args)
    return out
