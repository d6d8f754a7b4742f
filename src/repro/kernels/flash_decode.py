"""Pallas TPU kernel: flash-decoding with a KV cache and low-rank bias.

One new token per request attends to a cache of up to S keys. TPU adaptation:

- The G q-heads sharing one kv head form the *rows* of the logits tile
  (``(G, block_k)``), so GQA turns the tiny N=1 decode matmul into an MXU-
  shaped one — the TPU analogue of GPU flash-decoding's split-K blocks.
- A grid step covers a block of ``Hb`` kv heads, not one: it reads a
  ``(Hb, block_k, D)`` block of K and of V and runs ``q·kᵀ`` and ``p·v``
  as head-batched contractions (leading batch axis). A step costs a fixed
  ~0.3-0.5 µs on the chip whatever it moves, so the grid is
  ``(B, KVH / Hb, S / block_k)`` and a decode call takes ``B x S /
  block_k`` steps when ``Hb == KVH``, not ``KVH`` times as many.
- ``Hb`` is the largest divisor of ``KVH`` whose blocks, double-buffered,
  plus the per-head scratch and logits fit ``_VMEM_BUDGET`` (half the 16
  MiB default scoped VMEM limit of a v5e); it is read off the call's shapes
  and dtypes (``_head_block``). A shape whose heads do not all fit takes a
  smaller divisor through the same code.
- The cache sequence axis is the innermost grid axis; online-softmax state
  rides in VMEM scratch per head — m and l ``(Hb, G, 1)``, the accumulator
  ``(Hb, G, Dv)`` (TPU grids are sequential, so the accumulate-across-j
  pattern is exact, no cross-block reduction pass).
- Per-request lengths arrive via scalar prefetch (SMEM); blocks past the
  length are skipped entirely (``pl.when``) — compute *and* the copy of the
  skipped KV block are elided on real hardware by block-index aliasing.
- FlashBias factors: ``phi_q`` is (G, R) per kv head; ``phi_k`` rides with
  the cache at (block_k, R) — rank-R bias costs R/D extra MXU depth, never
  NM IO. A head-shared key factor (the paged slab) is one ``(Hb·G, R) x
  (R, block_k)`` product for all the block's query rows. The factor
  product runs at ``HIGHEST``: at the default precision the MXU rounds its
  operands to bf16, which holds the ALiBi key factor (1, pos) exactly only
  up to 256.
- ``slopes`` mode generates the rank-2 ALiBi bias in-kernel (App. C JIT
  trick): zero bias IO. The slopes arrive as a ``(KVH, G, 1)`` column, one
  ``(Hb, G, 1)`` block a step (last two dims whole, so a legal tile).

``flash_decode_paged_fwd`` is the PAGED variant: the KV cache (and the
per-page ``phi_k`` factor slab) lives in a shared page pool and each
request's pages are resolved through a scalar-prefetched page table. The
pool may be the whole layer stack ``(L, KVH, n_pages, ps, *)``: a third
scalar-prefetch operand names the layer, and the index maps read its pages
straight out of the stack, so the model's layer scan never materialises one
layer's pool. The kernel BODY is shared with the contiguous path — grid
axis j is the *logical* block index (page_size == block_k), so position
math and the length-based block skipping are unchanged; only the block
index maps differ (they read ``page_table[b, j]`` to find the physical
page, the same page for every head of the block). Blocks past the request
length clamp to the last mapped page, so skipped and unmapped pages alias
the previous block's index and their copies are elided on hardware
exactly like the contiguous path's skipped blocks.

Cache layout contract (ISSUE 5): both kernels read KV in the layouts
declared below — ``(B, KVH, S, *)`` contiguous, ``(KVH, n_pages, ps, *)``
paged — and since ISSUE 5 the model caches are *stored* in exactly these
layouts (lane-padded at allocation), so ``ops.py`` passes them zero-copy:
nobody owns a per-step transpose anymore. The paged ``phi_pages`` factor
slab may carry a leading kv-head axis of 1 (``(1, n_pages, ps, R)``): the
kv-head broadcast then happens in its block index map (every head block
reads the same physical page block), which is what lets the slab stay a
single layer- and head-shared copy in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.attention import DEFAULT_MASK_VALUE

__all__ = ["flash_decode_fwd", "flash_decode_paged_fwd"]

# bytes one grid step's head-scaled blocks may take: half of a v5e's 16 MiB
# default scoped VMEM limit, the rest left to Mosaic's own temporaries
_VMEM_BUDGET = 8 << 20
_LANES = 128


def _head_block(q, k, v, phi_q, phi_k, *, block_k: int,
                phi_per_head: bool) -> int:
    """Kv heads per grid step: the largest divisor of KVH whose VMEM
    footprint — double-buffered q, K, V, factor and output blocks, the
    per-head softmax scratch and the f32 logits — fits ``_VMEM_BUDGET``."""
    kvh, g, d = q.shape[1:]
    dv = v.shape[-1]
    io = (block_k * (d * k.dtype.itemsize + dv * v.dtype.itemsize)
          + g * (d + dv) * q.dtype.itemsize)
    if phi_q is not None:
        r = phi_q.shape[-1]
        io += g * r * phi_q.dtype.itemsize
        if phi_per_head:
            io += block_k * r * phi_k.dtype.itemsize
    scratch = g * (dv + 2 * _LANES) * 4     # acc; m and l pad to the lanes
    logits = 3 * g * block_k * 4            # s, its mask, p
    per_head = 2 * io + scratch + logits
    return max(hb for hb in range(1, kvh + 1)
               if kvh % hb == 0 and (hb == 1 or hb * per_head <= _VMEM_BUDGET))


def _decode_kernel(
    lengths_ref,                      # scalar prefetch: (B,) int32 in SMEM
    q_ref, k_ref, v_ref, phi_q_ref, phi_k_ref, slopes_ref,
    o_ref,
    m_scr, l_scr, acc_scr,
    *,
    scale: float,
    block_k: int,
    bias_mode: str,
):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    k_start = j * block_k

    @pl.when(k_start < length)
    def _body():
        k = k_ref[...]                                    # (Hb, bk, D)
        q = q_ref[...].astype(k.dtype)                    # (Hb, G, D)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale   # (Hb, G, bk)
        hb, g, _ = s.shape

        if bias_mode == "phi":
            pq = phi_q_ref[...].astype(jnp.float32)       # (Hb, G, R)
            pk = phi_k_ref[...].astype(jnp.float32)       # ([Hb,] bk, R)
            highest = jax.lax.Precision.HIGHEST
            if pk.ndim == 2:
                # head-shared key factors: one product over every query
                # row of the block
                s += jax.lax.dot_general(
                    pq.reshape(hb * g, pq.shape[-1]), pk,
                    (((1,), (1,)), ((), ())), precision=highest,
                    preferred_element_type=jnp.float32,
                ).reshape(hb, g, block_k)
            else:
                s += jax.lax.dot_general(
                    pq, pk, (((2,), (2,)), ((0,), (0,))), precision=highest,
                    preferred_element_type=jnp.float32)

        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        if bias_mode == "alibi":
            rel = (k_pos - (length - 1)).astype(jnp.float32)
            s += slopes_ref[...] * rel                    # (Hb, G, 1) column

        s = jnp.where(k_pos < length, s, DEFAULT_MASK_VALUE)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=2, keepdims=True)
        v = v_ref[...]                                    # (Hb, bk, Dv)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def _scratch(hb: int, g: int, dv: int):
    return [pltpu.VMEM((hb, g, 1), jnp.float32),
            pltpu.VMEM((hb, g, 1), jnp.float32),
            pltpu.VMEM((hb, g, dv), jnp.float32)]


def _bias_mode(phi_q, slopes) -> str:
    return ("phi" if phi_q is not None
            else ("alibi" if slopes is not None else "none"))


def flash_decode_fwd(
    q: jax.Array,                         # (B, KVH, G, D)
    k_cache: jax.Array,                   # (B, KVH, S, D)
    v_cache: jax.Array,                   # (B, KVH, S, Dv)
    lengths: jax.Array,                   # (B,) int32
    phi_q: Optional[jax.Array] = None,    # (B, KVH, G, R)
    phi_k: Optional[jax.Array] = None,    # (B, KVH, S, R)
    slopes: Optional[jax.Array] = None,   # (KVH, G, 1)
    *,
    scale: float,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Raw decode kernel — S must be a multiple of block_k (see ops.py)."""
    b, kvh, g, d = q.shape
    s_len = k_cache.shape[2]
    dv = v_cache.shape[-1]
    assert s_len % block_k == 0, (s_len, block_k)
    bias_mode = _bias_mode(phi_q, slopes)
    hb = _head_block(q, k_cache, v_cache, phi_q, phi_k, block_k=block_k,
                     phi_per_head=True)

    def head_map(b_, h_, j, *_):
        return (b_, h_, 0, 0)

    def seq_map(b_, h_, j, *_):
        return (b_, h_, j, 0)

    sq = pl.squeezed
    grid = (b, kvh // hb, s_len // block_k)
    in_specs = [
        pl.BlockSpec((sq, hb, g, d), head_map),
        pl.BlockSpec((sq, hb, block_k, d), seq_map),
        pl.BlockSpec((sq, hb, block_k, dv), seq_map),
    ]
    args = [q, k_cache, v_cache]
    if bias_mode == "phi":
        r = phi_q.shape[-1]
        in_specs += [pl.BlockSpec((sq, hb, g, r), head_map),
                     pl.BlockSpec((sq, hb, block_k, r), seq_map)]
        args += [phi_q, phi_k]
    else:
        in_specs += [None, None]
        args += [None, None]
    if bias_mode == "alibi":
        in_specs.append(pl.BlockSpec((hb, g, 1), lambda b_, h_, j, *_:
                                     (h_, 0, 0)))
        args.append(slopes.astype(jnp.float32))
    else:
        in_specs.append(None)
        args.append(None)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                               bias_mode=bias_mode)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((sq, hb, g, dv), head_map),
        scratch_shapes=_scratch(hb, g, dv),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dv), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(lengths.astype(jnp.int32), *args)
    return out


def _paged_decode_kernel(lengths_ref, page_table_ref, layer_ref, *rest,
                         **kw):
    # page and layer resolution happen entirely in the block index maps;
    # the body is the contiguous kernel verbatim (j stays the LOGICAL block
    # index)
    del page_table_ref, layer_ref
    _decode_kernel(lengths_ref, *rest, **kw)


def flash_decode_paged_fwd(
    q: jax.Array,                         # (B, KVH, G, D)
    k_pages: jax.Array,                   # ([L,] KVH, n_pages, ps, D)
    v_pages: jax.Array,                   # ([L,] KVH, n_pages, ps, Dv)
    lengths: jax.Array,                   # (B,) int32
    page_table: jax.Array,                # (B, P) int32 page ids
    phi_q: Optional[jax.Array] = None,    # (B, KVH, G, R)
    phi_pages: Optional[jax.Array] = None,  # (KVH|1, n_pages, ps, R)
    slopes: Optional[jax.Array] = None,   # (KVH, G, 1)
    *,
    layer: Optional[jax.Array] = None,    # int32 scalar or (1,)
    scale: float,
    interpret: bool = False,
) -> jax.Array:
    """Paged decode kernel: block_k == page_size, pages via scalar prefetch.

    ``page_table[b, j]`` holds the physical page of request b's j-th logical
    block; entries past the request's mapped prefix may be anything (they
    are clamped to the last in-length block, whose compute ``pl.when``
    skips). Every page id is clamped into the pool, so a stale table can
    never fault — at worst it reads a page the length mask then discards.

    With ``layer`` the pools are the stacked ``(L, KVH, n_pages, ps, *)``
    cache and the kernel reads layer ``layer`` in place; without it they
    are one layer's ``(KVH, n_pages, ps, *)`` pool (read as a stack of one,
    a reshape that moves nothing).

    ``phi_pages`` with a leading kv-head axis of 1 is the layer/kv-head-
    shared factor slab: its index map pins the head coordinate to 0, so the
    kv-head broadcast costs nothing (same block, every head block).
    """
    if layer is None:
        k_pages = k_pages.reshape(1, *k_pages.shape)
        v_pages = v_pages.reshape(1, *v_pages.shape)
        layer = 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    b, kvh, g, d = q.shape
    n_layers, _, n_pages, ps = k_pages.shape[:4]
    p_max = page_table.shape[1]
    dv = v_pages.shape[-1]
    bias_mode = _bias_mode(phi_q, slopes)
    shared = phi_pages is not None and phi_pages.shape[0] == 1
    hb = _head_block(q, k_pages, v_pages, phi_q, phi_pages, block_k=ps,
                     phi_per_head=not shared)

    def _page(b_, j, lens_ref, pt_ref):
        # clamp j to the last in-length block so skipped/unmapped blocks
        # alias the previous DMA; clamp the id so stale tables stay in-pool
        last = jnp.maximum(lens_ref[b_] - 1, 0) // ps
        return jnp.clip(pt_ref[b_, jnp.minimum(j, last)], 0, n_pages - 1)

    def pool_map(b_, h_, j, lens_ref, pt_ref, layer_ref):
        layer_ = jnp.clip(layer_ref[0], 0, n_layers - 1)
        return (layer_, h_, _page(b_, j, lens_ref, pt_ref), 0, 0)

    def head_map(b_, h_, j, *_):
        return (b_, h_, 0, 0)

    sq = pl.squeezed
    grid = (b, kvh // hb, p_max)
    in_specs = [
        pl.BlockSpec((sq, hb, g, d), head_map),
        pl.BlockSpec((sq, hb, sq, ps, d), pool_map),
        pl.BlockSpec((sq, hb, sq, ps, dv), pool_map),
    ]
    args = [q, k_pages, v_pages]
    if bias_mode == "phi":
        r = phi_q.shape[-1]

        def phi_map(b_, h_, j, lens_ref, pt_ref, layer_ref):
            # kv-head-shared slab (leading axis 1): every head block reads
            # head 0's page, so the broadcast happens in the index map
            return (0 if shared else h_, _page(b_, j, lens_ref, pt_ref), 0, 0)

        in_specs += [
            pl.BlockSpec((sq, hb, g, r), head_map),
            pl.BlockSpec((sq if shared else hb, sq, ps, r), phi_map),
        ]
        args += [phi_q, phi_pages]
    else:
        in_specs += [None, None]
        args += [None, None]
    if bias_mode == "alibi":
        in_specs.append(pl.BlockSpec((hb, g, 1), lambda b_, h_, j, *_:
                                     (h_, 0, 0)))
        args.append(slopes.astype(jnp.float32))
    else:
        in_specs.append(None)
        args.append(None)

    kernel = functools.partial(_paged_decode_kernel, scale=scale, block_k=ps,
                               bias_mode=bias_mode)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((sq, hb, g, dv), head_map),
        scratch_shapes=_scratch(hb, g, dv),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, dv), q.dtype),
        interpret=interpret,
        name="flash_decode_paged",
    )(lengths.astype(jnp.int32), page_table.astype(jnp.int32), layer, *args)
    return out
