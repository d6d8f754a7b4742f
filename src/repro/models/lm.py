"""Decoder LM covering the dense / MoE / SSM / hybrid assigned families.

One parameter template + three entry points per family:

- ``loss_fn(params, batch, cfg)``        — training loss (scan over layers,
  remat policy from cfg, FlashBias-ALiBi attention).
- ``prefill(params, batch, cfg)``        — run the prompt, build the cache.
- ``decode_step(params, cache, tokens, cfg)`` — one token against the cache
  (flash-decoding kernel / XLA path; ring cache for sliding-window layers;
  constant-size SSM state for ssm/hybrid).

TP padding (heads/vocab/experts -> multiples of cfg.tp) is *mathematically
exact*: padded q-heads have zero o-proj rows, padded experts get -inf router
logits, padded vocab rows are masked out of the loss. The waste is visible
as MODEL_FLOPS/HLO_FLOPS < 1 in the roofline table and is a hillclimb lever.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import flags
from repro.configs.base import ArchConfig
from repro.dist import sharding as dshard
from repro.dist.sharding import constrain
from repro.kernels import ops as kops
from repro.models import ssd
from repro.models.common import (
    PDef,
    cross_entropy_loss,
    embed_lookup,
    rmsnorm,
    stack_layers,
    swiglu,
    unembed_logits,
)

__all__ = ["lm_template", "loss_fn", "prefill", "prefill_chunk",
           "decode_step", "init_cache", "init_paged_cache",
           "insert_cache_at_slots", "insert_paged_cache_at_slots",
           "grow_page_tables_at_slots", "forward_hidden"]


# ---------------------------------------------------------------------------
# Template
# ---------------------------------------------------------------------------

def _attn_template(cfg: ArchConfig) -> dict:
    d, hp, kvp = cfg.d_model, cfg.heads_padded, cfg.kv_heads_padded
    hd = cfg.resolved_head_dim
    sd = 0.02
    return {
        "wq": PDef((d, hp, hd), ("fsdp", "heads", None), ("normal", sd)),
        "wk": PDef((d, kvp, hd), ("fsdp", "kv_heads", None), ("normal", sd)),
        "wv": PDef((d, kvp, hd), ("fsdp", "kv_heads", None), ("normal", sd)),
        "wo": PDef((hp, hd, d), ("heads", None, "fsdp"),
                   ("normal", sd / np.sqrt(2 * cfg.n_layers))),
        "slopes": PDef((hp,), (None,), ("slopes", cfg.n_heads)),
    }


def _mlp_template(cfg: ArchConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    sd = 0.02
    return {
        # gate+up FUSED (trailing dim 2) -> one matmul, one backward AR
        "wi": PDef((d, f, 2), ("fsdp", "mlp", None), ("normal", sd)),
        "wo": PDef((f, d), ("mlp", "fsdp"),
                   ("normal", sd / np.sqrt(2 * cfg.n_layers))),
    }


def _moe_template(cfg: ArchConfig) -> dict:
    d, f, ep = cfg.d_model, cfg.d_ff, cfg.experts_padded
    sd = 0.02
    return {
        "router": PDef((d, ep), ("fsdp", None), ("normal", sd)),
        "wi": PDef((ep, d, f, 2), ("expert", "fsdp", None, None),
                   ("normal", sd)),
        "wo": PDef((ep, f, d), ("expert", None, "fsdp"),
                   ("normal", sd / np.sqrt(2 * cfg.n_layers))),
    }


def _ssm_template(cfg: ArchConfig) -> dict:
    d = cfg.d_model
    hs, p, n = cfg.ssm_heads_padded, cfg.ssm_head_dim, cfg.ssm_state
    w = cfg.conv_width
    sd = 0.02
    return {
        "in_x": PDef((d, hs, p), ("fsdp", "heads", None), ("normal", sd)),
        "in_z": PDef((d, hs, p), ("fsdp", "heads", None), ("normal", sd)),
        "in_b": PDef((d, n), ("fsdp", None), ("normal", sd)),
        "in_c": PDef((d, n), ("fsdp", None), ("normal", sd)),
        "in_dt": PDef((d, hs), ("fsdp", "heads"), ("normal", sd)),
        "conv_w": PDef((w, hs, p), (None, "heads", None), ("normal", 0.2)),
        "conv_bc_w": PDef((w, 2 * n), (None, None), ("normal", 0.2)),
        **({"conv_b": PDef((hs, p), ("heads", None), ("zeros",)),
            "conv_bc_b": PDef((2 * n,), (None,), ("zeros",))}
           if cfg.conv_bias else {}),
        "a_log": PDef((hs,), ("heads",), ("zeros",)),
        "dt_bias": PDef((hs,), ("heads",), ("zeros",)),
        "d_skip": PDef((hs,), ("heads",), ("ones",)),
        "gate_norm": PDef((hs, p), ("heads", None), ("zeros",)),
        "out": PDef((hs, p, d), ("heads", None, "fsdp"),
                    ("normal", sd / np.sqrt(2 * cfg.n_layers))),
    }


def _layer_template(cfg: ArchConfig) -> dict:
    layer: dict = {"ln1": PDef((cfg.d_model,), (None,), ("zeros",))}
    if cfg.family in ("dense", "moe", "hybrid"):
        layer["attn"] = _attn_template(cfg)
    if cfg.family in ("ssm", "hybrid"):
        layer["ssm"] = _ssm_template(cfg)
    if cfg.family == "hybrid":
        layer["branch_norm_attn"] = PDef((cfg.d_model,), (None,), ("zeros",))
        layer["branch_norm_ssm"] = PDef((cfg.d_model,), (None,), ("zeros",))
    if cfg.family == "moe":
        layer["moe"] = _moe_template(cfg)
        layer["ln2"] = PDef((cfg.d_model,), (None,), ("zeros",))
    elif cfg.family in ("dense", "hybrid"):
        layer["mlp"] = _mlp_template(cfg)
        layer["ln2"] = PDef((cfg.d_model,), (None,), ("zeros",))
    return layer


def lm_template(cfg: ArchConfig) -> dict:
    return {
        "embed": PDef((cfg.vocab_padded, cfg.d_model), ("vocab", "fsdp"),
                      ("normal", 0.02)),
        "layers": stack_layers(_layer_template(cfg), cfg.n_layers),
        "final_norm": PDef((cfg.d_model,), (None,), ("zeros",)),
    }


# ---------------------------------------------------------------------------
# Attention (FlashBias-ALiBi; the paper's technique lives HERE)
# ---------------------------------------------------------------------------

def _attention(lp: dict, x: jax.Array, cfg: ArchConfig, *,
               mask_kind: str, q_offset=0) -> jax.Array:
    """Full-sequence attention (train / prefill). Returns (y, k, v).

    k, v come back in the cache layout, kv-head-major ``(B, KVH, S, hd)``
    (when the Pallas kernel runs, the projection einsums write head-major
    directly and the kernel consumes it zero-copy — prefill emits
    kernel-layout caches with no post-hoc fixup).
    """
    dt = x.dtype

    slopes = None
    phi_q = phi_k = None
    dense_bias = None
    if cfg.bias_kind == "alibi":
        if cfg.bias_mode == "flashbias":
            slopes = lp["slopes"].astype(jnp.float32)
        else:  # dense baseline: materialize the (H, N, M) bias (paper A/B)
            from repro.core.bias import alibi_dense
            n = x.shape[1]
            bd = alibi_dense(n, n, cfg.n_heads)
            pad = cfg.heads_padded - cfg.n_heads
            dense_bias = jnp.pad(bd, ((0, pad), (0, 0), (0, 0)))[None]

    # Compute layout follows the impl that will run: head-major projections
    # feed the Pallas kernel zero-copy; the XLA chunked fallback (and the
    # dense-bias baseline) speak canonical, so there the projections stay
    # canonical and only the cache emission transposes (once per prefill —
    # the "cheap view" the layout contract allows off the hot path).
    hm_compute = (dense_bias is None
                  and kops.resolve_impl(cfg.attn_impl) != "xla")
    if hm_compute:
        q = jnp.einsum("bsd,dhe->bhse", x, lp["wq"].astype(dt))
        k = jnp.einsum("bsd,dhe->bhse", x, lp["wk"].astype(dt))
        v = jnp.einsum("bsd,dhe->bhse", x, lp["wv"].astype(dt))
        q = constrain(q, "batch", "heads", "seq", None)
        k = constrain(k, "batch", "kv_heads", "seq", None)
        v = constrain(v, "batch", "kv_heads", "seq", None)
        o = kops.flash_attention(
            q, k, v, phi_q, phi_k, slopes, mask_kind=mask_kind,
            window=cfg.window, scale=cfg.attn_scale, impl=cfg.attn_impl,
            block_q=128, block_k=128, layout="bhsd")
        y = jnp.einsum("bhse,hed->bsd", o, lp["wo"].astype(dt))
        return constrain(y, "batch", "seq", None), k, v

    q = jnp.einsum("bsd,dhe->bshe", x, lp["wq"].astype(dt))
    k = jnp.einsum("bsd,dhe->bshe", x, lp["wk"].astype(dt))
    v = jnp.einsum("bsd,dhe->bshe", x, lp["wv"].astype(dt))
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)
    v = constrain(v, "batch", "seq", "kv_heads", None)

    if dense_bias is not None:
        from repro.core.attention import MaskSpec, attention as core_attn
        o = core_attn(q, k, v, mask=MaskSpec(mask_kind, cfg.window),
                      bias=dense_bias, impl="chunked",
                      chunk_size=cfg.attn_chunk)
    else:
        o = kops.flash_attention(
            q, k, v, phi_q, phi_k, slopes, mask_kind=mask_kind,
            window=cfg.window, scale=cfg.attn_scale, impl=cfg.attn_impl,
            block_q=128, block_k=128)
    y = jnp.einsum("bshe,hed->bsd", o, lp["wo"].astype(dt))
    # cache emission only, off the hot path
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    return constrain(y, "batch", "seq", None), k, v


def _attention_decode(lp: dict, x: jax.Array, k_cache, v_cache, lengths,
                      cfg: ArchConfig, *, active=None, page_table=None,
                      phi_pages=None, max_pages=None, layer=None):
    """One-token attention against a (ring / full / paged) cache.

    ``active`` (B,) bool freezes retired slot rows: their KV writes are
    dropped (scatter index pushed out of range, ``mode="drop"``) so an idle
    lane can never scribble on cache it no longer owns — under the paged
    layout a stale page table would otherwise corrupt pages that have been
    reallocated to ANOTHER request.

    Every cache arrives in the kernels' native kv-head-major layout —
    contiguous / ring ``(B, KVH, S, hd)``, page pools ``(KVH, n_pages, ps,
    hd_pad)`` — and is passed to ``ops.flash_decode`` ZERO-COPY; only the
    one new token's row is touched per step.

    Paged mode (``page_table`` given): the pools are the whole ``(L, ...)``
    stack and ``layer`` names this layer. The new token's rows land in
    that layer in place, through the slot's page table, and the kernel
    reads the layer straight out of the stack, so no per-layer pool is
    sliced out or stacked back. ``phi_pages`` is the per-page ALiBi key
    factor slab (layer- and kv-head-shared; lane-padded ``(n_pages, ps,
    r_pad)`` when a Pallas impl runs); when present the bias is computed
    from the CACHED factors (phi mode — factors ride with k, FlashBias
    Sec. 4.3) instead of re-materializing positions. ``max_pages`` statically caps the pages
    any request can reference (the serve engine derives it from host-side
    lengths).
    """
    dt = x.dtype
    q = jnp.einsum("bsd,dhe->bshe", x, lp["wq"].astype(dt))
    k_new = jnp.einsum("bsd,dhe->bshe", x, lp["wk"].astype(dt))
    v_new = jnp.einsum("bsd,dhe->bshe", x, lp["wv"].astype(dt))
    slopes = (lp["slopes"].astype(jnp.float32)
              if cfg.bias_kind == "alibi" else None)
    bidx = jnp.arange(x.shape[0])

    def drop_if_frozen(idx, oob):
        return idx if active is None else jnp.where(active, idx, oob)

    def new_row(x_new, pool_like):
        # (B, 1, KVH, hd) -> (B, KVH, hd[_pad]): the one token-sized write
        row = x_new[:, 0]
        pad = pool_like.shape[-1] - row.shape[-1]
        if pad:
            row = jnp.pad(row, ((0, 0), (0, 0), (0, pad)))
        return row

    # io_stub (dry-run accounting only): the donated cache is updated
    # IN PLACE on hardware (one row written); the functional `.at[].set`
    # would count a full cache read+write per layer in cost_analysis.
    skip_scatter = cfg.attn_impl == "io_stub"
    if page_table is not None:     # paged (L, KVH, n_pages, ps, hd_pad)
        n_pages, ps = k_cache.shape[2], k_cache.shape[3]
        pos = lengths - 1
        page = drop_if_frozen(page_table[bidx, pos // ps], n_pages)
        if not skip_scatter:
            # one scatter of the B x KVH token rows, every axis but the
            # lanes indexed: a window of lanes alone keeps the pool in the
            # layout the kernel reads (a window over kv heads too makes XLA
            # relay the whole stack out around the scatter)
            kv = jnp.arange(k_cache.shape[1])[None, :]
            at = (layer, kv, page[:, None], (pos % ps)[:, None])
            k_cache = k_cache.at[at].set(new_row(k_new, k_cache),
                                         mode="drop")
            v_cache = v_cache.at[at].set(new_row(v_new, v_cache),
                                         mode="drop")
        phi_q = phi_k = None
        if slopes is not None and phi_pages is not None:
            # same rank-2 q factor the ops ALiBi path materializes; the key
            # factors come from the paged slab instead
            b, hp = x.shape[0], q.shape[2]
            qpos = (lengths.astype(jnp.float32) - 1.0)[:, None, None, None]
            pq = jnp.concatenate([-jnp.broadcast_to(qpos, (b, 1, hp, 1)),
                                  jnp.ones((b, 1, hp, 1), jnp.float32)], -1)
            phi_q = pq * slopes.reshape(1, 1, hp, 1)
            phi_k, slopes = phi_pages, None
        o = kops.flash_decode(q, k_cache, v_cache, lengths, phi_q=phi_q,
                              phi_k=phi_k, slopes=slopes,
                              scale=cfg.attn_scale, impl=cfg.attn_impl,
                              block_k=cfg.attn_chunk, page_table=page_table,
                              max_pages=max_pages, layer=layer)
        # lane-padded pools return lane-padded values; the pad rows are
        # zero so slicing them off is exact (token-sized, not pool-sized)
        o = o[..., :v_new.shape[-1]]
    elif cfg.window and cfg.window == k_cache.shape[2]:       # ring (SWA)
        sc = cfg.window
        slot = drop_if_frozen((lengths - 1) % sc, sc)
        if not skip_scatter:
            k_cache = k_cache.at[bidx, :, slot].set(k_new[:, 0], mode="drop")
            v_cache = v_cache.at[bidx, :, slot].set(v_new[:, 0], mode="drop")
        o = _ring_window_attention(q, k_cache, v_cache, lengths, slopes, cfg)
    else:                                          # contiguous full cache
        sc = k_cache.shape[2]
        pos = drop_if_frozen(lengths - 1, sc)
        if not skip_scatter:
            k_cache = k_cache.at[bidx, :, pos].set(
                new_row(k_new, k_cache), mode="drop")
            v_cache = v_cache.at[bidx, :, pos].set(
                new_row(v_new, v_cache), mode="drop")
        o = kops.flash_decode(q, k_cache, v_cache, lengths, slopes=slopes,
                              scale=cfg.attn_scale, impl=cfg.attn_impl,
                              block_k=cfg.attn_chunk)
        o = o[..., :v_new.shape[-1]]     # lane-padded caches return padded
    y = jnp.einsum("bshe,hed->bsd", o, lp["wo"].astype(dt))
    return y, k_cache, v_cache


def _ring_window_attention(q, k_cache, v_cache, lengths, slopes, cfg):
    """Dense decode over a ring cache ``(B, KVH, W, hd)`` of size window
    (small: <= few K) — grouped einsums consume it directly (no G-fold
    ``jnp.repeat`` of the window, no transpose).

    Slot s holds absolute position p = len-1 - ((len-1 - s) mod W), valid
    iff p >= 0. ALiBi bias from absolute positions; softmax over the window.
    """
    b, _, h, e = q.shape
    scale = 1.0 / np.sqrt(e)
    kvh, w = k_cache.shape[1], k_cache.shape[2]
    g = h // kvh
    slot = jnp.arange(w)
    last = (lengths - 1)[:, None]
    pos = last - ((last - slot) % w)                     # (B, W)
    valid = pos >= 0
    qg = q[:, 0].reshape(b, kvh, g, e).astype(jnp.float32)
    s = jnp.einsum("bkge,bkwe->bkgw", qg,
                   k_cache.astype(jnp.float32)) * scale
    if slopes is not None:
        rel = (pos - last).astype(jnp.float32)           # <= 0
        s = s + slopes.reshape(kvh, g)[None, :, :, None] \
            * rel[:, None, None, :]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgw,bkwe->bkge", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, h, e).astype(q.dtype)


def _attention_chunk(lp: dict, x: jax.Array, k_cache, v_cache,
                     cfg: ArchConfig, *, offsets, chunk_lens,
                     page_table=None, max_pages=None):
    """C-token chunk attention against a (ring / full / paged) slot cache.

    Chunked prefill's attention step: row ``b``'s chunk occupies absolute
    positions ``offsets[b] .. offsets[b]+chunk_lens[b]-1``. Rows with
    ``chunk_lens[b] == 0`` are frozen lanes riding the fixed slot batch
    (live decoding slots, empty slots): their KV writes drop (out-of-range
    scatter indices, ``mode="drop"``) and their outputs are garbage nobody
    reads — exactly the ``active`` discipline of ``_attention_decode``.

    Full/paged caches scatter the chunk's keys FIRST and attend against the
    written cache under the offset causal mask (``k_pos <= q_pos``, see
    ``ops.flash_chunk_attention``). Ring caches must attend FIRST: a later
    chunk position may alias the ring slot an earlier query still needs, so
    queries read old keys from the PRE-write ring (slot validity per query:
    in-window and written) plus the chunk's own keys (causal + local), and
    only then does the chunk rotate into the ring — which also bounds the
    chunk size at ``window`` (positions must land on distinct slots).
    """
    dt = x.dtype
    b, c, _ = x.shape
    q = jnp.einsum("bsd,dhe->bshe", x, lp["wq"].astype(dt))
    k_new = jnp.einsum("bsd,dhe->bshe", x, lp["wk"].astype(dt))
    v_new = jnp.einsum("bsd,dhe->bshe", x, lp["wv"].astype(dt))
    slopes = (lp["slopes"].astype(jnp.float32)
              if cfg.bias_kind == "alibi" else None)
    bidx = jnp.arange(b)
    i = jnp.arange(c)
    pos = offsets[:, None] + i[None, :]                   # (B, C) absolute
    valid = i[None, :] < chunk_lens[:, None]              # (B, C)

    def pad_rows(x_new, pool_like):
        # (B, C, KVH, hd) -> (B, C, KVH, hd_pad): chunk-sized, like decode's
        # one-row pad against lane-padded pools
        pad = pool_like.shape[-1] - x_new.shape[-1]
        if pad:
            x_new = jnp.pad(x_new, ((0, 0),) * 3 + ((0, pad),))
        return x_new

    if page_table is not None:     # paged (KVH, n_pages, ps, hd_pad)
        n_pages, ps = k_cache.shape[1], k_cache.shape[2]
        page = jnp.where(valid, page_table[bidx[:, None], pos // ps], n_pages)
        flat_pg, flat_ix = page.reshape(-1), (pos % ps).reshape(-1)
        kvh = k_new.shape[2]

        def rows(x_new, pool):           # -> (KVH, B*C, hd_pad)
            r = pad_rows(x_new, pool)
            return r.transpose(2, 0, 1, 3).reshape(kvh, b * c, -1)
        k_cache = k_cache.at[:, flat_pg, flat_ix].set(
            rows(k_new, k_cache), mode="drop")
        v_cache = v_cache.at[:, flat_pg, flat_ix].set(
            rows(v_new, v_cache), mode="drop")
        o = kops.flash_chunk_attention(
            q, k_cache, v_cache, offsets, chunk_lens, slopes,
            scale=cfg.attn_scale, impl=cfg.attn_impl,
            page_table=page_table, max_pages=max_pages)
        o = o[..., :v_new.shape[-1]]
    elif cfg.window and cfg.window == k_cache.shape[2]:       # ring (SWA)
        w = cfg.window
        assert c <= w, (c, w)            # distinct ring slots per chunk
        h = q.shape[2]
        e = q.shape[-1]
        kvh = k_new.shape[2]
        g = h // kvh
        scale = 1.0 / np.sqrt(e)
        # old keys from the PRE-write ring: slot s holds absolute position
        # p_old = old_last - ((old_last - s) mod W), valid iff >= 0
        slot = jnp.arange(w)
        old_last = (offsets - 1)[:, None]                 # (B, 1)
        p_old = old_last - ((old_last - slot[None, :]) % w)      # (B, W)
        slot_written = (p_old >= 0) & (offsets[:, None] > 0)
        qg = (q.reshape(b, c, kvh, g, e).transpose(0, 2, 3, 1, 4)
              .astype(jnp.float32))                       # (B,KVH,G,C,E)
        s_old = jnp.einsum("bkgce,bkwe->bkgcw", qg,
                           k_cache.astype(jnp.float32)) * scale
        kn = k_new.transpose(0, 2, 1, 3).astype(jnp.float32)  # (B,KVH,C,E)
        vn = v_new.transpose(0, 2, 1, 3).astype(jnp.float32)
        s_in = jnp.einsum("bkgce,bkje->bkgcj", qg, kn) * scale
        if slopes is not None:
            sl = slopes.reshape(kvh, g)[None, :, :, None, None]
            rel_old = (p_old[:, None, :] - pos[:, :, None]).astype(jnp.float32)
            rel_in = (i[None, :] - i[:, None]).astype(jnp.float32)  # (C, C)
            s_old = s_old + sl * rel_old[:, None, None]
            s_in = s_in + sl * rel_in[None, None, None]
        # query at pos p sees old keys in (p - W, offsets) and chunk keys
        # j <= i within the window (all <= p by causality)
        m_old = slot_written[:, None, :] \
            & (p_old[:, None, :] > pos[:, :, None] - w)          # (B,C,W)
        m_in = ((i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < w)
                )[None] & (i[None, None, :] < chunk_lens[:, None, None])
        s_all = jnp.concatenate([
            jnp.where(m_old[:, None, None], s_old, -1e30),
            jnp.where(m_in[:, None, None], s_in, -1e30)], axis=-1)
        p_all = jax.nn.softmax(s_all, axis=-1)
        o = jnp.einsum("bkgcw,bkwe->bkgce", p_all[..., :w],
                       v_cache.astype(jnp.float32)) \
            + jnp.einsum("bkgcj,bkje->bkgce", p_all[..., w:], vn)
        o = (o.transpose(0, 3, 1, 2, 4).reshape(b, c, h, e)
             .astype(q.dtype))
        # rotate the chunk into the ring AFTER attending (frozen rows drop)
        ring_slot = jnp.where(valid, pos % w, w)
        k_cache = k_cache.at[bidx[:, None], :, ring_slot].set(
            k_new, mode="drop")
        v_cache = v_cache.at[bidx[:, None], :, ring_slot].set(
            v_new, mode="drop")
    else:                                                 # contiguous full
        sc = k_cache.shape[2]
        pos_w = jnp.where(valid, pos, sc)
        k_cache = k_cache.at[bidx[:, None], :, pos_w].set(
            pad_rows(k_new, k_cache), mode="drop")
        v_cache = v_cache.at[bidx[:, None], :, pos_w].set(
            pad_rows(v_new, v_cache), mode="drop")
        o = kops.flash_chunk_attention(
            q, k_cache, v_cache, offsets, chunk_lens, slopes,
            scale=cfg.attn_scale, impl=cfg.attn_impl)
        o = o[..., :v_new.shape[-1]]
    y = jnp.einsum("bshe,hed->bsd", o, lp["wo"].astype(dt))
    return y, k_cache, v_cache


# ---------------------------------------------------------------------------
# MoE FFN (GShard-style capacity dispatch; EP over the model axis)
# ---------------------------------------------------------------------------

def _moe_capacity(cfg: ArchConfig, s: int) -> int:
    c = int(np.ceil(s * cfg.top_k / cfg.experts_padded * cfg.capacity_factor))
    return max(1, c)


def _moe_ffn(mp: dict, x: jax.Array, cfg: ArchConfig, valid=None):
    """Returns (y, aux_loss). x: (B, S, D).

    ``valid`` (B, S) bool marks real positions of a right-padded batch:
    invalid positions are dropped from dispatch entirely, so they consume
    no expert capacity and receive a zero update.
    """
    b, s, d = x.shape
    ep, k = cfg.experts_padded, cfg.top_k
    cap = _moe_capacity(cfg, s)
    dt = x.dtype

    logits = jnp.einsum("bsd,de->bse", x, mp["router"].astype(dt))
    logits = logits.astype(jnp.float32)
    if cfg.n_experts < ep:                     # padded experts never win
        iota = jnp.arange(ep)
        logits = jnp.where(iota >= cfg.n_experts, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)                     # (B,S,E)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)               # (B,S,K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # slot-major one-hot: (B, K, S, E); positions assigned slot-0 first
    onehot = jax.nn.one_hot(gate_idx, ep, dtype=jnp.float32)    # (B,S,K,E)
    if valid is not None:
        onehot = onehot * valid[:, :, None, None].astype(jnp.float32)
    sel = onehot.transpose(0, 2, 1, 3)                          # (B,K,S,E)
    flat = sel.reshape(b, k * s, ep)
    pos = jnp.cumsum(flat, axis=1) - flat                       # pos within expert
    keep = (pos < cap) * flat                                   # drop overflow
    pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
    disp = pos_oh.reshape(b, k, s, ep, cap).transpose(0, 2, 3, 4, 1)
    dispatch = disp.sum(-1)                                     # (B,S,E,C)
    gates = gate_vals.transpose(0, 2, 1)[..., None, None]       # (B,K,S,1,1)
    combine = (disp * gates.transpose(0, 2, 3, 4, 1)).sum(-1)   # (B,S,E,C)

    dispatch = constrain(dispatch.astype(dt), "batch", "seq", "expert", None)
    xin = jnp.einsum("bsec,bsd->becd", dispatch, x)             # (B,E,C,D)
    xin = constrain(xin, "batch", "expert", None, None)
    h2 = jnp.einsum("becd,edft->becft", xin, mp["wi"].astype(dt))
    h = jax.nn.silu(h2[..., 0]) * h2[..., 1]
    eo = jnp.einsum("becf,efd->becd", h, mp["wo"].astype(dt))   # (B,E,C,D)
    y = jnp.einsum("bsec,becd->bsd", combine.astype(dt), eo)

    # load-balance aux (Switch): E * sum_e f_e * p_e over real experts
    frac = dispatch.astype(jnp.float32).sum((1, 3)) / max(s * cfg.top_k, 1)
    pmean = probs.mean(1)                                       # (B,E)
    aux = cfg.n_experts * jnp.mean((frac * pmean).sum(-1))
    return constrain(y, "batch", "seq", None), aux


# ---------------------------------------------------------------------------
# SSM branch (Mamba2 SSD)
# ---------------------------------------------------------------------------

def _ssm_proj(sp: dict, x: jax.Array):
    dt_ = x.dtype
    xs = jnp.einsum("bsd,dhp->bshp", x, sp["in_x"].astype(dt_))
    z = jnp.einsum("bsd,dhp->bshp", x, sp["in_z"].astype(dt_))
    bmat = jnp.einsum("bsd,dn->bsn", x, sp["in_b"].astype(dt_))
    cmat = jnp.einsum("bsd,dn->bsn", x, sp["in_c"].astype(dt_))
    dt = jnp.einsum("bsd,dh->bsh", x, sp["in_dt"].astype(dt_))
    return xs, z, bmat, cmat, dt


def _causal_conv(seq, w, tail=None, lengths=None, bias=None):
    """Depthwise causal conv. seq: (B,S,...) w: (W, ...); tail: (B,W-1,...);
    ``bias`` (...) is added to every output position.

    With ``lengths`` (B,) the returned tail holds the last W-1 inputs at or
    before position ``lengths[b]-1`` (ragged right-padded prefill); position
    ``p`` lives at index ``p + W-1`` of the padded buffer, so the tail spans
    indices ``lengths[b] .. lengths[b]+W-2``.
    """
    width = w.shape[0]
    if tail is None:
        tail = jnp.zeros((seq.shape[0], width - 1) + seq.shape[2:], seq.dtype)
    full = jnp.concatenate([tail, seq], axis=1)
    out = sum(full[:, i:i + seq.shape[1]] * w[i] for i in range(width))
    if bias is not None:
        out = out + bias
    if width == 1:
        new_tail = tail
    elif lengths is None:
        new_tail = full[:, -(width - 1):]
    else:
        idx = lengths[:, None].astype(jnp.int32) + jnp.arange(width - 1)
        idx = idx.reshape(idx.shape + (1,) * (full.ndim - 2))
        new_tail = jnp.take_along_axis(full, idx, axis=1)
    return out, new_tail


def _conv_biases(sp: dict, dt):
    """(x bias, B/C bias) of the causal conv, or (None, None)."""
    if "conv_b" not in sp:
        return None, None
    return sp["conv_b"].astype(dt), sp["conv_bc_b"].astype(dt)


def _gated_norm(y, z, scale, cfg: ArchConfig):
    """Mamba2's gated RMSNorm ``norm(y * silu(z))`` in f32. y, z: (..., H,
    P). Its group is d_inner / n_groups; B and C are shared by every head
    (n_groups 1), so it normalises all H x P channels at once."""
    y = y * jax.nn.silu(z.astype(jnp.float32))
    lead = y.shape[:-2]
    return rmsnorm(y.reshape(*lead, -1), scale.reshape(-1),
                   cfg.norm_eps).reshape(y.shape)


def _ssm_forward(sp: dict, x: jax.Array, cfg: ArchConfig, *, h0=None,
                 conv_tail_x=None, conv_tail_bc=None, lengths=None):
    """Full-sequence SSD. Returns (y (B,S,D), h_fin, tail_x, tail_bc).

    ``lengths`` (B,) marks the valid prefix of a right-padded batch: padded
    positions get dt = 0, which makes their state update the identity
    (decay exp(a*0) = 1, input term dt*x = 0), so ``h_fin`` and the conv
    tails are exactly the state after position ``lengths[b]-1``.
    """
    xs, z, bmat, cmat, dt = _ssm_proj(sp, x)
    dt_ = x.dtype
    bx, bbc = _conv_biases(sp, dt_)
    xs, tail_x = _causal_conv(xs, sp["conv_w"].astype(dt_), conv_tail_x,
                              lengths=lengths, bias=bx)
    xs = jax.nn.silu(xs)
    bc = jnp.concatenate([bmat, cmat], axis=-1)
    bc, tail_bc = _causal_conv(bc, sp["conv_bc_w"].astype(dt_), conv_tail_bc,
                               lengths=lengths, bias=bbc)
    bc = jax.nn.silu(bc)
    n = cfg.ssm_state
    bmat, cmat = bc[..., :n], bc[..., n:]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + sp["dt_bias"].astype(jnp.float32))
    if lengths is not None:
        keep = jnp.arange(x.shape[1])[None, :] < lengths[:, None]
        dt = jnp.where(keep[:, :, None], dt, 0.0)
    a = -jnp.exp(sp["a_log"].astype(jnp.float32))
    y, h_fin = kops.ssd_scan(xs.astype(jnp.float32), dt, a,
                             bmat.astype(jnp.float32),
                             cmat.astype(jnp.float32),
                             chunk=cfg.ssd_chunk, h0=h0, impl=cfg.attn_impl)
    y = y + sp["d_skip"].astype(jnp.float32)[None, None, :, None] \
        * xs.astype(jnp.float32)
    y = _gated_norm(y, z, sp["gate_norm"], cfg).astype(dt_)
    out = jnp.einsum("bshp,hpd->bsd", y, sp["out"].astype(dt_))
    return constrain(out, "batch", "seq", None), h_fin, tail_x, tail_bc


def _ssm_decode(sp: dict, x: jax.Array, h, tail_x, tail_bc, cfg: ArchConfig):
    """One-token SSD update; x (B,1,D). Returns (y, h, tail_x, tail_bc)."""
    xs, z, bmat, cmat, dt = _ssm_proj(sp, x)
    dt_ = x.dtype
    bx, bbc = _conv_biases(sp, dt_)
    xs, tail_x = _causal_conv(xs, sp["conv_w"].astype(dt_), tail_x, bias=bx)
    xs = jax.nn.silu(xs)
    bc = jnp.concatenate([bmat, cmat], axis=-1)
    bc, tail_bc = _causal_conv(bc, sp["conv_bc_w"].astype(dt_), tail_bc,
                               bias=bbc)
    bc = jax.nn.silu(bc)
    n = cfg.ssm_state
    bmat, cmat = bc[..., :n], bc[..., n:]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + sp["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(sp["a_log"].astype(jnp.float32))
    y1, h = ssd.ssd_decode_step(h, xs[:, 0].astype(jnp.float32), dt[:, 0], a,
                                bmat[:, 0].astype(jnp.float32),
                                cmat[:, 0].astype(jnp.float32))
    y1 = y1 + sp["d_skip"].astype(jnp.float32)[None, :, None] \
        * xs[:, 0].astype(jnp.float32)
    y1 = _gated_norm(y1, z[:, 0], sp["gate_norm"], cfg).astype(dt_)
    out = jnp.einsum("bhp,hpd->bd", y1, sp["out"].astype(dt_))[:, None]
    return out, h, tail_x, tail_bc


# ---------------------------------------------------------------------------
# Layer bodies
# ---------------------------------------------------------------------------

def _layer_train(lp: dict, x: jax.Array, cfg: ArchConfig):
    """Full-sequence layer (train / prefill w/o cache emission)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(x, lp["ln1"])
    mask_kind = "local" if cfg.window else "causal"
    if cfg.family == "dense" or cfg.family == "moe":
        y, _, _ = _attention(lp["attn"], h, cfg, mask_kind=mask_kind)
        x = x + y
    elif cfg.family == "ssm":
        y, _, _, _ = _ssm_forward(lp["ssm"], h, cfg)
        x = x + y
    elif cfg.family == "hybrid":
        ya, _, _ = _attention(lp["attn"], h, cfg, mask_kind=mask_kind)
        ys, _, _, _ = _ssm_forward(lp["ssm"], h, cfg)
        y = 0.5 * (rmsnorm(ya, lp["branch_norm_attn"])
                   + rmsnorm(ys, lp["branch_norm_ssm"]))
        x = x + y
    if cfg.family == "moe":
        h2 = rmsnorm(x, lp["ln2"])
        y2, aux = _moe_ffn(lp["moe"], h2, cfg)
        x = x + y2
    elif cfg.family in ("dense", "hybrid"):
        h2 = rmsnorm(x, lp["ln2"])
        m = lp["mlp"]
        dt = x.dtype
        x = x + swiglu(h2, m["wi"].astype(dt), m["wo"].astype(dt))
    return x, aux


def _layer_prefill(lp: dict, x: jax.Array, cfg: ArchConfig, lengths=None):
    """Like _layer_train but emits this layer's cache entries.

    ``lengths`` (B,) enables ragged right-padded prefill: the causal mask
    already keeps padded keys out of real queries' attention, so only the
    state-carrying paths (SSM scan, conv tails, MoE capacity) need it.
    """
    cache = {}
    h = rmsnorm(x, lp["ln1"])
    mask_kind = "local" if cfg.window else "causal"
    if cfg.family in ("dense", "moe", "hybrid"):
        y, k, v = _attention(lp["attn"], h, cfg, mask_kind=mask_kind)
        cache["k"], cache["v"] = k, v
    if cfg.family in ("ssm", "hybrid"):
        ys, hf, tx, tbc = _ssm_forward(lp["ssm"], h, cfg, lengths=lengths)
        cache["ssm_h"], cache["conv_x"], cache["conv_bc"] = hf, tx, tbc
    if cfg.family in ("dense", "moe"):
        x = x + y
    elif cfg.family == "ssm":
        x = x + ys
    else:
        x = x + 0.5 * (rmsnorm(y, lp["branch_norm_attn"])
                       + rmsnorm(ys, lp["branch_norm_ssm"]))
    if cfg.family == "moe":
        valid = None
        if lengths is not None:
            valid = jnp.arange(x.shape[1])[None, :] < lengths[:, None]
        y2, _ = _moe_ffn(lp["moe"], rmsnorm(x, lp["ln2"]), cfg, valid=valid)
        x = x + y2
    elif cfg.family in ("dense", "hybrid"):
        m = lp["mlp"]
        dt = x.dtype
        x = x + swiglu(rmsnorm(x, lp["ln2"]), m["wi"].astype(dt),
                       m["wo"].astype(dt))
    return x, cache


def _layer_decode(lp: dict, cache_l: dict, x: jax.Array, lengths,
                  cfg: ArchConfig, *, active=None, page_table=None,
                  phi_pages=None, max_pages=None, layer=None):
    """One layer of a decode step: layer ``layer`` of ``cache_l``'s page
    pools, which are the whole layer stack (paged caches only)."""
    new_cache = dict(cache_l)
    h = rmsnorm(x, lp["ln1"])
    if cfg.family in ("dense", "moe", "hybrid"):
        paged = "pages_k" in cache_l
        kk, vv = ("pages_k", "pages_v") if paged else ("k", "v")
        y, kc, vc = _attention_decode(
            lp["attn"], h, cache_l[kk], cache_l[vv], lengths, cfg,
            active=active, page_table=page_table if paged else None,
            phi_pages=phi_pages if paged else None, max_pages=max_pages,
            layer=layer)
        new_cache[kk], new_cache[vv] = kc, vc
    if cfg.family in ("ssm", "hybrid"):
        ys, hs, tx, tbc = _ssm_decode(lp["ssm"], h, cache_l["ssm_h"],
                                      cache_l["conv_x"], cache_l["conv_bc"],
                                      cfg)
        if active is not None:       # freeze retired slots' SSM state too
            hs = jnp.where(active[:, None, None, None], hs, cache_l["ssm_h"])
            tx = jnp.where(active[:, None, None, None], tx, cache_l["conv_x"])
            tbc = jnp.where(active[:, None, None], tbc, cache_l["conv_bc"])
        new_cache["ssm_h"], new_cache["conv_x"] = hs, tx
        new_cache["conv_bc"] = tbc
    if cfg.family in ("dense", "moe"):
        x = x + y
    elif cfg.family == "ssm":
        x = x + ys
    else:
        x = x + 0.5 * (rmsnorm(y, lp["branch_norm_attn"])
                       + rmsnorm(ys, lp["branch_norm_ssm"]))
    if cfg.family == "moe":
        y2, _ = _moe_ffn(lp["moe"], rmsnorm(x, lp["ln2"]), cfg)
        x = x + y2
    elif cfg.family in ("dense", "hybrid"):
        m = lp["mlp"]
        dt = x.dtype
        x = x + swiglu(rmsnorm(x, lp["ln2"]), m["wi"].astype(dt),
                       m["wo"].astype(dt))
    return x, new_cache


def _layer_chunk(lp: dict, cache_l: dict, x: jax.Array, cfg: ArchConfig, *,
                 offsets, chunk_lens, page_table=None, max_pages=None):
    """One layer of chunked prefill: C tokens appended against the slot
    cache. Mirrors ``_layer_decode``'s freeze discipline — rows with
    ``chunk_lens == 0`` keep their cache bit-identical."""
    new_cache = dict(cache_l)
    part = chunk_lens > 0                       # participating rows
    first = offsets == 0                        # rows starting a fresh prompt
    h = rmsnorm(x, lp["ln1"])
    if cfg.family in ("dense", "moe", "hybrid"):
        paged = "pages_k" in cache_l
        kk, vv = ("pages_k", "pages_v") if paged else ("k", "v")
        y, kc, vc = _attention_chunk(
            lp["attn"], h, cache_l[kk], cache_l[vv], cfg,
            offsets=offsets, chunk_lens=chunk_lens,
            page_table=page_table if paged else None, max_pages=max_pages)
        new_cache[kk], new_cache[vv] = kc, vc
    if cfg.family in ("ssm", "hybrid"):
        # a fresh prompt starts from zero state (the slot may hold a prior
        # occupant's state); continuation chunks carry the cached state.
        # _ssm_forward(lengths=chunk_lens) gives padded positions dt = 0,
        # so h_fin / conv tails land exactly after position chunk_lens-1;
        # non-participating rows are where-frozen like decode.
        h0 = jnp.where(first[:, None, None, None], 0.0, cache_l["ssm_h"])
        tx0 = jnp.where(first[:, None, None, None], 0.0, cache_l["conv_x"])
        tbc0 = jnp.where(first[:, None, None], 0.0, cache_l["conv_bc"])
        ys, hf, tx, tbc = _ssm_forward(lp["ssm"], h, cfg, h0=h0,
                                       conv_tail_x=tx0, conv_tail_bc=tbc0,
                                       lengths=chunk_lens)
        hf = jnp.where(part[:, None, None, None], hf, cache_l["ssm_h"])
        tx = jnp.where(part[:, None, None, None], tx, cache_l["conv_x"])
        tbc = jnp.where(part[:, None, None], tbc, cache_l["conv_bc"])
        new_cache["ssm_h"], new_cache["conv_x"] = hf, tx
        new_cache["conv_bc"] = tbc
    if cfg.family in ("dense", "moe"):
        x = x + y
    elif cfg.family == "ssm":
        x = x + ys
    else:
        x = x + 0.5 * (rmsnorm(y, lp["branch_norm_attn"])
                       + rmsnorm(ys, lp["branch_norm_ssm"]))
    if cfg.family == "moe":
        valid = jnp.arange(x.shape[1])[None, :] < chunk_lens[:, None]
        y2, _ = _moe_ffn(lp["moe"], rmsnorm(x, lp["ln2"]), cfg, valid=valid)
        x = x + y2
    elif cfg.family in ("dense", "hybrid"):
        m = lp["mlp"]
        dt = x.dtype
        x = x + swiglu(rmsnorm(x, lp["ln2"]), m["wi"].astype(dt),
                       m["wo"].astype(dt))
    return x, new_cache


def _maybe_remat(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


# ---------------------------------------------------------------------------
# Full-model entry points
# ---------------------------------------------------------------------------

def _embed_in(params, tokens, frontend, cfg: ArchConfig):
    dt = jnp.dtype(cfg.dtype)
    x = embed_lookup(params["embed"], tokens).astype(dt)
    # float(): a NUMPY scalar is strongly typed and silently promotes the
    # whole residual stream to f32 (doubled every activation byte and
    # collective model-wide — EXPERIMENTS.md §Perf iteration 3).
    x = x * float(np.sqrt(cfg.d_model))
    if frontend is not None:
        x = jnp.concatenate([frontend.astype(dt), x], axis=1)
    return constrain(x, "batch", "seq", None)


def _compute_layers(params, cfg: ArchConfig):
    """Cast stacked layer weights to the compute dtype BEFORE the scan so the
    per-layer FSDP all-gather moves bf16, not the fp32 master copy (halves
    the dominant collective in the train roofline).

    The cast copy is re-pinned to the parameter shardings. This matters for
    the BACKWARD pass: ``with_sharding_constraint`` is self-transposing, so
    the cotangent (the layer-scan transpose's gradient accumulator) inherits
    the same sharding — without it GSPMD materializes FULL per-device
    stacked gradients (measured 549 GB/device on command-r; §Perf iter 1)."""
    dt = jnp.dtype(cfg.dtype)
    casted = jax.tree.map(
        lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        params["layers"])
    ctx = dshard.get_active_mesh()
    if ctx is None:
        return casted
    mesh, rules = ctx
    from jax.sharding import NamedSharding
    from repro.models.common import stack_layers
    tmpl = stack_layers(_layer_template(cfg), cfg.n_layers)

    def pin(x, pdef):
        spec = dshard.spec_for(pdef.axes, mesh, rules)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))

    return jax.tree.map(pin, casted, tmpl)


def forward_hidden(params, tokens, cfg: ArchConfig, frontend=None):
    """Embed + layer scan + final norm. Returns (hidden (B,S,D), aux)."""
    x = _embed_in(params, tokens, frontend, cfg)

    def body(carry, lp):
        x, aux = carry
        x, a = _layer_train(lp, x, cfg)
        return (x, aux + a), None

    body = _maybe_remat(body, cfg)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               _compute_layers(params, cfg),
                               unroll=flags.scan_unroll(cfg.n_layers))
    return rmsnorm(x, params["final_norm"]), aux


def loss_fn(params, batch, cfg: ArchConfig):
    """Next-token CE (+ MoE aux) over the token region (frontend excluded)."""
    frontend = batch.get("frontend")
    hid, aux = forward_hidden(params, batch["tokens"], cfg, frontend)
    if frontend is not None:
        hid = hid[:, frontend.shape[1]:]
    logits = unembed_logits(hid, params["embed"].astype(hid.dtype))
    ce = cross_entropy_loss(logits, batch["labels"], cfg.vocab)
    return ce + 0.01 * aux


def prefill(params, batch, cfg: ArchConfig, *, max_len: Optional[int] = None,
            lengths=None):
    """Run the prompt; return (last-position logits, cache).

    The cache is allocated at ``max_len`` (>= prompt length + decode budget)
    or at ``window`` for sliding-window attention (ring buffer).

    ``lengths`` (B,) int32 enables RAGGED right-padded prefill: row ``b``'s
    valid prompt (frontend included) is positions ``0 .. lengths[b]-1``.
    Logits are gathered at each row's last valid position, the SSM state /
    conv tails freeze there, MoE capacity ignores padding, and the ring
    cache is filled per-request. Positions past ``lengths[b]`` hold junk
    that decode-time length masking never reads and decode writes overwrite.
    """
    tokens = batch["tokens"]
    frontend = batch.get("frontend")
    b, s = tokens.shape
    total = s + (frontend.shape[1] if frontend is not None else 0)
    max_len = max_len or total
    lengths = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    x = _embed_in(params, tokens, frontend, cfg)

    def body(x, lp):
        x, cache_l = _layer_prefill(lp, x, cfg, lengths=lengths)
        return x, cache_l

    x, caches = jax.lax.scan(body, x, _compute_layers(params, cfg),
                             unroll=flags.scan_unroll(cfg.n_layers))
    hid = rmsnorm(x, params["final_norm"])
    if lengths is None:
        last = hid[:, -1:]
    else:
        last = jnp.take_along_axis(hid, (lengths - 1)[:, None, None], axis=1)
    logits = unembed_logits(last, params["embed"].astype(hid.dtype))

    lens = (jnp.full((b,), total, jnp.int32) if lengths is None else lengths)
    return logits, _prefill_cache(caches, cfg, lens, total, max_len)


def _prefill_cache(caches: dict, cfg: ArchConfig, lens, total: int,
                   max_len: int) -> dict:
    """The cache a prefill hands on, from its per-layer entries: K/V padded
    to ``max_len`` (or rotated into a ring of ``window``) in the cache
    layout, SSM state and conv tails as they are."""
    b = lens.shape[0]
    cache = {"length": lens}
    if "k" in caches:
        sc = cfg.window if (cfg.window and cfg.window < max_len) else max_len
        # k/v ride in the cache layout straight out of _attention:
        # (L, B, KVH, S, hd), seq axis 3
        ring = bool(cfg.window) and cfg.window < max_len
        seq_ax = 3
        k, v = caches["k"], caches["v"]
        if sc >= total:
            # full caches AND ring caches whose prompt fits the window; the
            # lane pad matches init_cache's once-at-allocation pad
            pad = [(0, 0)] * 5
            pad[seq_ax] = (0, sc - total)
            pad[4] = (0, _contig_hd_alloc(cfg, ring) - k.shape[-1])
            cache["k"] = jnp.pad(k, pad)
            cache["v"] = jnp.pad(v, pad)
        else:
            # ring invariant: slot s holds the last position p < len with
            # p ≡ s (mod window); slots with no such p >= 0 are junk the
            # decode-side validity test (pos >= 0) never reads.
            slot = jnp.arange(sc)
            last_pos = (lens - 1)[:, None]                     # (B, 1)
            pos = last_pos - ((last_pos - slot[None, :]) % sc)  # (B, sc)
            idx = jnp.clip(pos, 0, total - 1)
            shape = [1, b, 1, 1, 1]
            shape[seq_ax] = sc
            idx = idx.reshape(shape)
            cache["k"] = jnp.take_along_axis(k, idx, axis=seq_ax)
            cache["v"] = jnp.take_along_axis(v, idx, axis=seq_ax)
    for key in ("ssm_h", "conv_x", "conv_bc"):
        if key in caches:
            cache[key] = caches[key]
    return cache


def decode_step(params, cache, tokens, cfg: ArchConfig, *, max_pages=None):
    """One decode step. tokens: (B, 1) — appended at position cache.length.

    Rows with ``cache["length"] == 0`` are INACTIVE (a freed serve slot, or
    a never-admitted lane) and are frozen: no KV/SSM write, no length
    advance. Prefill always leaves length >= 1, so length-0 is an exact
    idle marker — the serve engine zeroes a slot's length at retire and
    this mask keeps the lane inert until the slot is reused.

    ``max_pages`` (static) caps the pages any request can reference this
    step (paged caches only) — the serve engine passes a power-of-two
    rounding of its host-side longest live length, which bounds the paged
    XLA fallback's gather at Θ(longest request) instead of the full
    page-table width.

    The paged pools ride the layer scan's CARRY as the whole ``(L, ...)``
    stack: each layer writes its token rows into the stack in place and
    the kernel reads its layer out of the stack by index, so a step moves
    token rows, never a layer's pool (scanning the pools as ``xs``/``ys``
    would slice each layer's pool out and stack it back). With the cache
    donated, the pools are updated in the caller's buffers. Per-slot
    leaves (contiguous KV, SSM state) are scanned over as before.
    """
    active = cache["length"] > 0
    lengths = cache["length"] + active.astype(jnp.int32)
    x = _embed_in(params, tokens, None, cfg)

    paged = "pages_k" in cache
    page_table = cache.get("page_table")
    pools = {k: cache[k] for k in ("pages_k", "pages_v") if k in cache}
    layer_cache = {k: cache[k] for k in ("k", "v", "ssm_h", "conv_x",
                                          "conv_bc") if k in cache}

    new_cache = dict(cache)
    if paged and "pages_phi" in cache:
        # the key factor row for the new position is layer-independent —
        # write it once, outside the layer scan (frozen rows drop). The
        # kernel-layout slab is lane-padded: pad the row to match (the
        # trailing zeros are inert in the factor dot).
        phi_pages = cache["pages_phi"]
        n_pages, ps, r_slab = phi_pages.shape
        pos = lengths - 1
        page = page_table[jnp.arange(pos.shape[0]), pos // ps]
        page = jnp.where(active, page, n_pages)
        row = jnp.stack([jnp.ones_like(pos, jnp.float32),
                         pos.astype(jnp.float32)], axis=-1)
        if r_slab > 2:
            row = jnp.pad(row, ((0, 0), (0, r_slab - 2)))
        phi_pages = phi_pages.at[page, pos % ps].set(row, mode="drop")
        new_cache["pages_phi"] = phi_pages
    else:
        phi_pages = None

    def body(carry, inp):
        x, pools = carry
        lp, layer, cl = inp
        x, ncl = _layer_decode(lp, {**cl, **pools}, x, lengths, cfg,
                               active=active, page_table=page_table,
                               phi_pages=phi_pages, max_pages=max_pages,
                               layer=layer)
        pools = {k: ncl.pop(k) for k in pools}
        return (x, pools), ncl

    (x, pools), new_layer_cache = jax.lax.scan(
        body, (x, pools),
        (_compute_layers(params, cfg), jnp.arange(cfg.n_layers), layer_cache),
        unroll=flags.scan_unroll(cfg.n_layers))
    hid = rmsnorm(x, params["final_norm"])
    logits = unembed_logits(hid, params["embed"].astype(hid.dtype))
    new_cache.update(new_layer_cache)
    new_cache.update(pools)
    new_cache["length"] = lengths
    return logits, new_cache


def prefill_chunk(params, cache, tokens, cfg: ArchConfig, *, offsets,
                  chunk_lens, final_lens, max_pages=None):
    """One chunked-prefill step: append a C-token chunk per slot.

    The chunked-prefill contract (the serve backend's planner drives this):

    - ``tokens`` (B, C) int32 — one fixed-size chunk per slot row, right-
      padded; row ``b``'s valid tokens are ``tokens[b, :chunk_lens[b]]`` and
      land at absolute positions ``offsets[b] .. offsets[b]+chunk_lens[b]-1``
      of the slot's cache. ``chunk_lens[b] == 0`` marks a frozen lane (a
      live decoding slot or an empty slot riding the fixed batch): its cache
      stays bit-identical.
    - ``offsets[b] == 0`` starts a fresh prompt: SSM state / conv tails
      reset to zero (the slot may hold a prior occupant's state); KV needs
      no reset — the offset causal mask never reads past the written prefix.
    - ``final_lens`` (B,) int32 is the post-chunk ``cache["length"]`` where
      ``>= 0`` and "keep the current value" where negative. Mid-prompt
      chunks pass -1 for every row: ``length`` stays 0 until the LAST chunk,
      which keeps the lane frozen under interleaved ``decode_step`` calls
      (the length-0 idle contract) and invisible to host-side page-growth
      accounting. The final chunk passes the full prompt length.
    - Returns ``(logits, cache)`` with logits (B, 1, V) gathered at each
      row's last valid chunk position — meaningful only for final chunks
      (the first sampled token), garbage on frozen/mid-prompt rows.

    Works against every cache kind: contiguous full KV (offset scatter),
    ring KV (pre-write window read + chunk rotation — chunk size must be
    <= window), paged KV (scatter through the slot's page table, ``phi_k``
    factor rows at absolute positions, gather capped by static
    ``max_pages`` like decode), and SSM/hybrid state carry.
    """
    b, c = tokens.shape
    offsets = jnp.asarray(offsets, jnp.int32)
    chunk_lens = jnp.asarray(chunk_lens, jnp.int32)
    final_lens = jnp.asarray(final_lens, jnp.int32)
    x = _embed_in(params, tokens, None, cfg)

    paged = "pages_k" in cache
    page_table = cache.get("page_table")
    leaf_keys = (("pages_k", "pages_v") if paged else ("k", "v")) \
        + ("ssm_h", "conv_x", "conv_bc")
    layer_cache = {k: cache[k] for k in leaf_keys if k in cache}

    new_cache = dict(cache)
    if paged and "pages_phi" in cache:
        # layer-independent key factor rows [1, pos] for the whole chunk —
        # written once, outside the layer scan, exactly like decode_step
        phi_pages = cache["pages_phi"]
        n_pages, ps, r_slab = phi_pages.shape
        i = jnp.arange(c)
        pos = offsets[:, None] + i[None, :]
        valid = i[None, :] < chunk_lens[:, None]
        page = jnp.where(valid, page_table[jnp.arange(b)[:, None], pos // ps],
                         n_pages)
        row = jnp.stack([jnp.ones((b, c), jnp.float32),
                         pos.astype(jnp.float32)], axis=-1)
        if r_slab > 2:
            row = jnp.pad(row, ((0, 0), (0, 0), (0, r_slab - 2)))
        phi_pages = phi_pages.at[page.reshape(-1), (pos % ps).reshape(-1)].set(
            row.reshape(b * c, r_slab), mode="drop")
        new_cache["pages_phi"] = phi_pages

    def body(x, inp):
        lp, cl = inp
        x, ncl = _layer_chunk(lp, cl, x, cfg, offsets=offsets,
                              chunk_lens=chunk_lens, page_table=page_table,
                              max_pages=max_pages)
        return x, ncl

    x, new_layer_cache = jax.lax.scan(body, x,
                                      (_compute_layers(params, cfg),
                                       layer_cache),
                                      unroll=flags.scan_unroll(cfg.n_layers))
    hid = rmsnorm(x, params["final_norm"])
    last = jnp.take_along_axis(
        hid, jnp.clip(chunk_lens - 1, 0)[:, None, None], axis=1)
    logits = unembed_logits(last, params["embed"].astype(hid.dtype))
    new_cache.update(new_layer_cache)
    new_cache["length"] = jnp.where(final_lens >= 0, final_lens,
                                    cache["length"])
    return logits, new_cache


def _contig_hd_alloc(cfg: ArchConfig, ring: bool) -> int:
    """Stored head dim of a contiguous cache: 128-lane-padded
    when the Pallas kernel will consume it (pad once at allocation, never
    per step), raw ``hd`` for ring caches (dense XLA window path) and XLA
    backends (head-major einsums read unpadded pools directly)."""
    hd = cfg.resolved_head_dim
    if ring or not kops.resolve_impl(cfg.attn_impl).startswith("pallas"):
        return hd
    return -(-hd // 128) * 128


def init_cache(cfg: ArchConfig, batch: int, max_len: int, *,
               length: int = 0) -> dict:
    """Empty cache pytree (zeros) for decode-only dry-runs and serving.

    K/V are kernel-native kv-head-major ``(L, B, KVH, S, hd[_pad])``: the
    flash-decode kernel reads them zero-copy (see ops.py's layout
    contract). Like the paged pools, the head dim is 128-lane-padded HERE,
    ONCE, when a Pallas impl will run and the cache is full-KV — a
    non-aligned hd like stablelm's 160 would otherwise be re-padded every
    decode step; ring caches feed the dense XLA window path and stay
    unpadded.
    """
    dt = jnp.dtype(cfg.dtype)
    l = cfg.n_layers
    cache = {"length": jnp.full((batch,), length, jnp.int32)}
    if cfg.family in ("dense", "moe", "hybrid"):
        ring = bool(cfg.window) and cfg.window < max_len
        sc = cfg.window if ring else max_len
        shape = (l, batch, cfg.kv_heads_padded, sc, _contig_hd_alloc(cfg, ring))
        cache["k"] = jnp.zeros(shape, dt)
        cache["v"] = jnp.zeros(shape, dt)
    if cfg.family in ("ssm", "hybrid"):
        hs, p, n = cfg.ssm_heads_padded, cfg.ssm_head_dim, cfg.ssm_state
        w = cfg.conv_width
        cache["ssm_h"] = jnp.zeros((l, batch, hs, p, n), jnp.float32)
        cache["conv_x"] = jnp.zeros((l, batch, w - 1, hs, p), dt)
        cache["conv_bc"] = jnp.zeros((l, batch, w - 1, 2 * n), dt)
    return cache


def init_paged_cache(cfg: ArchConfig, batch: int, n_pages: int,
                     page_size: int, pages_per_slot: Optional[int] = None
                     ) -> dict:
    """Paged cache pytree: a shared page pool + per-slot page tables.

    Every full-KV cache leaf is paged — K, V, and the per-page ``phi_k``
    factor slab (``pages_phi``, float32 so positions stay exact: the rank-2
    ALiBi key factor ``[1, pos]`` rides with k at Theta(N R) storage,
    FlashBias Thm 3.2 / Sec. 4.3). ``page_table`` maps each slot's logical
    block j to its physical page; unmapped entries may hold anything — the
    decode path clamps them and the length mask discards what they read.
    Ring-KV (sliding window) and SSM state are constant-size per slot and
    stay on the slot-contiguous discipline; SSM leaves of a hybrid arch
    ride along unchanged.

    The pools are born in the flash-decode kernel's native layout —
    kv-head-major ``(L, KVH, n_pages, ps, hd[_pad])`` — and the slab stays
    layer- and kv-head-shared (the kv-head broadcast lives in the kernel's
    block index maps). The decode step then hands every pool to the kernel
    zero-copy. The 128-lane pad on the trailing dim (and the slab's, from
    2 to 128) exists purely for the Pallas TPU tiles, so it is applied
    HERE, ONCE, and only when a Pallas impl will actually run
    (``resolve_impl``); the XLA fallback keeps unpadded pools and would
    otherwise gather real padding bytes every step.
    """
    assert cfg.family in ("dense", "moe", "hybrid"), cfg.family
    dt = jnp.dtype(cfg.dtype)
    l = cfg.n_layers
    kvp, hd = cfg.kv_heads_padded, cfg.resolved_head_dim
    pps = pages_per_slot or n_pages
    pallas = kops.resolve_impl(cfg.attn_impl).startswith("pallas")
    hd_pad = (-(-hd // 128) * 128) if pallas else hd
    pool_shape = (l, kvp, n_pages, page_size, hd_pad)
    cache = {
        "length": jnp.zeros((batch,), jnp.int32),
        "pages_k": jnp.zeros(pool_shape, dt),
        "pages_v": jnp.zeros(pool_shape, dt),
        "page_table": jnp.zeros((batch, pps), jnp.int32),
    }
    if cfg.bias_kind == "alibi":
        r_slab = 128 if pallas else 2
        cache["pages_phi"] = jnp.zeros((n_pages, page_size, r_slab),
                                       jnp.float32)
    if cfg.family == "hybrid":
        hs, p, n = cfg.ssm_heads_padded, cfg.ssm_head_dim, cfg.ssm_state
        w = cfg.conv_width
        cache["ssm_h"] = jnp.zeros((l, batch, hs, p, n), jnp.float32)
        cache["conv_x"] = jnp.zeros((l, batch, w - 1, hs, p), dt)
        cache["conv_bc"] = jnp.zeros((l, batch, w - 1, 2 * n), dt)
    return cache


def insert_paged_cache_at_slots(dst: dict, src: dict, slots, tables) -> dict:
    """Scatter a prefilled wave into the paged cache, whole pages at a time.

    ``src`` is a contiguous wave cache from ``prefill`` whose sequence
    length S is a page multiple, kv-head-major like the pool (prefill emits
    it that way — its pages scatter into the pool DIRECTLY, there is no
    post-hoc fixup pass).
    ``tables`` (W, pages_per_slot) int32 holds each wave row's full
    page-table row — the pages covering its prompt first, then any pages
    reserved for decode growth; unused entries carry an out-of-range id
    (>= n_pages) and the corresponding page writes are DROPPED, exactly
    like out-of-range ``slots`` drop whole rows. Prompt pages scatter K/V
    content and position factors into the pool; the page table and
    per-slot ``length`` scatter at ``slots``; SSM leaves (hybrid) ride the
    slot path of ``insert_cache_at_slots``.
    """
    slots = jnp.asarray(slots, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    # pool (L, KVH, n_pages, ps, hd_pad); src (L, W, KVH, S, hd)
    n_pages, ps = dst["pages_k"].shape[2], dst["pages_k"].shape[3]
    s = src["k"].shape[3]
    w = tables.shape[0]
    assert s % ps == 0, (s, ps)
    p_w = s // ps
    if tables.shape[1] >= p_w:
        content_ids = tables[:, :p_w]
    else:
        content_ids = jnp.pad(tables, ((0, 0), (0, p_w - tables.shape[1])),
                              constant_values=n_pages)
    flat_ids = content_ids.reshape(-1)                    # (W * P_w,)

    out = dict(dst)
    for key, pool_key in (("k", "pages_k"), ("v", "pages_v")):
        kv = src[key]
        l, kvh, hd = kv.shape[0], kv.shape[2], kv.shape[4]
        pages = kv.reshape(l, w, kvh, p_w, ps, hd)
        pages = pages.transpose(0, 2, 1, 3, 4, 5)
        pages = pages.reshape(l, kvh, w * p_w, ps, hd)
        hd_pad = dst[pool_key].shape[-1]
        if hd_pad != hd:                  # pool is lane-padded at init
            pages = jnp.pad(pages, ((0, 0),) * 4 + ((0, hd_pad - hd),))
        out[pool_key] = dst[pool_key].at[:, :, flat_ids].set(
            pages, mode="drop")
    if "pages_phi" in dst:
        r_slab = dst["pages_phi"].shape[-1]
        pos = jnp.arange(s, dtype=jnp.float32)
        rows = jnp.stack([jnp.ones_like(pos), pos], -1)   # (S, 2): [1, pos]
        if r_slab > 2:                    # lane-padded slab (Pallas impls)
            rows = jnp.pad(rows, ((0, 0), (0, r_slab - 2)))
        rows = jnp.broadcast_to(rows.reshape(1, p_w, ps, r_slab),
                                (w, p_w, ps, r_slab))
        out["pages_phi"] = dst["pages_phi"].at[flat_ids].set(
            rows.reshape(w * p_w, ps, r_slab), mode="drop")
    out["page_table"] = dst["page_table"].at[slots].set(tables, mode="drop")
    out["length"] = dst["length"].at[slots].set(src["length"], mode="drop")
    for key in ("ssm_h", "conv_x", "conv_bc"):
        if key in dst:
            out[key] = dst[key].at[:, slots].set(src[key], mode="drop")
    return out


def grow_page_tables_at_slots(dst: dict, slots, tables) -> dict:
    """Rewrite the page-table rows of slots that grew a page mid-flight.

    Lazy page growth (ISSUE 4) appends physical pages to a live request as
    its length crosses page boundaries. Only the int32 table rows move —
    the pages already holding K/V content and ``phi_k`` factor rows are
    NOT re-scattered (``insert_paged_cache_at_slots`` moves content; this
    is its growth-only complement). ``tables`` (W, pages_per_slot) carries
    each growing slot's FULL new row (existing pages + the appended ones,
    then out-of-range sentinels); rows whose ``slots`` entry is out of
    range (>= n_slots) are dropped, so a fixed-width growth batch compiles
    once per engine."""
    slots = jnp.asarray(slots, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    out = dict(dst)
    out["page_table"] = dst["page_table"].at[slots].set(tables, mode="drop")
    return out


def copy_paged_pages(dst: dict, src_ids, dst_ids) -> dict:
    """Copy whole pages ``src_ids[i] -> dst_ids[i]`` across every paged
    pool leaf: K, V, and the ``pages_phi`` factor slab.

    The copy-on-write primitive for prefix caching (ISSUE 9): when a
    request must write into a page other holders share (its prompt re-run
    span or decode growth lands mid-page), the engine allocates a private
    page and copies the shared content here before any write. ``src_ids``
    and ``dst_ids`` are fixed-width ``(W,)`` int32 vectors; entries whose
    dst id is out of range (>= n_pages) are DROPPED and their src id is
    only clamped, so a fixed-width CoW batch compiles once per engine.
    All gathers read the pre-copy pool, so a batch may even reuse a
    just-evicted src page as another entry's dst. Theta(W * page) — never
    pool-sized, and no relayout of the pool itself (statcheck
    ``no-pool-relayout`` holds for this program)."""
    src_ids = jnp.asarray(src_ids, jnp.int32)
    dst_ids = jnp.asarray(dst_ids, jnp.int32)
    out = dict(dst)
    for pool_key in ("pages_k", "pages_v"):
        pool = dst[pool_key]                    # (L, KVH, n_pages, ps, hd_pad)
        take = jnp.clip(src_ids, 0, pool.shape[2] - 1)
        out[pool_key] = pool.at[:, :, dst_ids].set(pool[:, :, take],
                                                   mode="drop")
    if "pages_phi" in dst:
        phi = dst["pages_phi"]                  # (n_pages, ps, r_slab)
        take = jnp.clip(src_ids, 0, phi.shape[0] - 1)
        out["pages_phi"] = phi.at[dst_ids].set(phi[take], mode="drop")
    return out


def insert_cache_at_slots(dst: dict, src: dict, slots) -> dict:
    """Scatter wave-cache rows of ``src`` into batch slots of ``dst``.

    ``slots`` (W,) int32 gives the destination slot of each wave row; rows
    whose entry is out of range (>= n_slots) are DROPPED, so a fixed-size
    prefill wave can carry padding rows without a second compile. Works for
    every cache kind: ``length`` is per-slot, everything else is layer-major
    ``(L, B, ...)`` — including per-slot ``phi_k`` factor rows if a model
    caches them.
    """
    slots = jnp.asarray(slots, jnp.int32)
    out = {}
    for key, v in dst.items():
        if key == "length":
            out[key] = v.at[slots].set(src[key], mode="drop")
        else:
            out[key] = v.at[:, slots].set(src[key], mode="drop")
    return out
