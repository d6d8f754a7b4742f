"""Uniform Model interface consumed by the trainer, server and dry-run.

``get_model(cfg)`` returns a ``Model`` with:

- ``template()``                     — PDef tree (shapes + sharding axes),
- ``loss(params, batch)``            — training loss,
- ``prefill(params, batch, max_len, lengths)`` — prompt -> (logits, cache);
  ``lengths`` (B,) enables ragged right-padded prompts (logits gathered at
  each row's last valid position, state paths freeze there),
- ``prefill_chunk(params, cache, tokens, offsets, chunk_lens, final_lens,
  max_pages=None)`` — chunked prefill (LM families): append one fixed-size
  chunk per slot row against the EXISTING slot cache; rows with
  ``chunk_lens == 0`` stay bit-identical (the serve backend interleaves
  one chunk batch with each decode step),
- ``decode(params, cache, tokens, max_pages=None)`` — one token ->
  (logits, cache); ``max_pages`` (static) caps the pages a paged decode
  step can reference (the serve engine derives it from host-side lengths),
- ``init_cache(batch, max_len)``     — zeroed cache pytree (stored in the
  kernel-native kv-head-major layout — see ops.py's cache layout
  contract),
- ``insert_cache(dst, src, slots)``  — scatter prefilled wave rows into the
  serve engine's slot cache (out-of-range slot ids are dropped),
- ``init_paged_cache(batch, n_pages, page_size, pages_per_slot)`` — zeroed
  PAGED cache (shared K/V/phi-factor page pool + per-slot page tables) for
  full-KV decode families,
- ``insert_paged(dst, src, slots, tables)`` — scatter a prefilled wave into
  the paged cache whole pages at a time (``tables`` carries each row's
  page-table row; out-of-range page/slot ids are dropped),
- ``grow_page_table(dst, slots, tables)`` — rewrite page-table rows for
  slots that grew a page mid-flight (lazy growth); existing page CONTENT
  is not re-scattered, only the int32 rows move,
- ``copy_pages(dst, src_ids, dst_ids)`` — copy whole pages (K/V +
  ``pages_phi`` rows) between pool slots: the copy-on-write primitive for
  prefix caching (out-of-range dst ids are dropped),
- ``input_specs(shape)``             — ShapeDtypeStruct stand-ins for every
  model input of an assigned (shape) cell: weak-type-correct, shardable,
  never allocated. This is what the multi-pod dry-run lowers against.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.models import blocks, lm, pairformer, pde, swin

__all__ = ["Model", "get_model"]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    template: Callable[[], dict]
    loss: Callable
    prefill: Optional[Callable] = None
    prefill_chunk: Optional[Callable] = None
    decode: Optional[Callable] = None
    init_cache: Optional[Callable] = None
    insert_cache: Optional[Callable] = None
    init_paged_cache: Optional[Callable] = None
    insert_paged: Optional[Callable] = None
    grow_page_table: Optional[Callable] = None
    copy_pages: Optional[Callable] = None
    input_specs: Optional[Callable] = None


def _lm_model(cfg: ArchConfig) -> Model:
    def input_specs(shape: ShapeSpec, *, abstract_cache: bool = True):
        """Inputs for one dry-run cell. For decode kinds this includes the
        KV/SSM cache as ShapeDtypeStructs (``serve_step`` takes it as input).
        """
        b, s = shape.global_batch, shape.seq_len
        tok = jnp.int32
        front = cfg.frontend_len
        specs: dict = {}
        if shape.kind == "train":
            specs["tokens"] = jax.ShapeDtypeStruct((b, s - front), tok)
            specs["labels"] = jax.ShapeDtypeStruct((b, s - front), tok)
            if front:
                specs["frontend"] = jax.ShapeDtypeStruct(
                    (b, front, cfg.d_model), jnp.dtype(cfg.dtype))
        elif shape.kind == "prefill":
            specs["tokens"] = jax.ShapeDtypeStruct((b, s - front), tok)
            if front:
                specs["frontend"] = jax.ShapeDtypeStruct(
                    (b, front, cfg.d_model), jnp.dtype(cfg.dtype))
        elif shape.kind == "decode":
            if abstract_cache:   # never allocates (command-r 32k cache = TBs)
                cache = jax.eval_shape(lambda: lm.init_cache(cfg, b, s))
            else:
                cache = lm.init_cache(cfg, b, s)
            specs["cache"] = cache
            specs["tokens"] = jax.ShapeDtypeStruct((b, 1), tok)
        return specs

    return Model(
        cfg=cfg,
        template=lambda: lm.lm_template(cfg),
        loss=lambda p, batch: lm.loss_fn(p, batch, cfg),
        prefill=lambda p, batch, max_len=None, lengths=None: lm.prefill(
            p, batch, cfg, max_len=max_len, lengths=lengths),
        # chunked prefill (ISSUE 7): append a C-token chunk per slot against
        # the EXISTING slot cache — offsets/chunk_lens/final_lens per row,
        # chunk_lens == 0 freezes a lane (see lm.prefill_chunk's contract)
        prefill_chunk=lambda p, cache, tokens, offsets, chunk_lens,
            final_lens, max_pages=None: lm.prefill_chunk(
                p, cache, tokens, cfg, offsets=offsets,
                chunk_lens=chunk_lens, final_lens=final_lens,
                max_pages=max_pages),
        decode=lambda p, cache, tokens, max_pages=None: lm.decode_step(
            p, cache, tokens, cfg, max_pages=max_pages),
        init_cache=lambda b, max_len, length=0: lm.init_cache(
            cfg, b, max_len, length=length),
        insert_cache=lm.insert_cache_at_slots,
        init_paged_cache=(
            (lambda b, n_pages, page_size, pages_per_slot=None:
             lm.init_paged_cache(cfg, b, n_pages, page_size, pages_per_slot))
            if cfg.family in ("dense", "moe", "hybrid") else None),
        insert_paged=(lm.insert_paged_cache_at_slots
                      if cfg.family in ("dense", "moe", "hybrid") else None),
        grow_page_table=(lm.grow_page_tables_at_slots
                         if cfg.family in ("dense", "moe", "hybrid")
                         else None),
        copy_pages=(lm.copy_paged_pages
                    if cfg.family in ("dense", "moe", "hybrid") else None),
        input_specs=input_specs,
    )


def _blocks_model(cfg: ArchConfig) -> Model:
    """An LM whose layers mix kinds (``cfg.layer_kinds``): models/blocks.py
    for the model, lm.py's cache programs for the serve backend."""
    return Model(
        cfg=cfg,
        template=lambda: blocks.template(cfg),
        loss=lambda p, batch: blocks.loss_fn(p, batch, cfg),
        prefill=lambda p, batch, max_len=None, lengths=None: blocks.prefill(
            p, batch, cfg, max_len=max_len, lengths=lengths),
        prefill_chunk=lambda p, cache, tokens, offsets, chunk_lens,
            final_lens, max_pages=None: blocks.prefill_chunk(
                p, cache, tokens, cfg, offsets=offsets,
                chunk_lens=chunk_lens, final_lens=final_lens,
                max_pages=max_pages),
        decode=lambda p, cache, tokens, max_pages=None: blocks.decode_step(
            p, cache, tokens, cfg, max_pages=max_pages),
        init_cache=lambda b, max_len, length=0: blocks.init_cache(
            cfg, b, max_len, length=length),
        insert_cache=lm.insert_cache_at_slots,
        init_paged_cache=lambda b, n_pages, page_size, pages_per_slot=None:
            blocks.init_paged_cache(cfg, b, n_pages, page_size,
                                    pages_per_slot),
        insert_paged=lm.insert_paged_cache_at_slots,
        grow_page_table=lm.grow_page_tables_at_slots,
        copy_pages=lm.copy_paged_pages)


def _swin_model(cfg: ArchConfig) -> Model:
    def input_specs(shape: ShapeSpec, **_):
        b = shape.global_batch
        return {"patches": jax.ShapeDtypeStruct((b, 4, cfg.window, 48),
                                                jnp.float32),
                "labels": jax.ShapeDtypeStruct((b,), jnp.int32)}
    return Model(cfg=cfg,
                 template=lambda: swin.swin_template(cfg),
                 loss=lambda p, batch: swin.classify_loss(p, batch, cfg),
                 input_specs=input_specs)


def _pde_model(cfg: ArchConfig) -> Model:
    def input_specs(shape: ShapeSpec, **_):
        b, n = shape.global_batch, shape.seq_len
        return {"coords": jax.ShapeDtypeStruct((b, n, cfg.coord_dim),
                                               jnp.float32),
                "targets": jax.ShapeDtypeStruct((b, n, 4), jnp.float32)}
    return Model(cfg=cfg,
                 template=lambda: pde.pde_template(cfg),
                 loss=lambda p, batch: pde.regression_loss(p, batch, cfg),
                 input_specs=input_specs)


def _pairformer_model(cfg: ArchConfig) -> Model:
    def input_specs(shape: ShapeSpec, **_):
        b, n = shape.global_batch, shape.seq_len
        return {"feats": jax.ShapeDtypeStruct((b, n, 64), jnp.float32),
                "coords": jax.ShapeDtypeStruct((b, n, 3), jnp.float32)}
    return Model(
        cfg=cfg,
        template=lambda: pairformer.pairformer_template(cfg),
        loss=lambda p, batch: pairformer.denoise_loss(p, batch, cfg),
        # batched serve path (ISSUE 6): "prefill" is the admission trunk
        # pass capturing per-complex bias state, "decode" one refinement
        # iteration over the slot batch. ``factors`` (the fitted factor
        # MLPs) is backend state the PairBatchBackend closes over.
        prefill=lambda p, batch, max_len=None, lengths=None, factors=None:
            pairformer.serve_prefill(p, batch, cfg, factors,
                                     max_len=max_len, lengths=lengths),
        decode=lambda p, cache, tokens=None, max_pages=None:
            pairformer.serve_step(p, cache, cfg),
        init_cache=lambda b, max_len, length=0, factors=None:
            pairformer.init_serve_cache(cfg, b, max_len, factors=factors),
        insert_cache=pairformer.insert_serve_cache_at_slots,
        input_specs=input_specs)


def get_model(cfg: ArchConfig) -> Model:
    if cfg.layer_kinds:
        return _blocks_model(cfg)
    if cfg.family in ("dense", "moe", "ssm", "hybrid"):
        return _lm_model(cfg)
    if cfg.family == "swin":
        return _swin_model(cfg)
    if cfg.family == "pde":
        return _pde_model(cfg)
    if cfg.family == "pairformer":
        return _pairformer_model(cfg)
    raise ValueError(cfg.family)
