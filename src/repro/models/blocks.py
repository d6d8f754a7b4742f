"""Layer kinds, and the LM whose layers mix them in published order.

An interleaved hybrid (Granite 4.0-H: 36 Mamba2 and 4 attention layers,
``cfg.layer_kinds``) is a stack of layers that differ in their MIXER only:

    x = x + m * mixer(norm(x))        m = cfg.residual_multiplier
    x = x + m * swiglu(norm(x))

The SwiGLU's gate and up matrices are stored as ``wi`` (2, d, f), not the
homogeneous families' fused (d, f, 2): a trailing axis of 2 is not a layout
the chip's matmuls read, and XLA relaid the whole stacked (d, f, 2) weight
out in every decode step (2.4 GB of the Mamba layers' MLPs).

Each mixer kind sits behind one interface, ``Kind``:

- ``template(cfg)``: one layer's mixer parameters;
- ``forward(mp, h, cfg, lengths)``: the whole sequence (train, prefill),
  returning the layer's cache entries;
- ``decode(mp, h, cache, i, cfg, step)`` / ``chunk(...)``: one token or one
  prefill chunk against layer ``i`` of the kind's cache leaves, written in
  place (the whole stacks ride the loop's carry, as the paged pools of the
  homogeneous decode step do).

Each kind owns cache leaves stacked over the layers of that kind only
(leading axis ``cfg.layers_of(kind)``): attention its K/V (a paged pool, or
per-slot rows), Mamba the per-slot SSM state and conv tails.

The parameters hold one stack per kind, ``params["kinds"][kind]``, and the
layers run in order as scans over runs of consecutive layers of one kind
(``_runs``); a layer's weights are read out of its kind's stack by index
inside the loop. Cache leaves keep the names and layouts of ``lm.py``'s
caches, so the serve backend's insert, page-table, page-copy and sampling
programs (``lm.insert_paged_cache_at_slots`` and friends) serve both
kinds as they are.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import flags
from repro.configs.base import ArchConfig
from repro.models import lm
from repro.models.common import (
    PDef,
    cross_entropy_loss,
    embed_lookup,
    rmsnorm,
    stack_layers,
    unembed_logits,
)

__all__ = ["KINDS", "template", "loss_fn", "prefill", "prefill_chunk",
           "decode_step", "init_cache", "init_paged_cache"]

_SSM_KEYS = ("ssm_h", "conv_x", "conv_bc")


class Step:
    """What a decode or chunk step passes every layer: lengths, the
    active mask, and the paged cache's table and page cap."""

    def __init__(self, lengths=None, active=None, page_table=None,
                 max_pages=None, offsets=None, chunk_lens=None):
        self.lengths, self.active = lengths, active
        self.page_table, self.max_pages = page_table, max_pages
        self.offsets, self.chunk_lens = offsets, chunk_lens


class Kind:
    name: str = ""

    def template(self, cfg: ArchConfig) -> dict:
        raise NotImplementedError

    def forward(self, mp, h, cfg, lengths=None):
        """(y, cache entries of this layer)."""
        raise NotImplementedError

    def decode(self, mp, h, cache, i, cfg, step: Step):
        """(y, cache) with this layer's leaves updated at index ``i``."""
        raise NotImplementedError

    def chunk(self, mp, h, cache, i, cfg, step: Step):
        raise NotImplementedError


class Attention(Kind):
    """Causal attention (GQA, optional ALiBi) through the flash kernels."""
    name = "attention"

    def template(self, cfg):
        t = lm._attn_template(cfg)
        if cfg.bias_kind != "alibi":
            del t["slopes"]
        return t

    def forward(self, mp, h, cfg, lengths=None):
        y, k, v = lm._attention(mp, h, cfg, mask_kind="causal")
        return y, {"k": k, "v": v}

    def decode(self, mp, h, cache, i, cfg, step):
        kk, vv = _kv_keys(cache)
        if kk == "pages_k":       # the kernel reads layer i of the stack
            y, kc, vc = lm._attention_decode(
                mp, h, cache[kk], cache[vv], step.lengths, cfg,
                active=step.active, page_table=step.page_table,
                max_pages=step.max_pages, layer=i)
            return y, dict(cache, pages_k=kc, pages_v=vc)
        y, kc, vc = lm._attention_decode(mp, h, cache[kk][i], cache[vv][i],
                                         step.lengths, cfg,
                                         active=step.active)
        return y, dict(cache, k=cache[kk].at[i].set(kc),
                       v=cache[vv].at[i].set(vc))

    def chunk(self, mp, h, cache, i, cfg, step):
        kk, vv = _kv_keys(cache)
        paged = kk == "pages_k"
        y, kc, vc = lm._attention_chunk(
            mp, h, cache[kk][i], cache[vv][i], cfg, offsets=step.offsets,
            chunk_lens=step.chunk_lens,
            page_table=step.page_table if paged else None,
            max_pages=step.max_pages)
        return y, dict(cache, **{kk: cache[kk].at[i].set(kc),
                                 vv: cache[vv].at[i].set(vc)})


class Mamba(Kind):
    """Mamba2 (SSD) mixer; its state is per slot and constant-size."""
    name = "mamba"

    def template(self, cfg):
        return lm._ssm_template(cfg)

    def forward(self, mp, h, cfg, lengths=None):
        y, hf, tx, tbc = lm._ssm_forward(mp, h, cfg, lengths=lengths)
        return y, {"ssm_h": hf, "conv_x": tx, "conv_bc": tbc}

    def decode(self, mp, h, cache, i, cfg, step):
        old = [cache[k][i] for k in _SSM_KEYS]
        y, *new = lm._ssm_decode(mp, h, *old, cfg)
        # retired slots keep their state (the length-0 idle contract)
        new = [jnp.where(_rows(step.active, n), n, o)
               for n, o in zip(new, old)]
        return y, dict(cache, **{k: cache[k].at[i].set(n)
                                 for k, n in zip(_SSM_KEYS, new)})

    def chunk(self, mp, h, cache, i, cfg, step):
        # a fresh prompt (offset 0) starts from zero state; continuation
        # chunks carry the cached state; rows outside the chunk keep theirs
        old = [cache[k][i] for k in _SSM_KEYS]
        first = step.offsets == 0
        h0, tx0, tbc0 = (jnp.where(_rows(first, o), 0.0, o).astype(o.dtype)
                         for o in old)
        y, *new = lm._ssm_forward(mp, h, cfg, h0=h0, conv_tail_x=tx0,
                                  conv_tail_bc=tbc0, lengths=step.chunk_lens)
        part = step.chunk_lens > 0
        new = [jnp.where(_rows(part, n), n, o) for n, o in zip(new, old)]
        return y, dict(cache, **{k: cache[k].at[i].set(n)
                                 for k, n in zip(_SSM_KEYS, new)})


def _kv_keys(cache: dict) -> Tuple[str, str]:
    """The K/V leaves of a paged or a contiguous cache."""
    return ("pages_k", "pages_v") if "pages_k" in cache else ("k", "v")


def _rows(mask, like):
    """A (B,) mask shaped to broadcast over ``like``'s per-row axes."""
    return mask.reshape(mask.shape + (1,) * (like.ndim - 1))


KINDS: Dict[str, Kind] = {k.name: k for k in (Attention(), Mamba())}


# ---------------------------------------------------------------------------
# Parameters and layer order
# ---------------------------------------------------------------------------

def template(cfg: ArchConfig) -> dict:
    d = cfg.d_model

    def layer(kind: Kind) -> dict:
        return {"ln1": PDef((d,), (None,), ("zeros",)),
                "mixer": kind.template(cfg),
                "ln2": PDef((d,), (None,), ("zeros",)),
                "mlp": {"wi": PDef((2, d, cfg.d_ff), (None, "fsdp", "mlp"),
                                   ("normal", 0.02)),
                        "wo": PDef((cfg.d_ff, d), ("mlp", "fsdp"),
                                   ("normal", 0.02 / (2 * cfg.n_layers)
                                    ** 0.5))}}

    return {
        "embed": PDef((cfg.vocab_padded, d), ("vocab", "fsdp"),
                      ("normal", 0.02)),
        "kinds": {name: stack_layers(layer(KINDS[name]), cfg.layers_of(name))
                  for name in sorted(set(cfg.layer_kinds))},
        "final_norm": PDef((d,), (None,), ("zeros",)),
    }


def _runs(cfg: ArchConfig) -> List[Tuple[str, int, int]]:
    """(kind, first index within the kind's stack, count) of each run of
    consecutive layers of one kind, in published order."""
    runs: List[Tuple[str, int, int]] = []
    seen: Dict[str, int] = {}
    for kind in cfg.layer_kinds:
        if runs and runs[-1][0] == kind:
            k, start, n = runs[-1]
            runs[-1] = (k, start, n + 1)
        else:
            runs.append((kind, seen.get(kind, 0), 1))
        seen[kind] = seen.get(kind, 0) + 1
    return runs


def _over_layers(params, cfg: ArchConfig, carry, body):
    """``carry = body(kind, lp, i, carry)`` for every layer in order, where
    ``lp`` is layer ``i`` of its kind's stack: a scan over each run (the
    weights read by index in the loop), a plain call for a run of one."""
    dt = jnp.dtype(cfg.dtype)
    stacks = jax.tree.map(
        lambda a: a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, params["kinds"])
    for kind, start, n in _runs(cfg):
        stack = stacks[kind]

        def one(carry, i, kind=kind, stack=stack):
            lp = jax.tree.map(lambda a: a[i], stack)
            return body(KINDS[kind], lp, i, carry), None

        if n == 1:
            carry, _ = one(carry, start)
        else:
            carry, _ = jax.lax.scan(one, carry,
                                    jnp.arange(start, start + n),
                                    unroll=flags.scan_unroll(n))
    return carry


def _layer(lp, x, cfg: ArchConfig, mix):
    """One layer around its mixer: ``mix(h) -> (y, extra)``."""
    y, extra = mix(rmsnorm(x, lp["ln1"], cfg.norm_eps))
    rm = cfg.residual_multiplier
    x = x + y * rm
    m = lp["mlp"]
    h = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    g = jnp.einsum("bsd,df->bsf", h, m["wi"][0])
    u = jnp.einsum("bsd,df->bsf", h, m["wi"][1])
    x = x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, m["wo"]) * rm
    return x, extra


def _embed(params, tokens, cfg: ArchConfig):
    x = embed_lookup(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    return x * cfg.embed_scale


def _logits(params, hid, cfg: ArchConfig):
    hid = rmsnorm(hid, params["final_norm"], cfg.norm_eps)
    logits = unembed_logits(hid, params["embed"].astype(hid.dtype))
    return logits / cfg.logits_scaling


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

def forward_hidden(params, tokens, cfg: ArchConfig):
    def body(kind, lp, i, x):
        x, _ = _layer(lp, x, cfg,
                      lambda h: kind.forward(lp["mixer"], h, cfg))
        return x

    return _over_layers(params, cfg, _embed(params, tokens, cfg), body)


def loss_fn(params, batch, cfg: ArchConfig):
    hid = forward_hidden(params, batch["tokens"], cfg)
    return cross_entropy_loss(_logits(params, hid, cfg), batch["labels"],
                              cfg.vocab)


def prefill(params, batch, cfg: ArchConfig, *, max_len: Optional[int] = None,
            lengths=None):
    """Run the prompt; return (last-position logits, cache), as
    ``lm.prefill``: ragged rows (``lengths``) end their SSM scans and conv
    tails at their own last position; K/V entries of the attention layers
    are padded to ``max_len`` in the cache layout."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    max_len = max_len or s
    lengths = None if lengths is None else jnp.asarray(lengths, jnp.int32)

    def body(kind, lp, i, carry):
        x, ent = carry
        x, e = _layer(lp, x, cfg,
                      lambda h: kind.forward(lp["mixer"], h, cfg, lengths))
        return x, dict(ent, **{k: ent[k].at[i].set(v) for k, v in e.items()})

    x = _embed(params, tokens, cfg)
    x, caches = _over_layers(params, cfg,
                             (x, _empty_entries(params, cfg, x, lengths)),
                             body)
    if lengths is None:
        last = x[:, -1:]
    else:
        last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    logits = _logits(params, last, cfg)
    lens = (jnp.full((b,), s, jnp.int32) if lengths is None else lengths)
    return logits, lm._prefill_cache(caches, cfg, lens, s, max_len)


def _empty_entries(params, cfg: ArchConfig, x, lengths) -> dict:
    """Zero stacks of every cache entry a prefill of ``x`` writes, shaped
    by tracing one layer of each kind abstractly."""
    out = {}
    for name in sorted(set(cfg.layer_kinds)):
        kind = KINDS[name]
        lp = jax.tree.map(lambda a: a[0], params["kinds"][name])
        shapes = jax.eval_shape(
            lambda lp, h, n, kind=kind: kind.forward(lp["mixer"], h, cfg,
                                                     n)[1],
            lp, x, lengths)
        for key, sd in shapes.items():
            out[key] = jnp.zeros((cfg.layers_of(name),) + sd.shape, sd.dtype)
    return out


def decode_step(params, cache, tokens, cfg: ArchConfig, *, max_pages=None):
    """One decode step; the contract of ``lm.decode_step`` (length-0 rows
    frozen, ``max_pages`` the static page cap). Every cache leaf rides the
    layer loops' carry whole and each layer writes its own index in
    place."""
    active = cache["length"] > 0
    lengths = cache["length"] + active.astype(jnp.int32)
    step = Step(lengths=lengths, active=active,
                page_table=cache.get("page_table"), max_pages=max_pages)
    leaves = {k: v for k, v in cache.items()
              if k not in ("length", "page_table")}

    def body(kind, lp, i, carry):
        x, c = carry
        return _layer(lp, x, cfg,
                      lambda h: kind.decode(lp["mixer"], h, c, i, cfg, step))

    x, leaves = _over_layers(params, cfg,
                             (_embed(params, tokens, cfg), leaves), body)
    new = dict(cache, **leaves)
    new["length"] = lengths
    return _logits(params, x, cfg), new


def prefill_chunk(params, cache, tokens, cfg: ArchConfig, *, offsets,
                  chunk_lens, final_lens, max_pages=None):
    """One chunked-prefill step; the contract of ``lm.prefill_chunk``."""
    offsets = jnp.asarray(offsets, jnp.int32)
    chunk_lens = jnp.asarray(chunk_lens, jnp.int32)
    final_lens = jnp.asarray(final_lens, jnp.int32)
    step = Step(page_table=cache.get("page_table"), max_pages=max_pages,
                offsets=offsets, chunk_lens=chunk_lens)
    leaves = {k: v for k, v in cache.items()
              if k not in ("length", "page_table")}

    def body(kind, lp, i, carry):
        x, c = carry
        return _layer(lp, x, cfg,
                      lambda h: kind.chunk(lp["mixer"], h, c, i, cfg, step))

    x, leaves = _over_layers(params, cfg,
                             (_embed(params, tokens, cfg), leaves), body)
    last = jnp.take_along_axis(
        x, jnp.clip(chunk_lens - 1, 0)[:, None, None], axis=1)
    new = dict(cache, **leaves)
    new["length"] = jnp.where(final_lens >= 0, final_lens, cache["length"])
    return _logits(params, last, cfg), new


def _ssm_leaves(cfg: ArchConfig, batch: int) -> dict:
    n_m = cfg.layers_of("mamba")
    if not n_m:
        return {}
    dt = jnp.dtype(cfg.dtype)
    hs, p, n = cfg.ssm_heads_padded, cfg.ssm_head_dim, cfg.ssm_state
    w = cfg.conv_width
    return {"ssm_h": jnp.zeros((n_m, batch, hs, p, n), jnp.float32),
            "conv_x": jnp.zeros((n_m, batch, w - 1, hs, p), dt),
            "conv_bc": jnp.zeros((n_m, batch, w - 1, 2 * n), dt)}


def _attention_cfg(cfg: ArchConfig) -> ArchConfig:
    """The attention layers alone, as a dense model: what sizes their
    K/V leaves."""
    return cfg.replace(family="dense", n_layers=cfg.layers_of("attention"),
                       layer_kinds=())


def init_cache(cfg: ArchConfig, batch: int, max_len: int, *,
               length: int = 0) -> dict:
    cache = lm.init_cache(_attention_cfg(cfg), batch, max_len, length=length)
    cache.update(_ssm_leaves(cfg, batch))
    return cache


def init_paged_cache(cfg: ArchConfig, batch: int, n_pages: int,
                     page_size: int, pages_per_slot: Optional[int] = None
                     ) -> dict:
    """Two kinds of cache in one manager: K/V page pools of the attention
    layers only (``(n_attention, KVH, n_pages, ps, hd_pad)``), and per-slot
    SSM state and conv tails of the Mamba layers (``(n_mamba, B, ...)``)."""
    cache = lm.init_paged_cache(_attention_cfg(cfg), batch, n_pages,
                                page_size, pages_per_slot)
    cache.update(_ssm_leaves(cfg, batch))
    return cache
