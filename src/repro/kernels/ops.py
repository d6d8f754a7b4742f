"""Jit'd public wrappers around the Pallas kernels.

``flash_attention`` / ``flash_decode`` are the entry points the models call.
They dispatch between the Pallas kernel (TPU, or ``interpret=True`` on CPU
for tests) and the pure-XLA paths, pad tile dims to TPU alignment with
mathematically inert zeros, and (for training) expose a ``jax.custom_vjp``
whose backward re-runs attention via the XLA chunked path's VJP
(flash-style recompute — the paper likewise uses the Triton kernel for
inference and SDPA autograd for training).

Cache layout contract (the decode hot path)
-------------------------------------------

KV caches have one layout, the kernels' own **kv-head-major** one. The
models allocate them that way and every writer (token scatter, prefill
page scatter, ring rotation) writes it, so the jitted decode step hands
them to the kernels zero-copy — there is no per-step transpose, lane-pad
or factor broadcast of anything pool-sized:

- contiguous / ring KV: ``(B, KVH, S, hd)`` per layer. Store ``hd`` as a
  128-lane multiple and ``S`` as a multiple of the decode block (128 is
  always safe) for the zero-copy guarantee; other shapes fall back to a
  documented pad (correctness, not speed).
- paged KV: pools ``(KVH, n_pages, ps, hd_pad)`` per layer, ``hd_pad`` lane-
  padded at ``init_paged_cache``; the model's decode step passes the whole
  ``(L, KVH, n_pages, ps, hd_pad)`` stack with ``layer=`` and the kernel
  reads that layer in place; the per-page ``phi_k`` factor slab stays
  layer- AND kv-head-shared at ``(n_pages, ps, r_pad)`` — the kv-head
  broadcast happens in the kernel's block index maps, never as a
  ``broadcast_to`` on the pool.

The XLA fallbacks take cheap views of the kernel layout (head-major
einsums; the paged gather is capped at ``ceil(max(lengths)/page_size)``
pages when a static bound is known — pass ``max_pages`` from a host-side
length mirror, or call with concrete ``lengths``).

Under a mesh (``dist.use_mesh_rules``) every Pallas call runs inside
``jax.shard_map``: GSPMD cannot partition a Mosaic kernel, so each device
runs the kernel on its own kv heads (``"kv_heads"``, TP) and batch rows
(``"batch"``, DP) of arrays that are already laid out that way — no
collective touches a pool. With no mesh the call is made directly.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import attention as attn_mod
from repro.core.attention import DEFAULT_MASK_VALUE, MaskSpec
from repro.dist import sharding as dshard
from repro.kernels import flash_decode as _fd
from repro.kernels import flashbias_attn as _fa
from repro.kernels import ssd_scan as _ssd

__all__ = ["flash_attention", "flash_chunk_attention", "flash_decode",
           "ssd_scan", "resolve_impl", "IMPLS"]

IMPLS = ("xla", "pallas", "pallas_interpret", "io_stub")

_LANE = 128


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_axis(x: jax.Array, axis: int, target: int) -> jax.Array:
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl: str) -> str:
    """Public impl resolution ("auto" -> "pallas" on TPU, "xla" elsewhere).

    Models use this to pick the compute layout that will be zero-copy for
    the impl that actually runs (head-major for the Pallas kernels)."""
    if impl == "auto":
        return "pallas" if _on_tpu() else "xla"
    assert impl in IMPLS, impl
    return impl


def _pick_block(s_len: int, want: int) -> int:
    """Largest multiple-of-8 divisor of ``s_len`` that is <= ``want``
    (8 = TPU sublane: Mosaic rejects blocks whose second-minor dim isn't a
    multiple of it). Returns ``s_len`` itself below 8 (a single tiny
    block) and 0 when no
    aligned divisor exists — the caller then pads the seq axis once.

    Under the cache layout contract (S a multiple of 128, or S <= want)
    this finds >= min(want, 128), so the decode path never pads the cache
    sequence axis. Trace-time Python: <= want/8 steps."""
    if s_len < 8:
        return s_len
    b = (min(want, s_len) // 8) * 8
    while b >= 8:
        if s_len % b == 0:
            return b
        b -= 8
    return 0


def _per_shard(kernel, arrays: dict, roles: dict, out_role: tuple, *,
               batch: int, kv_heads: int):
    """``kernel(**arrays)``, run per shard when a mesh is active.

    ``roles[name]`` names each dim of ``arrays[name]``: ``"batch"``,
    ``"kv_heads"`` (any head dim grouped by kv head) or None (whole on
    every device). A logical axis shards only when its size (``batch`` /
    ``kv_heads``) divides its mesh axes — otherwise it stays whole on every
    device, so the kernel's head grouping is never split unevenly. None
    entries of ``arrays`` are passed through as None."""
    ctx = dshard.get_active_mesh()
    if ctx is None:
        return kernel(**arrays)
    mesh, rules = ctx

    def resolve(logical, size):
        ax = dshard.entry_axes(dshard.spec_for((logical,), mesh, rules)[0])
        n = math.prod(int(mesh.shape[a]) for a in ax)
        return ax if ax and size % n == 0 else None

    mesh_axes = {"batch": resolve("batch", batch),
                 "kv_heads": resolve("kv_heads", kv_heads)}

    def spec(role):
        return P(*(mesh_axes[r] if r else None for r in role))

    names = [k for k, v in arrays.items() if v is not None]
    nones = {k: None for k, v in arrays.items() if v is None}

    def local(*xs):
        return kernel(**dict(zip(names, xs)), **nones)

    return jax.shard_map(
        local, mesh=mesh, in_specs=tuple(spec(roles[k]) for k in names),
        out_specs=spec(out_role), check_vma=False)(
            *(arrays[k] for k in names))


# ---------------------------------------------------------------------------
# Full (training / prefill) attention
# ---------------------------------------------------------------------------

def _xla_path(q, k, v, phi_q, phi_k, slopes, mask_kind, window, scale,
              chunk_size=512, lengths=None):
    if slopes is not None:
        # materialize rank-2 ALiBi factors (cheap: (N+M)*2 elements)
        n, m, h = q.shape[1], k.shape[1], q.shape[2]
        qi = jnp.arange(n, dtype=jnp.float32)
        kj = jnp.arange(m, dtype=jnp.float32)
        pq = jnp.stack([-qi, jnp.ones_like(qi)], -1)[None, :, None, :]
        pq = pq * slopes.reshape(1, 1, h, 1)
        pk = jnp.stack([jnp.ones_like(kj), kj], -1)[None, :, None, :]
        phi_q = jnp.broadcast_to(pq, (q.shape[0], n, h, 2)).astype(jnp.float32)
        phi_k = jnp.broadcast_to(pk, (q.shape[0], m, 1, 2)).astype(jnp.float32)
    if phi_k is not None and phi_k.shape[2] not in (1, q.shape[2]):
        # per-kv-head factors (B, M, KVH, R): expand each kv head's factor
        # row over its group of query heads. (Collapsing to head 0 here
        # would silently mis-bias every non-first kv group under GQA.)
        kvh_pk = phi_k.shape[2]
        assert q.shape[2] % kvh_pk == 0, (phi_k.shape, q.shape)
        phi_k = jnp.repeat(phi_k, q.shape[2] // kvh_pk, axis=2)
    if phi_k is not None and phi_k.shape[2] == 1:
        phi_k = jnp.broadcast_to(
            phi_k, (*phi_k.shape[:2], q.shape[2], phi_k.shape[3]))
    return attn_mod.attention(
        q, k, v, mask=MaskSpec(mask_kind, window), scale=scale,
        phi_q=phi_q, phi_k=phi_k, kv_length=lengths, impl="chunked",
        chunk_size=chunk_size)


def _pallas_path(q, k, v, phi_q, phi_k, slopes, *args, **kwargs):
    """Token-major (``layout="bshd"``) Pallas dispatch: the head-major path
    on transposed views (swapping axes 1 and 2 is its own inverse)."""
    t = _to_bshd
    return t(_pallas_path_hm(t(q), t(k), t(v), t(phi_q), t(phi_k), slopes,
                             *args, **kwargs))


def _pallas_path_hm(q, k, v, phi_q, phi_k, slopes, mask_kind, window, scale,
                    block_q, block_k, interpret, lengths=None):
    """Head-major (``layout="bhsd"``) Pallas dispatch: the kernel's native
    layout arrives from the caller, so only tile padding remains (token-
    and channel-sized, never a whole-tensor transpose)."""
    b, h, n, d = q.shape
    kvh, m = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    n_p, m_p = _ceil_to(n, block_q), _ceil_to(m, block_k)
    d_p, dv_p = _ceil_to(d, _LANE), _ceil_to(dv, _LANE)

    qt = _pad_axis(_pad_axis(q, 2, n_p), 3, d_p)
    kt = _pad_axis(_pad_axis(k, 2, m_p), 3, d_p)
    vt = _pad_axis(_pad_axis(v, 2, m_p), 3, dv_p)

    pqt = pkt = None
    if phi_q is not None:
        r = phi_q.shape[-1]
        r_p = _ceil_to(r, _LANE)
        if phi_k.shape[1] not in (1, h):     # per-kv-head: expand per group
            assert h % phi_k.shape[1] == 0, (phi_k.shape, h)
            phi_k = jnp.repeat(phi_k, h // phi_k.shape[1], axis=1)
        phi_k_full = jnp.broadcast_to(phi_k, (b, h, m, r))
        pqt = _pad_axis(_pad_axis(phi_q, 2, n_p), 3, r_p)
        pkt = _pad_axis(_pad_axis(phi_k_full, 2, m_p), 3, r_p)

    kernel = functools.partial(
        _fa.flashbias_attention_fwd, scale=scale, mask_kind=mask_kind,
        window=window, kv_len=m, block_q=block_q, block_k=block_k,
        interpret=interpret)
    bh = ("batch", "kv_heads", None, None)
    out = _per_shard(
        kernel,
        dict(q=qt, k=kt, v=vt, phi_q=pqt, phi_k=pkt, slopes=slopes,
             lengths=lengths),
        dict(q=bh, k=bh, v=bh, phi_q=bh, phi_k=bh, slopes=("kv_heads",),
             lengths=("batch",)),
        bh, batch=b, kv_heads=kvh)
    return out[:, :, :n, :dv]


def _io_stub_path(q, k, v, phi_q, phi_k, dv):
    """Deployment-IO accounting stub (dry-run only, ``impl="io_stub"``).

    The Pallas kernel's HBM traffic is exactly: read q, k, v (+ factors)
    once, write o once — logits/softmax live in VMEM. This stub has the
    same HBM footprint and output shape but trivial FLOPs, so a cost
    lowering with it measures the *deployment* memory term (the XLA chunked
    fallback materializes its softmax pipeline, inflating bytes ~10x).
    Every input is consumed through a full-read reduction so XLA cannot
    DCE the loads. Layout-agnostic: the output mirrors q's leading axes.
    """
    eps = jnp.asarray(1e-30, jnp.float32)
    dep = (jnp.sum(k.astype(jnp.float32)) + jnp.sum(v.astype(jnp.float32)))
    if phi_q is not None:
        dep = dep + jnp.sum(phi_q.astype(jnp.float32)) \
            + jnp.sum(phi_k.astype(jnp.float32))
    o = q[..., :1].astype(jnp.float32) * eps + dep * eps
    o = jnp.broadcast_to(o, (*q.shape[:3], dv))
    return o.astype(q.dtype)


def _to_bshd(x):
    return None if x is None else x.transpose(0, 2, 1, 3)


def _xla_path_any_layout(q, k, v, phi_q, phi_k, slopes, mask_kind, window,
                         scale, layout, lengths=None):
    """XLA chunked fallback for either layout — the single canonicalize
    point for ``"bhsd"`` inputs (cheap views in, transposed view out;
    prefill-sized, one-time). The custom_vjp forward AND its backward
    recompute both go through here, so they can never desynchronize."""
    if layout == "bhsd":
        o = _xla_path(_to_bshd(q), _to_bshd(k), _to_bshd(v),
                      _to_bshd(phi_q), _to_bshd(phi_k), slopes,
                      mask_kind, window, scale, lengths=lengths)
        return o.transpose(0, 2, 1, 3)
    return _xla_path(q, k, v, phi_q, phi_k, slopes, mask_kind, window,
                     scale, lengths=lengths)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11, 12))
def _flash_attention_core(q, k, v, phi_q, phi_k, slopes,
                          mask_kind, window, scale, impl, block_q, block_k,
                          layout):
    if impl == "io_stub":
        return _io_stub_path(q, k, v, phi_q, phi_k, v.shape[-1])
    if impl == "xla":
        return _xla_path_any_layout(q, k, v, phi_q, phi_k, slopes,
                                    mask_kind, window, scale, layout)
    path = _pallas_path_hm if layout == "bhsd" else _pallas_path
    return path(q, k, v, phi_q, phi_k, slopes, mask_kind, window,
                scale, block_q, block_k,
                interpret=(impl == "pallas_interpret"))


def _fwd(q, k, v, phi_q, phi_k, slopes, mask_kind, window, scale, impl,
         block_q, block_k, layout):
    out = _flash_attention_core(q, k, v, phi_q, phi_k, slopes, mask_kind,
                                window, scale, impl, block_q, block_k, layout)
    return out, (q, k, v, phi_q, phi_k, slopes)


def _bwd(mask_kind, window, scale, impl, block_q, block_k, layout, res, g):
    q, k, v, phi_q, phi_k, slopes = res
    if impl == "io_stub":
        # deployment backward IO: the flash backward re-reads q,k,v(,phi) and
        # the cotangent once and writes dq,dk,dv(,dphi) once — the stub's own
        # vjp has exactly that HBM footprint.
        def fs(q, k, v, phi_q, phi_k):
            return _io_stub_path(q, k, v, phi_q, phi_k, v.shape[-1])
        _, vjp = jax.vjp(fs, q, k, v, phi_q, phi_k)
        return vjp(g) + (None,)

    # Recompute forward through the differentiable XLA path (flash
    # recompute); head-major inputs flow through the canonicalizing views,
    # so their cotangents come back head-major automatically.
    def f(q, k, v, phi_q, phi_k, slopes):
        return _xla_path_any_layout(q, k, v, phi_q, phi_k, slopes,
                                    mask_kind, window, scale, layout)
    _, vjp = jax.vjp(f, q, k, v, phi_q, phi_k, slopes)
    return vjp(g)


_flash_attention_core.defvjp(_fwd, _bwd)


def _flash_attention_ragged(q, k, v, phi_q, phi_k, slopes, lengths,
                            mask_kind, window, scale, impl, block_q,
                            block_k, layout):
    """Non-causal ragged-batch path (``lengths`` per batch row): the serve
    engine's padded wave of variable-length requests — each row masks keys
    at positions >= its own length, so zero-padded rows (whose factor-MLP
    biases are NOT zero) contribute exact zero.

    Lives outside the custom_vjp (an int32 array can't ride its residual
    contract): the XLA branch is natively differentiable, the Pallas branch
    is forward-only — which is the only way the serve engine calls it.
    """
    if impl == "io_stub":
        return _io_stub_path(q, k, v, phi_q, phi_k, v.shape[-1])
    if impl == "xla":
        return _xla_path_any_layout(q, k, v, phi_q, phi_k, slopes,
                                    mask_kind, window, scale, layout,
                                    lengths=lengths)
    path = _pallas_path_hm if layout == "bhsd" else _pallas_path
    return path(q, k, v, phi_q, phi_k, slopes, mask_kind, window,
                scale, block_q, block_k,
                interpret=(impl == "pallas_interpret"), lengths=lengths)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    phi_q: Optional[jax.Array] = None,
    phi_k: Optional[jax.Array] = None,
    slopes: Optional[jax.Array] = None,
    *,
    mask_kind: str = "none",
    window: int = 0,
    scale: Optional[float] = None,
    impl: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
    layout: str = "bshd",
    lengths: Optional[jax.Array] = None,
) -> jax.Array:
    """FlashBias attention.

    ``layout="bshd"`` (default): canonical (B, N, H, D) in and out.
    ``layout="bhsd"``: the kernels' head-major (B, H, N, D) in and out —
    zero-copy into the Pallas kernel (models that keep kernel-layout caches
    project straight into this layout; see the module docstring).

    Exactly one of {phi_q+phi_k, slopes, neither} selects the bias mode
    (factored / in-kernel ALiBi / none). Differentiable in q, k, v, phi_*.

    ``lengths`` (B,) int32 opts into the RAGGED BATCH path: row b attends
    only to keys at positions < lengths[b] (the serve engine's padded wave
    of variable-length requests). Rows with length 0 output zeros.
    Differentiable via the XLA path; the Pallas ragged kernel is
    forward-only (inference — the only way the serve engine calls it).
    """
    assert layout in ("bshd", "bhsd"), layout
    scale = (1.0 / float(np.sqrt(q.shape[-1]))) if scale is None else scale
    assert not (phi_q is not None and slopes is not None)
    if lengths is not None:
        return _flash_attention_ragged(q, k, v, phi_q, phi_k, slopes,
                                       jnp.asarray(lengths, jnp.int32),
                                       mask_kind, window, scale,
                                       resolve_impl(impl), block_q, block_k,
                                       layout)
    return _flash_attention_core(q, k, v, phi_q, phi_k, slopes, mask_kind,
                                 window, scale, resolve_impl(impl),
                                 block_q, block_k, layout)


# ---------------------------------------------------------------------------
# Decode (one token, KV cache) — inference only, no vjp needed
# ---------------------------------------------------------------------------

def _static_page_cap(lengths, ps: int, p_slot: int,
                     max_pages: Optional[int]) -> int:
    """Static bound on the pages any request can reference this step.

    Preference order: an explicit ``max_pages`` (the serve engine derives
    one from its host-side length mirror), else ``ceil(max(lengths)/ps)``
    when ``lengths`` is concrete (eager callers/tests), else the full
    page-table width (nothing static is known under tracing)."""
    if max_pages is not None:
        return max(1, min(int(max_pages), p_slot))
    try:
        longest = int(jax.device_get(jnp.max(lengths)))
    except jax.errors.ConcretizationTypeError:
        return p_slot
    return max(1, min(-(-longest // ps), p_slot))


def _xla_decode(q, k_cache, v_cache, lengths, phi_q, phi_k, slopes, scale):
    """XLA decode over kv-head-major caches — head-major einsums, no
    transpose or per-head factor materialization of anything pool-sized.

    q (B,1,H,D); k/v (B,KVH,S,E); phi_k (B,KVH,S,R); slopes (H,). Factor
    ranks align by slicing the wider operand (stored factor slabs are
    zero-padded to the lane boundary, so slicing them back is exact)."""
    b, _, h, d = q.shape
    kvh, s_len = k_cache.shape[1], k_cache.shape[2]
    dv = v_cache.shape[-1]
    g = h // kvh
    qg = q[:, 0].reshape(b, kvh, g, d).astype(jnp.float32)
    kf = k_cache.astype(jnp.float32)
    if kf.shape[-1] > d:                      # lane-padded pool vs raw q
        kf = kf[..., :d]
    s = jnp.einsum("bkgd,bksd->bkgs", qg, kf) * scale
    if phi_q is not None:
        r = min(phi_q.shape[-1], phi_k.shape[-1])
        pq = phi_q[:, 0].reshape(b, kvh, g, -1)[..., :r].astype(jnp.float32)
        s = s + jnp.einsum("bkgr,bksr->bkgs", pq,
                           phi_k[..., :r].astype(jnp.float32))
    k_pos = jnp.arange(s_len)
    if slopes is not None:
        rel = (k_pos[None] - (lengths - 1)[:, None]).astype(jnp.float32)
        s = s + slopes.reshape(kvh, g)[None, :, :, None] * rel[:, None, None]
    valid = k_pos[None] < lengths[:, None]
    s = jnp.where(valid[:, None, None], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bkse->bkge", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, h, dv).astype(q.dtype)


def flash_decode(
    q: jax.Array,                        # (B, 1, H, D)
    k_cache: jax.Array,                  # (B, KVH, S, D) or a page pool
    v_cache: jax.Array,
    lengths: jax.Array,                  # (B,) int32
    phi_q: Optional[jax.Array] = None,   # (B, 1, H, R)
    phi_k: Optional[jax.Array] = None,
    slopes: Optional[jax.Array] = None,  # (H,)
    *,
    scale: Optional[float] = None,
    impl: str = "auto",
    block_k: int = 512,
    page_table: Optional[jax.Array] = None,  # (B, P_slot) int32 -> paged mode
    max_pages: Optional[int] = None,
    layer: Optional[jax.Array] = None,   # int32 scalar: paged layer stack
) -> jax.Array:
    """Single-token decode against a kv-head-major KV cache (module
    docstring has the layout contract). Returns (B, 1, H, Dv).

    Contiguous caches are ``(B, KVH, S, *)`` with ``phi_k`` ``(B, KVH, S,
    R)``. With ``page_table`` the caches are a shared PAGE POOL ``(KVH,
    n_pages, ps, *)`` handed to the Pallas kernel zero-copy, and ``phi_k``
    is the layer/kv-head-shared slab ``(n_pages, ps, r_pad)`` (kv-head
    broadcast in the kernel's block index maps) or ``(KVH, n_pages, ps,
    R)``. ``page_table[b, j]`` maps request b's j-th logical block to its
    physical page; entries beyond the mapped prefix are ignored (clamped +
    length-masked). The XLA fallback gathers each request's logical view
    first, capped at ``ceil(max(lengths)/page_size)`` pages (see
    ``max_pages``) instead of the full table width.

    ``layer`` (paged only): the pools are the model's whole layer stack,
    ``(L, *pool)``, and the call attends over layer ``layer``. The Pallas
    path reads that layer in place (a scalar-prefetch operand of the
    kernel) and the XLA paths index it inside their page gather. ``None``:
    the pools are one layer's.
    """
    if page_table is not None:
        return _flash_decode_paged(q, k_cache, v_cache, lengths, page_table,
                                   phi_q, phi_k, slopes, scale=scale,
                                   impl=impl, max_pages=max_pages,
                                   layer=layer)
    assert layer is None, "layer= indexes a paged pool stack"
    b, _, h, d = q.shape
    dv = v_cache.shape[-1]
    scale = (1.0 / float(np.sqrt(d))) if scale is None else scale
    impl = resolve_impl(impl)

    if impl == "io_stub":
        # deployment IO of the decode kernel: read cache + q once, write o
        dep = (jnp.sum(k_cache.astype(jnp.float32))
               + jnp.sum(v_cache.astype(jnp.float32)))
        if phi_k is not None:
            dep = dep + jnp.sum(phi_k.astype(jnp.float32))
        eps = jnp.asarray(1e-30, jnp.float32)
        o = q[..., :1].astype(jnp.float32) * eps + dep * eps
        return jnp.broadcast_to(o, (b, 1, h, dv)).astype(q.dtype)
    if impl == "xla":
        return _xla_decode(q, k_cache, v_cache, lengths, phi_q, phi_k,
                           slopes, scale)
    return _pallas_decode(q, k_cache, v_cache, lengths, phi_q, phi_k, slopes,
                          scale, block_k,
                          interpret=(impl == "pallas_interpret"))


def _pallas_decode(q, k_cache, v_cache, lengths, phi_q, phi_k, slopes,
                   scale, block_k, interpret):
    """Contiguous Pallas decode: the cache IS the kernel layout — q-side
    reshapes/pads are token-sized, and under the layout contract (lane-
    aligned hd, block-divisible S) the cache tensors pass through
    untouched. Off-contract shapes fall back to a correctness pad (tiny
    test caches; never the serve engine)."""
    b, _, h, d = q.shape
    kvh, s_len = k_cache.shape[1], k_cache.shape[2]
    dv = v_cache.shape[-1]
    g = h // kvh
    g_p = _ceil_to(g, 8)
    d_p, dv_p = _ceil_to(d, _LANE), _ceil_to(dv, _LANE)

    bk = _pick_block(s_len, min(block_k, s_len))
    if bk == 0:
        # off-contract S (no sublane-aligned divisor): pad the seq axis
        # once to an aligned block — correctness fallback, never the
        # serve engine (its caches satisfy the layout contract)
        bk = _ceil_to(min(block_k, s_len), 8)
        s_p = _ceil_to(s_len, bk)
        k_cache = _pad_axis(k_cache, 2, s_p)
        v_cache = _pad_axis(v_cache, 2, s_p)
        if phi_k is not None:
            phi_k = _pad_axis(phi_k, 2, s_p)
    k_cache = _pad_axis(k_cache, 3, d_p)       # no-op on lane-aligned caches
    v_cache = _pad_axis(v_cache, 3, dv_p)

    def to_grouped_q(x, last_p):
        x = x[:, 0].reshape(b, kvh, g, x.shape[-1])
        return _pad_axis(_pad_axis(x, 2, g_p), 3, last_p)

    qt = to_grouped_q(_pad_axis(q, 3, d_p), d_p)
    pqt = pkt = None
    if phi_q is not None:
        r_p = _ceil_to(max(phi_q.shape[-1], phi_k.shape[-1]), _LANE)
        pqt = to_grouped_q(_pad_axis(phi_q, 3, r_p), r_p)
        pkt = _pad_axis(phi_k, 3, r_p)         # no-op on padded factor caches

    kernel = functools.partial(_fd.flash_decode_fwd, scale=scale,
                               block_k=bk, interpret=interpret)
    bh = ("batch", "kv_heads", None, None)
    out = _per_shard(
        kernel,
        dict(q=qt, k_cache=k_cache, v_cache=v_cache, lengths=lengths,
             phi_q=pqt, phi_k=pkt, slopes=_grouped_slopes(slopes, kvh, g)),
        dict(q=bh, k_cache=bh, v_cache=bh, lengths=("batch",), phi_q=bh,
             phi_k=bh, slopes=("kv_heads", None, None)),
        bh, batch=b, kv_heads=kvh)
    return out[:, :, :g, :dv].reshape(b, 1, h, dv)


def _grouped_slopes(slopes, kvh: int, g: int):
    """(H,) slopes -> the decode kernels' ``(KVH, G_pad, 1)`` column:
    kv-head-major, each group zero-padded to the 8-row sublane tile."""
    if slopes is None:
        return None
    return _pad_axis(slopes.reshape(kvh, g), 1, _ceil_to(g, 8))[..., None]


def _flash_decode_paged(q, k_pages, v_pages, lengths, page_table,
                        phi_q, phi_k, slopes, *, scale, impl, max_pages=None,
                        layer=None):
    """Paged dispatch for ``flash_decode`` (see its docstring for shapes)."""
    b, _, h, d = q.shape
    kvh, n_pages, ps = k_pages.shape[-4:-1]
    dv = v_pages.shape[-1]
    p_slot = page_table.shape[1]
    scale = (1.0 / float(np.sqrt(d))) if scale is None else scale
    impl = resolve_impl(impl)
    p_cap = _static_page_cap(lengths, ps, p_slot, max_pages)
    pt = jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1)[:, :p_cap]

    if impl in ("xla", "io_stub"):
        # logical views of the pool, gathered page-granular and capped at
        # p_cap pages — Θ(longest request), not Θ(table width). Everything
        # pool-sized stays in the gather's native (KVH, B, S, E) axis
        # order end to end; only token-sized tensors (q, the output)
        # transpose, so XLA never copies the view.
        def view(pool, e):                        # -> (KVH, B, S_view, e)
            if layer is not None:
                pool = pool[layer]
            return pool[:, pt, :, :e].reshape(kvh, b, p_cap * ps, e)
        gk, gv = view(k_pages, d), view(v_pages, dv)
        if impl == "io_stub":
            dep = gk.astype(jnp.float32).sum() + gv.astype(jnp.float32).sum()
            if phi_k is not None:
                # page axis: 0 on the shared 3-dim slab, 1 on the
                # per-kv-head (KVH, n_pages, ps, R) form
                gphi = phi_k[pt] if phi_k.ndim == 3 else phi_k[:, pt]
                dep = dep + jnp.sum(gphi.astype(jnp.float32))
            eps = jnp.asarray(1e-30, jnp.float32)
            o = q[..., :1].astype(jnp.float32) * eps + dep * eps
            return jnp.broadcast_to(o, (b, 1, h, dv)).astype(q.dtype)
        g = h // kvh
        qg = (q[:, 0].reshape(b, kvh, g, d).transpose(1, 0, 2, 3)
              .astype(jnp.float32))               # (KVH, B, G, D): tiny
        s = jnp.einsum("kbgd,kbsd->kbgs", qg,
                       gk.astype(jnp.float32)) * scale
        if phi_q is not None:
            if phi_k.ndim == 3:                   # (n_pages, ps, r_pad) slab
                gphi = phi_k[pt].reshape(b, p_cap * ps, phi_k.shape[-1])
                r = min(phi_q.shape[-1], gphi.shape[-1])
                pq = (phi_q[:, 0].reshape(b, kvh, g, -1)[..., :r]
                      .transpose(1, 0, 2, 3))
                s = s + jnp.einsum("kbgr,bsr->kbgs",
                                   pq.astype(jnp.float32),
                                   gphi[..., :r].astype(jnp.float32))
            else:                                 # (KVH, n_pages, ps, R)
                gphi = phi_k[:, pt].reshape(kvh, b, p_cap * ps,
                                            phi_k.shape[-1])
                r = min(phi_q.shape[-1], gphi.shape[-1])
                pq = (phi_q[:, 0].reshape(b, kvh, g, -1)[..., :r]
                      .transpose(1, 0, 2, 3))
                s = s + jnp.einsum("kbgr,kbsr->kbgs",
                                   pq.astype(jnp.float32),
                                   gphi[..., :r].astype(jnp.float32))
        k_pos = jnp.arange(p_cap * ps)
        if slopes is not None:
            rel = (k_pos[None] - (lengths - 1)[:, None]).astype(jnp.float32)
            s = s + slopes.reshape(kvh, g)[:, None, :, None] \
                * rel[None, :, None]
        valid = k_pos[None] < lengths[:, None]
        s = jnp.where(valid[None, :, None], s, DEFAULT_MASK_VALUE)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kbgs,kbse->kbge", p, gv.astype(jnp.float32))
        return (o.transpose(1, 0, 2, 3).reshape(b, 1, h, dv)
                .astype(q.dtype))

    # Pallas path: the pools (and the shared phi slab) go to the kernel
    # as they are; pages resolve in its scalar-prefetch block index maps
    # (no gather, no view materialization).
    g = h // kvh
    d_p, dv_p = _ceil_to(d, _LANE), _ceil_to(dv, _LANE)
    g_p = _ceil_to(g, 8)

    def to_grouped_q(x, last_p):
        x = x[:, 0].reshape(b, kvh, g, x.shape[-1])
        return _pad_axis(_pad_axis(x, 2, g_p), 3, last_p)

    kt = _pad_axis(k_pages, -1, d_p)             # no-op: pools lane-padded
    vt = _pad_axis(v_pages, -1, dv_p)

    qt = to_grouped_q(q, d_p)
    pqt = pkt = None
    if phi_q is not None:
        r = phi_q.shape[-1]
        assert phi_q.shape[2] in (h, kvh), (phi_q.shape, h, kvh)
        if phi_q.shape[2] == kvh and kvh != h:    # shared within each group
            phi_q = jnp.repeat(phi_q, g, axis=2)
        if phi_k.ndim == 3:                       # layer/kv-head-shared slab
            pkt = phi_k[None]                     # (1, n_pages, ps, r_pad)
        else:
            pkt = phi_k                           # (KVH, n_pages, ps, r_pad)
        r_p = _ceil_to(max(r, pkt.shape[-1]), _LANE)
        pkt = _pad_axis(pkt, 3, r_p)              # no-op on padded slabs
        pqt = to_grouped_q(_pad_axis(phi_q, 3, r_p), r_p)

    kernel = functools.partial(_fd.flash_decode_paged_fwd, scale=scale,
                               interpret=(impl == "pallas_interpret"))
    bh = ("batch", "kv_heads", None, None)
    pool = ("kv_heads", None, None, None)
    # the layer stack's leading axis is whole on every device
    stack = pool if layer is None else (None,) + pool
    out = _per_shard(
        kernel,
        dict(q=qt, k_pages=kt, v_pages=vt, lengths=lengths, page_table=pt,
             phi_q=pqt, phi_pages=pkt,
             slopes=_grouped_slopes(slopes, kvh, g),
             layer=None if layer is None
             else jnp.asarray(layer, jnp.int32).reshape(1)),
        dict(q=bh, k_pages=stack, v_pages=stack, lengths=("batch",),
             page_table=("batch", None), phi_q=bh, layer=(None,),
             # the layer/kv-head-shared slab (leading 1) is whole everywhere
             phi_pages=(pool if pkt is not None and pkt.shape[0] == kvh
                        else (None,) * 4),
             slopes=("kv_heads", None, None)),
        bh, batch=b, kv_heads=kvh)
    return out[:, :, :g, :dv].reshape(b, 1, h, dv)


# ---------------------------------------------------------------------------
# Chunked prefill (C queries, offset causal mask, KV cache) — inference only
# ---------------------------------------------------------------------------

def flash_chunk_attention(
    q: jax.Array,                        # (B, C, H, D) chunk queries
    k_cache: jax.Array,                  # (B, KVH, S, E) or a page pool
    v_cache: jax.Array,
    offsets: jax.Array,                  # (B,) int32: abs position of q[:, 0]
    chunk_lens: jax.Array,               # (B,) int32: valid queries (0=frozen)
    slopes: Optional[jax.Array] = None,  # (H,) ALiBi
    *,
    scale: Optional[float] = None,
    impl: str = "auto",
    page_table: Optional[jax.Array] = None,
    max_pages: Optional[int] = None,
) -> jax.Array:
    """Offset-masked chunk attention for chunked prefill.

    A fixed-size chunk of C queries per slot attends against the slot's KV
    cache — the chunk's own keys must already be scattered into the cache
    (write-then-attend), so the mask is purely positional: query i of row b
    sits at absolute position ``q_pos = offsets[b] + i`` and sees exactly the
    keys at positions ``<= q_pos`` (the offset causal mask; everything past
    the row's written prefix is masked by causality). Rows with
    ``chunk_lens[b] == 0`` are frozen lanes riding in the fixed slot batch —
    their output is unused by construction (the model gathers logits only at
    valid positions and freezes cache state elsewhere).

    ALiBi enters as ``slopes * (k_pos - q_pos)`` from absolute positions —
    the rank-2 factored form specialized in-place, matching
    ``core.bias.alibi_factors(q_offset=...)`` exactly.

    Caches are kv-head-major like ``flash_decode``'s: contiguous ``(B, KVH,
    S, E)``; with ``page_table`` page pools ``(KVH, n_pages, ps, E)``
    gathered into capped logical views (``max_pages`` from the host-side
    length mirror, like decode).

    Chunk attention is an ADMISSION-path program (runs once per chunk, not
    per token), so every impl routes to the head-major XLA path today — the
    decode hot path keeps its Pallas kernels. Returns (B, C, H, Dv_cache);
    lane-padded caches yield a lane-padded Dv for the caller to slice.
    """
    b, c, h, d = q.shape
    scale = (1.0 / float(np.sqrt(d))) if scale is None else scale
    resolve_impl(impl)                   # validate; all impls -> XLA here
    offsets = jnp.asarray(offsets, jnp.int32)
    chunk_lens = jnp.asarray(chunk_lens, jnp.int32)

    if page_table is not None:
        kvh, n_pages, ps = k_cache.shape[:3]
        p_slot = page_table.shape[1]
        p_cap = _static_page_cap(offsets + chunk_lens, ps, p_slot, max_pages)
        pt = jnp.clip(page_table.astype(jnp.int32), 0, n_pages - 1)[:, :p_cap]
        def view(pool):                  # (KVH, B, S_view, E) -> (B, KVH, ...)
            gth = pool[:, pt].reshape(kvh, b, p_cap * ps, pool.shape[-1])
            return gth.transpose(1, 0, 2, 3)
        kv, vv = view(k_cache), view(v_cache)
    else:
        kv, vv = k_cache, v_cache        # (B, KVH, S, E) native

    kvh, s_len = kv.shape[1], kv.shape[2]
    dv = vv.shape[-1]
    g = h // kvh
    kf = kv.astype(jnp.float32)
    if kf.shape[-1] > d:                 # lane-padded pool vs raw q
        kf = kf[..., :d]
    qg = (q.reshape(b, c, kvh, g, d).transpose(0, 2, 3, 1, 4)
          .astype(jnp.float32))          # (B, KVH, G, C, D): chunk-sized
    s = jnp.einsum("bkgcd,bksd->bkgcs", qg, kf) * scale
    k_pos = jnp.arange(s_len)
    q_pos = offsets[:, None] + jnp.arange(c)[None, :]          # (B, C)
    if slopes is not None:
        rel = (k_pos[None, None] - q_pos[:, :, None]).astype(jnp.float32)
        s = s + slopes.reshape(kvh, g)[None, :, :, None, None] \
            * rel[:, None, None]
    valid = k_pos[None, None] <= q_pos[:, :, None]             # (B, C, S)
    s = jnp.where(valid[:, None, None], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgcs,bkse->bkgce", p, vv.astype(jnp.float32))
    return (o.transpose(0, 3, 1, 2, 4).reshape(b, c, h, dv)
            .astype(q.dtype))


# ---------------------------------------------------------------------------
# Mamba2 SSD chunk scan (prefill) — inference only
# ---------------------------------------------------------------------------

def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 256,
             h0: Optional[jax.Array] = None, impl: str = "xla"):
    """SSD chunk scan in the model's layout: x (B, S, H, P); dt (B, S, H)
    (softplus'd); a (H,) < 0; b, c (B, S, N) shared by every head (n_groups
    1); h0 (B, H, P, N) or None. Returns ``(y (B, S, H, P) f32, h_final
    (B, H, P, N) f32)``.

    ``"xla"`` runs the jnp scan (``models.ssd.ssd_scan``); the Pallas impls
    run the fused kernel (``kernels/ssd_scan.py``, named ``ssd_scan`` in the
    trace) on head-major views, S padded to a whole chunk with dt = 0 so
    the padding leaves the state unchanged, per shard under a mesh (batch
    and heads). The views are prefill-sized transposes, made once per layer
    of an admission. A ragged batch passes dt = 0 past each row's length,
    which ends its scan there."""
    impl = resolve_impl(impl)
    if impl not in ("pallas", "pallas_interpret"):
        from repro.models import ssd as ssd_xla
        return ssd_xla.ssd_scan(x, dt, a, b, c, chunk=chunk, h0=h0)
    bsz, s, h, p = x.shape
    q = min(chunk, _ceil_to(s, 8))
    s_p = _ceil_to(s, q)
    xt = _pad_axis(x, 1, s_p).transpose(0, 2, 1, 3)         # (B, H, S, P)
    dtt = _pad_axis(dt, 1, s_p).transpose(0, 2, 1)[..., None]
    bt = _pad_axis(b, 1, s_p)[:, None]                       # (B, 1, S, N)
    ct = _pad_axis(c, 1, s_p)[:, None]
    kernel = functools.partial(_ssd.ssd_scan_fwd, chunk=q,
                               interpret=(impl == "pallas_interpret"))
    bh, shared = ("batch", "kv_heads", None, None), ("batch", None, None, None)
    y, h_fin = _per_shard(
        kernel, dict(x=xt, dt=dtt, a=a, b=bt, c=ct, h0=h0),
        dict(x=bh, dt=bh, a=("kv_heads",), b=shared, c=shared, h0=bh),
        bh, batch=bsz, kv_heads=h)
    return y.transpose(0, 2, 1, 3)[:, :s], h_fin
