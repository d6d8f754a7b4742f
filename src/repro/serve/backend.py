"""Serve backends: what a workload owns vs. what the engine core owns.

The engine core (``engine.ServeEngine``) owns the REQUEST machinery:
request ids, the scheduler and admission waves, the slot free-list and
live map, result/done bookkeeping, the preemption victim policy and the
determinism contract. A ``Backend`` owns everything DEVICE-side for one
workload family:

- allocated state (caches, factor slabs, page pools, sampling state),
- the jitted admit/step programs,
- per-slot resources and their accounting (pages),
- retire/freeze and preemption snapshots.

``TokenDecodeBackend`` is the autoregressive LM path (KV caches, paged
pools, sampling) — the pre-ISSUE-6 engine body moved here verbatim, so
LM serve behavior is bit-identical to the monolithic engine.

``PairBatchBackend`` serves batched Pairformer inference (the paper's
Sec. 4.4 workload): a request is one complex, admission runs the trunk
once and caches its per-layer pair-bias FACTORS (or the dense bias, for
the A/B baseline), and every step is one refinement iteration of
single-rep attention over the padded slot batch with per-slot ``n_res``
masking. See serve/README.md §Backend contract.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import Rules, shard_put, use_mesh_rules
from repro.models import pairformer
from repro.models.api import Model
from repro.serve.lifecycle import AdmissionRejected, PoolError, PoolExhausted
from repro.serve.pages import PagePool
from repro.serve.prefix import PrefixCache
from repro.serve.sampling import sample_tokens, sample_tokens_guarded
from repro.serve.scheduler import ChunkPlan, Request
from repro.serve.trace import span

__all__ = ["Backend", "TokenDecodeBackend", "PairBatchBackend"]


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def named(name: str, fn):
    """``fn`` under the program name ``name``: ``jax.jit`` names the
    compiled module ``jit_<name>``, which is how the device trace tells
    the backend's programs apart (a lambda traces as ``jit__lambda``).
    Only the two admission prefill programs are named ``_pf``."""
    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    return program


class Backend:
    """Protocol between the engine core and a workload backend.

    ``admit``/``step`` return ``(emissions, mask)``: ``mask[slot]`` marks
    slots that advanced one budget unit this call; ``emissions`` is a
    per-slot int array of emitted token ids, or None for backends that
    emit nothing incrementally (the engine then collects the result via
    ``fetch_result`` when the budget drains). Slot registration, budget
    accounting and retirement stay in the engine core.
    """

    paged: bool = False        # admission gated on page accounting
    wave_rows: Optional[int] = None   # most requests one admission wave takes
    lazy: bool = False         # pages grow mid-flight (may force preemption)
    guards: bool = True        # host-side non-finite guards (ISSUE 10)
    faults = None              # serve.faults.FaultPlan injection hook
    # prefill work since the engine started, read by ``engine.counters()``:
    # rows x padded length the prefill programs ran, and the real prompt
    # tokens (residues) among them
    n_prefill_rows_x_len: int = 0
    n_prefill_real: int = 0
    # bias-table SVDs at admission: the matrices factored and the
    # eigensolver calls that factored them
    n_svd_matrices: int = 0
    n_svd_solves: int = 0
    # device state per kind, read by ``engine.counters()`` once allocated:
    # recurrent-state bytes one slot holds (SSM state and conv tails) and
    # the layers whose K/V live in the page pool
    state_bytes_per_slot: int = 0
    paged_layers: int = 0

    def ensure_state(self) -> None:
        """Allocate device state on first use (idempotent)."""
        raise NotImplementedError

    def validate(self, req: Request) -> None:
        """Submit-time bounds check (raises on an inadmissible request)."""
        raise NotImplementedError

    def admit(self, wave: List[Request],
              slots: List[int]) -> Tuple[Optional[np.ndarray], np.ndarray]:
        """Prefill ``wave`` into ``slots``; returns (emissions, mask)."""
        raise NotImplementedError

    def step(self, live: Dict[int, object]
             ) -> Tuple[Optional[np.ndarray], np.ndarray]:
        """Advance every live slot one budget unit."""
        raise NotImplementedError

    # -- chunked prefill (ISSUE 7) --------------------------------------
    def prefill_pending(self) -> bool:
        """True while admitted prompts still have chunks queued — the
        engine then interleaves one ``prefill_step`` with each decode
        step. Backends without chunked admission never have any."""
        return False

    def prefill_step(self) -> Tuple[Optional[np.ndarray], np.ndarray]:
        """Advance every pending prompt one chunk; same (emissions, mask)
        contract as ``step`` — only slots whose FINAL chunk landed this
        call emit (their first sampled token) and advance a budget unit."""
        raise NotImplementedError

    def pending_slots(self):
        """Slots mid-chunked-prefill: live (they own resources and can be
        preempted) but not yet decoding — the engine and the backend's own
        ``step``/``growth_pending`` exclude them from decode accounting."""
        return ()

    def fetch_result(self, slot: int, st) -> Optional[np.ndarray]:
        """Final non-incremental result for a finishing slot (or None)."""
        return None

    def stream_result(self, slot: int, st) -> Optional[np.ndarray]:
        """Per-step streaming payload for non-emitting backends (engines
        pass it to a request's ``on_token`` sink when ``emissions`` is
        None). Token backends stream the emitted id instead."""
        return None

    def release(self, slot: int) -> None:
        """Retire a finished slot: freeze its cache row, free resources."""
        raise NotImplementedError

    def snapshot(self, slot: int, st, emitted) -> Request:
        """Preempt: freeze + free the slot, return the resumable Request."""
        raise NotImplementedError

    def snapshot_request(self, slot: int, st, emitted) -> Request:
        """The resumable Request ``snapshot`` would return, WITHOUT
        freezing or freeing anything — the engine checkpoint (ISSUE 10)
        serializes live slots through this, leaving the running engine
        untouched."""
        raise NotImplementedError

    # -- fault containment (ISSUE 10) -----------------------------------
    def take_guard_faults(self) -> Dict[int, str]:
        """Drain {slot: detail} for slots whose last admit/step tripped a
        non-finite guard. The engine drains after every backend call that
        can emit and quarantines the listed slots; a drained fault is
        forgotten (the quarantined slot re-admits with fresh state)."""
        bad = getattr(self, "_guard_bad", None)
        if not bad:
            return {}
        self._guard_bad = {}
        return bad

    def quarantine(self, slot: int) -> None:
        """Pre-release hook for a FAULTING slot: discard anything other
        requests could observe from it (the token backend invalidates
        prefix-index entries for pages the slot wrote). The engine calls
        this BEFORE the snapshot/release frees the slot's resources."""

    # -- resource accounting (paged backends) ---------------------------
    def admission_units(self, req: Request) -> int:
        """Resource units (pages) reserved when ``req`` is admitted."""
        return 0

    def units_free(self) -> int:
        return 0

    def growth_pending(self, live: Dict[int, object]) -> List[int]:
        """Slots whose next step needs a resource grown first."""
        return []

    def grow_slots(self, growing: List[int]) -> None:
        raise NotImplementedError

    def page_cap(self, live: Dict[int, object]) -> Optional[int]:
        """Static page bound for this step (None for unpaged backends)."""
        return None

    def stats(self) -> dict:
        return {}


class TokenDecodeBackend(Backend):
    """Autoregressive LM decode: KV caches, paged pools, per-slot sampling.

    This is the pre-refactor engine body: prefill waves right-padded and
    batch-padded to ``n_slots``, one jitted decode step over the full slot
    batch, per-request PRNG key chains, paged KV with lazy page growth.
    Every computation and its ordering is preserved from the monolithic
    engine, so behavior is bit-identical.

    ``prefill_chunk`` (ISSUE 7) switches admission from whole-prompt waves
    to CHUNKED prefill: ``admit`` becomes a pure planner (reserve pages,
    arm sampling state, enqueue a ``ChunkPlan``) and the engine drives one
    ``prefill_step`` — a single jitted fixed-shape (n_slots, chunk)
    program appending one chunk per pending slot — per engine step,
    interleaved with decode. A 4k-token arrival then costs each in-flight
    request one chunk's latency per step instead of a whole-prompt stall.
    Mid-prefill slots hold device length 0 (frozen for decode); the final
    chunk flips the length to the prompt length and samples the first
    token, so PRNG chains and decode behavior match the wave path exactly.
    Ring-KV archs clamp the chunk to the attention window (a chunk's
    positions must map to distinct ring slots).

    ``mesh``/``rules`` (ISSUE 7) make the backend mesh-aware: every jitted
    program traces under ``use_mesh_rules`` (so ``dist.constrain`` calls
    in model code bind — TP-sharded heads, DP-sharded slot rows) and
    ``ensure_state`` places persistent device state with explicit
    shardings — KV caches and page pools along ``kv_heads``, slot-batch
    rows along ``batch`` (dropped when ``n_slots`` does not divide DP),
    ``pages_phi`` and page tables replicated. The page ALLOCATOR and slot
    page lists stay host-side: planning is cheap python, only content
    moves through collectives.
    """

    def __init__(self, model: Model, params: dict, max_len: int,
                 n_slots: int, prefill_len: Optional[int] = None,
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 pages_per_slot: Optional[int] = None,
                 page_reservation: str = "lazy",
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = False,
                 mesh=None, rules: Optional[Rules] = None,
                 prefill_rows: Optional[int] = None):
        if page_reservation not in ("lazy", "whole"):
            raise ValueError(f"page_reservation must be 'lazy' or "
                             f"'whole', got {page_reservation!r}")
        self.model, self.params = model, params
        self.max_len, self.n_slots = max_len, n_slots
        self.prefill_len = prefill_len
        if prefill_rows is not None and not 1 <= prefill_rows <= n_slots:
            raise ValueError(f"prefill_rows must be in 1..{n_slots}, got "
                             f"{prefill_rows}")
        # rows of the wave prefill program: every wave pads to them, and
        # the engine takes at most this many requests a wave
        self.wave_rows = prefill_rows or n_slots
        self.mesh = mesh
        self.rules = (rules or Rules()) if mesh is not None else rules
        self._guard_bad: Dict[int, str] = {}
        self._slot_kept: Dict[int, int] = {}   # shared (unwritten) pages
        cfg = model.cfg
        self._vocab = cfg.vocab
        self._front_dim = (cfg.frontend_len, cfg.d_model)
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, "
                                 f"got {prefill_chunk}")
            if model.prefill_chunk is None:
                raise ValueError(
                    f"{cfg.family} model has no chunked-prefill path")
            if cfg.window and cfg.window < max_len:
                # ring cache: a chunk's positions must land on distinct
                # ring slots, so the chunk can never exceed the window
                prefill_chunk = min(prefill_chunk, cfg.window)
        self.chunk_size = prefill_chunk
        self._pending: Dict[int, ChunkPlan] = {}
        # full-KV families must fit prompt + budget inside the slot segment
        # (contiguous mode) or inside the page pool (paged mode)
        self._bounded_cache = (cfg.family in ("dense", "moe", "hybrid")
                               and not (cfg.window and cfg.window < max_len))
        self.paged = (page_size is not None and self._bounded_cache
                      and model.init_paged_cache is not None)
        self.lazy = self.paged and page_reservation == "lazy"
        if self.paged:
            self.page_size = page_size
            self.n_pages = n_pages or n_slots * _ceil_to(max_len,
                                                         page_size) // page_size
            self.pages_per_slot = min(pages_per_slot or self.n_pages,
                                      self.n_pages)
            self._pool = PagePool(self.n_pages, page_size)
            self._slot_pages: Dict[int, List[int]] = {}
        # prefix caching (ISSUE 9): content-hashed sharing of completed
        # prompt pages. Requires the paged pool (sharing is page-table
        # indirection), the chunked planner (the novel tail lands through a
        # mid-prompt ChunkPlan) and a family whose slot state lives
        # ENTIRELY in pages — hybrid's SSM state is recurrent and cannot
        # be rebuilt from a mid-prompt prefill start.
        self._prefix: Optional[PrefixCache] = None
        if prefix_cache:
            if not (self.paged and self.chunk_size):
                raise ValueError(
                    "prefix_cache needs paged KV (page_size) + chunked "
                    "prefill (prefill_chunk): shared pages map through "
                    "the page table and novel tails land via mid-prompt "
                    "ChunkPlans")
            if cfg.family not in ("dense", "moe"):
                raise ValueError(
                    f"prefix_cache shares KV pages only — family "
                    f"'{cfg.family}' carries per-slot recurrent state a "
                    f"mid-prompt prefill start cannot rebuild")
            if model.copy_pages is None:
                raise ValueError(
                    "prefix_cache needs the model's copy_pages program")
            self._prefix = PrefixCache(page_size)
            self._n_cow = 0                  # CoW page copies performed
            self._tok_matched = 0            # prefix tokens served from cache
            self._tok_matchable = 0          # full-block tokens seen at admit
        self._cache = None                        # allocated on first step
        one = jax.eval_shape(lambda: model.init_cache(1, 1))
        self.state_bytes_per_slot = sum(
            int(np.prod(one[k].shape)) * one[k].dtype.itemsize
            for k in ("ssm_h", "conv_x", "conv_bc") if k in one)
        if self.paged:
            self.paged_layers = int(jax.eval_shape(
                lambda: model.init_paged_cache(1, 1, 1))["pages_k"].shape[0])

        def _pf(p, toks, front, lengths, max_len):
            batch = {"tokens": toks}
            if front is not None:
                batch["frontend"] = front
            return model.prefill(p, batch, max_len=max_len, lengths=lengths)

        self._prefill = self._jit("_pf", _pf, static_argnames=("max_len",))
        # Every program that takes the cache and returns its successor
        # DONATES it, so the page pools are updated in their own buffers
        # instead of being copied whole into fresh ones. A donated cache is
        # dead after the call: ``_cache`` is replaced by each result.
        #
        # max_pages is a STATIC cap on the pages a paged decode step may
        # reference: the engine passes a power-of-two rounding of its
        # host-mirrored longest live length, so the paged XLA fallback
        # gathers Θ(longest request) instead of the full page-table width
        # while recompiling at most log2(pages_per_slot) times.
        decode = self._jit("decode", model.decode, donate_argnums=1,
                           static_argnames=("max_pages",))

        def run_decode(params, cache, tokens, max_pages=None):
            # the backend's cache goes in and its successor becomes the
            # backend's cache, so a caller that drops the result (a
            # warm-up) still leaves a live cache behind
            logits, self._cache = decode(params, cache, tokens,
                                         max_pages=max_pages)
            return logits, self._cache

        run_decode.lower, run_decode.trace = decode.lower, decode.trace
        self._decode = run_decode
        self._insert = self._jit("insert", model.insert_cache,
                                 donate_argnums=0)
        if self.paged:
            self._insert_paged = self._jit("insert_paged", model.insert_paged,
                                           donate_argnums=0)
            self._grow_tables = self._jit("grow_tables",
                                          model.grow_page_table,
                                          donate_argnums=0)
        if self._prefix is not None:
            # fixed-shape CoW program: (n_slots,) src/dst page ids per
            # call, out-of-range dst ids dropped — compiles once
            self._copy_pages = self._jit("copy_pages", model.copy_pages,
                                         donate_argnums=0)
        if self.chunk_size:
            self._chunk = self._jit("prefill_chunk", model.prefill_chunk,
                                    donate_argnums=1,
                                    static_argnames=("max_pages",))

    def _jit(self, name: str, fn, **kw):
        """``fn`` jitted as the program ``name``, traced under the mesh."""
        return jax.jit(named(name, self._with_mesh(fn)), **kw)

    def _with_mesh(self, fn):
        """Bind ``use_mesh_rules(mesh, rules)`` around ``fn`` at TRACE
        time, so every ``dist.constrain`` in the model body resolves
        against the backend's mesh inside the jitted program. Identity
        when no mesh is configured — single-device serve pays nothing."""
        if self.mesh is None:
            return fn
        mesh, rules = self.mesh, self.rules

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with use_mesh_rules(mesh, rules):
                return fn(*args, **kwargs)
        return wrapped

    # -- lifecycle ------------------------------------------------------

    def ensure_state(self) -> None:
        if self._cache is not None:
            return
        ns = self.n_slots
        if self.paged:
            self._cache = self.model.init_paged_cache(
                ns, self.n_pages, self.page_size, self.pages_per_slot)
        else:
            self._cache = self.model.init_cache(ns, self.max_len)
        self._temps = jnp.zeros((ns,), jnp.float32)
        self._topks = jnp.zeros((ns,), jnp.int32)
        self._keys = jnp.zeros((ns, 2), jnp.uint32)
        self._last_tok = jnp.zeros((ns, 1), jnp.int32)
        self._shard_state()

    def _state_axes(self) -> Dict[str, tuple]:
        """Logical axes of every persistent cache leaf (one entry per dim).

        The serve-path sharding contract: KV content shards along
        ``kv_heads`` (TP) and slot rows along ``batch`` (DP); everything
        host-planned — page tables, the phi position slab — replicates, so
        the allocator never needs a collective to rewrite an int32 row.
        SSM state shards on batch only (its head dim is padded for the
        kernel, not for the mesh)."""
        axes: Dict[str, tuple] = {
            "length": ("batch",),
            "ssm_h": ("layers", "batch", None, None, None),
            "conv_x": ("layers", "batch", None, None, None),
            "conv_bc": ("layers", "batch", None, None),
        }
        if self.paged:
            pool = ("layers", "kv_heads", None, None, None)
            axes.update(pages_k=pool, pages_v=pool,
                        page_table=(None, None),
                        pages_phi=(None, None, None))
        else:
            kv = ("layers", "batch", "kv_heads", None, None)
            axes.update(k=kv, v=kv)
        return axes

    def _shard_state(self) -> None:
        """Place persistent device state with explicit shardings so jit
        input shardings agree with the constraints traced by
        ``_with_mesh`` programs (no resharding on the first step)."""
        if self.mesh is None:
            return
        mesh, rules = self.mesh, self.rules
        axes = self._state_axes()
        for key, a in axes.items():
            if key in self._cache:
                self._cache[key] = shard_put(self._cache[key], mesh, rules,
                                             a)
        self._temps = shard_put(self._temps, mesh, rules, ("batch",))
        self._topks = shard_put(self._topks, mesh, rules, ("batch",))
        self._keys = shard_put(self._keys, mesh, rules, ("batch", None))
        self._last_tok = shard_put(self._last_tok, mesh, rules,
                                   ("batch", None))

    def validate(self, req: Request) -> None:
        if not np.issubdtype(req.tokens.dtype, np.integer):
            raise AdmissionRejected("token backend takes int token prompts")
        if self.chunk_size and req.frontend is not None:
            raise AdmissionRejected(
                "chunked prefill takes token prompts only (frontend "
                "embeddings ride the whole-prompt wave path)")
        if (self.prefill_len is not None
                and req.tokens.size > self.prefill_len):
            raise AdmissionRejected(
                f"prompt of {req.tokens.size} tokens exceeds the pinned "
                f"prefill_len={self.prefill_len}")
        if self._bounded_cache and self.paged:
            # paged: prompt + budget may exceed max_len (the PR-2 segment
            # bound is gone). The real bounds are the request's own
            # page-table row and the pool itself — a footprint the pool
            # can never cover would preempt everything and still deadlock
            needed = self._pages_needed(req)
            cap = min(self.pages_per_slot, self.n_pages)
            if needed > cap:
                # shared-prefix hits don't lift the bound (shared pages
                # still occupy page-table row entries and pool pages), but
                # the message must state what admission actually reserves
                shared = ""
                if self._prefix is not None:
                    hit = len(self._prefix.match(req.tokens)[0])
                    shared = (f", of which {hit} currently shared via the "
                              f"prefix cache — admission would reserve "
                              f"{needed - hit} fresh pages but the table "
                              f"row still references all {needed}")
                raise AdmissionRejected(
                    f"paged mode: request footprint {needed} pages "
                    f"(ceil((prompt {req.prompt_len} + budget "
                    f"{req.max_new_tokens} - 1) / page_size "
                    f"{self.page_size})){shared} exceeds {cap} "
                    f"(page-table row width {self.pages_per_slot}, "
                    f"pool {self.n_pages} pages)")
        elif self._bounded_cache:
            if req.prompt_len + req.max_new_tokens > self.max_len:
                raise AdmissionRejected(
                    f"contiguous mode: prompt {req.prompt_len} + budget "
                    f"{req.max_new_tokens} exceeds the per-slot segment "
                    f"max_len={self.max_len} (paged mode lifts this bound "
                    f"— pass page_size)")
        # ring-KV keeps only the last `window` keys and SSM state is
        # constant-size, so those families accept prompts of any length

    # -- paged accounting -----------------------------------------------

    def _pages_needed(self, req: Request) -> int:
        """Pages a request can ever touch: its final cache length is
        ``prompt + budget - 1`` (the last sampled token is never fed
        back)."""
        return self._pool.pages_needed(req.prompt_len + req.max_new_tokens
                                       - 1)

    def admission_units(self, req: Request) -> int:
        """Pages reserved at admission: just the prompt's under lazy
        growth, the full worst-case footprint under ``"whole"``. With
        prefix caching, matched pages that already have a LIVE holder
        (refcount >= 2) cost nothing — index-only matches (refcount 1)
        still count, because the same pages appear in ``units_free``'s
        evictable pool and must not be double-counted."""
        units = (self._pool.pages_needed(req.prompt_len) if self.lazy
                 else self._pages_needed(req))
        if self._prefix is not None:
            kept, _, _, _ = self._prefix_plan(req)
            units -= sum(1 for p in kept if self._pool.refcount(p) >= 2)
        return units

    def units_free(self) -> int:
        """Pages admission can draw on: the free list plus pages the
        prefix index retains with no live sharer (evictable on demand)."""
        free = self._pool.n_free
        if self._prefix is not None:
            free += self._prefix.n_evictable(self._pool)
        return free

    def _reclaim(self, n: int) -> None:
        """Make sure ``n`` pages are actually on the free list, evicting
        index-retained pages (LRU, leaf-first) if the free list is short.
        Callers gated on ``units_free`` so the eviction always suffices."""
        if self._prefix is not None and self._pool.n_free < n:
            self._prefix.evict(self._pool, n - self._pool.n_free)

    def _prefix_plan(self, req: Request) -> Tuple[List[int], List[int],
                                                  int, int]:
        """Resolve a request against the prefix index:
        ``(kept, cow_src, done, matched_tokens)``.

        ``kept`` pages are shared as-is (page-table indirection + incref).
        ``done`` — where the mid-prompt ChunkPlan starts — is ``matched``
        floored to a CHUNK multiple and capped below the prompt length:
        chunk boundaries then land exactly where the unshared engine's do
        (admission always chunks from a chunk-multiple offset), which is
        what makes shared outputs BIT-identical, and the final chunk always
        exists to produce the first sampled token. Matched pages covering
        the re-run span ``[done, matched)`` would be written by a sharer —
        they are returned as ``cow_src`` for copy-on-write instead."""
        pages, matched = self._prefix.match(req.tokens)
        if not pages:
            return [], [], 0, 0
        done = (min(matched, req.prompt_len - 1)
                // self.chunk_size) * self.chunk_size
        k = len(pages) if done >= matched else done // self.page_size
        return pages[:k], pages[k:], done, matched

    def page_cap(self, live) -> Optional[int]:
        """Static page bound for this decode step: pow2-rounded pages of
        the longest live length (+1 for the position being written), so
        the jitted step recompiles only when a length crosses a doubling
        boundary. None for unpaged engines."""
        if not self.paged:
            return None
        longest = max((st.length for s, st in live.items()
                       if s not in self._pending), default=0)
        need = max(1, -(-(longest + 1) // self.page_size))
        cap = 1
        while cap < need:
            cap *= 2
        return min(cap, self.pages_per_slot)

    def growth_pending(self, live) -> List[int]:
        # mid-chunked-prefill slots hold their full prompt reservation
        # already and are frozen for decode — they never grow here
        ps = self.page_size
        return [s for s, st in live.items()
                if s not in self._pending
                and st.length // ps >= len(self._slot_pages[s])]

    def grow_slots(self, growing: List[int]) -> None:
        """Allocate the next page for every growing slot and push the new
        table rows to the device in one fixed-shape jitted scatter.

        ATOMIC (ISSUE 10): every check and allocation happens before any
        host table mutates — a ``PoolExhausted`` (real or injected via
        the fault plan) leaves ``_slot_pages`` and the device tables
        exactly as they were, so the engine can contain it by preempting
        the growing slots and retrying."""
        if self.faults is not None and self.faults.alloc_fails():
            raise PoolExhausted("injected page-alloc failure (fault plan)")
        for slot in growing:
            if len(self._slot_pages[slot]) + 1 > self.pages_per_slot:
                raise PoolError(
                    f"slot {slot} page table full "
                    f"({self.pages_per_slot} rows) — admission validation "
                    f"should have rejected this footprint")
        self._reclaim(len(growing))
        grown = self._pool.grow(len(growing))   # all-or-nothing
        slot_ids = np.full((self.n_slots,), self.n_slots, np.int32)
        tables = np.full((self.n_slots, self.pages_per_slot), self.n_pages,
                         np.int32)
        for i, (slot, page) in enumerate(zip(growing, grown)):
            pages = self._slot_pages[slot]
            pages.append(page)
            slot_ids[i] = slot
            tables[i, :len(pages)] = pages
        self._cache = self._grow_tables(self._cache, jnp.asarray(slot_ids),
                                        jnp.asarray(tables))

    # -- admit / step ----------------------------------------------------

    def admit(self, wave: List[Request], slots: List[int]):
        """Prefill the wave into freed slots and sample each admitted
        request's first token from its prefill logits.

        Chunked mode (``prefill_chunk``): admission is a PLANNER — reserve
        each request's prompt pages and write its page-table row now, arm
        its sampling state (so a mid-prefill preemption snapshots a valid
        PRNG chain), and enqueue a ``ChunkPlan``. Nothing runs on device
        beyond the int32 table scatter; the prompt lands chunk by chunk
        through ``prefill_step``, and nothing emits until a final chunk."""
        if self.chunk_size:
            return self._plan_chunked(wave, slots)
        ns, w, rows = self.n_slots, len(wave), self.wave_rows
        assert w <= rows, (w, rows)

        # right-pad prompts; pad the wave batch to ``wave_rows`` so exactly
        # one prefill program serves every wave size (padding rows are
        # dropped at insert via an out-of-range slot id). A resumed prompt may
        # exceed a pinned prefill_len — that wave pads to the resumed
        # length, and _take_wave made it a SOLO wave so no co-admitted
        # request sees the changed padding
        padded = max(r.tokens.size for r in wave)
        if self.prefill_len is not None:
            padded = max(self.prefill_len, padded)
        toks = np.zeros((rows, padded), np.int32)
        lengths = np.ones((rows,), np.int32)
        for i, r in enumerate(wave):
            toks[i, :r.tokens.size] = r.tokens
            lengths[i] = r.prompt_len
        front = None
        has_front = [r.frontend is not None for r in wave]
        if any(has_front):
            assert all(has_front), \
                "wave mixes frontend/frontend-less requests"
            front = np.zeros((rows,) + self._front_dim, np.float32)
            for i, r in enumerate(wave):
                front[i] = r.frontend
            front = jnp.asarray(front)

        front_len = self._front_dim[0] if front is not None else 0
        if self.paged:
            # the wave cache only needs to hold the padded prompt, page-
            # aligned — NOT a full max_len segment; pages scatter from it
            pf_len = _ceil_to(padded + front_len, self.page_size)
        else:
            pf_len = self.max_len
        real = [r.prompt_len for r in wave]
        self.n_prefill_rows_x_len += rows * padded
        self.n_prefill_real += sum(real)
        with span("serve.prefill", requests=w, rows=rows, row_len=padded,
                  real=sum(real), real_sq=sum(n * n for n in real)):
            logits, wave_cache = self._prefill(
                self.params, jnp.asarray(toks), front, jnp.asarray(lengths),
                pf_len)
            slot_ids = np.full((rows,), ns, np.int32)  # padding rows: dropped
            slot_ids[:w] = slots
            if self.paged:
                # lazy: reserve only the prompt's pages — decode grows the
                # table on page-boundary crossings. whole: reserve the full
                # footprint so decode never allocates mid-flight
                tables = np.full((rows, self.pages_per_slot), self.n_pages,
                                 np.int32)
                for i, (slot, r) in enumerate(zip(slots, wave)):
                    pages = self._pool.alloc(self.admission_units(r))
                    self._slot_pages[slot] = pages
                    tables[i, :len(pages)] = pages
                self._cache = self._insert_paged(self._cache, wave_cache,
                                                 slot_ids,
                                                 jnp.asarray(tables))
            else:
                self._cache = self._insert(self._cache, wave_cache, slot_ids)

        # per-slot sampling state + per-request PRNG chains; a preempted
        # request resumes from its key snapshot so its sample stream stays
        # aligned with its token count
        sl = jnp.asarray(np.asarray(slots, np.int32))
        self._temps = self._temps.at[sl].set(jnp.asarray(
            [r.sampling.temperature for r in wave], jnp.float32))
        self._topks = self._topks.at[sl].set(jnp.asarray(
            [r.sampling.top_k for r in wave], jnp.int32))
        self._keys = self._keys.at[sl].set(jnp.stack(
            [jax.random.PRNGKey(r.sampling.seed) if r.key_override is None
             else jnp.asarray(r.key_override, jnp.uint32) for r in wave]))

        # first token: scatter wave-row logits into slot rows, sample
        lg = jnp.zeros((ns, logits.shape[-1]), logits.dtype)
        lg = lg.at[jnp.asarray(slot_ids)].set(logits[:, 0], mode="drop")
        mask = np.zeros((ns,), bool)
        mask[slots] = True
        return self._sample(lg, mask), mask

    def _plan_chunked(self, wave: List[Request], slots: List[int]):
        """Chunked admission: reserve resources + arm sampling state, then
        queue the prompts. Page content and phi factor rows are written by
        the chunk program itself (write-then-attend), so only the int32
        page-table rows move here — one fixed-shape jitted scatter."""
        ns = self.n_slots
        starts: Dict[int, int] = {}
        if self.paged:
            slot_ids = np.full((ns,), ns, np.int32)
            tables = np.full((ns, self.pages_per_slot), self.n_pages,
                             np.int32)
            cow_jobs: List[Tuple[int, int]] = []   # (src, dst) page copies
            for i, (slot, r) in enumerate(zip(slots, wave)):
                kept: List[int] = []
                cow_src: List[int] = []
                if self._prefix is not None:
                    kept, cow_src, done, matched = self._prefix_plan(r)
                    starts[slot] = done
                    self._tok_matched += matched
                    self._tok_matchable += (r.prompt_len // self.page_size
                                            ) * self.page_size
                    # pin shared pages (kept AND CoW sources) before any
                    # eviction this wave triggers can reach them
                    self._pool.incref(kept + cow_src)
                total = (self._pool.pages_needed(r.prompt_len) if self.lazy
                         else self._pages_needed(r))
                self._reclaim(total - len(kept))
                fresh = self._pool.alloc(total - len(kept))
                # fresh pages fill the table row after the kept prefix; the
                # first len(cow_src) of them are private copies of shared
                # pages the re-run span [done, matched) will write into
                cow_jobs += list(zip(cow_src, fresh))
                if cow_src:
                    self._n_cow += len(cow_src)
                pages = kept + fresh
                self._slot_pages[slot] = pages
                # pages after the kept prefix are WRITTEN by this slot
                # (novel tail, CoW copies, decode growth) — quarantine
                # invalidates exactly those from the prefix index
                self._slot_kept[slot] = len(kept)
                slot_ids[i] = slot
                tables[i, :len(pages)] = pages
            self._cache = self._grow_tables(self._cache,
                                            jnp.asarray(slot_ids),
                                            jnp.asarray(tables))
            if cow_jobs:
                # copy shared content into the private pages BEFORE any
                # chunk program writes; one fixed-shape program per
                # n_slots-wide batch, in-batch gathers read the pre-copy
                # pool so a same-wave evict/reuse cannot misorder
                for j0 in range(0, len(cow_jobs), ns):
                    batch = cow_jobs[j0:j0 + ns]
                    src = np.full((ns,), self.n_pages, np.int32)
                    dst = np.full((ns,), self.n_pages, np.int32)
                    for j, (s_pg, d_pg) in enumerate(batch):
                        src[j], dst[j] = s_pg, d_pg
                    self._cache = self._copy_pages(self._cache,
                                                   jnp.asarray(src),
                                                   jnp.asarray(dst))
                # drop the planning pin on CoW sources: the sharer now owns
                # a private copy, the cached entry stays valid for others
                self._pool.free([s_pg for s_pg, _ in cow_jobs])
        sl = jnp.asarray(np.asarray(slots, np.int32))
        self._temps = self._temps.at[sl].set(jnp.asarray(
            [r.sampling.temperature for r in wave], jnp.float32))
        self._topks = self._topks.at[sl].set(jnp.asarray(
            [r.sampling.top_k for r in wave], jnp.int32))
        self._keys = self._keys.at[sl].set(jnp.stack(
            [jax.random.PRNGKey(r.sampling.seed) if r.key_override is None
             else jnp.asarray(r.key_override, jnp.uint32) for r in wave]))
        for slot, r in zip(slots, wave):
            # prefix hits start the cursor mid-prompt: the shared pages
            # already hold positions [0, done), only the tail lands
            self._pending[slot] = ChunkPlan(r, done=starts.get(slot, 0))
        return None, np.zeros((ns,), bool)

    def prefill_pending(self) -> bool:
        return bool(self._pending)

    def pending_slots(self):
        return self._pending.keys()

    def _chunk_page_cap(self) -> Optional[int]:
        """Static page cap of one chunk program: pow2-rounded pages of the
        longest pending prefix, same doubling-boundary recompile bound as
        the decode ``page_cap``."""
        if not self.paged:
            return None
        longest = max(p.done for p in self._pending.values())
        need = max(1, -(-longest // self.page_size))
        cap = 1
        while cap < need:
            cap *= 2
        return min(cap, self.pages_per_slot)

    def prefill_step(self):
        """Append one chunk for EVERY pending slot in a single jitted
        fixed-shape (n_slots, chunk) program. Mid-prompt chunks keep the
        device length at 0 (the ``final_lens`` -1 sentinel) so decode
        freezes the lane; a final chunk sets the prompt length and its
        logits sample the request's first token — mask marks exactly those
        finalized slots, keeping each PRNG chain aligned with its token
        count."""
        ns, c = self.n_slots, self.chunk_size
        toks = np.zeros((ns, c), np.int32)
        offs = np.zeros((ns,), np.int32)
        clens = np.zeros((ns,), np.int32)
        flens = np.full((ns,), -1, np.int32)
        finalized: List[int] = []
        real = 0
        for slot, plan in self._pending.items():
            off, chunk_toks, final = plan.next_chunk(c)
            toks[slot, :chunk_toks.size] = chunk_toks
            offs[slot] = off
            clens[slot] = chunk_toks.size
            real += chunk_toks.size
            if final:
                flens[slot] = plan.req.prompt_len
                finalized.append(slot)
        cap = self._chunk_page_cap()
        self.n_prefill_rows_x_len += ns * c
        self.n_prefill_real += real
        with span("serve.chunk", rows=ns, row_len=c, real=real, pages=cap):
            logits, self._cache = self._chunk(
                self.params, self._cache, jnp.asarray(toks),
                jnp.asarray(offs), jnp.asarray(clens), jnp.asarray(flens),
                max_pages=cap)
        for slot in finalized:
            plan = self._pending.pop(slot)
            if self._prefix is not None:
                # the prompt has fully landed: its FULL pages are immutable
                # from here (decode writes at positions >= prompt_len, and
                # partial last pages are never registered) — index them
                self._prefix.insert(plan.req.tokens,
                                    self._slot_pages[slot], self._pool)
        mask = np.zeros((ns,), bool)
        mask[finalized] = True
        return self._sample(logits[:, 0], mask), mask

    def step(self, live):
        """One jitted decode step over the full slot batch. Slots still
        mid-chunked-prefill ride the batch frozen (device length 0) and
        are EXCLUDED from the advance mask — committing their sampling
        state here would burn a PRNG split the wave path never spends."""
        cap = self.page_cap(live)
        with span("serve.decode", pages=cap, live=len(live)):
            logits, self._cache = self._decode(self.params, self._cache,
                                               self._last_tok, max_pages=cap)
        mask = np.zeros((self.n_slots,), bool)
        for s, st in live.items():
            if s not in self._pending:
                st.length += 1
                mask[s] = True
        return self._sample(logits[:, 0], mask), mask

    def _sample(self, logits2d, mask: np.ndarray) -> np.ndarray:
        """Sample all slots; commit key/token state for ``mask`` slots only
        (keeping every request's key chain aligned with its token
        count).

        Guarded (ISSUE 10): before committing, the emitting slots'
        logits are checked host-side for non-finite values — NaN, +inf,
        or an all(-inf) row. ``max`` is the right reduction: -inf
        entries are LEGITIMATE (vocab-padding mask, top-k truncation),
        but the row maximum is finite for any sane distribution and
        poisoned by any NaN. A guard trip withholds the slot's key/token
        commit entirely (its PRNG chain stays aligned with its COMMITTED
        token count, so the quarantine retry resumes bit-identically)
        and records the slot in ``_guard_bad`` for the engine to
        quarantine. Fault-plan ``nan`` injections overwrite the chosen
        slots' logits on device first, so drills flow through the same
        guard as real poison."""
        with span("serve.sample"):
            if self.faults is not None:
                bad = self.faults.nan_slots()
                if bad:
                    rows = (jnp.arange(self.n_slots) if -1 in bad
                            else jnp.asarray(sorted(bad)))
                    logits2d = jnp.asarray(logits2d).at[rows].set(jnp.nan)
            commit = mask
            if self.guards:
                # fused sampler variant: the guard's row-max reduction rides
                # the same dispatch as sampling, and the peak vector comes
                # back in the same host transfer as the tokens — the guarded
                # path costs no extra device round-trip over the unguarded
                # one (gated at <= 5% overhead by check_bench).
                toks, new_keys, peak_dev = sample_tokens_guarded(
                    logits2d, self._temps, self._topks, self._keys, self._vocab)
                with span("serve.fetch"):
                    toks_h, peak = np.asarray(toks), np.asarray(peak_dev)
                if mask.any():
                    trip = ~np.isfinite(peak) & mask
                    if trip.any():
                        commit = mask & ~trip
                        for s in np.nonzero(trip)[0]:
                            self._guard_bad[int(s)] = (
                                f"non-finite logits (row max {peak[s]!r}) at "
                                f"slot {int(s)} — emission withheld")
            else:
                toks, new_keys = sample_tokens(logits2d, self._temps,
                                               self._topks, self._keys,
                                               self._vocab)
                with span("serve.fetch"):
                    toks_h = np.asarray(toks)
            m = jnp.asarray(commit)
            self._keys = jnp.where(m[:, None], new_keys, self._keys)
            self._last_tok = jnp.where(m[:, None], toks[:, None],
                                       self._last_tok)
            return toks_h

    # -- retire / preempt ------------------------------------------------

    def release(self, slot: int) -> None:
        """Free a finished slot: zero its cache length so the decode
        step's active mask freezes the lane (ISSUE 3: retired slots used
        to keep advancing their length and writing garbage KV every step —
        fatal under paging, where the stale page table points at pages
        that may already belong to another request), and return its
        pages."""
        self._cache["length"] = self._cache["length"].at[slot].set(0)
        self._pending.pop(slot, None)
        self._slot_kept.pop(slot, None)
        if self.paged:
            self._pool.free(self._slot_pages.pop(slot))

    def quarantine(self, slot: int) -> None:
        """Invalidate prefix-index entries for every page this slot WROTE
        (everything after the shared prefix kept at admission): the
        slot's prompt pages were indexed when its prefill finalized, so a
        fault in the slot means other requests could match — and read —
        content it produced. Pages it only SHARED are untouched:
        copy-on-write guarantees a sharer never writes them, so their
        content predates the fault."""
        if self._prefix is None or slot not in self._slot_pages:
            return
        kept = self._slot_kept.get(slot, 0)
        written = self._slot_pages[slot][kept:]
        if written:
            self._prefix.invalidate(written, self._pool)

    def snapshot_request(self, slot: int, st, emitted) -> Request:
        """The resumable request, PURE (no freeze/free — ``snapshot``
        adds that): generated-so-far folds into the prompt (budget
        shrinks by the same amount) and the PRNG key chain is
        snapshotted into ``key_override``. Re-prefill of prompt +
        generated reproduces the exact cache the preempted decode had
        built — prefill/decode parity is the tested invariant.

        A slot caught MID-CHUNKED-PREFILL has emitted nothing: the
        original request re-queues whole (partial chunk writes are dead
        once the lane freezes), so the resumed run is bit-identical by
        construction."""
        req = st.req
        # guard the generated == 0 slice: [-0:] is the WHOLE list, and a
        # mid-chunk preemption is exactly the case that reaches it
        gen = emitted[-st.generated:] if st.generated else []
        return Request(
            req.rid, np.concatenate([req.tokens,
                                     np.asarray(gen, np.int32)]),
            req.max_new_tokens - st.generated, req.sampling, req.frontend,
            key_override=np.asarray(self._keys)[slot],
            priority=req.priority, on_token=req.on_token)

    def snapshot(self, slot: int, st, emitted) -> Request:
        """Preemption: build the resume request (``snapshot_request``),
        then freeze the slot (length 0) and return its pages to the pool
        immediately."""
        resumed = self.snapshot_request(slot, st, emitted)
        self._cache["length"] = self._cache["length"].at[slot].set(0)
        self._pending.pop(slot, None)
        self._slot_kept.pop(slot, None)
        if self.paged:
            self._pool.free(self._slot_pages.pop(slot))
        return resumed

    def stats(self) -> dict:
        if not self.paged:
            return {}
        out = {"n_pages": self.n_pages, "n_free": self._pool.n_free,
               "watermark": self._pool.watermark,
               "grown": self._pool.n_grown}
        if self._prefix is not None:
            matched, matchable = self._tok_matched, self._tok_matchable
            out["prefix"] = {
                "entries": len(self._prefix),
                "cached_pages": self._prefix.n_cached(self._pool),
                "tokens_matched": matched,
                "tokens_matchable": matchable,
                "hit_rate": matched / matchable if matchable else 0.0,
                "cow_copies": self._n_cow,
                "evictions": self._prefix.n_evicted,
                "collisions_rejected": self._prefix.n_rejected,
                "invalidated": self._prefix.n_invalidated,
            }
        return out


class PairBatchBackend(Backend):
    """Batched Pairformer inference (FlashBias Sec. 4.4).

    A request is ONE COMPLEX: its payload is a float (n_res, F) residue
    feature array, its budget ``max_new_tokens`` is the number of
    refinement iterations, and its result is the final single
    representation (n_res, d_model). Admission runs the full trunk once
    (triangle updates, pair transitions) and caches each layer's pair-bias
    state per slot — factor-MLP phi_q/phi_k when ``factors`` is given
    (Eq. 5), truncated-SVD factors of the projected bias when not
    (Sec. 4.3), or the dense (H, N, N) bias itself under
    ``cfg.bias_mode="dense"`` (the A/B baseline). The pair representation
    z is then DISCARDED: steps run attention + transition over the single
    rep only, reusing the frozen factors — the factor cache is the
    Pairformer analogue of the LM path's KV cache.

    Batching pads every wave to ``max_len`` residues (pinned, like the LM
    ``prefill_len``) and masks attention per slot at its own ``n_res`` —
    factor-MLP biases are nonzero at padded residues, so the mask is
    load-bearing, not cosmetic. Preemption restarts a complex from scratch
    (nothing is emitted incrementally, so the re-run is trivially
    deterministic and the snapshot carries no device state).
    """

    def __init__(self, model: Model, params: dict, max_len: int,
                 n_slots: int, factors: Optional[dict] = None):
        self.model, self.params = model, params
        self.max_len, self.n_slots = max_len, n_slots
        self.factors = factors
        self._guard_bad: Dict[int, str] = {}
        self._cache = None

        def _pf(p, feats, lengths, factors, max_len):
            return model.prefill(p, {"feats": feats}, max_len=max_len,
                                 lengths=lengths, factors=factors)

        self._prefill = jax.jit(named("_pf", _pf),
                                static_argnames=("max_len",))
        self._step = jax.jit(named("refine", model.decode))
        self._insert = jax.jit(named("insert_factors", model.insert_cache))
        self._svds = pairformer.admission_svds(model.cfg, n_slots, max_len,
                                               factors)

    def ensure_state(self) -> None:
        if self._cache is None:
            self._cache = self.model.init_cache(self.n_slots, self.max_len,
                                                factors=self.factors)

    def validate(self, req: Request) -> None:
        if req.tokens.dtype != np.float32 or req.tokens.ndim != 2:
            raise AdmissionRejected(
                "pair request payload must be a float (n_res, F) feature "
                "array")
        if req.tokens.shape[0] > self.max_len:
            raise AdmissionRejected(
                f"complex has {req.tokens.shape[0]} residues; slot batch "
                f"is padded to max_len={self.max_len}")
        if req.frontend is not None:
            raise AdmissionRejected("pair requests carry no frontend")

    def admit(self, wave: List[Request], slots: List[int]):
        """Trunk pass over the padded wave; scatter per-layer bias state
        into the slot factor cache. Emits nothing (mask all-False): the
        budget counts refinement STEPS, and admission is step 0."""
        ns, w = self.n_slots, len(wave)
        f = wave[0].tokens.shape[1]
        feats = np.zeros((ns, self.max_len, f), np.float32)
        lengths = np.zeros((ns,), np.int32)
        real = [r.tokens.shape[0] for r in wave]
        for i, r in enumerate(wave):
            feats[i, :real[i]] = r.tokens
            lengths[i] = real[i]
        self.n_prefill_rows_x_len += ns * self.max_len
        self.n_prefill_real += sum(real)
        self.n_svd_matrices += self._svds[0]
        self.n_svd_solves += self._svds[1]
        with span("serve.prefill", requests=w, rows=ns, row_len=self.max_len,
                  real=sum(real), real_sq=sum(n * n for n in real)):
            _, wave_cache = self._prefill(self.params, jnp.asarray(feats),
                                          jnp.asarray(lengths), self.factors,
                                          self.max_len)
            if self.guards:
                self._guard_factors(wave_cache, slots)
            slot_ids = np.full((ns,), ns, np.int32)  # padding rows: dropped
            slot_ids[:w] = slots
            self._cache = self._insert(self._cache, wave_cache,
                                       jnp.asarray(slot_ids))
        return None, np.zeros((ns,), bool)

    def _guard_factors(self, wave_cache, slots: List[int]) -> None:
        """Admission-time factor guard: the trunk ran once
        and its cached per-layer bias state is frozen for every
        refinement step — a NaN/Inf here (bad features, unstable
        factorization) poisons ALL of the request's steps, so catch it
        now, per wave row, before the engine registers the slot."""
        ns = self.n_slots
        flags = [jnp.isfinite(leaf).all(axis=tuple(range(1, leaf.ndim)))
                 for leaf in jax.tree_util.tree_leaves(wave_cache)
                 if jnp.issubdtype(leaf.dtype, jnp.floating)
                 and leaf.ndim >= 1 and leaf.shape[0] == ns]
        if not flags:
            return
        with span("serve.fetch"):
            ok = np.asarray(functools.reduce(jnp.logical_and, flags))
        for i, slot in enumerate(slots):
            if not ok[i]:
                self._guard_bad[slot] = (
                    f"non-finite factor cache at admission of slot {slot} "
                    f"(trunk produced NaN/Inf from the complex features)")

    def step(self, live):
        """One refinement iteration over every live slot (single jitted
        call; retired slots are frozen by their zero length)."""
        with span("serve.decode", live=len(live)):
            self._cache = self._step(self.params, self._cache)
        mask = np.zeros((self.n_slots,), bool)
        mask[list(live)] = True
        return None, mask

    def fetch_result(self, slot: int, st) -> np.ndarray:
        n = st.req.tokens.shape[0]
        with span("serve.fetch"):
            return np.asarray(self._cache["s"][slot, :n], np.float32)

    def stream_result(self, slot: int, st) -> np.ndarray:
        """Per-iteration single rep for streaming sinks: the pair backend
        emits no tokens, so ``on_token`` subscribers drain the current
        (n_res, d_model) state after every refinement step instead of
        waiting for retirement."""
        return self.fetch_result(slot, st)

    def release(self, slot: int) -> None:
        self._cache["length"] = self._cache["length"].at[slot].set(0)

    def snapshot_request(self, slot: int, st, emitted) -> Request:
        """Preemption = restart: the resume request is the ORIGINAL with
        its full budget (no incremental output was emitted, so the
        re-run is deterministic by construction). Pure — ``snapshot``
        adds the freeze."""
        req = st.req
        return Request(req.rid, req.tokens, req.max_new_tokens,
                       req.sampling, req.frontend, priority=req.priority,
                       on_token=req.on_token)

    def snapshot(self, slot: int, st, emitted) -> Request:
        resumed = self.snapshot_request(slot, st, emitted)
        self._cache["length"] = self._cache["length"].at[slot].set(0)
        return resumed
