"""Continuous-batching serve engine core (backend-abstracted since ISSUE 6).

The engine owns ``n_slots`` lanes and everything REQUEST-shaped: request
ids, the scheduler and admission waves, the slot free-list and live map,
result/done bookkeeping, budget accounting, and the preemption victim
policy. Everything DEVICE-shaped — caches, page pools, sampling state, the
jitted admit/step programs — lives in a ``serve.backend.Backend``:

- ``TokenDecodeBackend`` (LM families): KV caches (full / paged / ring /
  SSM), per-request PRNG sampling chains, lazy page growth. This is the
  pre-refactor engine body moved verbatim — LM serve behavior is
  bit-identical to the monolithic engine.
- ``PairBatchBackend`` (``cfg.family == "pairformer"``): batched
  Pairformer inference where a request is one complex, admission runs the
  trunk once and caches the per-layer pair-bias FACTORS per slot
  (FlashBias Sec. 4.4), and each step is one refinement iteration over the
  padded slot batch with per-slot ``n_res`` masking.

The admission/step loop is backend-agnostic: a FIFO scheduler (with
priority classes — higher admits first, preempts last) fills freed slots,
each admission wave is padded to ``n_slots`` and prefilled in one jitted
call, and every engine step advances the full slot batch in ONE jitted
program — per-request raggedness rides in the ``lengths`` vector, so
arbitrary arrival/finish patterns never recompile and never stall on the
slowest request.

Determinism contract (tested in tests/test_serve_engine.py /
test_pair_serve.py): every per-slot computation is batch-row independent
and sampler key chains are per-request, so a request's output is identical
whether it runs alone or packed with strangers — provided the padded
prompt length is pinned (``prefill_len`` for LM; ``max_len`` pins it
structurally for the pair backend).

Paged mode, lazy growth and preemption semantics are unchanged from
ISSUEs 3-5 (see serve/README.md §Paged KV): when the pool runs dry the
engine preempts the lowest-priority live request (lowest priority class,
then latest arrival), whose snapshot re-enters at the head of the queue —
greedy outputs are bit-identical to the never-preempted run.

FAULT TOLERANCE (ISSUE 10, serve/README.md §Failure semantics): every
request carries a lifecycle record (``QUEUED -> RUNNING -> OK / FAILED /
TIMED_OUT / CANCELLED / REJECTED``); ``result`` returns a
``RequestRecord`` — the result array plus its status and structured
error. Host-side non-finite guards on emissions (and on the pair
backend's admission-time factor caches) QUARANTINE a faulting slot
through the preemption-snapshot machinery: its emission is withheld, its
written prefix pages are invalidated from the index, and its request
retries bit-identically up to ``max_retries`` before terminating FAILED.
Pool exhaustion and injected step faults are contained the same way
(preempt + retry, never crash). ``snapshot_engine`` serializes the whole
host state for crash-safe restore on a fresh engine, and a
``serve.faults.FaultPlan`` drives all of it deterministically in the
chaos suite (tests/test_faults.py).
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional

import numpy as np

from repro.models.api import Model
from repro.serve.backend import PairBatchBackend, TokenDecodeBackend
from repro.serve.faults import FaultPlan
from repro.serve.lifecycle import (
    CANCELLED, FAILED, OK, QUEUED, REJECTED, RUNNING, TERMINAL_STATUSES,
    TIMED_OUT, AdmissionRejected, EngineStalled, InjectedFault,
    PoolExhausted, RequestNotLive, RequestRecord)
from repro.serve.sampling import SamplingParams
from repro.serve.scheduler import FIFOScheduler, Request
from repro.serve.trace import span, tracing

__all__ = ["ServeEngine"]

_SNAPSHOT_VERSION = 1


@dataclasses.dataclass
class _Slot:
    """Host-side state of one occupied lane.

    ``length`` mirrors ``cache["length"][slot]``: it is the position the
    NEXT decode step will write, which is what lazy page growth gates on
    (no device read-back in the decode loop)."""
    req: Request
    generated: int = 0
    length: int = 0


@dataclasses.dataclass
class _ReqMeta:
    """Lifecycle record of one request (host bookkeeping, ISSUE 10)."""
    status: str = QUEUED
    error: Optional[dict] = None
    retries: int = 0                  # quarantine retries consumed
    max_retries: int = 1
    deadline: Optional[int] = None    # absolute engine step, None = never


def _ser_arr(x) -> dict:
    """JSON-serializable encoding of a result payload: a token-id list
    (mid-flight token results) or an ndarray (prompts, keys, pair
    results)."""
    if isinstance(x, np.ndarray):
        return {"kind": "array", "dtype": str(x.dtype),
                "shape": list(x.shape), "data": x.ravel().tolist()}
    return {"kind": "ids", "data": [int(t) for t in x]}


def _de_arr(d: dict):
    if d["kind"] == "array":
        return np.asarray(d["data"], dtype=np.dtype(d["dtype"])).reshape(
            d["shape"])
    return list(d["data"])


class ServeEngine:
    """Slot-based continuous-batching engine (admit/step/commit core).

    Args:
        model: a serve-capable ``Model`` (prefill/decode/init_cache/
            insert_cache). ``cfg.family`` selects the backend:
            ``"pairformer"`` gets the batched pair-inference backend,
            every decode family gets the token backend.
        params: parameter pytree.
        max_len: per-slot cache segment length. For the pair backend this
            is the pinned residue padding — every wave pads to it, so one
            prefill/step program serves all complexes and outputs are
            independent of wave composition.
        eos_id: generation stops when this id is sampled (kept in the
            output; ``generate`` pads remaining columns with it). -1 never
            matches, i.e. requests always run out their budget. Ignored by
            non-emitting backends.
        n_slots: fixed batch — the number of concurrent requests.
        prefill_len: pinned padded prompt length (token backend only).
            None pads each admission wave to its own maximum (fewest
            wasted FLOPs); pinning it makes request outputs independent of
            wave composition and bounds prefill compiles to one.
        page_size / n_pages / pages_per_slot / page_reservation: paged-KV
            knobs, forwarded to the token backend (see its docstring and
            serve/README.md §Paged KV). Ignored by the pair backend.
        scheduler_policy: ``"fifo"`` (default) admits in arrival order;
            ``"spf"`` admits the shortest queued prompt first. Priority
            classes order above either policy; preempted requests resume
            ahead of same-priority arrivals.
        factors: fitted pair-bias factor MLP params (pair backend only).
            None selects per-complex SVD factors at ``cfg.bias_rank``
            (``cfg.bias_mode="dense"`` caches the dense bias instead —
            the A/B baseline).
        prefill_chunk: > 0 switches the token backend to CHUNKED prefill
            (ISSUE 7): admission becomes planning, prompts land
            ``prefill_chunk`` tokens per engine step interleaved with the
            decode batch — a long arrival can never stall in-flight
            decodes for more than one chunk's latency. None (default)
            keeps whole-prompt admission waves, bit-identical to the
            pre-chunking engine. Ring-KV archs clamp the chunk to the
            attention window.
        prefix_cache: True enables content-hashed prefix caching
            (ISSUE 9): completed prompt pages are indexed by token
            content, a new request whose prompt starts with an indexed
            prefix maps its page table onto the existing pages (refcounted
            sharing + copy-on-write) and prefills only the novel tail.
            Requires ``page_size`` + ``prefill_chunk`` and a full-KV
            family without recurrent state (dense / moe). Outputs stay
            bit-identical to the unshared engine; see serve/README.md
            §Prefix caching contract.
        mesh / rules: device mesh + logical-axis rules for the token
            backend (ISSUE 7). The backend traces every jitted program
            under ``use_mesh_rules`` and places its persistent state with
            explicit shardings — KV/pools along ``kv_heads``, slot rows
            along ``batch`` — while the page allocator and tables stay
            host-side. None serves single-device, unchanged.
        guards: host-side non-finite emission/admission guards
            (ISSUE 10, default on — the ``guard_overhead`` bench gates
            their cost at <= 5%). Off restores the pre-lifecycle
            behavior: poison flows into results undetected.
        faults: a ``serve.faults.FaultPlan`` for deterministic fault
            injection (chaos drills). None (default) injects nothing.
        prefill_rows: rows of the token backend's whole-prompt prefill
            program (default ``n_slots``): an admission wave takes at most
            this many requests and pads to exactly this many rows. Fewer
            rows than slots keep a wave of one or two long prompts from
            paying for ``n_slots`` rows of padding.
        stall_limit: ``run()`` raises ``EngineStalled`` after this many
            consecutive steps with queued/live work but zero progress
            (no admission, no chunk landed, no token committed, no
            preemption) — a diagnostic instead of an infinite spin.
    """

    def __init__(self, model: Model, params: dict, max_len: int = 1024,
                 eos_id: int = -1, n_slots: int = 4,
                 prefill_len: Optional[int] = None,
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 pages_per_slot: Optional[int] = None,
                 page_reservation: str = "lazy",
                 scheduler_policy: str = "fifo",
                 factors: Optional[dict] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = False,
                 mesh=None, rules=None,
                 guards: bool = True,
                 faults: Optional[FaultPlan] = None,
                 stall_limit: int = 64,
                 prefill_rows: Optional[int] = None):
        if model.prefill is None or model.decode is None:
            raise ValueError("model is not serve-capable (needs "
                             "prefill/decode programs)")
        if stall_limit < 1:
            raise ValueError(f"stall_limit must be >= 1, got {stall_limit}")
        self.model, self.params = model, params
        self.max_len, self.eos_id = max_len, eos_id
        self.n_slots, self.prefill_len = n_slots, prefill_len
        if model.cfg.family == "pairformer":
            if prefill_chunk is not None or mesh is not None or prefix_cache:
                raise ValueError(
                    "chunked prefill / prefix cache / mesh sharding are "
                    "token-backend paths")
            self.backend = PairBatchBackend(model, params, max_len=max_len,
                                            n_slots=n_slots, factors=factors)
        else:
            self.backend = TokenDecodeBackend(
                model, params, max_len=max_len, n_slots=n_slots,
                prefill_len=prefill_len, page_size=page_size,
                n_pages=n_pages, pages_per_slot=pages_per_slot,
                page_reservation=page_reservation,
                prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
                mesh=mesh, rules=rules, prefill_rows=prefill_rows)
        if self.backend.paged:
            self.page_size = self.backend.page_size
            self.n_pages = self.backend.n_pages
            self.pages_per_slot = self.backend.pages_per_slot
        self.guards = guards
        self.backend.guards = guards
        self.faults = faults
        self.backend.faults = faults
        self.stall_limit = stall_limit
        self.step_idx = 0               # engine steps taken (fault clock)
        self.n_preemptions = 0
        self.n_quarantines = 0          # guard trips contained
        self.n_faults_contained = 0     # step/alloc faults contained
        self.n_waves = 0                # admission waves taken
        self.scheduler = FIFOScheduler(policy=scheduler_policy)
        self._next_rid = 0
        self._results: Dict[int, object] = {}   # rid -> [ids] | result array
        self._done: Dict[int, bool] = {}
        self._meta: Dict[int, _ReqMeta] = {}    # rid -> lifecycle record
        self._live: Dict[int, _Slot] = {}         # slot -> _Slot
        self._free: List[int] = list(range(n_slots))
        self._advanced = 0              # committed budget units (stall sig)

    def _page_cap(self) -> Optional[int]:
        return self.backend.page_cap(self._live)

    # ------------------------------------------------------------------
    # Request lifecycle
    # ------------------------------------------------------------------

    def submit(self, tokens, max_new_tokens: int,
               sampling: Optional[SamplingParams] = None,
               frontend: Optional[np.ndarray] = None,
               priority: int = 0, on_token=None,
               deadline_steps: Optional[int] = None,
               max_retries: int = 1, strict: bool = True) -> int:
        """Queue one request; returns its request id.

        ``priority`` is the request's class: higher admits before lower
        regardless of arrival order, and preemption victims are drawn from
        the lowest class first. The default 0 for every request reproduces
        the pre-class engine exactly.

        ``on_token`` streams the request's progress: the engine calls it
        once per budget unit the request advances, with the emitted token
        id (token backend) or the backend's per-step ``stream_result``
        (pair backend — the current single rep). The callback rides the
        request descriptor, so it survives preemption and resume.

        ``deadline_steps`` (ISSUE 10): the request terminates
        ``TIMED_OUT`` (keeping its partial result) if still incomplete
        after this many further engine steps. None = no deadline.

        ``max_retries``: quarantine retries before a guard-tripping
        request terminates ``FAILED`` (each retry resumes bit-identically
        from its preemption snapshot).

        ``strict``: when False, a request that fails admission validation
        returns a rid whose record is terminal ``REJECTED`` (with the
        validation message as its error) instead of raising
        ``AdmissionRejected``.
        """
        if deadline_steps is not None and deadline_steps < 1:
            raise ValueError(
                f"deadline_steps must be >= 1, got {deadline_steps}")
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        rid = self._next_rid
        self._next_rid += 1
        deadline = (None if deadline_steps is None
                    else self.step_idx + deadline_steps)
        self._results[rid] = []
        self._done[rid] = False
        self._meta[rid] = _ReqMeta(max_retries=max_retries,
                                   deadline=deadline)
        try:
            req = Request(rid, np.asarray(tokens), max_new_tokens,
                          sampling or SamplingParams(), frontend,
                          priority=priority, on_token=on_token)
            self.backend.validate(req)
        except AdmissionRejected as e:
            if strict:
                del self._results[rid], self._done[rid], self._meta[rid]
                raise
            self._finish(rid, REJECTED,
                         error={"kind": "admission", "detail": str(e)})
            return rid
        self.scheduler.add(req)
        return rid

    def result(self, rid: int) -> RequestRecord:
        """The ``(status, tokens, error)`` record for ``rid`` — a
        ``RequestRecord``: the result array so far (complete iff
        ``is_done``) carrying ``status`` and ``error`` attributes.
        Generated ids for the token backend, the final (n_res, d_model)
        single representation for the pair backend."""
        if rid not in self._results:
            raise RequestNotLive(f"unknown request id {rid}")
        res = self._results[rid]
        meta = self._meta[rid]
        if not isinstance(res, np.ndarray):
            res = np.asarray(res, np.int32)
        return RequestRecord(res, status=meta.status, error=meta.error)

    def status(self, rid: int) -> str:
        """Lifecycle status of ``rid`` (see serve.lifecycle)."""
        if rid not in self._meta:
            raise RequestNotLive(f"unknown request id {rid}")
        return self._meta[rid].status

    def status_counts(self) -> Dict[str, int]:
        """{status: count} over every submitted request (the launcher's
        final stats line)."""
        counts: Dict[str, int] = {}
        for meta in self._meta.values():
            counts[meta.status] = counts.get(meta.status, 0) + 1
        return counts

    def cancel(self, rid: int) -> bool:
        """Terminate ``rid`` as ``CANCELLED``, releasing its slot/pages.

        Returns True if the cancel landed, False if the request already
        reached a terminal status (too late to cancel). Unknown rids
        raise ``RequestNotLive``. The partial result (tokens emitted
        before the cancel) stays readable via ``result``."""
        meta = self._meta.get(rid)
        if meta is None:
            raise RequestNotLive(f"unknown request id {rid}")
        if meta.status in TERMINAL_STATUSES:
            return False
        if self.scheduler.remove(rid) is None:
            slots = [s for s, st in self._live.items()
                     if st.req.rid == rid]
            if not slots:
                raise RequestNotLive(
                    f"request {rid} is neither queued nor in flight")
            self._retire_slot(slots[0])
        self._finish(rid, CANCELLED)
        return True

    def is_done(self, rid: int) -> bool:
        if rid not in self._done:
            raise RequestNotLive(f"unknown request id {rid}")
        return self._done[rid]

    def _finish(self, rid: int, status: str,
                error: Optional[dict] = None) -> None:
        meta = self._meta[rid]
        meta.status = status
        if error is not None:
            meta.error = error
        self._done[rid] = True

    def _retire_slot(self, slot: int) -> None:
        """Pop a live slot and free its backend resources (no requeue)."""
        del self._live[slot]
        bisect.insort(self._free, slot)
        self.backend.release(slot)

    @property
    def occupancy(self) -> int:
        return len(self._live)

    def counters(self) -> Dict[str, int]:
        """Cumulative counts since the engine started (a restored engine
        continues its snapshot's): engine ``steps``, admission ``waves``,
        ``prefill_rows_x_len`` (rows x padded length the prefill programs
        ran) and ``prefill_real`` (the real prompt tokens, or residues,
        among them), ``svd_matrices`` and ``svd_solves`` (the bias
        matrices admission factored by SVD and the eigensolver calls that
        factored them), ``preemptions``, ``quarantines`` and
        ``faults_contained``. ``1 - prefill_real / prefill_rows_x_len``
        is the share of prefill rows that were padding. Two sizes of the
        device state ride along once it is allocated:
        ``state_bytes_per_slot`` (the recurrent SSM state and conv tails
        one slot holds) and ``paged_layers`` (the layers whose K/V live
        in the page pool)."""
        return {"steps": self.step_idx, "waves": self.n_waves,
                "prefill_rows_x_len": self.backend.n_prefill_rows_x_len,
                "prefill_real": self.backend.n_prefill_real,
                "svd_matrices": self.backend.n_svd_matrices,
                "svd_solves": self.backend.n_svd_solves,
                "preemptions": self.n_preemptions,
                "quarantines": self.n_quarantines,
                "faults_contained": self.n_faults_contained,
                "state_bytes_per_slot": self.backend.state_bytes_per_slot,
                "paged_layers": self.backend.paged_layers}

    def page_stats(self) -> dict:
        """Pool accounting snapshot (empty for unpaged backends)."""
        stats = self.backend.stats()
        if stats:
            stats["preemptions"] = self.n_preemptions
            stats["quarantines"] = self.n_quarantines
            stats["faults_contained"] = self.n_faults_contained
        return stats

    # ------------------------------------------------------------------
    # Engine steps
    # ------------------------------------------------------------------

    def step(self) -> List[int]:
        """Admit queued requests into free slots, advance every pending
        prompt one prefill chunk, then advance every decoding slot one
        budget unit. Returns rids that reached a terminal status this
        step (OK and TIMED_OUT alike).

        The chunk/decode INTERLEAVE is the chunked-prefill latency
        contract: each engine step costs the decode batch exactly one
        chunk program, so a long arrival's admission is amortized one
        chunk per step instead of stalling the whole batch behind a
        monolithic prompt prefill."""
        with span("serve.step", step=self.step_idx, live=len(self._live)):
            self._ensure_state()
            if self.faults is not None:
                self.faults.tick(self.step_idx)
            finished = self._expire_deadlines()
            delayed = (self.faults is not None
                       and self.faults.admission_delayed())
            if self._free and len(self.scheduler) and not delayed:
                finished += self.admit()
            if self.backend.prefill_pending():
                emissions, mask = self.backend.prefill_step()
                finished += self._commit_guarded(emissions, mask)
            if self._live:
                try:
                    finished += self.decode()
                except InjectedFault:
                    # a failed jitted step dispatched nothing and advanced
                    # no state — containment is retrying next step
                    self.n_faults_contained += 1
            self.step_idx += 1
        return finished

    def run(self) -> None:
        """Step until the queue and all slots drain.

        Stall guard (ISSUE 10): ``stall_limit`` consecutive steps with
        work outstanding but NO progress — no admission, no chunk
        landed, no budget unit committed, no preemption/quarantine, no
        terminal transition — raise ``EngineStalled`` with queue/pool/
        slot diagnostics instead of spinning forever."""
        self._ensure_state()
        idle = 0
        while self._live or len(self.scheduler):
            before = self._progress_sig()
            self.step()
            if self._progress_sig() == before:
                idle += 1
                if idle >= self.stall_limit:
                    raise EngineStalled(
                        f"no progress for {idle} consecutive steps: "
                        f"{len(self.scheduler)} queued, "
                        f"{len(self._live)} live "
                        f"(slots {sorted(self._live)}), "
                        f"free slots {self._free}, "
                        f"page stats {self.page_stats() or None}, "
                        f"statuses {self.status_counts()}")
            else:
                idle = 0

    def _progress_sig(self) -> tuple:
        """Everything that changes when the engine makes progress —
        compared across steps by the ``run`` stall guard."""
        pending = getattr(self.backend, "_pending", None) or {}
        return (len(self.scheduler), len(self._live), self._advanced,
                self.n_preemptions, self.n_quarantines,
                sum(self._done.values()),
                tuple(sorted((s, p.done) for s, p in pending.items())))

    def _expire_deadlines(self) -> List[int]:
        """Terminate every queued/live request whose deadline elapsed
        (TIMED_OUT, partial result retained)."""
        expired: List[int] = []
        for req in self.scheduler.queued():
            meta = self._meta[req.rid]
            if meta.deadline is not None and self.step_idx >= meta.deadline:
                self.scheduler.remove(req.rid)
                expired.append(req.rid)
        for slot in sorted(self._live):
            meta = self._meta[self._live[slot].req.rid]
            if meta.deadline is not None and self.step_idx >= meta.deadline:
                expired.append(self._live[slot].req.rid)
                self._retire_slot(slot)
        for rid in expired:
            self._finish(rid, TIMED_OUT, error={
                "kind": "deadline", "step": self.step_idx,
                "detail": f"deadline (step {self._meta[rid].deadline}) "
                          f"elapsed before completion"})
        return expired

    def _take_wave(self) -> List[Request]:
        """Pop the next admission wave: one request per free slot, gated in
        paged mode on free-page accounting — admit while the head request's
        admission reservation (prompt pages under lazy growth, the full
        footprint under whole-request reservation) fits; no head-of-line
        bypass within the policy. A resumed request whose prompt outgrew a
        pinned ``prefill_len`` rides a SOLO wave: padding a mixed wave to
        the resumed length would change co-admitted requests' padded
        prompt length, which is exactly the shape the determinism contract
        pins (it feeds MoE expert capacity)."""
        wave: List[Request] = []
        reserved = 0
        room = min(len(self._free), self.backend.wave_rows or self.n_slots)
        while len(wave) < room:
            r = self.scheduler.peek()
            if r is None:
                break
            over = (self.prefill_len is not None
                    and r.tokens.size > self.prefill_len)
            if over and wave:
                break                    # over-length request: next wave
            if self.backend.paged:
                needed = self.backend.admission_units(r)
                if needed > self.backend.units_free() - reserved:
                    break                # backpressure: wait for frees
                reserved += needed
            wave.append(self.scheduler.take(1)[0])
            if over:
                break                    # solo wave for the resumed prompt
        return wave

    def admit(self) -> List[int]:
        """Prefill the next admission wave into freed slots; the backend
        decides what (if anything) each admission emits — the token
        backend samples each request's first token from its prefill
        logits, the pair backend emits nothing (its budget counts
        refinement steps)."""
        self._ensure_state()
        with span("serve.admit") as sp:
            wave = self._take_wave()
            if not wave:
                return []
            if tracing():
                sp.set_metadata(rids=";".join(str(r.rid) for r in wave))
            self.n_waves += 1
            slots = [self._free.pop(0) for _ in wave]
            emissions, mask = self.backend.admit(wave, slots)
            for slot, r in zip(slots, wave):
                self._live[slot] = _Slot(r, length=r.prompt_len)
                self._meta[r.rid].status = RUNNING
        return self._commit_guarded(emissions, mask)

    def decode(self) -> List[int]:
        """Advance every live slot one budget unit in one jitted backend
        step. Lazy paged mode first grows any slot whose write position
        crossed a page boundary — preempting the lowest-priority request
        while the pool is dry — so the jitted step itself never
        allocates.

        Fault containment (ISSUE 10): an injected step fault skips the
        dispatch (nothing advanced — the retry next iteration is free);
        a ``PoolExhausted`` escaping growth (injected, or an accounting
        bug) preempts the growing slots — their snapshots resume
        bit-identically — instead of crashing the engine."""
        self._ensure_state()
        pending = self.backend.pending_slots()
        if pending and all(s in pending for s in self._live):
            return []               # nothing decoding yet — chunks only
        if self.faults is not None and self.faults.step_fails():
            raise InjectedFault("injected decode-step failure (fault plan)")
        if self.backend.lazy:
            # when the pool can't cover the growth, preempt lowest-
            # priority live requests (possibly a growing request itself —
            # freeing it both clears its demand and returns its pages)
            # until it can; (priority, arrival) is a total order, so the
            # highest-priority earliest-arrived request always makes
            # progress and the engine can never preempt itself into a
            # livelock
            growing = self.backend.growth_pending(self._live)
            while growing and self.backend.units_free() < len(growing):
                victim = self._victim_slot()
                self._preempt_slot(victim)
                growing = [s for s in growing if s != victim]
            if growing:
                try:
                    with span("serve.grow", slots=len(growing)):
                        self.backend.grow_slots(growing)
                except PoolExhausted:
                    # growth is atomic (no partial table state): preempt
                    # the growing slots — snapshots resume bit-identically
                    # — and let the rest of the batch proceed
                    self.n_faults_contained += 1
                    for slot in growing:
                        if slot in self._live:
                            self._preempt_slot(slot)
        if not self._live:
            return []
        emissions, mask = self.backend.step(self._live)
        return self._commit_guarded(emissions, mask)

    def generate(self, prompts, max_new_tokens: int, frontend=None,
                 sampling: Optional[SamplingParams] = None) -> np.ndarray:
        """Batch convenience wrapper (the PR-1 era API, now ragged-capable).

        prompts: (B, T) int32 array OR a list of 1-D ragged prompts.
        Returns (B, max_new_tokens) generated ids; rows that stop early at
        ``eos_id`` pad the remaining columns with ``eos_id``.
        """
        if not isinstance(self.backend, TokenDecodeBackend):
            raise TypeError(
                "generate() is a token-emitting API; submit()/result() "
                "serve pair requests")
        rows = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        rids = [self.submit(row, max_new_tokens, sampling=sampling,
                            frontend=None if frontend is None
                            else np.asarray(frontend[i]))
                for i, row in enumerate(rows)]
        self.run()
        out = np.full((len(rows), max_new_tokens), self.eos_id, np.int32)
        for i, rid in enumerate(rids):
            got = self.result(rid)
            out[i, :got.size] = got
        return out

    # ------------------------------------------------------------------
    # Preemption (lazy paged mode; public for any backend)
    # ------------------------------------------------------------------

    def _victim_slot(self) -> int:
        """Lowest priority class first, then latest arrival (highest rid)
        — with all-default priorities this is exactly the pre-class
        victim, so existing preemption behavior is unchanged."""
        return min(self._live,
                   key=lambda s: (self._live[s].req.priority,
                                  -self._live[s].req.rid))

    def preempt(self, rid: Optional[int] = None) -> Optional[int]:
        """Preempt one in-flight request and re-queue it at the head.

        Default victim is the lowest-priority live request (lowest class,
        latest arrival). Returns the preempted rid, or None when nothing
        is live. The engine calls this automatically when lazy page growth
        finds the pool dry; it is public so tests and external policies
        can force it for ANY backend (ring-KV / SSM slots hold no pages
        but preempt the same way; a pair slot restarts its complex).
        """
        self._ensure_state()
        if not self._live:
            return None
        if rid is None:
            slot = self._victim_slot()
        else:
            matches = [s for s, st in self._live.items()
                       if st.req.rid == rid]
            if not matches:
                raise RequestNotLive(f"request {rid} is not in flight")
            slot = matches[0]
        return self._preempt_slot(slot)

    def _preempt_slot(self, slot: int) -> int:
        """Snapshot + free + re-queue one slot.

        The backend builds the resumable request — the token backend folds
        generated-so-far into the prompt and snapshots the PRNG key chain
        (greedy outputs stay bit-identical to the never-preempted run; a
        sampled request continues its key chain unbroken), the pair
        backend restarts the complex from scratch. Either way the slot
        freezes, its resources free immediately, and the snapshot re-
        enters at the head of its priority class.
        """
        st = self._live.pop(slot)
        with span("serve.preempt", rid=st.req.rid):
            bisect.insort(self._free, slot)
            resumed = self.backend.snapshot(slot, st,
                                            self._results[st.req.rid])
            self.scheduler.add_front(resumed)
            self._meta[st.req.rid].status = QUEUED
            self.n_preemptions += 1
        return st.req.rid

    # ------------------------------------------------------------------
    # Fault containment (ISSUE 10)
    # ------------------------------------------------------------------

    def _quarantine(self, slot: int, detail: str) -> List[int]:
        """Contain a guard trip on ``slot``: invalidate anything other
        requests could observe from it (written prefix pages), then
        either RETRY — the preemption snapshot resumes the request
        bit-identically, its budget and emitted-so-far intact — or, when
        ``max_retries`` is spent, terminate it FAILED with a structured
        error. Pool state stays intact either way (freed pages return to
        the free list; nothing leaks)."""
        st = self._live[slot]
        rid = st.req.rid
        meta = self._meta[rid]
        self.backend.quarantine(slot)
        self.n_quarantines += 1
        if meta.retries < meta.max_retries:
            meta.retries += 1
            self._preempt_slot(slot)
            return []
        error = {"kind": "guard", "slot": slot, "step": self.step_idx,
                 "retries": meta.retries, "detail": detail}
        self._retire_slot(slot)
        self._finish(rid, FAILED, error)
        return [rid]

    def _commit_guarded(self, emissions: Optional[np.ndarray],
                        mask: np.ndarray) -> List[int]:
        """Drain the backend's guard verdicts BEFORE committing: a slot
        that tripped the non-finite guard this call has its emission
        withheld (the backend already withheld its PRNG/token commit) and
        is quarantined; everything else commits normally."""
        with span("serve.commit") as sp:
            advanced = self._advanced
            finished: List[int] = []
            if self.guards:
                for slot, detail in sorted(
                        self.backend.take_guard_faults().items()):
                    if slot in self._live:
                        mask[slot] = False
                        finished += self._quarantine(slot, detail)
            finished += self._commit(emissions, mask)
            sp.set_metadata(emitted=self._advanced - advanced,
                            finished=len(finished))
        return finished

    # ------------------------------------------------------------------
    # Crash-safe checkpoint / restore (ISSUE 10)
    # ------------------------------------------------------------------

    def _ser_req(self, req: Request) -> dict:
        """Serialize one request descriptor. ``on_token`` callbacks are
        host closures and cannot survive a process boundary — they are
        dropped (documented in serve/README.md §Failure semantics)."""
        return {
            "rid": req.rid,
            "tokens": _ser_arr(np.asarray(req.tokens)),
            "max_new_tokens": req.max_new_tokens,
            "sampling": {"temperature": req.sampling.temperature,
                         "top_k": req.sampling.top_k,
                         "seed": req.sampling.seed},
            "frontend": (None if req.frontend is None
                         else _ser_arr(np.asarray(req.frontend))),
            "key_override": (None if req.key_override is None
                             else _ser_arr(np.asarray(req.key_override))),
            "priority": req.priority,
        }

    def _de_req(self, d: dict) -> Request:
        return Request(
            d["rid"], _de_arr(d["tokens"]), d["max_new_tokens"],
            SamplingParams(d["sampling"]["temperature"],
                           d["sampling"]["top_k"], d["sampling"]["seed"]),
            None if d["frontend"] is None else _de_arr(d["frontend"]),
            key_override=(None if d["key_override"] is None
                          else np.asarray(_de_arr(d["key_override"]),
                                          np.uint32)),
            priority=d["priority"])

    def _config_sig(self) -> dict:
        """The config facts a restore target must agree on."""
        backend = self.backend
        return {"family": self.model.cfg.family, "arch": self.model.cfg.name,
                "n_slots": self.n_slots, "max_len": self.max_len,
                "prefill_len": self.prefill_len,
                "paged": backend.paged, "lazy": backend.lazy,
                "page_size": getattr(backend, "page_size", None),
                "n_pages": getattr(backend, "n_pages", None),
                "chunk_size": getattr(backend, "chunk_size", None),
                "prefix_cache": getattr(backend, "_prefix", None)
                is not None}

    def snapshot_engine(self) -> dict:
        """Serialize ALL host state to a JSON-compatible dict, without
        perturbing the running engine: lifecycle records, results so
        far, scheduler queues, and every live slot as its preemption-
        snapshot resume request (generated folded into the prompt, PRNG
        chain in ``key_override`` — the exact state the bit-identical
        preemption/resume contract is built on; a mid-ChunkPlan slot
        serializes as its whole original request, nothing emitted yet).

        Device state is deliberately NOT captured: pages, caches and the
        prefix index are rebuilt by re-prefill on the restored engine,
        which the parity contracts make bit-identical. Prefix-index
        chain digests ride along for audit only.

        ``restore_engine`` on a freshly constructed engine of the same
        config resumes the workload bit-identically (greedy and
        sampled), for every cache family."""
        front, arrivals = self.scheduler.snapshot()
        live = [self._ser_req(self.backend.snapshot_request(
                    slot, st, self._results[st.req.rid]))
                for slot, st in sorted(self._live.items())]
        prefix = getattr(self.backend, "_prefix", None)
        return {
            "version": _SNAPSHOT_VERSION,
            "config": self._config_sig(),
            "step_idx": self.step_idx,
            "next_rid": self._next_rid,
            "counters": dict(self.counters(), advanced=self._advanced),
            "meta": {str(rid): {"status": m.status, "error": m.error,
                                "retries": m.retries,
                                "max_retries": m.max_retries,
                                "deadline": m.deadline}
                     for rid, m in self._meta.items()},
            "results": {str(rid): _ser_arr(res)
                        for rid, res in self._results.items()},
            "done": {str(rid): bool(v) for rid, v in self._done.items()},
            "queue": {"front": [self._ser_req(r) for r in front],
                      "arrivals": [self._ser_req(r) for r in arrivals]},
            "live": live,
            "prefix_digests": ([k.hex() for k in prefix._entries]
                               if prefix is not None else []),
        }

    def restore_engine(self, state: dict) -> None:
        """Rebuild the checkpointed host state on THIS engine (freshly
        constructed, same config, nothing submitted). Live requests
        re-enter at the head of the queue exactly as preempted requests
        do; the next ``run()``/``step()`` re-admits and resumes them
        bit-identically."""
        if state.get("version") != _SNAPSHOT_VERSION:
            raise ValueError(
                f"engine snapshot version {state.get('version')!r} != "
                f"{_SNAPSHOT_VERSION} — refusing to restore")
        if self._live or len(self.scheduler) or self._results:
            raise ValueError("restore_engine needs a fresh engine: this "
                             "one already has requests")
        mine, theirs = self._config_sig(), state["config"]
        if mine != theirs:
            diff = {k: (theirs.get(k), mine.get(k))
                    for k in set(mine) | set(theirs)
                    if theirs.get(k) != mine.get(k)}
            raise ValueError(f"engine config mismatch (snapshot vs this "
                             f"engine): {diff}")
        self.step_idx = state["step_idx"]
        self._next_rid = state["next_rid"]
        counters = state["counters"]
        self.n_preemptions = counters["preemptions"]
        self.n_quarantines = counters["quarantines"]
        self.n_faults_contained = counters["faults_contained"]
        self._advanced = counters["advanced"]
        # absent from snapshots written before these counters existed
        self.n_waves = counters.get("waves", 0)
        self.backend.n_prefill_rows_x_len = counters.get(
            "prefill_rows_x_len", 0)
        self.backend.n_prefill_real = counters.get("prefill_real", 0)
        self.backend.n_svd_matrices = counters.get("svd_matrices", 0)
        self.backend.n_svd_solves = counters.get("svd_solves", 0)
        self._meta = {int(rid): _ReqMeta(status=m["status"],
                                         error=m["error"],
                                         retries=m["retries"],
                                         max_retries=m["max_retries"],
                                         deadline=m["deadline"])
                      for rid, m in state["meta"].items()}
        self._results = {int(rid): _de_arr(res)
                         for rid, res in state["results"].items()}
        self._done = {int(rid): v for rid, v in state["done"].items()}
        live = [self._de_req(d) for d in state["live"]]
        front = [self._de_req(d) for d in state["queue"]["front"]]
        arrivals = [self._de_req(d) for d in state["queue"]["arrivals"]]
        for req in live:
            # a live slot resumes the way a preempted request does: at
            # the queue head, QUEUED until re-admission
            self._meta[req.rid].status = QUEUED
        self.scheduler.restore(
            sorted(live + front, key=lambda r: r._order), arrivals)

    # ------------------------------------------------------------------
    # Internals
    # ------------------------------------------------------------------

    def _ensure_state(self) -> None:
        self.backend.ensure_state()

    def _commit(self, emissions: Optional[np.ndarray],
                mask: np.ndarray) -> List[int]:
        """Record this step's emissions and retire finished requests.

        ``mask`` marks slots that advanced one budget unit; ``emissions``
        is per-slot token ids (token backend) or None (pair backend —
        nothing emitted incrementally; the result is fetched from the
        backend when the budget drains)."""
        finished = []
        for slot in [s for s in self._live if mask[s]]:
            st = self._live[slot]
            t = None if emissions is None else int(emissions[slot])
            if t is not None:
                self._results[st.req.rid].append(t)
            st.generated += 1
            self._advanced += 1
            if st.req.on_token is not None:
                # streaming: emitted id for token backends; non-emitting
                # backends drain their per-step output instead
                st.req.on_token(t if t is not None
                                else self.backend.stream_result(slot, st))
            if ((t is not None and t == self.eos_id)
                    or st.generated >= st.req.max_new_tokens):
                res = self.backend.fetch_result(slot, st)
                if res is not None:
                    self._results[st.req.rid] = res
                self._finish(st.req.rid, OK)
                finished.append(st.req.rid)
                del self._live[slot]
                bisect.insort(self._free, slot)
                self.backend.release(slot)
        return finished
