"""Jaxpr-walking rules: what ops may appear inside a jitted serve program.

FlashBias's serve-path wins are *absence* properties — no Θ(pool) relayout
in the decode step (ISSUE 5), no host round-trip inside jit, the Eq. 3
single-matmul fold instead of two matmuls + add — and absence is exactly
what a benchmark can only catch after the regression ships. These rules
assert the properties on the CLOSED JAXPR of each traced program, so a
violating commit fails CI before anyone times anything.

Each rule takes a ``ClosedJaxpr`` (plus calibration arguments) and returns
a list of :class:`Finding`. ``walk_eqns`` descends into every sub-jaxpr
(scan/while/cond bodies, pjit calls, custom-vjp wrappers, pallas_call
bodies), so a violation cannot hide inside the layer scan — which is where
a per-layer pool relayout would live.

Calibration notes (empirically pinned by ``tests/test_statcheck.py``):

- Pool-sized means "at least one full per-layer KV slab": contiguous
  cache leaves enter the layer scan sliced along L (paged pools ride it
  whole, which only makes a relayout of them larger), so the threshold is
  ``min(leaf.size // n_layers)`` over the K/V leaves, not the whole-leaf
  size. Token-batch operands (Θ(B·H·D)) sit orders of magnitude below it.
- The kv-head-major cache layout emits zero banned pool-sized eqns under
  both the XLA and interpret-mode Pallas decode paths for every family.
  ``contracts.verify_tripwire`` keeps the rule honest as a built-in
  negative test: a synthetic layer scan that transposes each layer's
  pool must trip it at the dense family's real threshold.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Iterator, List, Optional

__all__ = [
    "BANNED_RELAYOUT_PRIMITIVES",
    "CALLBACK_PRIMITIVES",
    "Finding",
    "count_primitive",
    "eq3_fold_present",
    "no_host_callback",
    "no_pool_relayout",
    "pool_threshold_for",
    "walk_eqns",
]

# the PR-5 regression tripwire: a transpose / dtype convert / broadcast of
# a pool-sized operand in the decode step is Θ(pool) HBM traffic per token
BANNED_RELAYOUT_PRIMITIVES = ("transpose", "convert_element_type",
                              "broadcast_in_dim")

# host round-trips inside jit: a callback forces a device sync per call
# and disables XLA fusion across it — never legal on the serve hot path
CALLBACK_PRIMITIVES = ("pure_callback", "io_callback", "debug_callback",
                       "callback")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation: which rule, where, and the offending eqn."""

    rule: str            # rule id, e.g. "no-pool-relayout"
    program: str         # traced program, e.g. "dense/decode"
    message: str         # human-readable diagnosis
    eqn: str = ""        # offending equation (primitive + avals), if any

    def __str__(self) -> str:
        loc = f" [{self.eqn}]" if self.eqn else ""
        return f"[{self.rule}] {self.program}: {self.message}{loc}"


def _sub_jaxprs(value) -> Iterator:
    """Yield every jaxpr reachable from one eqn param value.

    Params hold sub-jaxprs in three shapes: a ``ClosedJaxpr`` (scan/pjit),
    a raw ``Jaxpr`` (pallas_call), or a tuple of either (cond branches).
    """
    values = value if isinstance(value, (list, tuple)) else [value]
    for v in values:
        if hasattr(v, "jaxpr"):        # ClosedJaxpr -> unwrap
            v = v.jaxpr
        if hasattr(v, "eqns"):         # Jaxpr
            yield v


def walk_eqns(jaxpr) -> Iterator:
    """Every eqn of ``jaxpr`` and all nested sub-jaxprs, depth-first.

    Accepts a ``ClosedJaxpr`` or a raw ``Jaxpr``.
    """
    if hasattr(jaxpr, "jaxpr"):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in _sub_jaxprs(param):
                yield from walk_eqns(sub)


def _shape_of(var) -> tuple:
    return tuple(getattr(var.aval, "shape", ()))


def _size_of(var) -> int:
    return int(getattr(var.aval, "size", 0))


def _eqn_str(eqn) -> str:
    ins = ",".join(str(_shape_of(v)) for v in eqn.invars)
    outs = ",".join(str(_shape_of(v)) for v in eqn.outvars)
    return f"{eqn.primitive.name} {ins} -> {outs}"


def no_pool_relayout(jaxpr, pool_threshold: int, *,
                     program: str = "decode") -> List[Finding]:
    """ISSUE-5 tripwire: no relayout primitive may consume a pool-sized
    operand inside the decode step.

    ``pool_threshold`` is the size (element count) of the smallest
    per-layer KV slab of the live cache — anything at or above it is pool
    traffic, not token traffic. The kv-head-major caches feed the kernels
    zero-copy, so a good decode jaxpr has no such eqn; a per-layer
    ``transpose`` of a pool or a GQA ``broadcast_in_dim`` of a cache
    (``jnp.repeat`` over the query groups) would cost Θ(pool) every
    decoded token.
    """
    assert pool_threshold > 0, pool_threshold
    findings = []
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name not in BANNED_RELAYOUT_PRIMITIVES:
            continue
        worst = max((_size_of(v) for v in eqn.invars), default=0)
        if worst >= pool_threshold:
            findings.append(Finding(
                rule="no-pool-relayout",
                program=program,
                message=(f"{eqn.primitive.name} consumes a pool-sized "
                         f"operand ({worst} elems >= per-layer KV slab "
                         f"{pool_threshold}) — Θ(pool) relayout per "
                         "decoded token (ISSUE 5 regression)"),
                eqn=_eqn_str(eqn)))
    return findings


def no_host_callback(jaxpr, *, program: str) -> List[Finding]:
    """No ``pure_callback``/``io_callback``/host sync inside a jitted
    serve program: a callback stalls the device once per call and splits
    the program into unfusable halves."""
    findings = []
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name in CALLBACK_PRIMITIVES:
            findings.append(Finding(
                rule="no-host-callback",
                program=program,
                message=(f"{eqn.primitive.name} inside a jitted serve "
                         "program forces a host round-trip per step"),
                eqn=_eqn_str(eqn)))
    return findings


def eq3_fold_present(jaxpr, head_dim: int, rank: int, *,
                     program: str) -> List[Finding]:
    """FlashBias Eq. 3: the precision-free factored-bias path must fold
    ``qk^T + phi_q phi_k^T`` into ONE matmul of depth ``D + R`` by
    concatenating the factors onto q/k (``core.attention
    .flashbias_concat_qk``). The jaxpr signature of the fold is a
    ``concatenate`` whose output feature dim is exactly ``D + R`` — its
    absence means the path regressed to two matmuls + add (or worse, to a
    materialized dense bias)."""
    want = head_dim + rank
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name != "concatenate":
            continue
        shape = _shape_of(eqn.outvars[0])
        if shape and shape[-1] == want:
            return []
    return [Finding(
        rule="eq3-fold",
        program=program,
        message=(f"no concatenate producing feature dim {want} "
                 f"(= head_dim {head_dim} + rank {rank}): the Eq. 3 "
                 "single-matmul QK fold is missing from the precision-"
                 "free factored-bias path"))]


def count_primitive(jaxpr, name: str,
                    min_operand_size: int = 0) -> int:
    """How many eqns of ``name`` (optionally: with an operand at least
    ``min_operand_size`` elements) the program contains — the building
    block for ad-hoc assertions in tests."""
    n = 0
    for eqn in walk_eqns(jaxpr):
        if eqn.primitive.name != name:
            continue
        if max((_size_of(v) for v in eqn.invars),
               default=0) >= min_operand_size:
            n += 1
    return n


def pool_threshold_for(cache: dict, n_layers: int,
                       kv_keys: Iterable[str] = ("k", "v", "pages_k",
                                                 "pages_v"),
                       fallback_keys: Iterable[str] = ("ssm_h", "conv_x",
                                                       "conv_bc"),
                       ) -> Optional[int]:
    """Pool-size threshold for ``no_pool_relayout``, from a live cache.

    KV leaves carry a leading layer axis and enter the decode layer scan
    as per-layer slices, so the threshold is the smallest per-layer K/V
    slab. Families without attention KV (pure SSM) fall back to their
    recurrent-state leaves; returns None when the cache has neither
    (nothing pool-shaped to protect).
    """
    sizes = [int(v.size) // n_layers
             for k, v in cache.items() if k in tuple(kv_keys)]
    if not sizes:
        sizes = [int(v.size) // n_layers
                 for k, v in cache.items() if k in tuple(fallback_keys)]
    return min(sizes) if sizes else None
