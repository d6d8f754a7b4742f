"""Reading the profiler's trace of the measured window.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Device planes are named ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per operation run on the chip and their ``XLA
Modules`` line one event per program execution. Host planes hold the
benchmark's own annotations (``bench.window``, ``bench.step``, ...) and
JAX's dispatch events on the same clock.

Everything here works on plain ``Event`` tuples, so the reduction can be
checked on a small recorded trace and on hand-made events alike:

- ``busy``: the union of operation intervals inside the window, per chip,
  of all programs or of those whose name matches a pattern;
- ``matching``: summed device time of the operations whose name matches a
  pattern (the collectives), or of the Pallas kernels, in the programs
  whose name matches a pattern, clipped to the window;
- ``breakdown``: the operations that took most time, and the longest idle
  gaps, each labelled with what the host was doing in it.

On the TPU the ``XLA Ops`` line names each operation by its HLO text
(``%closed_call.12 = bf16[...] custom-call(...), custom_call_target=
"tpu_custom_call" ...``), and an operation that runs others (a ``while``
of a layer scan, a ``cond``) holds their events inside its own. Only the
instruction name is kept; a Pallas kernel is known by its custom-call
target in that text. Sums over operations that can nest (a program's
share) take the union of intervals, never the sum.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import heapq
import os
import re
import shutil
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# A Pallas kernel is one ``tpu_custom_call`` instruction of its program.
# XLA names it after the innermost name scope around the call (in this
# program ``%closed_call.<n>``), and its HLO text and metadata name the
# custom-call target and the ``pallas_call``.
KERNEL_NAME = re.compile(r"^%?closed_call\b")
KERNEL_STAT = re.compile(r"tpu_custom_call|pallas_call")
CONTROL = re.compile(r"^%?(while|cond|conditional|call)[.\d]*$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter", re.I)


class Event(NamedTuple):
    start: float          # ns
    end: float            # ns
    name: str
    module: str = ""      # device ops: the program they ran in
    kernel: bool = False  # device ops: a Pallas kernel


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    ops: Dict[str, List[Event]]        # device -> operations
    modules: Dict[str, List[Event]]    # device -> program executions
    host: List[Event]                  # host-side spans, all threads
    # (device, program pattern) -> busy intervals, filled by ``busy_spans``
    _busy: Dict = dataclasses.field(default_factory=dict, repr=False)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _clip(ev: Event, lo: float, hi: float) -> float:
    return max(0.0, min(ev.end, hi) - max(ev.start, lo))


def merged(events: List[Event], lo: float, hi: float) -> List[Tuple]:
    """Union of the events' intervals, clipped to [lo, hi], sorted."""
    spans = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                   if e.end > lo and e.start < hi)
    out: List[list] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def _matcher(pattern) -> Callable[[str], bool]:
    """Whether a name matches ``pattern`` (None: every name), decided once
    per distinct name: a trace holds millions of operations but few names
    of programs."""
    if pattern is None:
        return lambda name: True
    rx, seen = re.compile(pattern), {}

    def test(name: str) -> bool:
        hit = seen.get(name)
        if hit is None:
            hit = seen[name] = rx.search(name) is not None
        return hit
    return test


def busy_spans(trace: Trace, device: str, module=None) -> List[Tuple]:
    """The intervals in which some operation ran on ``device`` (with
    ``module``, some operation of a program whose name matches it), inside
    the window; worked out once per trace."""
    key = (device, module)
    if key not in trace._busy:
        keep = _matcher(module)
        trace._busy[key] = merged(
            [e for e in trace.ops[device] if keep(e.module)], *trace.window)
    return trace._busy[key]


def busy_s(trace: Trace, module=None) -> float:
    """Seconds in which some operation ran (with ``module``, some
    operation of a program whose name matches it), averaged over the
    chips."""
    per = [sum(b - a for a, b in busy_spans(trace, dev, module))
           for dev in trace.ops]
    return sum(per) / len(per) * 1e-9 if per else 0.0


def matching_s(trace: Trace, pattern=None, where: str = "ops",
               module=None, kernel: bool = False) -> float:
    """Device seconds of the operations (or programs, ``where="modules"``)
    whose name matches ``pattern``, with ``kernel`` only the Pallas kernels,
    and with ``module`` only those run by a program whose name matches it;
    summed over chips, inside the window. Kernels and collectives never
    hold other operations, so their sum is the time the chip spent in
    them; for operations that can nest, use ``busy_s``."""
    name_ok, mod_ok = _matcher(pattern), _matcher(module)
    lo, hi = trace.window
    table = trace.ops if where == "ops" else trace.modules
    return sum(_clip(e, lo, hi) for evs in table.values() for e in evs
               if (e.kernel or not kernel) and mod_ok(e.module)
               and name_ok(e.name)) * 1e-9


def collective_s(trace: Trace) -> float:
    return matching_s(trace, COLLECTIVE)


def idle_pct(trace: Trace) -> float:
    """Share (%) of the window in which no operation ran, averaged over
    the chips."""
    return 100.0 * (1.0 - busy_s(trace) / trace.window_s)


def _op_kind(ev: Event) -> str:
    """A stable label for an operation: its name without the numeric
    suffix XLA gives each instance, within its program."""
    base = ("pallas_kernel" if ev.kernel else
            re.sub(r"[.\d]+$", "", ev.name.lstrip("%")) or ev.name)
    mod = re.sub(r"[(.]\d+\)?$", "", ev.module)
    return f"{mod}:{base}" if mod else base


def _host_labels(host: List[Event], gaps: List[Tuple]) -> List[str]:
    """What the host was doing over each gap (a, b), the gaps in order and
    disjoint: the shortest host span that covers the gap's midpoint (the
    most specific one). One sweep: spans enter a heap by duration as the
    midpoints pass their start, and leave it once a midpoint passes their
    end."""
    spans = sorted(host, key=lambda e: e.start)
    heap: List[Tuple] = []
    i, out = 0, []
    for a, b in gaps:
        mid = (a + b) / 2
        while i < len(spans) and spans[i].start <= mid:
            heapq.heappush(heap, (spans[i].end - spans[i].start, i))
            i += 1
        while heap and spans[heap[0][1]].end < mid:
            heapq.heappop(heap)
        out.append(spans[heap[0][1]].name if heap else "no host span")
    return out


def leaf(ev: Event) -> bool:
    """Whether an operation holds no other: all but the control flow (a
    loop, a branch, a call), whose event spans the operations it runs."""
    return not CONTROL.match(ev.name)


def breakdown(trace: Trace, top: int = 10) -> dict:
    """``device_ops``: the operation kinds that took most device time,
    counting only operations that hold no other; ``idle_gaps``: idle time
    on the first chip, by what the host was doing, the longest first.
    Seconds, unrounded."""
    lo, hi = trace.window
    per_kind: Dict[str, float] = collections.defaultdict(float)
    kinds: Dict[Tuple, str] = {}          # one label per distinct operation
    for evs in trace.ops.values():
        for e in evs:
            key = (e.name, e.module, e.kernel)
            kind = kinds.get(key)
            if kind is None:
                kind = kinds[key] = _op_kind(e) if leaf(e) else ""
            if kind and e.end > lo and e.start < hi:
                per_kind[kind] += min(e.end, hi) - max(e.start, lo)
    ops = sorted(((k, v * 1e-9) for k, v in per_kind.items()),
                 key=lambda kv: -kv[1])[:top]
    gaps: Dict[str, float] = collections.defaultdict(float)
    if trace.ops:
        spans = busy_spans(trace, sorted(trace.ops)[0])
        edges = [lo] + [x for ab in spans for x in ab] + [hi]
        idle = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for (a, b), label in zip(idle, _host_labels(trace.host, idle)):
            gaps[label] += (b - a) * 1e-9
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}


def _is_kernel(name: str, text: str, ev) -> bool:
    if KERNEL_NAME.search(name) or KERNEL_STAT.search(text):
        return True
    try:
        return any(KERNEL_STAT.search(str(v)) for _, v in ev.stats)
    except Exception:                     # stats of some events are opaque
        return False


def _instruction(text: str) -> str:
    """``%closed_call.12`` of an operation named by its HLO text."""
    i = text.find(" = ")
    return text[:i] if i >= 0 else text


def _device_plane(plane) -> Tuple[List[Event], List[Event]]:
    """A chip's operations and program executions. Each operation is
    given the program whose execution holds its start; whether it is a
    Pallas kernel is read once per (program, operation name)."""
    lines = {line.name: list(line.events) for line in plane.lines}
    mods = sorted((Event(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in lines.get(MODULES_LINE, [])),
                  key=lambda e: e.start)
    starts = [m.start for m in mods]
    kinds: Dict[Tuple[str, str], bool] = {}
    ops = []
    for e in lines.get(OPS_LINE, []):
        start, text = e.start_ns, e.name
        name = _instruction(text)
        i = bisect.bisect_right(starts, start) - 1
        mod = mods[i].name if i >= 0 and mods[i].end >= start else ""
        key = (mod, name)
        if key not in kinds:
            kinds[key] = _is_kernel(name, text, e)
        ops.append(Event(start, start + e.duration_ns, name, mod,
                         kinds[key]))
    return ops, mods


def load(path: str, keep: Optional[str] = None) -> Trace:
    """Read the ``.xplane.pb`` at ``path``, or the newest one under the
    directory ``path``; with ``keep``, copy the file there first."""
    from jax.profiler import ProfileData
    paths = ([path] if os.path.isfile(path) else sorted(
        glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    if keep:
        shutil.copyfile(paths[-1], keep)
    data = ProfileData.from_file(paths[-1])
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops[plane.name], modules[plane.name] = _device_plane(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Event(e.start_ns, e.start_ns + e.duration_ns,
                               e.name) for e in line.events
                         if e.duration_ns > 0]
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    win = spans[-1]
    return Trace((win.start, win.end), ops, modules,
                 [e for e in host if e.name != WINDOW_SPAN])
