"""The load loop: a closed loop of requests through one serve session (each
of ``clients`` callers sends its next request when its last one ends), a
ramp, then the measured window.

A session (``bench/families/<family>.py``) offers ``submit(spec, rec)``,
``step()`` (one engine step, returning the request ids that ended),
``status(rid)``, ``work(ended)``, ``occupancy`` and ``idle()``. The loop
keeps, per request, a ``Record`` of its submit, token and finish
times on the host clock, and per engine step the time it ended, the slots
live after it and the work the session says it did. Metrics are read from
these records afterwards; nothing here decides what a metric is.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import List, Optional

from bench import traffic


@dataclasses.dataclass
class Record:
    spec: dict
    rid: int = -1
    submitted: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    status: Optional[str] = None


@dataclasses.dataclass
class Step:
    end: float
    live: int                     # slots live after the step
    work: dict                    # what the session says the step did


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    records: List[Record]
    steps: List[Step]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def inside(self, t: Optional[float]) -> bool:
        return t is not None and self.t0 <= t <= self.t1

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.inside(s.end)]

    # -- token sessions: every request's committed-token times ----------
    def tokens(self) -> int:
        return sum(self.inside(t) for r in self.records for t in r.times)

    def gaps(self) -> List[float]:
        """Every gap between consecutive tokens of a request, both tokens
        inside the window."""
        return [b - a for r in self.records for a, b in
                zip(r.times, r.times[1:]) if self.inside(a)
                and self.inside(b)]

    def decode_contexts(self) -> List[int]:
        """For every token decoded in the window (all but a request's
        first, which its prefill produces), the keys its step attended:
        the prompt and every token before it."""
        return [r.spec["prompt"] + i for r in self.records
                for i, t in enumerate(r.times) if i and self.inside(t)]

    def admitted_prompts(self) -> List[int]:
        """Prompt lengths of the requests prefilled in the window."""
        return [r.spec["prompt"] for r in self.records
                if r.times and self.inside(r.times[0])]

    # -- step sessions: the work each engine step did --------------------
    def work(self, key: str) -> List[int]:
        return [n for s in self.window_steps() for n in s.work.get(key, ())]


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def drive(session, mix: dict, seed: int, seconds: float,
          on_open=None, on_close=None) -> Window:
    """Serve the mix: ``ramp_steps`` engine steps, then ``seconds`` of
    measured window. ``on_open``/``on_close`` run at the window's edges
    (the profiler)."""
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    stream = itertools.cycle(traffic.requests(mix, seed))
    records: List[Record] = []
    by_rid = {}
    steps: List[Step] = []

    def send() -> None:
        rec = Record(dict(next(stream)))
        with _annotate("bench.submit"):
            rec.submitted = time.perf_counter()
            rec.rid = session.submit(rec.spec, rec)
        records.append(rec)
        by_rid[rec.rid] = rec

    def step() -> List[Record]:
        with _annotate("bench.step"):
            ended = session.step()
        t = time.perf_counter()
        done = [by_rid[r] for r in ended]
        for rec in done:
            rec.finished = t
            rec.status = session.status(rec.rid)
        steps.append(Step(t, session.occupancy, session.work(ended)))
        return done

    for _ in range(mix["clients"]):
        send()
    for _ in range(mix.get("ramp_steps", 0)):
        for _ in step():
            send()
    if on_open:
        on_open()
    t0 = time.perf_counter()
    while time.perf_counter() < t0 + seconds:
        for _ in step():
            send()
    t1 = time.perf_counter()
    if on_close:
        on_close()
    return Window(t0, t1, records, steps)
