"""The program's own host spans (``serve.*``) in the traced window.

The serve engine and its backends emit them (``repro.serve.trace``); the
trace keeps each one's name and times. A build of the program that emits
none gives empty lists here, and the readers built on them report
nothing.
"""
from __future__ import annotations

import bisect
from typing import List

from bench import trace as tr


def named(trace: tr.Trace, name: str) -> List[tr.Event]:
    """Every span called ``name``, in order of start."""
    return sorted((e for e in trace.host if e.name == name),
                  key=lambda e: e.start)


def ending(trace: tr.Trace, name: str) -> List[tr.Event]:
    """Spans called ``name`` that end inside the window."""
    lo, hi = trace.window
    return [e for e in named(trace, name) if lo <= e.end <= hi]


def covered_ns(outer: tr.Event, inner: List[tr.Event]) -> float:
    """Nanoseconds of ``outer`` that the spans of ``inner`` (sorted by
    start) cover: the union of those that start inside it, clipped."""
    starts = [e.start for e in inner]
    i = bisect.bisect_left(starts, outer.start)
    j = bisect.bisect_right(starts, outer.end)
    return sum(b - a for a, b in tr.merged(inner[i:j], outer.start,
                                           outer.end))
