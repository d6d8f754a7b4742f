"""Host spans of the serve path, on the profiler's clock.

``span(name, **attrs)`` is ``jax.profiler.TraceAnnotation``: while a
profiler trace runs (``jax.profiler.trace`` / ``start_trace``) each span is
one event on the host plane, on the clock the device planes share, and its
attributes come back as the event's stats. With no trace running a span
costs about a microsecond and records nothing.

Attributes known only inside a span are added with ``set_metadata``;
those that take work to build (a string of request ids) are built only
while ``tracing()`` is true. Values must not hold ``,`` ``=`` or ``#``,
which the event encoding reserves.

The engine's spans (``serve.step``, ``serve.admit``, ``serve.commit``,
``serve.grow``, ``serve.preempt``) and the backends' (``serve.prefill``,
``serve.chunk``, ``serve.decode``, ``serve.sample``, ``serve.fetch``) are
listed with their attributes in ``serve/README.md`` (Tracing).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["span", "tracing"]


def span(name: str, **attrs) -> TraceAnnotation:
    """A host span named ``name`` with ``attrs`` as its stats."""
    return TraceAnnotation(name, **attrs)


def tracing() -> bool:
    """Whether a profiler trace is recording host spans now."""
    return TraceAnnotation.is_enabled()
