"""Profiler spans at the program's layer boundaries (DESIGN.md §16).

``span(name, batch=None)`` is a ``jax.profiler.TraceAnnotation`` while
a profiler trace is being taken, and one shared no-op context manager
at all other times: off, a span costs the profiler's own enabled check
and nothing is built or formatted (``batch`` is a plain parameter, so
no keyword dict is made either).  The spans land on the profiler's host
plane, on the same clock as the device ops, and nest on the host
thread; a batch number becomes the stat ``batch`` of the event, whose
name stays bare.  There is no switch and no sink of our own: the
profiler's trace is the one sink.

An operator sees them by profiling a live server, e.g.
``with jax.profiler.trace("/tmp/prof"): <serve for a few seconds>``,
and opening the trace in TensorBoard or Perfetto.
"""

from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

__all__ = ["SPANS", "span"]

# every span the program emits, one per batch or call, never per request
SPANS = (
    "fe.form", "fe.dispatch", "fe.gather", "fe.resolve",
    "nfl.lookup", "nfl.scan", "nfl.insert", "nfl.features",
    "afli.point.enqueue", "afli.point.wait", "afli.scan.wait",
    "afli.insert.delta", "afli.tier_sync", "afli.run_merge",
    "afli.fold_tick",
)

_enabled = TraceAnnotation.is_enabled
_OFF = contextlib.nullcontext()


def span(name: str, batch: int | None = None):
    """A host span named ``name`` (tagged with the front end's ``batch``
    number, if given) while a trace is being taken."""
    if _enabled():
        if batch is None:
            return TraceAnnotation(name)
        return TraceAnnotation(name, batch=batch)
    return _OFF
