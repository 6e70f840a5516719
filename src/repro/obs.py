"""Program spans and counters, on the profiler's clock.

``span(name)`` marks host code at a layer boundary of the program (the
front door's submit, the engine's filter and fold dispatch, the host
planner's coverage checks).  Every span is a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so a profiler
capture (``jax.profiler.trace``, ``start_trace``, or the profiler server)
shows it on the host timeline beside the device's operations, and an
operator can put each idle gap of the device down to the span the host
was in.

While a capture runs, spans and ``count`` calls are also kept in a
bounded in-memory buffer, so that the process can read its own
breakdown without parsing the trace: :func:`recorded` lists the spans,
:func:`summary` totals them per name.  The first span or count seen
after a capture starts clears the buffer, so both describe the latest
capture.  With no capture running nothing is recorded; a span then costs
one ``TraceAnnotation`` and one ``is_enabled()`` call.

Spans wrap synchronous code only: none stays open across an ``await``.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import time

from jax._src import profiler as _jax_profiler
from jax.profiler import TraceAnnotation

__all__ = ["PREFIX", "span", "count", "recorded", "summary"]

PREFIX = "repro."
# Spans kept per capture; the oldest go first past this.
MAX_SPANS = 1 << 16

_parent = contextvars.ContextVar("repro_obs_parent", default=None)
_spans: collections.deque = collections.deque(maxlen=MAX_SPANS)
_counts: collections.Counter = collections.Counter()
_calls: collections.Counter = collections.Counter()
_capture = [None]        # the capture the buffer describes, or None


def _fresh() -> None:
    """Clear the buffer if a capture has started since the last record.

    A capture from ``jax.profiler.start_trace`` is told apart from the
    one before by its session object; one from the profiler server has
    none, and starts a fresh record after any span seen with no capture
    running.
    """
    session = getattr(_jax_profiler._profile_state, "profile_session", None)
    current = session if session is not None else "server"
    if current is not _capture[0]:
        _capture[0] = current
        _spans.clear()
        _counts.clear()
        _calls.clear()


@contextlib.contextmanager
def span(name: str, units: int = 1, **attrs):
    """Mark the enclosed host code as ``repro.<name>``.

    ``units`` is how much work the span covers (views, matrices),
    ``attrs`` go into the trace event.  Yields the record's attribute
    dict while a capture runs, so that the body can add what it learns
    (as ``engine.fold`` adds device bytes at its exit), else ``None``.
    """
    with TraceAnnotation(PREFIX + name, **attrs):
        if not TraceAnnotation.is_enabled():
            _capture[0] = None
            yield None
            return
        _fresh()
        attrs = dict(attrs)
        token = _parent.set(name)
        t0 = time.perf_counter_ns()
        try:
            yield attrs
        finally:
            t1 = time.perf_counter_ns()
            _parent.reset(token)
            _spans.append((name, _parent.get(), t0, t1, units, attrs))


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` while a capture runs."""
    if not TraceAnnotation.is_enabled():
        _capture[0] = None
        return
    _fresh()
    _counts[name] += n
    _calls[name] += 1


def recorded() -> list:
    """The latest capture's spans, in the order they ended, as ``(name,
    parent, t0_ns, t1_ns, units, attrs)``; ``parent`` is the name of the
    span open around it, or ``None``.  Times are
    ``time.perf_counter_ns``."""
    return list(_spans)


def summary() -> dict:
    """Per name, over the latest capture: ``count``, ``units``,
    ``total_s`` and ``self_s`` (the total less the time of the spans
    directly inside).  A counter reads its calls as ``count`` and its
    sum as ``units``, with no time."""
    out: dict = {}
    inner: collections.Counter = collections.Counter()
    for name, parent, t0, t1, units, _ in _spans:
        row = out.setdefault(name, {"count": 0, "units": 0,
                                    "total_s": 0.0, "self_s": 0.0})
        row["count"] += 1
        row["units"] += units
        row["total_s"] += (t1 - t0) * 1e-9
        if parent is not None:
            inner[parent] += (t1 - t0) * 1e-9
    for name, row in out.items():
        row["self_s"] = row["total_s"] - inner[name]
    for name, n in _counts.items():
        out[name] = {"count": _calls[name], "units": n, "total_s": 0.0,
                     "self_s": 0.0}
    return out
