"""Host spans, compile clocks, and the reduction of a device trace.

Spans are the benchmark's own, around its calls into the program; each
is also a ``jax.profiler.TraceAnnotation`` so that a traced run puts it
on the same clock as the device's operations.

The trace reduction runs in two steps.  :func:`device_events` reads the
profiler's ``.xplane.pb`` and keeps, for each TPU, the operations of its
``XLA Ops`` line as ``(module, op, start_ns, duration_ns)`` (the module
from the event's ``hlo_module`` stat, else from the ``XLA Modules`` run
that covers it), and the host's annotations as ``(name, start_ns, duration_ns)``.  :func:`reduce`
turns those lists into numbers: busy time as the union of operation
intervals, device seconds per layer by name patterns, the operations
that took most time, and the device's idle time by what the host was
doing meanwhile.
"""

from __future__ import annotations

import collections
import re
import time
from contextlib import contextmanager

SPAN_PREFIX = "chipbench."


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading from
    the persistent cache), and how many programs it compiled or loaded,
    since the last :meth:`take`."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")
    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self._jax = jax
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self._EVENTS:
            self.seconds += duration
        if event == self._BACKEND:
            self.programs += 1

    def take(self) -> tuple[float, int]:
        out = (self.seconds, self.programs)
        self.seconds, self.programs = 0.0, 0
        return out

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


class Spans:
    """Host-clock spans by name: ``(seconds, units)`` per occurrence."""

    def __init__(self):
        self.by_name: dict[str, list[tuple[float, int]]] = \
            collections.defaultdict(list)

    @contextmanager
    def span(self, name: str, units: int = 1):
        import jax

        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            t = time.perf_counter()
            try:
                yield
            finally:
                self.by_name[name].append((time.perf_counter() - t, units))

    def mean_ms_per_unit(self, name: str) -> float | None:
        rows = self.by_name.get(name)
        if not rows:
            return None
        units = sum(u for _, u in rows)
        return 1e3 * sum(s for s, _ in rows) / units if units else None


# ----------------------------------------------------------------------
# Trace reduction
# ----------------------------------------------------------------------

def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def device_events(path: str):
    """``(per_device, host)`` from an ``.xplane.pb`` file: ``per_device``
    maps each TPU plane's name to its ``XLA Ops`` events ``(module, op,
    start_ns, dur_ns)``; ``host`` lists this benchmark's annotations
    ``(name, start_ns, dur_ns)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    per_device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            rows, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(float(e.start_ns), float(e.duration_ns),
                                 e.name) for e in line.events]
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    module = _stat(e, "hlo_module") or ""
                    rows.append((str(module), e.name, float(e.start_ns),
                                 float(e.duration_ns)))
            rows = [(m or module_at(s, modules), op, s, d)
                    for m, op, s, d in rows]
            if rows:
                per_device[plane.name] = rows
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append((e.name[len(SPAN_PREFIX):],
                                     float(e.start_ns),
                                     float(e.duration_ns)))
    return per_device, host


def module_at(t: float, modules) -> str:
    """Name of the module run ``(start_ns, dur_ns, name)`` that covers
    time ``t``, or ``""``: the program of an operation whose event names
    none."""
    for s, d, name in modules:
        if s <= t <= s + d:
            return str(name)
    return ""


def union(intervals) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals, sorted."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _host_activity(times, host) -> list[str]:
    """For each time in ``times``, the innermost benchmark annotation
    that covers it."""
    import numpy as np

    if not host:
        return ["outside benchmark spans"] * len(times)
    names = [h[0] for h in host]
    start = np.array([h[1] for h in host])
    dur = np.array([h[2] for h in host])
    out = []
    for t in times:
        cover = np.where((start <= t) & (t <= start + dur), dur, np.inf)
        i = int(np.argmin(cover))
        out.append(names[i] if np.isfinite(cover[i])
                   else "outside benchmark spans")
    return out


def reduce(events, host, layers: dict[str, str], top: int = 10) -> dict:
    """Numbers from one device's ``events`` and the ``host`` annotations.

    ``layers`` maps a layer name to a regular expression searched in
    ``"<module>/<op>"``; an operation counts for each layer it matches.
    Returns ``busy_s`` (union of operation intervals), ``layer_s`` (union
    of the matched operations' intervals per layer, so that an operation
    nested in another, as a loop's body in its ``while``, counts once),
    ``device_ops`` (the ``top`` operations by
    summed time, as ``[name, seconds]``) and ``idle_gaps`` (device idle
    time between operations, summed by the host activity at each gap's
    middle, largest first).
    """
    if not events:
        return {}
    pats = {k: re.compile(v) for k, v in layers.items()}
    spans_of = {k: [] for k in layers}
    by_op = collections.Counter()
    for module, op, s, d in events:
        key = f"{module}/{op}"
        by_op[key] += d
        for k, p in pats.items():
            if p.search(key):
                spans_of[k].append((s, s + d))
    layer_s = {k: 1e-9 * sum(e - s for s, e in union(v))
               for k, v in spans_of.items()}
    merged = union((s, s + d) for _, _, s, d in events)
    busy = sum(e - s for s, e in merged)
    gaps = collections.Counter()
    spans = [(e0, s1) for (_, e0), (s1, _) in zip(merged, merged[1:])]
    labels = _host_activity([(a + b) / 2 for a, b in spans], host)
    for (a, b), label in zip(spans, labels):
        gaps[label] += b - a
    return {
        "busy_s": busy * 1e-9,
        "layer_s": layer_s,
        "device_ops": [[k, v * 1e-9] for k, v in by_op.most_common(top)],
        "idle_gaps": [[k, v * 1e-9] for k, v in gaps.most_common(top)],
    }
