"""Per-view host milliseconds from the program's own spans.

The program records its spans (``repro.obs``) while a profiler capture
runs, and ``repro.obs.summary()`` totals the latest capture's per name:
after a traced window, the window's.  The readers of the host layers of
the streamed path divide one of those totals by the views the window
submitted.

A reading is ``None`` where the program has no ``repro.obs``, and where
the record does not hold this window's submits: its ``frontdoor.submit``
spans must be as many as the benchmark's own ``submit`` spans, one
around each call of ``CTFrontDoor.submit``.  A window that submitted
nothing through the front door, or a record left by an earlier capture
in the same process, reads ``None``.
"""

from __future__ import annotations

ROOT = "frontdoor.submit"


def per_view_ms(run, name: str, field: str = "total_s") -> float | None:
    """``field`` (``total_s`` or ``self_s``) of the program's spans
    ``name`` in milliseconds per view submitted; 0 where the window
    recorded none of them."""
    try:
        from repro import obs
    except ImportError:
        return None
    spans = obs.summary()
    submits = len(run.spans.by_name.get("submit", ()))
    views = run.work.get("views")
    if not submits or not views \
            or spans.get(ROOT, {}).get("count") != submits:
        return None
    return 1e3 * spans.get(name, {}).get(field, 0.0) / views
