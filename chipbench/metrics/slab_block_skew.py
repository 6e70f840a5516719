"""How unevenly the z-slabs share the back projection's MXU work.

The program counts, while a capture runs, the 128-column blocks its
kernel contracts over the whole cube (``kernel.col_blocks``) and, for
each fold's stack, those of the slab with the most
(``mesh.col_blocks_max_slab``), both by the host's copy of the
kernel's block rule.  The skew is ``chips x max / total`` over the
window: 1.0 when every chip contracts the same, more when the fold
waits on the busiest chip.  ``None`` where either count is 0 or
missing, or where the record is not this window's (as in
``harness/program_spans.py``).
"""


def read(run):
    try:
        from repro import obs
    except ImportError:
        return None
    s = obs.summary()
    submits = len(run.spans.by_name.get("submit", ()))
    if not submits or s.get("frontdoor.submit", {}).get("count") != submits:
        return None
    total = s.get("kernel.col_blocks", {}).get("units", 0)
    most = s.get("mesh.col_blocks_max_slab", {}).get("units", 0)
    if not total or not most:
        return None
    return run.cell.chips * most / total
