"""The back projection's share of the mesh's memory roofline, in percent.

The algorithm's bytes over the window (``harness/counts.py``: each view
read once, the volume read and written once per scan), unchanged, at
the chips' summed peak bandwidth (``chips x hbm_bytes_per_s``), over
one chip's back projection time (the mean over the chips).  Every chip
reads every view: that replicated read is a cost of the split, not work
of the algorithm, so it is not counted.
"""

from harness import counts


def read(run):
    if run.trace is None or run.peak is None:
        return None
    seconds = run.trace["layer_s"].get("backprojection", 0.0)
    if seconds <= 0:
        return None
    a, w = run.acq, run.work
    moved = counts.bytes_moved(a.L, a.n_u, a.n_v, w["views"], w["passes"])
    return counts.roofline(moved / run.cell.chips, seconds, run.peak)
