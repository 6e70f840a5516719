"""The back projection's share of its memory roofline, in percent.

The time the chip's peak memory bandwidth (``peaks.json`` by device
kind) needs for the bytes the algorithm moves in the window (each view
read once, the volume read and written once per scan or call;
``harness/counts.py``), over the back projection's device time in the
trace.  Listing 1's 25 float32 flops per update run on the vector unit,
whose rate is not published, so no flop bound is priced; the flops per
byte are logged beside the share.
"""

import sys

from harness import counts


def read(run):
    if run.trace is None or run.peak is None:
        return None
    seconds = run.trace["layer_s"].get("backprojection", 0.0)
    if seconds <= 0:
        return None
    a, w = run.acq, run.work
    args = (a.L, a.n_u, a.n_v, w["views"], w["passes"])
    print(f"bp_roofline: {counts.intensity(*args)!r} flops per byte",
          file=sys.stderr)
    return counts.roofline(counts.bytes_moved(*args), seconds, run.peak)
