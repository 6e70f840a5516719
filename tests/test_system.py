"""End-to-end system tests: the full CT pipeline, full and short scans."""

import dataclasses
import math

from repro.core import (Geometry, filter_projections, quality_report,
                        reconstruct)
from repro.core.phantom import make_dataset


def test_ct_pipeline_end_to_end():
    """Scan -> filter -> back-project -> quality, in density units."""
    geom = dataclasses.replace(Geometry().scaled(32, n_proj=48),
                               sweep=2 * math.pi)
    projs, mats, ref = make_dataset(geom)
    filt = filter_projections(projs, geom)
    vol = reconstruct(filt, mats, geom, strategy="gather")
    q = quality_report(vol, ref)
    # Absolute levels reconstruct: interior density ~0.2-1.0 region
    assert q["psnr_roi_db"] > 14.0, q
    centre = float(vol[16, 16, 16])
    assert abs(centre - ref[16, 16, 16]) < 0.25


def test_short_scan_parker_weights_match_full_scan():
    g_short = Geometry().scaled(24, n_proj=48)            # 200 degrees
    g_full = dataclasses.replace(g_short, sweep=2 * math.pi)
    out = {}
    for name, g in (("short", g_short), ("full", g_full)):
        projs, mats, ref = make_dataset(g)
        filt = filter_projections(projs, g)
        vol = reconstruct(filt, mats, g, strategy="gather")
        out[name] = quality_report(vol, ref)["psnr_roi_db"]
    # Parker-weighted short scan within ~4 dB of the full scan.
    assert out["short"] > out["full"] - 4.0, out
