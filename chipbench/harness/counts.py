"""Operations and bytes the back projection needs, and the chip's peaks.

The counts are of the algorithm, Listing 1 of the paper, never of an
implementation: they do not depend on the batch depth, the kernel tiles
or which path ran the work, so a roofline share reads the same for any
path that does the same work.

Flops per voxel update, with the per-line terms of the transform hoisted
as Listing 1 hoists them:

* Part 1, the transform: ``u``, ``v`` and ``w`` each take one multiply
  and one add per voxel (6), one reciprocal of ``w`` (1) and two
  multiplies for ``ix`` and ``iy`` (2): 9.
* Part 2, bilinear interpolation: two fractional parts (2), ``1 - sx``
  and ``1 - sy`` (2), two horizontal lerps of two multiplies and an add
  (6) and one vertical lerp (3): 13.
* Part 3: ``1/w^2`` (1), the weighting (1) and the accumulation (1): 3.

Bytes: each view is read once (``n_v * n_u`` float32) and the volume is
read and written once per volume pass (``2 * L^3`` float32), where a
pass is one scan of a scan cell or one call of a one-shot cell.
"""

from __future__ import annotations

import json
from pathlib import Path

FLOPS_PER_UPDATE = 25
F32 = 4

PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


def updates(L: int, views: int) -> int:
    """Voxel updates: every voxel once per view."""
    return views * L ** 3


def flops(L: int, views: int) -> int:
    return FLOPS_PER_UPDATE * updates(L, views)


def bytes_moved(L: int, n_u: int, n_v: int, views: int,
                volume_passes: int) -> int:
    return F32 * (views * n_u * n_v + 2 * volume_passes * L ** 3)


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def roofline(byte_count: float, seconds: float, peak: dict) -> float:
    """Share (%) of ``seconds`` that moving ``byte_count`` at the peak
    memory bandwidth takes: the memory roofline.  Listing 1's flops run
    on the vector unit, whose float32 rate the chip's maker does not
    publish, so they set no bound here; :func:`intensity` says how many
    flops each byte carries."""
    return 100.0 * byte_count / peak["hbm_bytes_per_s"] / seconds


def intensity(L: int, n_u: int, n_v: int, views: int,
              volume_passes: int) -> float:
    """Listing 1's flops per byte the algorithm moves."""
    return flops(L, views) / bytes_moved(L, n_u, n_v, views, volume_passes)
