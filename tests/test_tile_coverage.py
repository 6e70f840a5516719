"""The host planner's per-tile coverage bound, from a tile's corners.

``validate_strip_config`` takes the ``(band, width)`` a kernel tile
needs from the tile's four corner voxels (``tile_coverage_need``), the
rule the kernel places its window by, and refuses a projection that
puts a voxel at or behind the source plane.  Held here to the exact
per-line strip plan it replaced, to a brute-force pass over every
voxel's float32 taps, to the interpret-mode kernel at the smallest
config it accepts, and to its refusal.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backproject import GeomStatic
from repro.core.clipping import plan_strips
from repro.core.geometry import Geometry, projection_matrices
from repro.kernels.backproject import _EPS_W, strip_window
from repro.kernels.backproject_ops import (_padded_dims,
                                           pallas_backproject_batch,
                                           tile_corner_extents,
                                           tile_coverage_need,
                                           validate_strip_config)

from _col_blocks import CASES, Case, assert_matches_oracle

RABBITCT_128 = Geometry(L=128, voxel_mm=2.0)
RABBITCT_256 = Geometry(L=256, voxel_mm=1.0)


def _line_plan_need(geom: Geometry, A: np.ndarray, *, ty: int,
                    chunk: int) -> tuple[int, int]:
    """The ``(band, width)`` the check asked before the corner bound:
    the exact per-line strip plan (monotone-beam property), adjacent
    lines' strips merged by min/max origins over a tile's ``ty``
    lines."""
    plan = plan_strips(geom, A, chunk=chunk)
    L = geom.L
    g = plan.r0.astype(np.int64).reshape(L, L // ty, ty, -1)
    gc = plan.c0.astype(np.int64).reshape(L, L // ty, ty, -1)
    span_r = g.max(axis=2) - g.min(axis=2) + plan.required_band
    span_c = gc.max(axis=2) - gc.min(axis=2) + plan.required_width
    return int(span_r.max()), int(span_c.max())


def _mats(geom: Geometry, step: int = 1) -> np.ndarray:
    """Every ``step``-th view's matrix, in float32 as the kernel takes
    it."""
    return np.asarray(projection_matrices(geom), np.float32)[::step]


@pytest.mark.parametrize("geom,ty,chunk,step", [
    (Geometry().scaled(16), 8, 16, 1),
    (Geometry().scaled(32), 4, 16, 1),
    (Geometry().scaled(32), 8, 32, 1),
    (Geometry().scaled(64), 8, 64, 1),
    (RABBITCT_128, 8, 128, 31),
    (RABBITCT_256, 8, 128, 62),
], ids=["L16", "L32-t4x16", "L32-t8x32", "L64", "rabbitct128",
        "rabbitct256"])
def test_corner_need_within_line_need(geom, ty, chunk, step):
    """Over a sweep's matrices the corner bound asks no more than the
    exact per-line plan it replaced: no config accepted for the sweep
    before is refused.  (One matrix alone may ask more: below.)"""
    gs = GeomStatic.of(geom)
    needs = np.array([(tile_coverage_need(gs, A, ty=ty, chunk=chunk),
                       _line_plan_need(geom, A, ty=ty, chunk=chunk))
                      for A in _mats(geom, step).astype(np.float64)])
    corner, line = needs.max(axis=0)
    assert (corner <= line).all(), (corner, line)


def _part1(gs: GeomStatic, A: np.ndarray):
    """The kernel's Part 1 in float32 for every voxel, ``[z, y, x]``."""
    f32 = np.float32
    c = f32(gs.O) + np.arange(gs.L, dtype=f32) * f32(gs.MM)
    wz, wy, wx = c[:, None, None], c[None, :, None], c[None, None, :]
    u, v, w = (wx * a[0] + wy * a[1] + wz * a[2] + a[3] for a in A)
    with np.errstate(divide="ignore"):
        r = np.where(w > _EPS_W, f32(1) / w, f32(0))
    return u * r, v * r, w


def _corners32(gs: GeomStatic, A, tiles):
    """The kernel's float32 corner floors per tile: first padded row and
    column its window is placed from."""
    ext = [np.concatenate(parts) for parts in zip(*tile_corner_extents(
        gs, A, ty=tiles["ty"], chunk=tiles["chunk"], dtype=np.float32))]
    return (np.floor(np.clip(ext[2], -1, gs.n_v)),
            np.floor(np.clip(ext[0], -1, gs.n_u)))


def _taps_outside(gs: GeomStatic, A, tiles, r0, c0, rows, cols) -> int:
    """Brute force over every voxel: the float32 taps on detector data,
    in the tiles the kernel finds active, that fall outside each tile's
    padded rows ``[r0, r0 + rows)`` and columns ``[c0, c0 + cols)``."""
    ty, chunk = tiles["ty"], tiles["chunk"]
    ix, iy, w = (a.reshape(gs.L, gs.L // ty, ty, gs.L // chunk, chunk)
                 for a in _part1(gs, A))
    active = ((ix.min(axis=(2, 4)) < gs.n_u) & (ix.max(axis=(2, 4)) > -1)
              & (iy.min(axis=(2, 4)) < gs.n_v) & (iy.max(axis=(2, 4)) > -1)
              & (w.max(axis=(2, 4)) > _EPS_W))
    tile = (slice(None), slice(None), None, slice(None), None)
    active, r0, c0 = active[tile], r0[tile], c0[tile]
    out = 0
    for row in (np.floor(iy) + 1, np.floor(iy) + 2):
        for col in (np.floor(ix) + 1, np.floor(ix) + 2):
            on = (active & (w > _EPS_W) & (row >= 1) & (row <= gs.n_v)
                  & (col >= 1) & (col <= gs.n_u))
            out += int((on & ((row < r0) | (row >= r0 + rows) | (col < c0)
                              | (col >= c0 + cols))).sum())
    return out


BRUTE = {
    "L16": (Geometry().scaled(16), dict(ty=8, chunk=16), 1),
    "L32": (Geometry().scaled(32), dict(ty=4, chunk=16), 3),
    "L32-t8x32": (Geometry().scaled(32), dict(ty=8, chunk=32), 3),
    "seam": (CASES["seam"].geom, dict(ty=8, chunk=8), 1),
    "wide": (CASES["wide"].geom, dict(ty=8, chunk=16), 1),
}


@pytest.mark.parametrize("name", sorted(BRUTE))
def test_every_float32_tap_inside_the_proved_footprint(name):
    """In each tile the kernel finds active, every float32 tap on
    detector data lies in the ``need_band`` rows and ``need_width``
    columns from the floor of the kernel's own corner minimum, where its
    window starts — without the spare row the window's tile rounding
    adds."""
    geom, tiles, step = BRUTE[name]
    gs = GeomStatic.of(geom)
    for A in _mats(geom, step):
        band, width = tile_coverage_need(gs, A.astype(np.float64), **tiles)
        r0, c0 = _corners32(gs, A, tiles)
        assert _taps_outside(gs, A, tiles, r0, c0, band, width) == 0


def test_line_need_missed_taps_the_corner_need_keeps():
    """The line plan's need is not what the kernel needs: it bounds each
    voxel line's clipped range, while the kernel places its window from
    the tile's corners, off-detector voxels included.  At RabbitCT 128³,
    view 124, the line plan accepted ``band=112``, where the kernel's
    window misses taps on detector data; the corner bound asks 115 and
    that window holds them all."""
    gs, tiles = GeomStatic.of(RABBITCT_128), dict(ty=8, chunk=128)
    A = _mats(RABBITCT_128)[124]
    line = _line_plan_need(RABBITCT_128, A.astype(np.float64), **tiles)
    corner = tile_coverage_need(gs, A.astype(np.float64), **tiles)
    assert corner[0] > line[0]
    r_lo, c_lo = _corners32(gs, A, tiles)

    def outside(band, width):
        # The kernel's window: origin clamped into the padded image and
        # aligned down to the (8, 128) tile, strip_window's dims.
        wband, wwidth = strip_window(band, width, 4)
        rows, cols = _padded_dims(gs.n_v, gs.n_u, band, width, 4)
        r0 = np.clip(r_lo, 0, rows - wband) // 8 * 8
        c0 = np.clip(c_lo, 0, cols - wwidth) // 128 * 128
        return _taps_outside(gs, A, tiles, r0, c0, wband, wwidth)

    width = max(line[1], corner[1])
    assert outside(line[0], width) > 0
    assert outside(corner[0], width) == 0
    with pytest.raises(ValueError, match="does not cover"):
        validate_strip_config(RABBITCT_128, A, band=line[0], width=width,
                              **tiles)


@pytest.mark.parametrize("case", [
    Case(Geometry().scaled(16, n_proj=8), (0.3, 1.7, 3.0),
         dict(ty=8, chunk=16)),
    Case(Geometry().scaled(32, n_proj=8), (0.9, 2.4),
         dict(ty=4, chunk=32)),
], ids=["L16", "L32"])
def test_kernel_at_the_smallest_accepted_config(case):
    """The interpret-mode batch kernel at the least ``(band, width)`` the
    check accepts for the stack matches the oracle on noise images, and
    one row or column less is refused."""
    mats = case.mats()
    needs = np.array([tile_coverage_need(case.gs, A.astype(np.float64),
                                         **case.tiles) for A in mats])
    band, width = (int(n) for n in needs.max(axis=0))
    for k, small in ((needs[:, 0].argmax(), dict(band=band - 1,
                                                 width=width)),
                     (needs[:, 1].argmax(), dict(band=band,
                                                 width=width - 1))):
        with pytest.raises(ValueError, match="does not cover"):
            validate_strip_config(case.geom, mats[k], **small,
                                  **case.tiles)
    images = case.images(seed=3)
    vol0 = jnp.zeros((case.geom.L,) * 3, jnp.float32)
    out = pallas_backproject_batch(vol0, images, mats, case.geom, pbatch=2,
                                   band=band, width=width, **case.tiles)
    assert_matches_oracle(out, dataclasses.replace(
        case, tiles=dict(case.tiles, band=band, width=width)),
        images, mats, "float32")


def test_source_inside_the_volume_is_refused():
    """A source within the volume's reach puts voxels behind it: no tile
    across the source plane has a corner bound, so the check refuses
    every such matrix, even at the whole padded detector, and still
    accepts the same scan from RabbitCT's distance."""
    near = dataclasses.replace(Geometry().scaled(16, n_proj=6), sid=100.0)
    far = Geometry().scaled(16, n_proj=6)
    tiles = dict(ty=8, chunk=16)
    band, width = near.n_v + 2, near.n_u + 2
    for A in _mats(near):
        with pytest.raises(ValueError, match="behind the source"):
            validate_strip_config(near, A, band=band, width=width, **tiles)
    for A in _mats(far):
        validate_strip_config(far, A, band=band, width=width, **tiles)
