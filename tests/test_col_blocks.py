"""The back projection kernel's column-block rule, on the host.

The kernel contracts only the 128-column blocks of its DMA window that
can hold one of a tile's taps (``_col_blocks`` of the corner rule in
``repro.kernels.backproject``).  ``tile_col_blocks`` is the host's copy
of that rule.  Here it is held to the planner's per-line footprint at
RabbitCT sizes, and its counters to what a traced call records.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.backproject import GeomStatic
from repro.core.clipping import _line_coeffs, line_clip_exact, plan_strips
from repro.core.geometry import Geometry, projection_matrices
from repro.kernels import backproject_ops
from repro.kernels.backproject import strip_window
from repro.kernels.backproject_ops import (pallas_backproject_batch,
                                           pallas_backproject_one,
                                           tile_col_blocks)

from _col_blocks import CASES

# The scan cells' tiles (chipbench/configs): ty=8, chunk=128.
RABBITCT = {512: (Geometry(), dict(ty=8, chunk=128, band=192, width=1152)),
            256: (Geometry(L=256, voxel_mm=1.0),
                  dict(ty=8, chunk=128, band=232, width=1280))}


def _line_taps(geom: Geometry, A: np.ndarray, chunk: int):
    """The planner's per-line footprint: for each ``[z, y, chunk]`` line,
    whether it is active and its first and last padded tap column, from
    the endpoints of the chunk's exact clip range, as ``plan_strips``
    takes them."""
    clip = line_clip_exact(geom, A)
    (pu, _, pw), (qu, _, qw) = _line_coeffs(geom, A)
    xs = np.arange(geom.L // chunk) * chunk
    x0 = clip.x0[..., None].astype(np.float64)
    x1 = clip.x1[..., None].astype(np.float64)
    xa = np.maximum(xs, x0)
    xb = np.maximum(np.minimum(xs + chunk - 1, x1 - 1.0), xa)
    ends = [np.clip((pu[..., None] + qu * x) / (pw[..., None] + qw * x),
                    -1.0, geom.n_u) for x in (xa, xb)]
    active = np.minimum(x1, xs + chunk) > np.maximum(x0, xs)
    first = np.floor(np.minimum(*ends)).astype(np.int64) + 1
    last = np.floor(np.maximum(*ends)).astype(np.int64) + 2
    return active, first, last


def block_counts(gs, A, tiles):
    """Blocks contracted over the active tiles, and the window's."""
    active, _, kb_lo, kb_hi = tile_col_blocks(gs, A, **tiles)
    nb = strip_window(tiles["band"], tiles["width"], 4)[1] // 128
    return int((kb_hi - kb_lo + 1)[active].sum()), int(active.sum()) * nb


@pytest.mark.parametrize("L", sorted(RABBITCT))
def test_block_rule_covers_the_planners_footprint(L):
    """At RabbitCT sizes, for sampled angles: every tap on detector data
    of every active line of the planner's footprint lies in an active
    tile, inside the blocks the kernel contracts there, and the blocks
    contracted are fewer than the window's."""
    geom, tiles = RABBITCT[L]
    gs = GeomStatic.of(geom)
    ty = tiles["ty"]
    mats = np.asarray(projection_matrices(geom), np.float32)
    contracted = window = 0
    for k in range(0, geom.n_proj, 83):
        A = mats[k].astype(np.float64)
        active, first, last = _line_taps(geom, A, tiles["chunk"])
        # The footprint read here is the planner's: its first column is
        # plan_strips' origin wherever the origin is not clamped.
        plan = plan_strips(geom, A, chunk=tiles["chunk"], width=1)
        free = active & (first >= 1)
        np.testing.assert_array_equal(plan.c0[free], first[free] - 1)

        t_act, c0, kb_lo, kb_hi = tile_col_blocks(gs, A, **tiles)
        per_line = [np.repeat(a, ty, axis=1) for a in (t_act, c0, kb_lo,
                                                       kb_hi)]
        t_act, c0, kb_lo, kb_hi = per_line
        lo, hi = np.maximum(first, 1), np.minimum(last, geom.n_u)
        taps = active & (lo <= hi)
        assert t_act[taps].all()
        assert (lo[taps] >= (c0 + 128 * kb_lo)[taps]).all()
        assert (hi[taps] <= (c0 + 128 * kb_hi + 127)[taps]).all()

        blocks, nb = block_counts(gs, A, tiles)
        contracted += blocks
        window += nb
    assert contracted < window


def _traced_counts(tmp_path, call):
    with jax.profiler.trace(str(tmp_path)):
        call()
    s = obs.summary()
    return {name: s[name]["units"]
            for name in ("kernel.col_blocks", "kernel.col_blocks_window")}


@pytest.mark.parametrize("entry", ["batch", "one"])
def test_traced_check_counts_col_blocks(tmp_path, monkeypatch, entry):
    """A traced call's planner check records the blocks the kernel
    contracts and those of the whole window over the active tiles, as
    the host rule reckons them; with no capture nothing is recorded."""
    c = CASES["seam"]
    images, mats = c.images(), c.mats()
    vol0 = jnp.zeros((c.geom.L,) * 3, jnp.float32)
    if entry == "batch":
        def call():
            pallas_backproject_batch(vol0, images, mats, c.geom, pbatch=2,
                                     validate=True, **c.tiles)
        want = np.sum([block_counts(c.gs, A, c.tiles) for A in mats], 0)
    else:
        def call():
            pallas_backproject_one(vol0, images[0], mats[0], c.geom,
                                   validate=True, **c.tiles)
        want = block_counts(c.gs, mats[0], c.tiles)
    monkeypatch.setattr(backproject_ops, "_VALIDATED_STACKS", set())
    got = _traced_counts(tmp_path, call)
    assert (got["kernel.col_blocks"], got["kernel.col_blocks_window"]) \
        == tuple(want)
    assert 0 < want[0] < want[1]

    monkeypatch.setattr(backproject_ops, "_VALIDATED_STACKS", set())
    before, totals = obs.recorded(), obs.summary()
    call()
    assert obs.recorded() == before and obs.summary() == totals
