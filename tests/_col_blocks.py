"""Column-block seam cases for the back projection kernel's tests.

The kernel contracts only the 128-column blocks of its DMA window that
hold a tile's taps (``repro.kernels.backproject._col_blocks``).  Two
small problems put that rule at its edges:

* ``seam``: tiles contract two or three of the window's four blocks; in
  projection 0 a tile's highest tap pair on the detector straddles a
  block boundary, in projection 1 a tile's lowest;
* ``wide``: one tile's footprint spans every block of the window, while
  others contract fewer.

The images are seeded noise, so a dropped or misplaced tap shows.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.backproject import GeomStatic
from repro.core.geometry import Geometry, projection_matrix
from repro.kernels.backproject import strip_window
from repro.kernels.backproject_ops import tile_col_blocks
from repro.kernels.backproject_ref import backproject_volume_ref


@dataclasses.dataclass(frozen=True)
class Case:
    geom: Geometry
    thetas: tuple
    tiles: dict              # ty, chunk, band, width

    @property
    def gs(self) -> GeomStatic:
        return GeomStatic.of(self.geom)

    def mats(self) -> np.ndarray:
        return np.stack([projection_matrix(self.geom, th)
                         for th in self.thetas]).astype(np.float32)

    def images(self, seed: int = 0) -> np.ndarray:
        rng = np.random.default_rng(seed)
        return rng.standard_normal(
            (len(self.thetas), self.geom.n_v, self.geom.n_u)
        ).astype(np.float32)


CASES = {
    "seam": Case(Geometry().scaled(16, n_proj=8, n_u=360, n_v=16),
                 (2.15, 1.9), dict(ty=8, chunk=8, band=16, width=362)),
    "wide": Case(Geometry().scaled(16, n_proj=8, n_u=640, n_v=16),
                 (1.1, 2.0), dict(ty=8, chunk=16, band=16, width=640)),
}


def block_spans(case: Case, k: int):
    """Blocks the kernel contracts in each active tile of projection
    ``k``, and the blocks of the whole window."""
    active, _, kb_lo, kb_hi = tile_col_blocks(case.gs, case.mats()[k],
                                              **case.tiles)
    wwidth = strip_window(case.tiles["band"], case.tiles["width"], 4)[1]
    return (kb_hi - kb_lo + 1)[active], wwidth // 128


def edge_seams(case: Case, k: int) -> tuple[int, int]:
    """Active tiles of projection ``k`` whose lowest, and whose highest,
    tap pair straddles a 128-column boundary of the tile's window, with
    both taps on detector data (float64 projection): the taps the block
    rule is tightest on."""
    g, A = case.geom, case.mats()[k].astype(np.float64)
    ty, chunk = case.tiles["ty"], case.tiles["chunk"]
    c = g.O + np.arange(g.L) * g.MM
    wz, wy, wx = np.meshgrid(c, c, c, indexing="ij")
    u, v, w = (A[i, 0] * wx + A[i, 1] * wy + A[i, 2] * wz + A[i, 3]
               for i in range(3))
    ix, iy = np.clip(u / w, -1, g.n_u), v / w
    tap = np.floor(ix).astype(np.int64) + 1       # padded first tap
    on = ((w > 0) & (iy > -1) & (iy < g.n_v) & (tap >= 1)
          & (tap + 1 <= g.n_u))
    by_tile = (g.L, g.L // ty, ty, g.L // chunk, chunk)
    tap, on = tap.reshape(by_tile), on.reshape(by_tile)
    _, c0, _, _ = tile_col_blocks(case.gs, A, **case.tiles)
    out = []
    for edge in (tap.min(axis=(2, 4)), tap.max(axis=(2, 4))):
        reached = (on & (tap == edge[:, :, None, :, None])).any(axis=(2, 4))
        out.append(int((reached & ((edge - c0) % 128 == 127)).sum()))
    return tuple(out)


def assert_matches_oracle(out, case: Case, images, mats, wire: str):
    """The kernel's volume against ``backproject_volume_ref`` summed
    over the projections, at the tolerances of the wire's existing
    tests: f32 and bf16 (against the oracle of the bf16-rounded images)
    to 1e-5, int8 within 2% of the volume's scale."""
    seen = images
    if wire == "bfloat16":
        seen = np.asarray(jnp.asarray(images, jnp.bfloat16), np.float32)
    ref = jnp.zeros((case.geom.L,) * 3, jnp.float32)
    for img, A in zip(seen, mats):
        ref = backproject_volume_ref(ref, img, A, case.gs)
    out, ref = np.asarray(out), np.asarray(ref)
    if wire == "int8":
        assert float(np.abs(out - ref).max()) < 0.02 * np.abs(ref).max()
    else:
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def assert_case_holds(name: str, k: int = 0) -> None:
    """The case exercises what its name says, for projection ``k``."""
    case = CASES[name]
    spans, nb = block_spans(case, k)
    if name == "seam":
        assert edge_seams(case, k)[1 - k] > 0   # 0: highest, 1: lowest
        assert spans.max() < nb           # every tile skips a block
    else:
        assert spans.max() == nb and spans.min() < nb
