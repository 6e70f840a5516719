"""Analytic 3-D ellipsoid phantom and exact cone-beam forward projector.

The RabbitCT dataset (a filtered C-arm scan of a rabbit) is not
redistributable, so the framework generates its own data: a Shepp-Logan-like
superposition of ellipsoids whose cone-beam line integrals have a closed
form.  This is strictly *stronger* than the benchmark's setup: we hold both
an exact projection dataset and an exact voxelised reference volume, so
reconstruction quality (benchmarks/quality) is measured against analytic
ground truth instead of another implementation's output.

For an ellipsoid with centre ``c``, semi-axes ``(a, b, cz)``, z-rotation
``phi`` and density ``rho``, a ray ``X(t) = S + t * d`` (``|d| = 1``)
intersects where ``|D^-1 R^T (X - c)|^2 = 1``; the chord length is
``2 sqrt(b^2 - a c0) / a`` with the usual quadratic coefficients, and the
line integral contribution is ``rho * chord``.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from .geometry import Geometry, detector_basis, source_position

__all__ = [
    "Ellipsoid",
    "shepp_logan_3d",
    "voxelize",
    "forward_project",
    "make_dataset",
]


@dataclasses.dataclass(frozen=True)
class Ellipsoid:
    center: tuple[float, float, float]   # WCS mm
    semi_axes: tuple[float, float, float]  # mm
    rho: float                            # density (additive)
    phi: float = 0.0                      # rotation about world z (radians)

    def rotation(self) -> np.ndarray:
        c, s = np.cos(self.phi), np.sin(self.phi)
        return np.array([[c, -s, 0.0], [s, c, 0.0], [0.0, 0.0, 1.0]])


def shepp_logan_3d(extent_mm: float) -> list[Ellipsoid]:
    """A compact 3-D Shepp-Logan-flavoured phantom scaled to ``extent_mm``.

    ``extent_mm`` should be the world half-extent of the reconstruction
    volume; the phantom fills ~90% of it so every projection sees the full
    object (no truncation artefacts).
    """
    e = extent_mm

    def E(cx, cy, cz, a, b, c, rho, phi=0.0):
        return Ellipsoid((cx * e, cy * e, cz * e), (a * e, b * e, c * e),
                         rho, phi)

    return [
        E(0.0, 0.0, 0.0, 0.69, 0.92, 0.81, 1.0),
        E(0.0, -0.0184, 0.0, 0.6624, 0.874, 0.78, -0.8),
        E(0.22, 0.0, 0.0, 0.11, 0.31, 0.22, -0.2, np.radians(-18)),
        E(-0.22, 0.0, 0.0, 0.16, 0.41, 0.28, -0.2, np.radians(18)),
        E(0.0, 0.35, -0.15, 0.21, 0.25, 0.41, 0.1),
        E(0.0, 0.1, 0.25, 0.046, 0.046, 0.05, 0.1),
        E(0.0, -0.1, 0.25, 0.046, 0.046, 0.05, 0.1),
        E(-0.08, -0.605, 0.0, 0.046, 0.023, 0.05, 0.1),
        E(0.0, -0.605, 0.0, 0.023, 0.023, 0.02, 0.1),
        E(0.06, -0.605, 0.0, 0.023, 0.046, 0.02, 0.1),
    ]


# ----------------------------------------------------------------------
# Voxelisation (the quality-metric reference volume)
# ----------------------------------------------------------------------

def voxelize(geom: Geometry, ellipsoids: Sequence[Ellipsoid] | None = None,
             dtype=np.float32) -> np.ndarray:
    """Exact voxel-centre sampling of the phantom, shape ``(L, L, L)``.

    Index order is ``volume[z, y, x]`` to match the paper's loop nest
    (``VOL[z*L*L + y*L + x]``).
    """
    if ellipsoids is None:
        ellipsoids = shepp_logan_3d(-geom.O)
    L = geom.L
    coords = geom.O + np.arange(L, dtype=np.float64) * geom.MM
    zz, yy, xx = np.meshgrid(coords, coords, coords, indexing="ij")
    pts = np.stack([xx, yy, zz], axis=-1)          # (L, L, L, 3), world xyz
    vol = np.zeros((L, L, L), dtype=np.float64)
    for ell in ellipsoids:
        rel = pts - np.asarray(ell.center)
        rel = rel @ ell.rotation()                  # rotate into body frame
        q = (rel / np.asarray(ell.semi_axes)) ** 2
        vol += ell.rho * (q.sum(axis=-1) <= 1.0)
    return vol.astype(dtype)


# ----------------------------------------------------------------------
# Exact cone-beam forward projection (the synthetic scanner)
# ----------------------------------------------------------------------

def forward_project(geom: Geometry,
                    ellipsoids: Sequence[Ellipsoid] | None = None,
                    angles: np.ndarray | None = None,
                    dtype=np.float32) -> np.ndarray:
    """Closed-form line integrals; returns ``(n_proj, n_v, n_u)``.

    Per pixel we cast the ray through the pixel centre using the same
    detector frame that builds the projection matrices, so forward and back
    projection are exactly consistent (tested in
    ``tests/test_geometry.py::test_forward_back_consistency``).
    """
    if ellipsoids is None:
        ellipsoids = shepp_logan_3d(-geom.O)
    if angles is None:
        angles = geom.angles
    n_u, n_v = geom.n_u, geom.n_v
    iu = (np.arange(n_u, dtype=np.float64) - geom.cu) * geom.du
    iv = (np.arange(n_v, dtype=np.float64) - geom.cv) * geom.dv
    uu, vv = np.meshgrid(iu, iv)                   # (n_v, n_u)

    out = np.zeros((len(angles), n_v, n_u), dtype=np.float64)
    for k, theta in enumerate(angles):
        e_u, e_v, e_w = detector_basis(geom, float(theta))
        s = source_position(geom, float(theta))
        # Ray directions through every pixel centre.
        d = (uu[..., None] * e_u + vv[..., None] * e_v
             + geom.sdd * e_w)                     # (n_v, n_u, 3)
        d /= np.linalg.norm(d, axis=-1, keepdims=True)
        acc = np.zeros((n_v, n_u), dtype=np.float64)
        for ell in ellipsoids:
            R = ell.rotation()
            inv_ax = 1.0 / np.asarray(ell.semi_axes)
            p = ((s - np.asarray(ell.center)) @ R) * inv_ax     # (3,)
            q = (d @ R) * inv_ax                                # (nv,nu,3)
            a = np.sum(q * q, axis=-1)
            b = np.sum(q * p, axis=-1)
            c0 = float(p @ p) - 1.0
            disc = b * b - a * c0
            chord = 2.0 * np.sqrt(np.maximum(disc, 0.0)) / a
            acc += ell.rho * chord
        out[k] = acc
    return out.astype(dtype)


def make_dataset(geom: Geometry, seed: int = 0):
    """Convenience bundle: (projections, matrices, reference_volume).

    ``seed`` is accepted but unused: the phantom itself is
    deterministic.
    """
    from .geometry import projection_matrices

    ells = shepp_logan_3d(-geom.O)
    projections = forward_project(geom, ells)
    matrices = projection_matrices(geom)
    reference = voxelize(geom, ells)
    return projections, matrices, reference
