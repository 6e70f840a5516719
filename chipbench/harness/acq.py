"""The acquisition as the benchmark describes it, apart from the program.

A configuration file's ``geometry`` group gives a circular cone-beam
C-arm: detector ``n_u`` x ``n_v`` pixels of ``du`` x ``dv`` mm, source at
``sid`` mm from the isocentre and ``sdd`` mm from the detector,
``n_proj`` views spread evenly over ``sweep_deg``, and a cubic volume of
``L`` voxels of ``voxel_mm`` centred on the isocentre, indexed
``volume[z, y, x]``.  The projection matrices are the RabbitCT kind: a
3x4 matrix per view, scaled so that the homogeneous ``w`` is 1 at the
isocentre, with ``ix = u / w`` and ``iy = v / w`` in detector pixels.
Both the program and the reference are handed these float64 matrices.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Acq:
    n_u: int
    n_v: int
    du: float
    dv: float
    sid: float
    sdd: float
    L: int
    voxel_mm: float
    n_proj: int
    sweep: float                     # radians

    @classmethod
    def from_config(cls, geometry: dict) -> "Acq":
        g = dict(geometry)
        return cls(n_u=int(g["n_u"]), n_v=int(g["n_v"]),
                   du=float(g["du_mm"]), dv=float(g["dv_mm"]),
                   sid=float(g["sid_mm"]), sdd=float(g["sdd_mm"]),
                   L=int(g["L"]), voxel_mm=float(g["voxel_mm"]),
                   n_proj=int(g["n_proj"]),
                   sweep=math.radians(float(g["sweep_deg"])))

    @property
    def O(self) -> float:            # noqa: E743 (RabbitCT's name)
        """World coordinate (mm) of voxel index 0 on every axis."""
        return -(self.L - 1) / 2.0 * self.voxel_mm

    @property
    def angles(self) -> np.ndarray:
        return np.linspace(0.0, self.sweep, self.n_proj, endpoint=False)

    @property
    def voxels(self) -> int:
        return self.L ** 3


def frames(acq: Acq, theta: np.ndarray):
    """Source position and detector frame ``(s, e_u, e_v, e_w)`` for the
    angles ``theta``: ``e_u`` along detector rows, ``e_v`` the world z
    axis, ``e_w`` from the source towards the detector."""
    theta = np.asarray(theta, np.float64)
    zero, one = np.zeros_like(theta), np.ones_like(theta)
    s = np.stack([acq.sid * np.cos(theta), acq.sid * np.sin(theta), zero],
                 axis=-1)
    e_u = np.stack([-np.sin(theta), np.cos(theta), zero], axis=-1)
    e_v = np.stack([zero, zero, one], axis=-1)
    e_w = np.stack([-np.cos(theta), -np.sin(theta), zero], axis=-1)
    return s, e_u, e_v, e_w


def projection_matrices(acq: Acq) -> np.ndarray:
    """``(n_proj, 3, 4)`` float64 pinhole matrices, ``w == 1`` at the
    isocentre."""
    s, e_u, e_v, e_w = frames(acq, acq.angles)
    cu, cv = (acq.n_u - 1) / 2.0, (acq.n_v - 1) / 2.0
    r0 = acq.sdd / acq.du * e_u + cu * e_w
    r1 = acq.sdd / acq.dv * e_v + cv * e_w
    R = np.stack([r0, r1, e_w], axis=1)                  # (n, 3, 3)
    t = -np.einsum("nij,nj->ni", R, s)
    return np.concatenate([R, t[..., None]], axis=2) / acq.sid


def scan_angles(offset: int, per_scan: int, n_proj: int) -> np.ndarray:
    """Angle indices of one scan of ``per_scan`` views spread evenly over
    the sweep, starting at ``offset`` (``0 <= offset < n_proj //
    per_scan``)."""
    stride = n_proj // per_scan
    return offset + stride * np.arange(per_scan)
