"""The plain reference, and the control that has to fail against it.

The reference is FDK in float64 numpy: cosine weights, Parker short-scan
weights picked by angle index, a band-limited Ram-Lak ramp along the
detector rows by linear FFT convolution, the FDK constant, and then
Listing 1 of the paper on a sample of voxels: project each voxel,
floor-bilinear sample with zero outside the detector, weight by
``1/w^2`` and sum over the views.  It imports nothing of the program and
takes nothing the program made: only the acquisition, the raw views and
their float64 matrices.

The control is the same computation put where the program would be, in
the precision one step below what the configurations state.  They state
float32 at ``HIGHEST``; the step below is ``HIGH``, three bfloat16
passes, so the control runs the reference in float32 with its one matrix
product, the projective transform of the voxels, computed as three
bfloat16 products (:func:`dot_high`), written out so that it rounds
alike on the chip and on the CPU.
"""

from __future__ import annotations

import math

import numpy as np

from .acq import Acq


def _filter_tables(acq: Acq):
    n_u, n_v = acq.n_u, acq.n_v
    u = (np.arange(n_u) - (n_u - 1) / 2.0) * acq.du
    v = (np.arange(n_v) - (n_v - 1) / 2.0) * acq.dv
    cosw = acq.sdd / np.sqrt(acq.sdd ** 2 + u[None, :] ** 2
                             + v[:, None] ** 2)
    gamma = np.arctan2(u, acq.sdd)
    delta = float(np.abs(gamma).max())
    beta = (acq.angles - acq.angles[0])[:, None]
    if not math.pi + 2 * delta <= acq.sweep < 2 * math.pi:
        raise ValueError("the reference implements the short-scan case "
                         "only")
    with np.errstate(invalid="ignore", divide="ignore"):
        up = np.sin(math.pi / 4 * beta / (delta - gamma)) ** 2
        down = np.sin(math.pi / 4 * (math.pi + 2 * delta - beta)
                      / (delta + gamma)) ** 2
    parker = np.ones((acq.n_proj, n_u))
    parker = np.where(beta <= 2 * (delta - gamma), np.nan_to_num(up),
                      parker)
    parker = np.where(beta >= math.pi - 2 * gamma, np.nan_to_num(down),
                      parker)
    parker = np.where(beta > math.pi + 2 * delta, 0.0, parker)
    parker *= 2.0                      # the filter keeps FDK's 1/2
    pad = 1 << (2 * n_u - 1).bit_length()
    k = np.arange(-(pad // 2), pad - pad // 2)
    h = np.zeros(pad)
    h[k == 0] = 1.0 / (4 * acq.du ** 2)
    odd = k % 2 == 1
    h[odd] = -1.0 / (math.pi * k[odd] * acq.du) ** 2
    hf = np.fft.rfft(np.roll(h, -(pad // 2)))
    scale = acq.sweep / acq.n_proj * acq.sdd / (2 * acq.sid) * acq.du
    return cosw, parker, pad, hf, scale


def filter64(raw: np.ndarray, views, acq: Acq) -> np.ndarray:
    """FDK pre-processing of ``raw`` ``(k, n_v, n_u)``, the views with
    angle indices ``views``, in float64."""
    cosw, parker, pad, hf, scale = _filter_tables(acq)
    weighted = (np.asarray(raw, np.float64) * cosw
                * parker[np.asarray(views)][:, None, :])
    ramp = np.fft.irfft(np.fft.rfft(weighted, n=pad, axis=-1) * hf,
                        n=pad, axis=-1)[..., :acq.n_u]
    return ramp * scale


def world(vox: np.ndarray, acq: Acq) -> np.ndarray:
    """World coordinates ``(n, 3)`` as ``(x, y, z)`` of voxel indices
    ``vox`` ``(n, 3)`` given as ``(z, y, x)``."""
    return acq.O + np.asarray(vox, np.float64)[:, ::-1] * acq.voxel_mm


def _bilinear(padded, ix, iy, n_u: int, n_v: int, xp):
    fx, fy = xp.floor(ix), xp.floor(iy)
    sx, sy = ix - fx, iy - fy
    # Taps outside the detector read the zero border.
    c0 = xp.clip(fx + 1, 0, n_u + 1).astype(xp.int32)
    c1 = xp.clip(fx + 2, 0, n_u + 1).astype(xp.int32)
    r0 = xp.clip(fy + 1, 0, n_v + 1).astype(xp.int32)
    r1 = xp.clip(fy + 2, 0, n_v + 1).astype(xp.int32)
    bot = (1 - sx) * padded[r0, c0] + sx * padded[r0, c1]
    top = (1 - sx) * padded[r1, c0] + sx * padded[r1, c1]
    return (1 - sy) * bot + sy * top


def backproject64(filtered: np.ndarray, mats: np.ndarray, vox: np.ndarray,
                  acq: Acq) -> np.ndarray:
    """Listing 1 in float64 at the voxels ``vox`` ``(n, 3)`` (``z, y,
    x``), summed over the filtered views and their matrices."""
    pts = world(vox, acq)
    homo = np.concatenate([pts, np.ones((len(pts), 1))], axis=1)
    out = np.zeros(len(pts))
    for img, A in zip(filtered, np.asarray(mats, np.float64)):
        u, v, w = homo @ A[0], homo @ A[1], homo @ A[2]
        val = _bilinear(np.pad(img, 1), u / w, v / w, acq.n_u, acq.n_v, np)
        out += val / (w * w)
    return out


def reference_values(raw: np.ndarray, views, mats: np.ndarray,
                     vox: np.ndarray, acq: Acq) -> np.ndarray:
    """What a volume built from these raw views holds at ``vox``."""
    return backproject64(filter64(raw, views, acq), mats, vox, acq)


class Reference:
    """The reference at ``vox`` for any set of angle indices of one
    acquisition: ``raw`` and ``mats`` hold every view, indexed by angle.
    Each view's share is computed once, :data:`BATCH` views at a time,
    and an answer's value is the sum of its views' shares."""

    BATCH = 8

    def __init__(self, raw: np.ndarray, mats: np.ndarray, vox: np.ndarray,
                 acq: Acq):
        self.raw, self.mats, self.vox, self.acq = raw, mats, vox, acq
        self.share: dict[int, np.ndarray] = {}

    def __call__(self, views) -> np.ndarray:
        views = [int(v) for v in views]
        todo = sorted(set(views) - set(self.share))
        for k in range(0, len(todo), self.BATCH):
            b = np.asarray(todo[k:k + self.BATCH])
            filt = filter64(self.raw[b], b, self.acq)
            for img, v in zip(filt, b):
                self.share[int(v)] = backproject64(
                    img[None], self.mats[[v]], self.vox, self.acq)
        out = np.zeros(len(self.vox))
        for v in views:
            out += self.share[v]
        return out


# ----------------------------------------------------------------------
# The control: the reference at HIGH, one step below HIGHEST
# ----------------------------------------------------------------------

def dot_high(a, b):
    """``a @ b`` for float32 operands as TPU ``Precision.HIGH`` computes
    it: each operand split into a bfloat16 head and a bfloat16 tail, and
    the three products head*head + head*tail + tail*head summed in
    float32 (the tail*tail term is dropped)."""
    import jax.numpy as jnp

    def split(x):
        hi = x.astype(jnp.bfloat16)
        lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
        return hi, lo

    ah, al = split(a)
    bh, bl = split(b)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return mm(ah, bh) + mm(ah, bl) + mm(al, bh)


def control_values(raw: np.ndarray, views, mats: np.ndarray,
                   vox: np.ndarray, acq: Acq, high: bool = True
                   ) -> np.ndarray:
    """The reference in float32 with its projective transform at HIGH
    (at HIGHEST with ``high=False``), run on JAX's default device;
    returns float64 values at ``vox``."""
    import jax
    import jax.numpy as jnp

    def highest(a, b):
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    dot = dot_high if high else highest

    cosw, parker, pad, hf, scale = _filter_tables(acq)
    views = np.asarray(views)
    pts = world(vox, acq)
    homo = jnp.asarray(np.concatenate([pts, np.ones((len(pts), 1))], 1),
                       jnp.float32)
    out = jnp.zeros(len(pts), jnp.float32)
    for b in range(0, len(views), Reference.BATCH):
        sl = slice(b, b + Reference.BATCH)
        x = jnp.asarray(raw[sl], jnp.float32) * jnp.asarray(cosw,
                                                            jnp.float32)
        x = x * jnp.asarray(parker[views[sl]], jnp.float32)[:, None, :]
        ramp = jnp.fft.irfft(jnp.fft.rfft(x, n=pad, axis=-1)
                             * jnp.asarray(hf, jnp.complex64),
                             n=pad, axis=-1)[..., :acq.n_u]
        filt = ramp * jnp.float32(scale)
        for k, A in enumerate(mats[sl]):
            uvw = dot(homo, jnp.asarray(A, jnp.float32).T)
            u, v, w = uvw[:, 0], uvw[:, 1], uvw[:, 2]
            val = _bilinear(jnp.pad(filt[k], 1), u / w, v / w, acq.n_u,
                            acq.n_v, jnp)
            out = out + val / (w * w)
    return np.asarray(out, np.float64)
