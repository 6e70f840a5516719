"""Synthetic projections made on the device from the seed.

The phantom is the 3-D Shepp-Logan-like set of ellipsoids the program's
own examples use, each one moved, stretched, turned and re-weighted by a
few percent drawn from the seed, so that every seed scans a different
object of the same size.  A ray's line integral through an ellipsoid has
a closed form: with the ray ``S + t d`` (``|d| = 1``) taken into the
ellipsoid's unit-sphere frame as ``p + t q``, the chord is
``2 sqrt(|q|^2 - |q x p|^2) / |q|^2`` (Lagrange's identity turns the
usual ``b^2 - a c`` into a form without cancellation).  It is computed
in float32, a batch of views per jitted call, and copied to host memory
view by view, since a scanner delivers its projections from the host.
"""

from __future__ import annotations

import functools

import numpy as np

from .acq import Acq, frames

# (centre x, y, z, semi-axes a, b, c) as fractions of the volume's
# half-extent, density, rotation about z in degrees.
SHEPP_LOGAN = (
    (0.0, 0.0, 0.0, 0.69, 0.92, 0.81, 1.0, 0.0),
    (0.0, -0.0184, 0.0, 0.6624, 0.874, 0.78, -0.8, 0.0),
    (0.22, 0.0, 0.0, 0.11, 0.31, 0.22, -0.2, -18.0),
    (-0.22, 0.0, 0.0, 0.16, 0.41, 0.28, -0.2, 18.0),
    (0.0, 0.35, -0.15, 0.21, 0.25, 0.41, 0.1, 0.0),
    (0.0, 0.1, 0.25, 0.046, 0.046, 0.05, 0.1, 0.0),
    (0.0, -0.1, 0.25, 0.046, 0.046, 0.05, 0.1, 0.0),
    (-0.08, -0.605, 0.0, 0.046, 0.023, 0.05, 0.1, 0.0),
    (0.0, -0.605, 0.0, 0.023, 0.023, 0.02, 0.1, 0.0),
    (0.06, -0.605, 0.0, 0.023, 0.046, 0.02, 0.1, 0.0),
)

VIEWS_PER_CALL = 16       # views per generator call: 496 = 31 x 16


def ellipsoids(acq: Acq, seed: int | None) -> np.ndarray:
    """``(n, 8)`` float64 rows ``cx, cy, cz, a, b, c, rho, phi_rad`` in mm.

    ``seed=None`` gives the unperturbed phantom.  The perturbation keeps
    every ellipsoid inside the volume: centres move by up to 2% of the
    half-extent, axes scale by up to 5%, densities by up to 10%, and
    rotations turn by up to 5 degrees.
    """
    e = -acq.O
    base = np.asarray(SHEPP_LOGAN, np.float64)
    rows = base.copy()
    if seed is not None:
        rng = np.random.default_rng(seed)
        n = len(base)
        rows[:, 0:3] += rng.uniform(-0.02, 0.02, (n, 3))
        rows[:, 3:6] *= rng.uniform(0.95, 1.05, (n, 3))
        rows[:, 6] *= rng.uniform(0.9, 1.1, n)
        rows[:, 7] += rng.uniform(-5.0, 5.0, n)
    rows[:, 0:6] *= e
    rows[:, 7] = np.radians(rows[:, 7])
    return rows


@functools.lru_cache(maxsize=4)
def _project_fn(n_u: int, n_v: int, du: float, dv: float, sdd: float):
    import jax
    import jax.numpy as jnp

    cu, cv = (n_u - 1) / 2.0, (n_v - 1) / 2.0

    @jax.jit
    def project(src, e_u, e_v, e_w, ells):
        # src, e_*: (k, 3) float32; ells: (n, 8) float32.  Written
        # component by component so that XLA fuses it into elementwise
        # passes over the (k, n_v, n_u) output and holds no (.., 3) array.
        uu = ((jnp.arange(n_u, dtype=jnp.float32) - cu) * du)[None, None, :]
        vv = ((jnp.arange(n_v, dtype=jnp.float32) - cv) * dv)[None, :, None]

        def col(a, j):
            return a[:, j][:, None, None]

        d = [uu * col(e_u, j) + vv * col(e_v, j) + sdd * col(e_w, j)
             for j in range(3)]
        inv = jax.lax.rsqrt(d[0] * d[0] + d[1] * d[1] + d[2] * d[2])
        dx, dy, dz = (c * inv for c in d)
        acc = jnp.zeros(dx.shape, jnp.float32)
        for i in range(ells.shape[0]):
            cx, cy, cz, a, b, c, rho, phi = (ells[i, j] for j in range(8))
            cs, sn = jnp.cos(phi), jnp.sin(phi)
            # Body frame: the rotation's transpose, then the axes.
            sx, sy, sz = (col(src, 0) - cx, col(src, 1) - cy,
                          col(src, 2) - cz)
            p0, p1, p2 = ((sx * cs + sy * sn) / a, (sy * cs - sx * sn) / b,
                          sz / c)
            q0, q1, q2 = ((dx * cs + dy * sn) / a, (dy * cs - dx * sn) / b,
                          dz / c)
            qq = q0 * q0 + q1 * q1 + q2 * q2
            x0, x1, x2 = (q1 * p2 - q2 * p1, q2 * p0 - q0 * p2,
                          q0 * p1 - q1 * p0)
            disc = jnp.maximum(qq - (x0 * x0 + x1 * x1 + x2 * x2), 0.0)
            acc = acc + rho * 2.0 * jnp.sqrt(disc) / qq
        return acc

    return project


def generate(acq: Acq, seed: int | None, views=None) -> np.ndarray:
    """Raw line integrals ``(len(views), n_v, n_u)`` float32 in host
    memory, made on JAX's default device :data:`VIEWS_PER_CALL` views at
    a time.  ``views`` are angle indices (all ``n_proj`` by default)."""
    import jax
    import jax.numpy as jnp

    views = np.arange(acq.n_proj) if views is None else np.asarray(views)
    project = _project_fn(acq.n_u, acq.n_v, acq.du, acq.dv, acq.sdd)
    ells = jnp.asarray(ellipsoids(acq, seed), jnp.float32)
    out = np.empty((len(views), acq.n_v, acq.n_u), np.float32)
    k = VIEWS_PER_CALL
    pending = None
    for start in range(0, len(views), k):
        chunk = views[start:start + k]
        pad = np.concatenate([chunk, np.repeat(chunk[-1:], k - len(chunk))])
        s, e_u, e_v, e_w = (jnp.asarray(a, jnp.float32)
                            for a in frames(acq, acq.angles[pad]))
        batch = project(s, e_u, e_v, e_w, ells)
        # Copy the previous batch out while this one computes.
        if pending is not None:
            pstart, pn, parr = pending
            out[pstart:pstart + pn] = np.asarray(parr)[:pn]
        pending = (start, len(chunk), batch)
    if pending is not None:
        pstart, pn, parr = pending
        out[pstart:pstart + pn] = np.asarray(jax.device_get(parr))[:pn]
    return out
