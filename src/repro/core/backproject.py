"""Voxel-driven cone-beam back projection — the paper's kernel, in JAX.

Listing 1 of the paper splits the line-update kernel into three parts; we
keep that structure so the HLO op census (``benchmarks/table2``) can be
reported per part:

* **Part 1** (:func:`plane_coords`): VCS->WCS->ICS transform +
  de-homogenisation.  Streaming arithmetic; trivially vectorizable on any
  SIMD machine — and on the TPU VPU.
* **Part 2** (``sample_*``): fetch the four bilinear taps and blend them.
  The scattered-access part; each ``sample_*`` function is one point in the
  x86-ISA -> TPU design-space mapping (see DESIGN.md §2):

  ========== ==========================================================
  strategy    TPU mechanism (x86 analogue)
  ========== ==========================================================
  ``scalar``  per-tap bounds-checked loads (scalar baseline, Listing 1)
  ``gather``  XLA gather HLO on a zero-padded image (AVX2/IMCI
              ``vgatherdps``)
  ``onehot``  full one-hot matmuls on the MXU (GPU texture-unit
              emulation; the systolic array performs the interpolation)
  ``strip``   per-chunk strip block load + banded one-hot
              (SSE/AVX pairwise loads + in-register shuffles)
  ``strip2``  two-level: strip -> per-8-voxel micro-window + VPU selects
              (beyond-paper refinement; the Pallas kernel's scheme)
  ========== ==========================================================

* **Part 3** (:func:`accumulate`): inverse-square-law weighting + voxel
  update.  Streaming; includes the paper's reciprocal trick (one
  reciprocal replaces three divides).

All strategies implement *identical* semantics — floor-based bilinear
interpolation with zero outside the detector — and are cross-validated in
``tests/test_backproject.py``.  (The reference C code's ``(int)`` cast
truncates toward zero, which *extrapolates* for ``ix in (-1, 0)``; we use
mathematically correct ``floor`` semantics everywhere.  The difference is
confined to a sub-pixel border band and is invisible in the quality
metric.)
"""

from __future__ import annotations

import functools
import hashlib
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.quant import RowQuant, quantize_rows

from .geometry import Geometry

__all__ = [
    "STRATEGIES",
    "DEFAULT_PBATCH",
    "GeomStatic",
    "plane_coords",
    "sample_scalar",
    "sample_gather",
    "sample_onehot",
    "sample_strip",
    "sample_strip2",
    "strip_wire_dtype",
    "contribution",
    "accumulate",
    "backproject_plane",
    "backproject_plane_batch",
    "backproject_one",
    "backproject_batch",
    "fold_projections",
    "validate_strip_opts",
    "reconstruct",
]

STRATEGIES = ("scalar", "gather", "onehot", "strip", "strip2")

# Wire dtypes the strip strategies (and the Pallas kernels) may carry
# strip data in.  ``None`` means "leave the image dtype alone" — the
# float32 path must stay bitwise-identical to the pre-option code, so it
# never inserts so much as a no-op ``astype``.  bf16 halves strip HBM/
# VMEM bytes; the one-hot interpolation always upcasts the window back
# to f32 and accumulates in f32, so the only quality loss is the bf16
# rounding of the strip values themselves (~8 mantissa bits).  int8
# quarters them: the padded image is encoded ONCE at pad time into
# per-row affine codes + f32 scale/offset (``repro.quant``, error
# feedback along each row), windows move at 1 byte/pixel, and the
# samplers dequantise *after* the gather next to the f32 accumulator
# (DESIGN.md §12).
_STRIP_WIRE_DTYPES = {"float32": None, "bfloat16": jnp.bfloat16,
                      "int8": jnp.int8}


def strip_wire_dtype(strip_dtype: str):
    """Map a ``strip_dtype`` option to a jnp dtype (``None`` = f32
    passthrough).  Raises ``ValueError`` on unknown names — a typo'd
    dtype must never silently run the f32 path."""
    try:
        return _STRIP_WIRE_DTYPES[str(strip_dtype)]
    except KeyError:
        raise ValueError(
            f"unknown strip_dtype {strip_dtype!r}; want one of "
            f"{tuple(_STRIP_WIRE_DTYPES)}") from None

# Projections folded into the volume per volume pass when the caller does
# not say otherwise (untuned ``pbatch``).  Each pass streams the L^3
# volume through memory exactly once, so volume traffic scales with
# ``ceil(n_proj / pbatch)`` — see DESIGN.md §7 for the traffic model.
DEFAULT_PBATCH = 4

_EPS_W = 1e-6

# MXU precision of the one-hot interpolation dots.  On a TPU an f32 dot
# at default precision runs as one bf16 pass, which rounds every tap
# weight and value to 8 bits: a v5e volume then misses a float64
# reference by 3.2e-3 of its scale, and by 1.8e-4 at HIGHEST.  (No
# effect off-TPU: CPU dots are f32 either way.)
_MXU_PRECISION = jax.lax.Precision.HIGHEST


class GeomStatic(NamedTuple):
    """The static scalars a kernel needs (hashable -> jit-static)."""

    L: int
    n_u: int
    n_v: int
    O: float
    MM: float

    @classmethod
    def of(cls, geom: Geometry) -> "GeomStatic":
        return cls(L=geom.L, n_u=geom.n_u, n_v=geom.n_v,
                   O=float(geom.O), MM=float(geom.MM))


# ----------------------------------------------------------------------
# Part 1 — geometry (streaming arithmetic)
# ----------------------------------------------------------------------

def plane_coords(A, gs: GeomStatic, z, *, use_reciprocal: bool = True):
    """ICS coordinates for one z-plane: ``(ix, iy, w)`` each ``(L, L)``.

    ``[y, x]`` index order.  The single reciprocal replaces the two divides
    of Listing 1 lines 14-15 (paper section 5.1: "replace the divide with a
    reciprocal instruction"); it is also reused by Part 3 for the ``1/w^2``
    weight, saving a third divide.
    """
    A = jnp.asarray(A, dtype=jnp.float32)
    coords = gs.O + jnp.arange(gs.L, dtype=jnp.float32) * gs.MM
    wx = coords[None, :]                      # (1, L)  varies along x
    wy = coords[:, None]                      # (L, 1)  varies along y
    wz = gs.O + z.astype(jnp.float32) * gs.MM if hasattr(z, "dtype") \
        else gs.O + float(z) * gs.MM
    u = wx * A[0, 0] + wy * A[0, 1] + wz * A[0, 2] + A[0, 3]
    v = wx * A[1, 0] + wy * A[1, 1] + wz * A[1, 2] + A[1, 3]
    w = wx * A[2, 0] + wy * A[2, 1] + wz * A[2, 2] + A[2, 3]
    if use_reciprocal:
        r = jnp.where(w > _EPS_W, 1.0 / w, 0.0)
        return u * r, v * r, w
    return u / w, v / w, w


def _taps(ix, iy):
    """Floor taps and interpolation weights (Listing 1 lines 17-21)."""
    fx = jnp.floor(ix)
    fy = jnp.floor(iy)
    iix = fx.astype(jnp.int32)
    iiy = fy.astype(jnp.int32)
    return iix, iiy, ix - fx, iy - fy


# ----------------------------------------------------------------------
# Part 2 — the four-tap fetch + bilinear blend (scattered access)
# ----------------------------------------------------------------------

def sample_scalar(image, ix, iy, gs: GeomStatic):
    """Listing-1 transliteration: four bounds-checked loads per voxel.

    The oracle for every other strategy.  ``image`` is the *unpadded*
    ``(n_v, n_u)`` projection; each tap is masked exactly like the four
    ``if`` statements of Listing 1 lines 24-36.
    """
    iix, iiy, sx, sy = _taps(ix, iy)

    def tap(r, c):
        ok = (r >= 0) & (r < gs.n_v) & (c >= 0) & (c < gs.n_u)
        rc = jnp.clip(r, 0, gs.n_v - 1)
        cc = jnp.clip(c, 0, gs.n_u - 1)
        return jnp.where(ok, image[rc, cc], 0.0)

    valbl = tap(iiy, iix)
    valbr = tap(iiy, iix + 1)
    valtl = tap(iiy + 1, iix)
    valtr = tap(iiy + 1, iix + 1)
    valb = (1.0 - sx) * valbl + sx * valbr
    valt = (1.0 - sx) * valtl + sx * valtr
    return (1.0 - sy) * valb + sy * valt


def sample_gather(padded, ix, iy, gs: GeomStatic):
    """Hardware-gather analogue: four XLA gathers on the padded image.

    ``padded`` is the 1-pixel zero-padded ``(n_v + 2, n_u + 2)`` buffer
    (paper section 5.1.1: zero padding beats mask registers).  Indices are
    clamped into the padded buffer; every clamped-out tap lands on a zero
    border cell, so no per-tap conditional survives — exactly the paper's
    "gather everything unconditionally" scheme.
    """
    iix, iiy, sx, sy = _taps(ix, iy)
    r = jnp.clip(iiy + 1, 0, gs.n_v + 1)
    r2 = jnp.clip(iiy + 2, 0, gs.n_v + 1)
    c = jnp.clip(iix + 1, 0, gs.n_u + 1)
    c2 = jnp.clip(iix + 2, 0, gs.n_u + 1)
    valbl = padded[r, c]
    valbr = padded[r, c2]
    valtl = padded[r2, c]
    valtr = padded[r2, c2]
    valb = (1.0 - sx) * valbl + sx * valbr
    valt = (1.0 - sx) * valtl + sx * valtr
    return (1.0 - sy) * valb + sy * valt


def sample_onehot(padded, ix, iy, gs: GeomStatic, *, vox_block: int = 512):
    """Texture-unit emulation: bilinear sampling as two one-hot matmuls.

    ``val[p] = rowsel[p, :] @ padded @ colsel[p, :]`` where ``rowsel``
    carries the vertical interpolation weights on taps ``iiy``/``iiy+1``
    and ``colsel`` the horizontal ones.  The MXU performs the
    interpolation, like a GPU texture unit — at the cost of ``2*R + 4*W``
    flops per voxel.  Out-of-range taps produce all-zero one-hot rows, so
    the zero-outside semantics are *exact* with no clamping at all.
    """
    R, W = gs.n_v + 2, gs.n_u + 2
    shape = ix.shape
    n = int(np.prod(shape))
    vb = min(vox_block, n)
    pad_to = (-n) % vb

    iix, iiy, sx, sy = _taps(ix, iy)
    flat = [jnp.pad(a.reshape(-1), (0, pad_to)).reshape(-1, vb)
            for a in (iix, iiy, sx, sy)]
    iixf, iiyf, sxf, syf = flat

    riota = jax.lax.broadcasted_iota(jnp.int32, (vb, R), 1)
    ciota = jax.lax.broadcasted_iota(jnp.int32, (vb, W), 1)

    def block(args):
        iixb, iiyb, sxb, syb = args
        rr = iiyb[:, None] + 1                  # padded row of lower tap
        cc = iixb[:, None] + 1
        rowsel = ((riota == rr) * (1.0 - syb[:, None])
                  + (riota == rr + 1) * syb[:, None])
        colsel = ((ciota == cc) * (1.0 - sxb[:, None])
                  + (ciota == cc + 1) * sxb[:, None])
        rowmix = jnp.matmul(rowsel.astype(padded.dtype), padded,
                            precision=_MXU_PRECISION)      # (vb, W)
        return jnp.sum(rowmix * colsel, axis=-1)

    vals = jax.lax.map(block, (iixf, iiyf, sxf, syf))
    return vals.reshape(-1)[:n].reshape(shape)


def _divisor_at_most(n: int, k: int) -> int:
    """Largest divisor of ``n`` that is <= ``k`` (memory-block sizing)."""
    k = max(1, min(k, n))
    while n % k:
        k -= 1
    return k


def _strip_bounds(idx, lo_clip, hi_clip, pad_origin_max):
    """Chunk-min tap origin, clamped into the padded image.

    The lowest contributing tap of the chunk sits at padded coordinate
    ``floor(min(idx)) + 1``; using ``floor(min(idx))`` as the origin leaves
    one margin row/col below it (+1 pad and -1 margin cancel).
    """
    clipped = jnp.clip(idx, lo_clip, hi_clip)
    lo = jnp.floor(jnp.min(clipped, axis=-1)).astype(jnp.int32)
    return jnp.clip(lo, 0, pad_origin_max)


def sample_strip(padded, ix, iy, gs: GeomStatic, *, chunk: int = 128,
                 band: int = 16, width: int = 512,
                 strips_per_block: int = 64,
                 strip_dtype: str = "float32"):
    """Structured block loads: the fastrabbit "pairwise loads" analogue.

    Voxel lines are cut into x-chunks; per chunk one contiguous
    ``(band, width)`` strip is block-loaded (``dynamic_slice``) and the
    four taps are selected from it with a banded one-hot — zero XLA
    gathers of individual elements.  The strip origin is the chunk-min tap
    coordinate (exact: no monotonicity assumption needed in-graph), so all
    contributing taps are in-band by construction; out-of-band one-hot rows
    are identically zero, preserving exact zero-outside semantics.

    ``strip_dtype="bfloat16"`` carries the strips on the wire in bf16
    (halving strip bytes); the one-hot mix upcasts back to f32 and
    accumulates in f32, so only the tap *values* are rounded.  The
    default f32 path is bitwise-identical to the pre-option code.
    ``strip_dtype="int8"`` moves per-row affine codes (1 byte/pixel;
    ``padded`` may be a pre-encoded :class:`repro.quant.RowQuant` from
    the drivers' pad-time encode) and dequantises the window *after*
    the gather, at the same f32 dot the bf16 upcast uses.
    """
    wire = strip_wire_dtype(strip_dtype)
    quant = None
    if wire is jnp.int8:
        # Drivers encode once at pad time; a direct caller handing a
        # plain array pays the (per-call) encode here instead.
        quant = padded if isinstance(padded, RowQuant) \
            else quantize_rows(padded)
        padded = quant.codes
    elif isinstance(padded, RowQuant):
        raise TypeError(
            f"RowQuant-encoded image requires strip_dtype='int8'; got "
            f"{strip_dtype!r}")
    elif wire is not None:
        padded = padded.astype(wire)
    L = gs.L
    assert ix.shape == (L, L)
    chunk = _divisor_at_most(L, chunk)
    ns = L // chunk
    band = min(band, gs.n_v + 2)
    width = min(width, gs.n_u + 2)

    def reshard(a):
        return a.reshape(L * ns, chunk)

    ixs, iys = reshard(ix), reshard(iy)
    iix, iiy, sx, sy = _taps(ixs, iys)

    r0 = _strip_bounds(iys, -1.0, float(gs.n_v), gs.n_v + 2 - band)
    c0 = _strip_bounds(ixs, -1.0, float(gs.n_u), gs.n_u + 2 - width)

    rel_r = iiy + 1 - r0[:, None]                # padded-relative tap rows
    rel_c = iix + 1 - c0[:, None]

    biota = jax.lax.broadcasted_iota(jnp.int32, (chunk, band), 1)
    wiota = jax.lax.broadcasted_iota(jnp.int32, (chunk, width), 1)

    nstrips = L * ns
    spb = _divisor_at_most(nstrips, strips_per_block)

    def block(args):
        r0b, c0b, rrel, crel, sxb, syb = args

        def one(r0i, c0i, rreli, creli, sxi, syi):
            strip = jax.lax.dynamic_slice(padded, (r0i, c0i), (band, width))
            rowsel = ((biota == rreli[:, None]) * (1.0 - syi[:, None])
                      + (biota == rreli[:, None] + 1) * syi[:, None])
            colsel = ((wiota == creli[:, None]) * (1.0 - sxi[:, None])
                      + (wiota == creli[:, None] + 1) * sxi[:, None])
            if wire is None:
                rowmix = jnp.matmul(rowsel.astype(padded.dtype), strip,
                                    precision=_MXU_PRECISION)
            else:
                if quant is not None:   # int8: dequant after the gather
                    scl = jax.lax.dynamic_slice(quant.scale, (r0i,),
                                                (band,))
                    off = jax.lax.dynamic_slice(quant.offset, (r0i,),
                                                (band,))
                    strip = (strip.astype(jnp.float32) * scl[:, None]
                             + off[:, None])
                # f32 weights x (bf16 | dequantised) strip -> f32
                rowmix = jax.lax.dot_general(
                    rowsel, strip.astype(jnp.float32),
                    (((1,), (0,)), ((), ())), precision=_MXU_PRECISION,
                    preferred_element_type=jnp.float32)
            return jnp.sum(rowmix * colsel, axis=-1)       # (chunk, width)

        return jax.vmap(one)(r0b, c0b, rrel, crel, sxb, syb)

    def rb(a):
        return a.reshape((nstrips // spb, spb) + a.shape[1:])

    vals = jax.lax.map(
        block, (rb(r0), rb(c0), rb(rel_r), rb(rel_c), rb(sx), rb(sy)))
    return vals.reshape(L, ns * chunk).reshape(L, L)


def sample_strip2(padded, ix, iy, gs: GeomStatic, *, group: int = 8,
                  gband: int = 8, gwidth: int = 64,
                  groups_per_block: int = 512,
                  strip_dtype: str = "float32"):
    """Two-level micro-window sampling (beyond-paper; Pallas kernel scheme).

    Refines ``strip``: per *group* of 8 voxels, a tiny
    ``(gband, gwidth)`` window is block-loaded and the taps selected with
    VPU-width one-hot compares.  Per-voxel cost drops from
    ``2*band*width`` flops to ``~2*gband*gwidth`` — the napkin math behind
    hillclimb iteration CT-1 in EXPERIMENTS.md.  Semantics identical to
    every other strategy *provided* the window covers the group's tap
    footprint — taps past the window edge select all-zero one-hot rows
    and vanish silently, which is why :func:`reconstruct` runs the
    planner-backed :func:`validate_strip_opts` check.  (``gband`` used to
    default to 4, which silently dropped taps for standard RabbitCT-scaled
    geometries at L>=48; 8 covers every geometry in the repo's sweeps.)

    ``strip_dtype="bfloat16"``: bf16 windows on the wire, f32 upcast at
    the one-hot mix, f32 accumulate; ``strip_dtype="int8"``: per-row
    affine codes on the wire, dequantised after the gather (see
    :func:`sample_strip`).
    """
    wire = strip_wire_dtype(strip_dtype)
    quant = None
    if wire is jnp.int8:
        quant = padded if isinstance(padded, RowQuant) \
            else quantize_rows(padded)
        padded = quant.codes
    elif isinstance(padded, RowQuant):
        raise TypeError(
            f"RowQuant-encoded image requires strip_dtype='int8'; got "
            f"{strip_dtype!r}")
    elif wire is not None:
        padded = padded.astype(wire)
    L = gs.L
    group = _divisor_at_most(L, group)
    ng = L // group
    gband = min(gband, gs.n_v + 2)
    gwidth = min(gwidth, gs.n_u + 2)
    ixg = ix.reshape(L * ng, group)
    iyg = iy.reshape(L * ng, group)
    iix, iiy, sx, sy = _taps(ixg, iyg)

    r0 = _strip_bounds(iyg, -1.0, float(gs.n_v), gs.n_v + 2 - gband)
    c0 = _strip_bounds(ixg, -1.0, float(gs.n_u), gs.n_u + 2 - gwidth)
    rel_r = iiy + 1 - r0[:, None]
    rel_c = iix + 1 - c0[:, None]

    biota = jax.lax.broadcasted_iota(jnp.int32, (group, gband), 1)
    wiota = jax.lax.broadcasted_iota(jnp.int32, (group, gwidth), 1)

    ngroups = L * ng
    gpb = _divisor_at_most(ngroups, groups_per_block)

    def block(args):
        r0b, c0b, rrel, crel, sxb, syb = args

        def one(r0i, c0i, rreli, creli, sxi, syi):
            win = jax.lax.dynamic_slice(padded, (r0i, c0i), (gband, gwidth))
            rowsel = ((biota == rreli[:, None]) * (1.0 - syi[:, None])
                      + (biota == rreli[:, None] + 1) * syi[:, None])
            colsel = ((wiota == creli[:, None]) * (1.0 - sxi[:, None])
                      + (wiota == creli[:, None] + 1) * sxi[:, None])
            if wire is None:
                rowmix = jnp.matmul(rowsel.astype(padded.dtype), win,
                                    precision=_MXU_PRECISION)
            else:
                if quant is not None:   # int8: dequant after the gather
                    scl = jax.lax.dynamic_slice(quant.scale, (r0i,),
                                                (gband,))
                    off = jax.lax.dynamic_slice(quant.offset, (r0i,),
                                                (gband,))
                    win = (win.astype(jnp.float32) * scl[:, None]
                           + off[:, None])
                # f32 weights x (bf16 | dequantised) window -> f32
                rowmix = jax.lax.dot_general(
                    rowsel, win.astype(jnp.float32),
                    (((1,), (0,)), ((), ())), precision=_MXU_PRECISION,
                    preferred_element_type=jnp.float32)
            return jnp.sum(rowmix * colsel, axis=-1)       # (group, gwidth)

        return jax.vmap(one)(r0b, c0b, rrel, crel, sxb, syb)

    def rb(a):
        return a.reshape((ngroups // gpb, gpb) + a.shape[1:])

    vals = jax.lax.map(
        block, (rb(r0), rb(c0), rb(rel_r), rb(rel_c), rb(sx), rb(sy)))
    return vals.reshape(L, L)


# ----------------------------------------------------------------------
# Part 3 — weighting + voxel update (streaming)
# ----------------------------------------------------------------------

def contribution(val, w, clip_mask=None):
    """``val / w**2``: one projection's additive contribution to a plane.

    ``w <= 0`` voxels (behind the source; impossible for sane geometries
    but reachable in property-test sweeps) contribute zero.  Split out of
    :func:`accumulate` so the batched plane update can sum several
    projections' contributions before touching the plane once.
    """
    r = jnp.where(w > _EPS_W, 1.0 / w, 0.0)
    contrib = val * (r * r)
    if clip_mask is not None:
        contrib = contrib * clip_mask
    return contrib


def accumulate(plane, val, w, clip_mask=None):
    """``VOL += val / w**2`` with the reciprocal already amortised."""
    return plane + contribution(val, w, clip_mask).astype(plane.dtype)


# ----------------------------------------------------------------------
# Drivers
# ----------------------------------------------------------------------

def _pad_image(image):
    return jnp.pad(image, ((1, 1), (1, 1)))


def _wire_padded(padded, opts):
    """Encode the padded image(s) once at pad time for the int8 wire.

    The drivers call this right after :func:`_pad_image`, *outside* the
    z-plane ``fori_loop`` — the encode (a ``lax.scan`` along each row's
    columns carrying the error-feedback residual) is loop-invariant but
    XLA will not hoist it out of a ``while``, so it must happen here,
    not inside the samplers.  Every other wire dtype passes through
    untouched (the f32 path stays bitwise-identical; bf16 casts inside
    the samplers as before).
    """
    if opts.get("strip_dtype") != "int8":
        return padded
    if padded.ndim == 3:                # stacked projections
        return jax.vmap(quantize_rows)(padded)
    return quantize_rows(padded)


def _sample(strategy, image, padded, ix, iy, gs, opts):
    if strategy == "scalar":
        return sample_scalar(image, ix, iy, gs)
    if strategy == "gather":
        return sample_gather(padded, ix, iy, gs)
    if strategy == "onehot":
        return sample_onehot(padded, ix, iy, gs, **opts)
    if strategy == "strip":
        return sample_strip(padded, ix, iy, gs, **opts)
    if strategy == "strip2":
        return sample_strip2(padded, ix, iy, gs, **opts)
    raise ValueError(f"unknown strategy {strategy!r}; want {STRATEGIES}")


def backproject_plane(plane, image, padded, A, gs: GeomStatic, z,
                      strategy: str = "strip2", clip_mask=None, **opts):
    """Back-project one projection into one z-plane of the volume."""
    ix, iy, w = plane_coords(A, gs, z)
    val = _sample(strategy, image, padded, ix, iy, gs, opts)
    return accumulate(plane, val, w, clip_mask)


def backproject_plane_batch(plane, images, padded, mats, gs: GeomStatic, z,
                            strategy: str = "strip2", clip_mask=None,
                            **opts):
    """Back-project a *batch* of projections into one z-plane.

    The inverted loop nest (DESIGN.md §7): the plane is read once,
    receives the summed contribution of every projection in the batch
    (Part 1 vmapped over the batch), and is written once — volume
    traffic per reconstruction drops from ``2·n_proj·L³`` to
    ``2·ceil(n_proj/pbatch)·L³`` elements.  Summation order per voxel is
    projection-major within the batch, so results match the sequential
    path to fp32 rounding, not bit-for-bit.
    """

    def one(image, pimg, A):
        ix, iy, w = plane_coords(A, gs, z)
        val = _sample(strategy, image, pimg, ix, iy, gs, opts)
        return contribution(val, w, clip_mask)

    contribs = jax.vmap(one)(images, padded, mats)
    return plane + jnp.sum(contribs, axis=0).astype(plane.dtype)


def _explicit_plan(strategy: str, opts: dict, pbatch: int | None = None):
    """Strictly validated plan for an explicitly named strategy.

    Lazy import: ``repro.dispatch`` depends on this module, so the plan
    type is only pulled in at call time (same pattern as the old
    ``repro.tune.cache`` imports).
    """
    from repro.dispatch.plan import ExecutionPlan

    return ExecutionPlan.explicit(strategy, opts, pbatch)


@functools.partial(jax.jit, static_argnames=("gs", "plan"))
def _backproject_one_jit(volume, image, A, gs, plan):
    opts = plan.jnp_opts()
    padded = _wire_padded(_pad_image(image), opts)

    def body(z, vol):
        plane = jax.lax.dynamic_index_in_dim(vol, z, axis=0, keepdims=False)
        plane = backproject_plane(plane, image, padded, A, gs, z,
                                  plan.strategy, **opts)
        return jax.lax.dynamic_update_index_in_dim(vol, plane, z, axis=0)

    return jax.lax.fori_loop(0, gs.L, body, volume)


def backproject_one(volume, image, A, geom: Geometry | GeomStatic,
                    strategy: str = "strip2", **opts):
    """Add one projection's contribution to ``volume`` (``(L, L, L)``)."""
    gs = geom if isinstance(geom, GeomStatic) else GeomStatic.of(geom)
    plan = _explicit_plan(strategy, opts)
    return _backproject_one_jit(volume, jnp.asarray(image),
                                jnp.asarray(A, jnp.float32), gs, plan)


def _backproject_batch_body(volume, images, mats, gs: GeomStatic, plan,
                            z0):
    """Volume-resident update for one projection batch (plane-major).

    ``volume`` may be a z-slab: the plane loop runs over
    ``volume.shape[0]`` and ``z0`` is the slab's first global z index
    (traced; the sharded pipeline passes its rank offset).  ``plan`` is
    the resolved :class:`repro.dispatch.ExecutionPlan`.  Callers jit.
    """
    strategy, opts = plan.strategy, plan.jnp_opts()
    padded = _wire_padded(jax.vmap(_pad_image)(images), opts)

    def body(zi, vol):
        plane = jax.lax.dynamic_index_in_dim(vol, zi, axis=0, keepdims=False)
        plane = backproject_plane_batch(plane, images, padded, mats, gs,
                                        z0 + zi, strategy, **opts)
        return jax.lax.dynamic_update_index_in_dim(vol, plane, zi, axis=0)

    return jax.lax.fori_loop(0, volume.shape[0], body, volume)


def _stream_batches(projections, matrices, volume, pbatch: int, call):
    """Fold the projection stack into ``volume``, ``pbatch`` at a time.

    The one batch-chunking driver every batched backend shares (jnp here,
    the Pallas wrapper in ``kernels/backproject_ops.py``): full batches
    run under a ``fori_loop`` (one static batch shape), and a ``pbatch ∤
    n_proj`` remainder runs as one final smaller batch — shapes are
    static because ``n_proj`` is known at trace time.  ``call(vol, imgs,
    mats)`` performs one volume pass for one batch.

    ``projections`` may be any pytree whose leaves share the leading
    projection axis (a plain stacked array, or the ``(codes, scales)``
    pair the int8 kernel wire streams) — each batch is the same
    leading-axis slice of every leaf.  A bare array is a single leaf,
    so the f32 path lowers to the identical ``dynamic_slice`` as
    before.
    """
    n_proj = jax.tree.leaves(projections)[0].shape[0]
    pbatch = max(1, min(int(pbatch), n_proj)) if n_proj else 1
    n_full = n_proj // pbatch

    def body(b, vol):
        imgs = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, b * pbatch, pbatch),
            projections)
        mats = jax.lax.dynamic_slice_in_dim(matrices, b * pbatch, pbatch)
        return call(vol, imgs, mats)

    if n_full:
        volume = jax.lax.fori_loop(0, n_full, body, volume)
    if n_proj - n_full * pbatch:
        volume = call(volume,
                      jax.tree.map(lambda a: a[n_full * pbatch:],
                                   projections),
                      matrices[n_full * pbatch:])
    return volume


def _reconstruct_batched(projections, matrices, volume, gs: GeomStatic,
                         plan, z0):
    """Stream all projections through ``volume``, ``plan.pbatch`` at a
    time.

    The inverted loop nest: batches outer, z-planes inner, projections
    innermost (vmapped) — each batch streams the volume through memory
    exactly once.
    """
    return _stream_batches(
        projections, matrices, volume, plan.pbatch,
        lambda vol, imgs, mats: _backproject_batch_body(
            vol, imgs, mats, gs, plan, z0))


def backproject_batch(volume, images, mats, geom: Geometry | GeomStatic,
                      strategy: str = "strip2",
                      pbatch: int = DEFAULT_PBATCH, **opts):
    """Add a stack of projections to ``volume``, ``pbatch`` per pass.

    The batched analogue of :func:`backproject_one` (a
    :func:`fold_projections` at ``z0=0``, sharing its jitted body):
    ``images`` is ``(n_proj, n_v, n_u)``, ``mats`` ``(n_proj, 3, 4)``.
    Unlike :func:`reconstruct` this does not validate strip windows —
    callers timing raw kernels (the tuner sweep) validate once
    themselves.
    """
    gs = geom if isinstance(geom, GeomStatic) else GeomStatic.of(geom)
    plan = _explicit_plan(strategy, opts, int(pbatch))
    return _fold_jit(jnp.asarray(volume), jnp.asarray(images),
                     jnp.asarray(mats, jnp.float32), jnp.int32(0), gs,
                     plan)


@functools.partial(jax.jit, static_argnames=("gs", "plan"))
def _fold_jit(volume, images, mats, z0, gs, plan):
    return _reconstruct_batched(images, mats, volume, gs, plan, z0)


def fold_projections(volume, images, mats, geom: Geometry | GeomStatic,
                     strategy: str = "strip2",
                     pbatch: int = DEFAULT_PBATCH, z0=0, **opts):
    """Incremental fold: add a projection *chunk* to an existing volume.

    The streaming entry point (DESIGN.md §8): unlike
    :func:`backproject_batch` the z offset ``z0`` is a traced argument,
    so one compiled fold serves every z-slab of a sharded stream, and
    ``volume`` may be a partial accumulation from earlier chunks — a
    reconstruction becomes any sequence of folds whose chunks cover the
    projection set exactly once, in any arrival order (fp32 summation
    order differs, so cross-order agreement is ~1e-5, not bitwise).
    Chunks longer than ``pbatch`` stream through
    :func:`_stream_batches` exactly like :func:`reconstruct`.

    Strip windows are validated against the host planner (memoised)
    when ``geom`` is a full :class:`Geometry`; a bare
    :class:`GeomStatic` caller must have validated the ``(geometry,
    matrices, window)`` triple itself — the planner needs the full
    acquisition description.
    """
    if isinstance(geom, Geometry):
        gs = GeomStatic.of(geom)
        validate_strip_opts(geom, mats, strategy, opts)
    else:
        gs = geom
    images = jnp.asarray(images)
    n = int(images.shape[0])
    plan = _explicit_plan(strategy, opts,
                          max(1, min(int(pbatch), n)) if n else 1)
    return _fold_jit(jnp.asarray(volume), images,
                     jnp.asarray(mats, jnp.float32),
                     jnp.asarray(z0, jnp.int32), gs, plan)


# Memo of (geometry, strategy, window, matrices) combinations already
# proven safe — validation is host-side numpy and should be paid once per
# distinct problem, not once per reconstruct() call.
_VALIDATED_STRIPS: set = set()


def validate_strip_opts(geom: Geometry, matrices, strategy: str,
                        opts: dict) -> None:
    """Planner-backed check that strip/strip2 windows cover every footprint.

    The jnp ``strip``/``strip2`` strategies select taps from a statically
    sized window with one-hot compares; a tap outside the window selects
    an all-zero row and is *silently dropped*.  The Pallas path guards
    this with ``validate_strip_config``; this is the same guard for the
    jnp paths, reusing the host planner (:func:`repro.core.clipping
    .plan_strips`, exact by the monotone-beam property).  Raises
    ``ValueError`` with the required window sizes when the static config
    is too small.  No-op for strategies without windows.
    """
    if strategy == "strip":
        chunk = _divisor_at_most(geom.L, int(opts.get("chunk", 128)))
        band = min(int(opts.get("band", 16)), geom.n_v + 2)
        width = min(int(opts.get("width", 512)), geom.n_u + 2)
        what = f"strip (chunk={chunk}, band={band}, width={width})"
    elif strategy == "strip2":
        chunk = _divisor_at_most(geom.L, int(opts.get("group", 8)))
        band = min(int(opts.get("gband", 8)), geom.n_v + 2)
        width = min(int(opts.get("gwidth", 64)), geom.n_u + 2)
        what = f"strip2 (group={chunk}, gband={band}, gwidth={width})"
    else:
        return
    if isinstance(matrices, jax.core.Tracer):
        return                      # in-trace call: host check impossible
    mats = np.asarray(matrices, np.float64).reshape(-1, 3, 4)
    key = (GeomStatic.of(geom), strategy, chunk, band, width,
           hashlib.sha1(mats.tobytes()).hexdigest())
    if key in _VALIDATED_STRIPS:
        return
    from .clipping import plan_strips

    need_band = need_width = 0
    for A in mats:
        plan = plan_strips(geom, A, chunk=chunk)
        need_band = max(need_band, plan.required_band)
        need_width = max(need_width, plan.required_width)
    # A full-detector window can never lose a tap: its origin clamps to 0
    # and it spans the whole padded image, so the planner's margin must
    # not push the requirement past the satisfiable maximum.
    need_band = min(need_band, geom.n_v + 2)
    need_width = min(need_width, geom.n_u + 2)
    if band < need_band or width < need_width:
        raise ValueError(
            f"{what} does not cover the chunk tap footprint for this "
            f"geometry; need at least (band={need_band}, "
            f"width={need_width}) — undersized windows drop taps "
            f"silently")
    if len(_VALIDATED_STRIPS) >= 4096:   # bound a long-lived process
        _VALIDATED_STRIPS.clear()
    _VALIDATED_STRIPS.add(key)


@functools.partial(jax.jit, static_argnames=("gs", "plan"))
def _reconstruct_jit(projections, matrices, volume, gs, plan):
    return _reconstruct_batched(projections, matrices, volume, gs, plan,
                                jnp.int32(0))


def reconstruct(projections, matrices, geom: Geometry, *,
                strategy: str = "strip2", volume=None,
                pbatch: int | None = None, plan=None, **opts):
    """Full reconstruction: stream every projection into the volume.

    ``projections`` are the *filtered* images ``(n_proj, n_v, n_u)``;
    ``matrices`` the stacked ``(n_proj, 3, 4)`` RabbitCT matrices.  The
    loop nest is batch-major (DESIGN.md §7): projections are folded into
    the volume ``pbatch`` at a time, so the volume streams through
    memory ``ceil(n_proj / pbatch)`` times instead of ``n_proj`` times.
    ``pbatch=None`` takes the resolved plan's depth
    (:data:`DEFAULT_PBATCH` when nothing tuned); ``pbatch=1`` recovers
    the per-projection nest.

    Resolution happens in ONE place — the process dispatcher
    (:mod:`repro.dispatch`, DESIGN.md §11): ``strategy="auto"`` is a
    cache hit, an in-situ first-call selection, or a logged ``strip2``
    fallback; explicit strategies validate their options strictly.  A
    pre-resolved ``plan`` (:class:`repro.dispatch.ExecutionPlan`)
    bypasses resolution entirely — ``strategy``/``opts``/``pbatch`` are
    then ignored.  For ``strip``/``strip2`` the static windows are
    validated against the host planner before any device work (see
    :func:`validate_strip_opts`).

    The jitted body is a module-level function with ``(gs, plan)``
    static, so repeated calls with one problem hit one compile-cache
    entry (``_reconstruct_jit._cache_size()``).
    """
    gs = GeomStatic.of(geom)
    if plan is None:
        from repro.dispatch import get_dispatcher

        plan = get_dispatcher().resolve(geom, strategy, opts,
                                        pbatch=pbatch)
    validate_strip_opts(geom, matrices, plan.strategy, plan.jnp_opts())
    projections = jnp.asarray(projections)
    matrices = jnp.asarray(matrices, jnp.float32)
    if volume is None:
        volume = jnp.zeros((gs.L, gs.L, gs.L), dtype=jnp.float32)
    return _reconstruct_jit(projections, matrices, volume, gs, plan)
