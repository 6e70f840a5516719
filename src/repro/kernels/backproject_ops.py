"""Public jit'd wrapper for the Pallas back projection kernel.

Handles everything the kernel assumes away: zero-padding the projection to
the 1-pixel border the zero-outside semantics rely on, rounding the padded
buffer up so every (band, width) strip slice is in-bounds, validating the
static strip size against the host planner, and running the kernels in
interpret mode off-TPU so the same entry point works everywhere (kernels
are *validated* on CPU, *targeted* at TPU).  On a TPU backend the
kernels always compile: there is no interpret fallback to hide a kernel
the chip's compiler refuses.
"""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.backproject import (DEFAULT_PBATCH, GeomStatic,
                                    strip_wire_dtype)
from repro.core.clipping import (_MARGIN, _round8, _round128,
                                 shared_window_requirement)
from repro.core.geometry import Geometry

from .backproject import (_EPS_W, _LANE, SUBLANE, backproject_volume_pallas,
                          backproject_volume_pallas_batch, strip_window)

__all__ = ["pallas_backproject_one", "pallas_backproject_batch",
           "validate_batch", "validate_strip_config", "shared_window_dims",
           "clamp_tiles", "tile_col_blocks", "tile_corner_extents",
           "tile_coverage_need"]


def _interpret() -> bool:
    """Interpret mode exactly when the backend is not a TPU."""
    return jax.default_backend() != "tpu"


def clamp_tiles(gs: GeomStatic, ty: int, chunk: int, band: int,
                width: int) -> tuple[int, int, int, int]:
    """Geometry-clamp the kernel tile parameters.

    The single definition both :func:`pallas_backproject_one` and the
    autotuner's candidate validation go through, so a config validated
    by the sweep is exactly the config the kernel will run.
    """
    ty = min(ty, gs.L)
    chunk = min(chunk, gs.L)
    band = min(band, max(8, gs.n_v + 2 + (-(gs.n_v + 2)) % 8))
    width = min(width, max(128, gs.n_u + 2 + (-(gs.n_u + 2)) % 128))
    return ty, chunk, band, width


def _padded_dims(n_v: int, n_u: int, band: int, width: int,
                 itemsize: int) -> tuple[int, int]:
    """Padded projection dims: a 1-pixel zero border, at least the strip
    window, rounded to the wire's ``(sublane, 128)`` tile so every
    tile-aligned window slice stays in-bounds."""
    wband, wwidth = strip_window(band, width, itemsize)
    rows = max(wband, n_v + 2)
    rows += (-rows) % SUBLANE[itemsize]
    cols = max(wwidth, n_u + 2)
    cols += (-cols) % 128
    return rows, cols


def _pad_up(images, band: int, width: int, itemsize: int | None = None):
    """Zero-border one projection ``(n_v, n_u)`` or a stack ``(P, n_v,
    n_u)`` and round it up to :func:`_padded_dims` for a wire of
    ``itemsize`` bytes (default: the images' own)."""
    n_v, n_u = images.shape[-2:]
    rows, cols = _padded_dims(n_v, n_u, band, width,
                              itemsize or images.dtype.itemsize)
    lead = ((0, 0),) * (images.ndim - 2)
    return jnp.pad(images, lead + ((1, rows - n_v - 1),
                                   (1, cols - n_u - 1)))


def _wire_pad(images, band: int, width: int, strip_dtype: str):
    """Put one projection or a stack on the strip wire.

    Returns ``(padded, scales)``.  f32 passes through bit-for-bit, bf16
    casts before padding, and int8 pads the f32 image to the 1-byte tile
    shape *first*, then row-encodes it (:func:`repro.quant.quantize_rows`
    — per-row affine grid, residual feedback along the row), so pad
    rows/cols are all-zero rows that decode to exactly 0.0 and the codes
    slab is directly DMA-sliceable.  ``scales`` is ``None`` off the int8
    wire, else f32 ``(..., rows, 2)`` — ``scale, offset`` per padded
    row, the layout :func:`repro.kernels.backproject._dequant_strip`
    reads.
    """
    wire = strip_wire_dtype(strip_dtype)
    if wire is not jnp.int8:
        if wire is not None:
            images = images.astype(wire)
        return _pad_up(images, band, width), None
    from repro.quant import quantize_rows

    padded = _pad_up(images.astype(jnp.float32), band, width, itemsize=1)
    encode = quantize_rows if padded.ndim == 2 else jax.vmap(quantize_rows)
    rq = encode(padded)
    return rq.codes, jnp.stack([rq.scale, rq.offset], axis=-1)


# Tiles a block of the corner pass holds: each temporary of a block
# stays in cache, where whole-cube temporaries would not.
_CORNER_BLOCK = 1 << 15


def tile_corner_extents(gs: GeomStatic, A, *, ty: int, chunk: int,
                        dtype):
    """The kernel's corner rule, one block of z-planes at a time.

    Per ``(z, y-group, x-chunk)`` tile, the projective map at the tile's
    four corner voxels (:func:`repro.kernels.backproject._strip_origin`)
    in ``dtype``, in the kernel's order of operations.  Yields, block
    by block of z-planes in order, ``(ix_lo, ix_hi, iy_lo, iy_hi,
    w_hi)``: the extremes over the corners, each shaped ``(planes,
    L // ty, L // chunk)``.  Where ``w <= _EPS_W`` a corner's ``ix`` and
    ``iy`` read 0, as the kernel's reciprocal trick gives.
    """
    dt = np.dtype(dtype).type
    A = np.asarray(A, dt).reshape(3, 4)
    O, MM = dt(gs.O), dt(gs.MM)
    wz = O + np.arange(gs.L, dtype=dt) * MM
    y0 = np.arange(0, gs.L, ty, dtype=dt)[:, None]
    x0 = np.arange(0, gs.L, chunk, dtype=dt)
    corners = []               # per corner: the x and y terms of each row
    for dy in (0, ty - 1):
        wy = O + (y0 + dt(dy)) * MM
        for dx in (0, chunk - 1):
            wx = O + (x0 + dt(dx)) * MM
            corners.append([wx * a[0] + wy * a[1] for a in A])
    planes = max(1, _CORNER_BLOCK // (y0.size * x0.size))
    for z0 in range(0, gs.L, planes):
        zs = wz[z0:z0 + planes, None, None]
        ext = None
        for xy in corners:
            u, v, w = (zs * a[2] + t for a, t in zip(A, xy))
            for row, a in zip((u, v, w), A):
                row += a[3]
            with np.errstate(divide="ignore"):
                r = np.reciprocal(w)
            r[w <= _EPS_W] = 0
            u *= r
            v *= r
            if ext is None:
                ext = [u, u.copy(), v, v.copy(), w]
            else:
                for acc, val, op in zip(ext, (u, u, v, v, w),
                                        (np.minimum, np.maximum) * 2
                                        + (np.maximum,)):
                    op(acc, val, out=acc)
        yield tuple(ext)


def _f32_slack(gs: GeomStatic, A: np.ndarray, w_min: float
               ) -> tuple[float, float]:
    """Pixels by which the kernel's float32 ``ix`` and ``iy`` of any
    voxel can miss their float64 values, where ``w >= w_min``.

    Each float32 row of the projective map is a sum of three products of
    rounded coordinates and rounded matrix entries, and a rounded
    constant: it misses its exact value by less than ``10 u S`` with ``u
    = 2**-24`` and ``S`` the sum of the terms' magnitudes at the
    volume's largest coordinate.  Then ``ix = u' / w`` misses by less
    than ``(err(u') + |ix| err(w)) / w`` plus two roundings of ``ix``;
    ``|ix| <= n_u + 1`` wherever the clip to ``[-1, n_u]`` leaves it.
    The slack kept is at least twice that.
    """
    u = 2.0 ** -24
    mag = np.abs(A[:, :3]).sum(axis=1) * abs(gs.O) + np.abs(A[:, 3])
    return tuple(20 * u * ((mag[i] + (n + 1) * mag[2]) / w_min + n + 1)
                 for i, n in ((0, gs.n_u), (1, gs.n_v)))


def tile_coverage_need(gs: GeomStatic, A, *, ty: int, chunk: int
                       ) -> tuple[int, int]:
    """The ``(band, width)`` that cover every tile's taps, from the
    tile's four corners.

    The argument (DESIGN.md §3).  ``w`` is affine, so it is above
    ``_EPS_W`` at every voxel exactly when it is at the volume's eight
    corner voxels; then over a tile ``ix`` and ``iy`` are
    linear-fractional in ``(x, y)``, so quasilinear, and their extremes
    lie at the tile's corners.  The float64 corner extremes, widened by
    :func:`_f32_slack` and clipped to ``[-1, n]``, floor to ``lo`` and
    ``hi``, and every float32 ``iy`` of the tile floors into ``[lo,
    hi]``.  A voxel's taps are padded rows ``floor(iy) + 1`` and
    ``floor(iy) + 2``, so rows ``lo + 1 .. hi + 2``.  The kernel's window
    starts at the floor of its own float32 corner minimum, in ``[lo, lo +
    1]``, and by :func:`strip_window`'s rounding holds ``band + 1`` rows
    from there (or reaches the padded image's end), so ``band >= hi - lo
    + 2`` covers every tap.  The need ``hi - lo + 1 + _MARGIN`` is that
    and one spare row: the planner's margin for the floor tap pair and
    the rounding between the two precisions.  Columns likewise.  A
    need beyond the padded detector (``n + 2``) reads as that: a window
    so wide is clamped to the padded image's origin and holds all of it.
    Inactive tiles — no corner extreme overlaps the padded detector by
    the kernel's test, widened by the slack — need nothing.

    Raises ``ValueError`` where a voxel of the volume lies at or behind
    the source plane (``w <= _EPS_W``): a tile across that plane has no
    corner bound, and the kernel, which places both its window and the
    column blocks it contracts from the corners, can drop taps there
    whatever the window's size.
    """
    A = np.asarray(A, np.float64).reshape(3, 4)
    ends = gs.O + np.array([0.0, gs.L - 1]) * gs.MM
    cube = np.stack(np.meshgrid(ends, ends, ends, [1.0]), -1).reshape(-1, 4)
    w_min = float((cube @ A[2]).min())
    if w_min <= _EPS_W:
        raise ValueError(
            f"projection puts part of the volume at or behind the source "
            f"plane (w={w_min:.3g} at a corner voxel); the kernel's corner "
            f"rule does not cover such tiles")
    s_c, s_r = _f32_slack(gs, A, w_min)
    need_r = need_c = 0
    for ix_lo, ix_hi, iy_lo, iy_hi, _ in tile_corner_extents(
            gs, A, ty=ty, chunk=chunk, dtype=np.float64):
        active = ((ix_lo < gs.n_u + s_c) & (ix_hi > -1 - s_c)
                  & (iy_lo < gs.n_v + s_r) & (iy_hi > -1 - s_r))
        if not active.any():
            continue
        span_r = (np.floor(np.clip(iy_hi + s_r, -1, gs.n_v))
                  - np.floor(np.clip(iy_lo - s_r, -1, gs.n_v)))
        span_c = (np.floor(np.clip(ix_hi + s_c, -1, gs.n_u))
                  - np.floor(np.clip(ix_lo - s_c, -1, gs.n_u)))
        need_r = max(need_r, int(span_r[active].max()))
        need_c = max(need_c, int(span_c[active].max()))
    return (min(need_r + 1 + _MARGIN, gs.n_v + 2),
            min(need_c + 1 + _MARGIN, gs.n_u + 2))


def validate_strip_config(geom: Geometry, A: np.ndarray, *, ty: int,
                          chunk: int, band: int, width: int) -> None:
    """Host-side check that (band, width) covers every tile footprint.

    The need comes from each tile's four corners
    (:func:`tile_coverage_need`), the rule the kernel places its window
    by.  Raises with the required sizes if the static config is too
    small, and refuses a projection that puts a voxel at or behind the
    source plane — silent tap loss is never possible.
    """
    need_band, need_width = tile_coverage_need(
        GeomStatic.of(geom), A, ty=ty, chunk=chunk)
    if band < need_band or width < need_width:
        raise ValueError(
            f"strip config (band={band}, width={width}) does not cover the "
            f"tile footprint; need at least (band={need_band}, "
            f"width={need_width}) for ty={ty}, chunk={chunk}")


def tile_col_blocks(gs: GeomStatic, A, *, ty: int, chunk: int, band: int,
                    width: int):
    """The kernel's column-block rule for every tile of one projection.

    The host's float32 copy of the kernel's corner rule
    (:func:`repro.kernels.backproject._strip_origin` and ``_col_blocks``),
    vectorised over the ``(L, L // ty, L // chunk)`` tiles: each tile's
    four corner voxels (:func:`tile_corner_extents`) give its tap
    columns, the window's aligned column ``c0`` and the first and last
    128-column block of the window that the kernel contracts.
    ``band``/``width`` are the footprint dims the wrappers take; the
    window is :func:`strip_window` of them.  Returns ``(active, c0,
    kb_lo, kb_hi)``, each shaped by tile; ``active`` is the kernel's tile
    test, on the corners.
    """
    wwidth = strip_window(band, width, 4)[1]
    pad_cols = _padded_dims(gs.n_v, gs.n_u, band, width, 4)[1]
    last = wwidth // _LANE - 1
    out = []
    for ix_lo, ix_hi, iy_lo, iy_hi, w_hi in tile_corner_extents(
            gs, A, ty=ty, chunk=chunk, dtype=np.float32):
        active = ((ix_lo < gs.n_u) & (ix_hi > -1) & (iy_lo < gs.n_v)
                  & (iy_hi > -1) & (w_hi > _EPS_W))
        # Clipping commutes with the corner min/max: clip once per tile.
        lo = np.floor(np.clip(ix_lo, -1, gs.n_u)).astype(np.int64)
        hi = np.floor(np.clip(ix_hi, -1, gs.n_u)).astype(np.int64) + 3
        c0 = np.clip(lo, 0, pad_cols - wwidth) // _LANE * _LANE
        out.append((active, c0, np.clip((lo - c0) // _LANE, 0, last),
                    np.clip((hi - c0) // _LANE, 0, last)))
    return tuple(np.concatenate(parts) for parts in zip(*out))


def _count_col_blocks(gs: GeomStatic, A, *, ty: int, chunk: int, band: int,
                      width: int, slabs: int = 1) -> np.ndarray:
    """Count the column blocks the kernel contracts for one projection
    (``kernel.col_blocks``) and those of the whole window over the same
    active tiles (``kernel.col_blocks_window``); returns the contracted
    blocks of each of ``slabs`` equal z-slabs."""
    active, _, kb_lo, kb_hi = tile_col_blocks(gs, A, ty=ty, chunk=chunk,
                                              band=band, width=width)
    blocks = np.where(active, kb_hi - kb_lo + 1, 0)
    obs.count("kernel.col_blocks", int(blocks.sum()))
    obs.count("kernel.col_blocks_window",
              int(active.sum()) * (strip_window(band, width, 4)[1] // _LANE))
    return blocks.reshape(slabs, -1).sum(axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("gs", "ty", "chunk", "band", "width",
                     "double_buffer", "db_depth", "strip_dtype",
                     "interpret"))
def _run(volume, image, A, gs: GeomStatic, ty, chunk, band, width,
         double_buffer, db_depth, strip_dtype, interpret):
    padded, scales = _wire_pad(image, band, width, strip_dtype)
    return backproject_volume_pallas(
        volume, padded, A,
        o_mm=(gs.O, gs.MM), n_u=gs.n_u, n_v=gs.n_v,
        ty=ty, chunk=chunk, band=band, width=width,
        double_buffer=double_buffer, db_depth=db_depth, scales=scales,
        interpret=interpret)


def pallas_backproject_one(volume, image, A, geom: Geometry | GeomStatic,
                           *, ty: int = 8, chunk: int = 128, band: int = 16,
                           width: int = 512, double_buffer: bool = False,
                           db_depth: int = 2,
                           strip_dtype: str = "float32",
                           validate: bool = False,
                           strategy: str = "fixed"):
    """Add one projection to ``volume`` using the Pallas kernel.

    ``strip_dtype="bfloat16"`` carries the padded projection (and so
    every strip DMA and the VMEM scratch) in bf16; the kernels already
    upcast the window to f32 at the one-hot matmul and accumulate in
    f32, so only the tap values are rounded.  ``strip_dtype="int8"``
    encodes the padded projection once (:func:`_wire_pad` — per-row
    affine codes + error feedback) and moves 1-byte codes on every strip
    DMA, dequantising in-register next to the accumulator.  The f32
    default path is bitwise-unchanged.

    The kernel compiles on a TPU backend and runs in interpret mode
    elsewhere.  ``validate=True`` runs the host planner check first
    (cheap; recommended once per geometry).  ``double_buffer=True``
    overlaps strip DMA with compute, ``db_depth`` slots in rotation.

    ``strategy="auto"`` pulls the tile parameters (``ty``/``chunk``/
    ``band``/``width``/``double_buffer``/``db_depth``) from the process
    dispatcher (:mod:`repro.dispatch` — cache hit, in-situ first-call
    selection, or a logged fallback) for this geometry/backend/device;
    when no decision carries a kernel config the explicitly passed
    parameters stand.  (``pbatch`` is the one tuned key with no
    single-projection meaning — there is nothing to batch here; batch
    callers resolve it through :func:`pallas_backproject_batch`.)
    """
    gs = geom if isinstance(geom, GeomStatic) else GeomStatic.of(geom)
    if strategy == "auto":
        from repro.dispatch import get_dispatcher

        tuned = get_dispatcher().resolve_kernel(geom)
        if tuned is not None:
            ty = int(tuned.get("ty", ty))
            chunk = int(tuned.get("chunk", chunk))
            band = int(tuned.get("band", band))
            width = int(tuned.get("width", width))
            double_buffer = bool(tuned.get("double_buffer", double_buffer))
            # A tuned pipeline decision was timed at a specific depth;
            # resolve it with the flag (same rotation ledger on the
            # single-projection kernel as on the batched one).
            db_depth = int(tuned.get("db_depth", db_depth))
            strip_dtype = str(tuned.get("strip_dtype", strip_dtype))
    elif strategy != "fixed":
        raise ValueError(
            f"unknown strategy {strategy!r}; want 'fixed' or 'auto'")
    strip_wire_dtype(strip_dtype)   # loud on typos, before any tracing
    ty, chunk, band, width = clamp_tiles(gs, ty, chunk, band, width)
    if validate:
        if isinstance(geom, GeomStatic):
            raise ValueError("validate=True needs the full Geometry")
        with obs.span("planner.check") as rec:
            validate_strip_config(geom, np.asarray(A, np.float64), ty=ty,
                                  chunk=chunk, band=band, width=width)
            if rec is not None:
                _count_col_blocks(gs, A, ty=ty, chunk=chunk, band=band,
                                  width=width)
    return _run(jnp.asarray(volume), jnp.asarray(image),
                jnp.asarray(A, jnp.float32), gs, ty, chunk, band, width,
                double_buffer, int(db_depth), strip_dtype, _interpret())


@functools.partial(
    jax.jit,
    static_argnames=("gs", "ty", "chunk", "band", "width", "pbatch",
                     "double_buffer", "db_depth", "shared_window",
                     "strip_dtype", "interpret", "planes"))
def _run_batched(volume, images, mats, gs: GeomStatic, ty, chunk, band,
                 width, pbatch, double_buffer, db_depth, shared_window,
                 strip_dtype, interpret, z0=0, row0=0, planes=None):
    from repro.core.backproject import _stream_batches

    # With shared_window the (band, width) passed here are already the
    # superset-window dims sized by the caller.  Encode once for the
    # whole stack; _stream_batches slices the (codes, scales) pair per
    # batch as one pytree.
    padded, scales = _wire_pad(images, band, width, strip_dtype)

    def call(vol, imgs, A):
        codes, scl = imgs
        return backproject_volume_pallas_batch(
            vol, codes, A, o_mm=(gs.O, gs.MM), n_u=gs.n_u, n_v=gs.n_v,
            ty=ty, chunk=chunk, band=band, width=width,
            double_buffer=double_buffer, db_depth=db_depth,
            shared_window=shared_window, scales=scl, z0=z0, row0=row0,
            planes=planes, interpret=interpret)

    return _stream_batches((padded, scales), mats, volume, pbatch, call)


# Projection stacks already proven covered by (geom, tile config) — the
# planner pass is host-side numpy and paid once per distinct problem,
# mirroring repro.core.backproject._VALIDATED_STRIPS.
_VALIDATED_STACKS: set = set()

# (gs, ty, chunk, pbatch, sha1(mats)) -> planner-tight superset needs.
# The group planner pass is host-side numpy over every projection; pay
# it once per distinct problem like the validation memos above.
_SHARED_REQS: dict = {}


def shared_window_dims(geom: Geometry, mats, *, ty: int, chunk: int,
                       pbatch: int, shared_band: int | None = None,
                       shared_width: int | None = None
                       ) -> tuple[int, int]:
    """Size (and check) the shared superset window for a projection set.

    Returns the ``(band, width)`` the shared-window batch kernel must
    run with: the planner-tight group requirement
    (:func:`repro.core.clipping.shared_window_requirement`, saturated at
    the full padded detector — a full-detector window can never lose a
    tap), rounded up to hardware tiles when auto-sized.  Explicit dims
    smaller than the requirement raise — an undersized superset window
    drops taps silently, same hazard class as an undersized strip.
    """
    gs = GeomStatic.of(geom)
    mats64 = np.asarray(mats, np.float64).reshape(-1, 3, 4)
    key = (gs, ty, chunk, pbatch,
           hashlib.sha1(mats64.tobytes()).hexdigest())
    need = _SHARED_REQS.get(key)
    if need is not None:
        obs.count("planner.memo_hit", len(mats64))
    else:
        with obs.span("planner.check", units=len(mats64)):
            need = shared_window_requirement(geom, mats64, ty=ty,
                                             chunk=chunk, pbatch=pbatch)
        if len(_SHARED_REQS) >= 4096:
            _SHARED_REQS.clear()
        _SHARED_REQS[key] = need
    need_band = min(need[0], gs.n_v + 2)
    need_width = min(need[1], gs.n_u + 2)
    band = _round8(need_band) if shared_band is None else int(shared_band)
    width = (_round128(need_width) if shared_width is None
             else int(shared_width))
    if band < need_band or width < need_width:
        raise ValueError(
            f"shared window (shared_band={band}, shared_width={width}) "
            f"does not cover the projection group's superset footprint; "
            f"need at least (shared_band={need_band}, "
            f"shared_width={need_width}) for ty={ty}, chunk={chunk}, "
            f"pbatch={pbatch} — undersized windows drop taps silently")
    return band, width


def pallas_backproject_batch(volume, images, mats,
                             geom: Geometry | GeomStatic, *, ty: int = 8,
                             chunk: int = 128, band: int = 16,
                             width: int = 512,
                             pbatch: int = DEFAULT_PBATCH,
                             double_buffer: bool = False,
                             db_depth: int = 2,
                             shared_window: bool = False,
                             shared_band: int | None = None,
                             shared_width: int | None = None,
                             strip_dtype: str = "float32",
                             validate: bool = True,
                             strategy: str = "fixed", z0=0, slot=None):
    """Add a stack of projections to ``volume``, ``pbatch`` per kernel
    launch, with the volume tile resident in VMEM across the in-kernel
    projection loop (DESIGN.md §7).

    ``volume`` is the ``(L, L, L)`` cube, or a z-slab ``(S, L, L)`` of it
    whose first plane is the cube's plane ``z0``; with ``slot`` it is a
    stack ``(n, S, L, L)`` of such slabs and only ``volume[slot]``
    folds, in place.  ``z0`` and ``slot`` may be traced scalars (one
    compiled kernel for every slab), so a ``shard_map`` body folds its
    rank's slab with ``validate=False`` after the host checked the stack
    (:func:`validate_batch`): the check covers the whole cube, and so
    every slab of it.

    ``images``: unpadded ``(n_proj, n_v, n_u)`` filtered projections —
    padded once for the whole stack; ``mats``: ``(n_proj, 3, 4)``.
    ``n_proj`` is chunked into ``pbatch``-sized batches inside one jit
    (a ``pbatch ∤ n_proj`` remainder runs as one final smaller batch).
    Every projection's footprint is validated against the host planner
    by default (memoised per problem); pass ``validate=False`` only when
    the exact (geometry, matrices, tile) triple was already validated.
    The kernel compiles on a TPU backend and runs in interpret mode
    elsewhere.

    ``double_buffer=True`` selects the deep DMA pipeline
    (``db_depth``-slot rotation crossing the plane loop, DESIGN.md §9).
    ``strategy="auto"`` pulls the full tuned surface — ``ty``/``chunk``/
    ``band``/``width``, ``pbatch``, *and* the ``double_buffer``/
    ``db_depth``/``shared_window`` variant flags — from the process
    dispatcher (:mod:`repro.dispatch`) for this key: every tuned
    decision runs the kernel it was timed on, and an impossible
    combination raises instead of being shed.

    ``strip_dtype="bfloat16"`` carries the padded stack (all strip/
    window DMAs and the VMEM scratch) in bf16 — the kernels upcast to
    f32 at the one-hot matmul and accumulate in f32, so only the tap
    values round; ``strip_dtype="int8"`` encodes the stack once into
    per-row affine codes plus a ``(pbatch, rows, 2)`` scale block
    (:func:`_wire_pad`) and every strip/window DMA moves 1-byte codes,
    dequantised in-register; the f32 default is bitwise-unchanged.
    ``shared_window=True`` selects the superset-window kernel: one
    ``(pbatch, band, width)`` window DMA per (volume tile, projection
    group) instead of ``pbatch`` strip fetches.  The window dims are
    sized by the host group planner (:func:`shared_window_dims`) — pass
    ``shared_band``/``shared_width`` to pin them, which raises if they
    under-cover.  Sizing needs the full :class:`Geometry` (not a bare
    ``GeomStatic``) and runs regardless of ``validate`` — it is the
    correctness guard for this variant, not an optional check.
    """
    gs = geom if isinstance(geom, GeomStatic) else GeomStatic.of(geom)
    if strategy == "auto":
        from repro.dispatch import get_dispatcher

        tuned = get_dispatcher().resolve_kernel(geom)
        if tuned is not None:
            ty = int(tuned.get("ty", ty))
            chunk = int(tuned.get("chunk", chunk))
            band = int(tuned.get("band", band))
            width = int(tuned.get("width", width))
            pbatch = int(tuned.get("pbatch", pbatch))
            double_buffer = bool(tuned.get("double_buffer", double_buffer))
            db_depth = int(tuned.get("db_depth", db_depth))
            shared_window = bool(tuned.get("shared_window", shared_window))
            shared_band = tuned.get("shared_band", shared_band)
            shared_width = tuned.get("shared_width", shared_width)
            strip_dtype = str(tuned.get("strip_dtype", strip_dtype))
    elif strategy != "fixed":
        raise ValueError(
            f"unknown strategy {strategy!r}; want 'fixed' or 'auto'")
    if shared_window and double_buffer:
        raise ValueError(
            f"batch kernel variants are exclusive: got double_buffer="
            f"{double_buffer}, shared_window={shared_window}; a tuned "
            f"decision names exactly one")
    if double_buffer and int(db_depth) < 2:
        raise ValueError(
            f"db_depth={db_depth}: the pipelined batch kernel needs an "
            f"in-flight slot rotation of at least 2")
    strip_wire_dtype(strip_dtype)   # loud on typos, before any tracing
    ty, chunk, band, width = clamp_tiles(gs, ty, chunk, band, width)
    images = jnp.asarray(images)
    mats_f32 = jnp.asarray(mats, jnp.float32)
    n_proj = int(images.shape[0])
    pbatch = max(1, min(int(pbatch), n_proj)) if n_proj else 1
    if shared_window:
        # Mandatory sizing/coverage pass — see the docstring.  The
        # resulting superset dims *replace* (band, width) for the rest
        # of the pipeline: they are what the kernel DMAs and what the
        # one-hot selectors span.
        if isinstance(geom, GeomStatic):
            raise ValueError(
                "shared_window=True needs the full Geometry: the host "
                "group planner sizes the superset window")
        band, width = shared_window_dims(
            geom, mats, ty=ty, chunk=chunk, pbatch=pbatch,
            shared_band=shared_band, shared_width=shared_width)
        _, _, band, width = clamp_tiles(gs, ty, chunk, band, width)
    elif validate:
        if isinstance(geom, GeomStatic):
            raise ValueError("validate=True needs the full Geometry")
        validate_batch(geom, mats, ty=ty, chunk=chunk, band=band,
                       width=width)
    volume = jnp.asarray(volume)
    if slot is None:
        return _run_batched(volume, images, mats_f32, gs, ty, chunk, band,
                            width, pbatch, double_buffer, int(db_depth),
                            shared_window, strip_dtype, _interpret(),
                            z0=z0)
    # One slot of a stack of slabs, folded in place: the stack seen as
    # rows of planes, the slot's rows from slot * planes on.
    planes = volume.shape[1]
    out = _run_batched(volume.reshape((-1,) + volume.shape[2:]), images,
                       mats_f32, gs, ty, chunk, band, width, pbatch,
                       double_buffer, int(db_depth), shared_window,
                       strip_dtype, _interpret(), z0=z0,
                       row0=slot * planes, planes=planes)
    return out.reshape(volume.shape)


def validate_batch(geom: Geometry, mats, *, ty: int, chunk: int,
                   band: int, width: int, slabs: int = 1) -> None:
    """Check a projection stack against the host planner for the batch
    kernel's (already clamped) tile config, memoised per stack.

    A slab's tiles are the cube's own, so the whole cube's check covers
    every slab.  While a capture runs the check also counts the column
    blocks the kernel contracts (``kernel.col_blocks``, the whole cube)
    and, for a cube split into ``slabs`` equal z-slabs, the largest
    slab's blocks over the stack (``mesh.col_blocks_max_slab``): the
    MXU work of the chip that finishes a fold last.
    """
    gs = GeomStatic.of(geom)
    mats64 = np.asarray(mats, np.float64).reshape(-1, 3, 4)
    key = (gs, ty, chunk, band, width,
           hashlib.sha1(mats64.tobytes()).hexdigest())
    if key in _VALIDATED_STACKS:
        obs.count("planner.memo_hit", len(mats64))
        return
    with obs.span("planner.check", units=len(mats64)) as rec:
        per_slab = np.zeros(slabs, np.int64)
        for A in mats64:
            validate_strip_config(geom, A, ty=ty, chunk=chunk, band=band,
                                  width=width)
            if rec is not None:
                per_slab += _count_col_blocks(gs, A, ty=ty, chunk=chunk,
                                              band=band, width=width,
                                              slabs=slabs)
        if rec is not None and slabs > 1:
            obs.count("mesh.col_blocks_max_slab", int(per_slab.max()))
    if len(_VALIDATED_STACKS) >= 4096:
        _VALIDATED_STACKS.clear()
    _VALIDATED_STACKS.add(key)
