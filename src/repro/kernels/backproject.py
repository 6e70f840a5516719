"""Pallas TPU kernel: strip-blocked cone-beam back projection.

The TPU-native re-think of the paper's fastest CPU scheme (AVX/FMA3
"pairwise loads beat hardware gather", section 6.1), built from three
mechanisms the x86 kernels could only approximate:

1. **Strip DMA instead of gather** — per grid step the kernel computes the
   detector footprint of its ``(TY, CHUNK)`` voxel tile *in-kernel* (Part 1
   on the VPU), then issues one ``make_async_copy`` HBM->VMEM block copy of
   the minimal ``(band, width)`` strip.  One DMA descriptor replaces
   ``4 * TY * CHUNK`` scattered loads: this is the pairwise-load idea at
   DMA granularity.
2. **MXU as texture unit** — per tile row the horizontal interpolation is
   a one-hot matmul ``strip(band, 128) @ colsel(128, CHUNK)`` on the MXU
   for each 128-lane column block of the strip that can hold one of the
   tile's taps, summed over those blocks; the vertical 2-tap blend runs
   as iota-compare/select plus a sublane reduction on the VPU.  The
   blocks come from the same scalar corner geometry that places the DMA
   (:func:`_strip_origin`, :func:`_col_blocks`): a tile's footprint is
   far narrower than the window sized for the whole sweep, and every
   block it skips would have contracted an all-zero selector, so the MXU
   work follows the footprint and no result changes.  Voxels stay on the
   lanes throughout, so no step needs a relayout.  Out-of-window one-hot
   entries are identically zero, which (with the 1-pixel zero border
   added by ops.py) gives exact zero-outside-detector semantics with *no*
   per-tap conditionals — the paper's zero-padded buffer trick (section
   5.1.1).
3. **Grid pipelining instead of SMT** — KNC needed 4-way SMT to hide
   gather latency and still failed (section 6.4); here the volume-tile
   loads/stores are pipelined by the Pallas grid machinery, and the strip
   DMA for step ``k+1`` can be issued during step ``k``'s compute
   (double-buffered variant, ``double_buffer=True``).

Semantics are identical to ``repro.core.backproject.sample_scalar`` +
``accumulate`` (floor bilinear, zero outside, ``1/w^2`` weighting), which
is the oracle in ``backproject_ref.py``; correctness requires
``band``/``width`` to cover each tile's footprint (guaranteed by the
host-side planner in ``repro.core.clipping`` — ops.py checks it).

The DMA engine slices HBM only at ``(sublane, 128)`` tile boundaries, so
every strip origin is aligned *down* to a tile and the window the kernel
moves is one tile larger per axis than the footprint the planner
validated (:func:`strip_window`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["backproject_kernel", "backproject_kernel_batch",
           "backproject_kernel_batch_db", "backproject_kernel_batch_shared",
           "backproject_kernel_db", "backproject_volume_pallas",
           "backproject_volume_pallas_batch", "strip_window", "SUBLANE"]

_EPS_W = 1e-6
_LANE = 128

# Rows per HBM/VMEM tile for each strip wire itemsize (f32 8, bf16 16,
# int8 32): strip origins and padded row counts are multiples of this.
SUBLANE = {1: 32, 2: 16, 4: 8}

# MXU precision of the one-hot interpolation dot, as
# ``repro.core.backproject._MXU_PRECISION``: at Mosaic's default a TPU
# v5e volume misses a float64 reference by 3.2e-3 of its scale, at
# HIGHEST by 1.8e-4.
_PRECISION = jax.lax.Precision.HIGHEST


def strip_window(band: int, width: int, itemsize: int) -> tuple[int, int]:
    """DMA window dims that hold a ``(band, width)`` footprint.

    Strip origins are aligned down to the ``(SUBLANE[itemsize], 128)``
    tile, which moves the footprint up to one tile into the window, so
    the window is the footprint rounded up to the tile plus one tile.
    """
    sub = SUBLANE[itemsize]
    return (band + (-band) % sub + sub,
            width + (-width) % _LANE + _LANE)


def _row_tile(ref) -> int:
    return SUBLANE[jnp.dtype(ref.dtype).itemsize]


def _read_A(A_ref, p=None):
    """Load a 3x4 projection matrix from SMEM as a nested scalar tuple.

    ``p`` indexes a stacked ``(P, 3, 4)`` matrix buffer (batch kernel;
    ``p`` may be a traced loop index — SMEM scalar loads take dynamic
    indices).  Scalars instead of a reloaded array so every kernel
    variant shares one Part-1 implementation.
    """
    if p is None:
        return tuple(tuple(A_ref[i, j] for j in range(4)) for i in range(3))
    return tuple(tuple(A_ref[p, i, j] for j in range(4)) for i in range(3))


def _part1_tile(A, o_mm, z, y0, x0, ty, chunk):
    """Part 1 on the VPU: ICS coords for a (ty, chunk) voxel tile.

    ``A`` is the nested scalar tuple from :func:`_read_A`.  Mosaic only
    builds integer iotas, so the voxel offsets are cast after the iota.
    """
    O, MM = o_mm
    ys = y0 + jax.lax.broadcasted_iota(jnp.int32, (ty, chunk), 0).astype(
        jnp.float32)
    xs = x0 + jax.lax.broadcasted_iota(jnp.int32, (ty, chunk), 1).astype(
        jnp.float32)
    wx = O + xs * MM
    wy = O + ys * MM
    wz = O + z.astype(jnp.float32) * MM
    u = wx * A[0][0] + wy * A[0][1] + wz * A[0][2] + A[0][3]
    v = wx * A[1][0] + wy * A[1][1] + wz * A[1][2] + A[1][3]
    w = wx * A[2][0] + wy * A[2][1] + wz * A[2][2] + A[2][3]
    r = jnp.where(w > _EPS_W, 1.0 / w, 0.0)   # reciprocal trick (paper 5.1)
    return u * r, v * r, w, r


def _strip_origin(A, o_mm, z, y0, x0, *, n_u, n_v, ty, chunk, band, width,
                  pad_rows, pad_cols, row_tile):
    """Tile-aligned strip origin and tap columns for a (ty, chunk) tile
    from its four *corner* voxels.

    ``w`` is affine over the tile, so its minimum sits at a corner, and
    where ``w > 0`` both detector coordinates are monotone along each
    voxel axis — the tile extremes of ``ix``/``iy`` are corner values.
    Twelve scalar FMAs per corner replace a full ``(ty, chunk)`` Part-1
    pass; prefetch, wait and compute always agree because all of them
    call this one helper.  The origin is clamped so the ``(band, width)``
    window stays in the padded image, then aligned down to the
    ``(row_tile, 128)`` DMA tile (``pad_rows``/``band`` and
    ``pad_cols``/``width`` are tile multiples, so the clamp survives the
    alignment).

    Returns ``(r0, c0, cols)``.  ``cols = (lo, hi)`` are padded columns
    that bracket every tap of the tile: the taps of ``ix`` are
    ``floor(ix) + 1`` and ``floor(ix) + 2``, and the bracket keeps one
    more column on each side, so a voxel whose vector Part-1 ``ix``
    rounds across an integer from the scalar corner value still lands
    inside (:func:`_col_blocks`).
    """
    O, MM = o_mm
    wz = O + z.astype(jnp.float32) * MM
    r_lo = c_lo = c_hi = None
    for dy in (0.0, float(ty - 1)):
        for dx in (0.0, float(chunk - 1)):
            wy = O + (y0 + dy) * MM
            wx = O + (x0 + dx) * MM
            u = wx * A[0][0] + wy * A[0][1] + wz * A[0][2] + A[0][3]
            v = wx * A[1][0] + wy * A[1][1] + wz * A[1][2] + A[1][3]
            w = wx * A[2][0] + wy * A[2][1] + wz * A[2][2] + A[2][3]
            r = jnp.where(w > _EPS_W, 1.0 / w, 0.0)
            ix = jnp.clip(u * r, -1.0, jnp.float32(n_u))
            iy = jnp.clip(v * r, -1.0, jnp.float32(n_v))
            c_lo = ix if c_lo is None else jnp.minimum(c_lo, ix)
            c_hi = ix if c_hi is None else jnp.maximum(c_hi, ix)
            r_lo = iy if r_lo is None else jnp.minimum(r_lo, iy)
    lo = jnp.floor(c_lo).astype(jnp.int32)
    hi = jnp.floor(c_hi).astype(jnp.int32) + 3
    r0 = jnp.clip(jnp.floor(r_lo).astype(jnp.int32), 0, pad_rows - band)
    c0 = jnp.clip(lo, 0, pad_cols - width)
    r0 = pl.multiple_of(jax.lax.div(r0, row_tile) * row_tile, row_tile)
    c0 = pl.multiple_of(jax.lax.div(c0, _LANE) * _LANE, _LANE)
    return r0, c0, (lo, hi)


def _col_blocks(cols, c0, width):
    """First and last 128-lane block of a ``width``-wide window at padded
    column ``c0`` that hold any of the columns ``cols`` brackets.

    Clamped to the window: a block outside it holds nothing to contract.
    Every tap outside ``[kb_lo, kb_hi]`` selects an all-zero one-hot
    entry, so contracting only these blocks changes no result.
    """
    lo, hi = cols
    last = width // _LANE - 1
    return (jnp.clip(jax.lax.div(lo - c0, _LANE), 0, last),
            jnp.clip(jax.lax.div(hi - c0, _LANE), 0, last))


def _tile_active(ix, iy, w, n_u, n_v):
    """Does any voxel of the tile project onto the detector?"""
    return ((jnp.min(ix) < jnp.float32(n_u)) & (jnp.max(ix) > -1.0)
            & (jnp.min(iy) < jnp.float32(n_v)) & (jnp.max(iy) > -1.0)
            & (jnp.max(w) > _EPS_W))


def _dequant_strip(strip, scl_ref, r0, band, p=None):
    """Decode an int8 code strip in-register, next to the accumulator.

    ``scl_ref`` is the per-detector-row scale block, VMEM-resident for
    the whole kernel: ``scl_ref[row] = (scale, offset)`` per padded row
    (stacked ``(P, rows, 2)`` in the batch kernels, indexed by ``p``), so
    ``value = code * scale[row] + offset[row]``.  Rows sit on sublanes,
    so the window's scales load with the strip's own (tile-aligned) row
    offset and broadcast across its lanes.  ``scl_ref=None`` means the
    wire is not quantised and the strip passes through untouched — every
    variant calls this unconditionally and the f32 path traces to a
    no-op.  Only 1-byte codes ever move on the strip wire, and only the
    column blocks the tile contracts widen to f32.
    """
    if scl_ref is None:
        return strip
    if p is None:
        sc = scl_ref[pl.ds(r0, band), :]
    else:
        sc = scl_ref[p, pl.ds(r0, band), :]
    return strip.astype(jnp.float32) * sc[:, 0:1] + sc[:, 1:2]


def _window_blocks(read, scl_ref, r0, band, p=None):
    """Reader of a resident window's 128-lane column blocks.

    ``read(cols)`` loads the window's ``(band, 128)`` columns ``cols``
    from its VMEM ref; the returned ``block(kb)`` gives block ``kb``
    decoded (:func:`_dequant_strip`) and in f32.
    """
    def block(kb):
        cols = pl.ds(pl.multiple_of(kb * _LANE, _LANE), _LANE)
        return _dequant_strip(read(cols), scl_ref, r0, band,
                              p).astype(jnp.float32)
    return block


def _tile_contrib(get_blocks, ix, iy, r, r0, c0, blocks, *, ty, chunk,
                  band):
    """Parts 2+3 for one (ty, chunk) tile against a resident
    ``(band, width)`` strip at padded origin ``(r0, c0)``.

    ``ix``/``iy``/``r`` are the tile's Part-1 arrays.  Row by row, with
    the row's ``chunk`` voxels on the lanes: for each 128-lane column
    block ``kb`` of ``blocks = (kb_lo, kb_hi)`` (:func:`_col_blocks`) the
    horizontal 2-tap selector ``colsel (128, chunk)`` is built by
    iota-compare and the MXU contracts it with the block into ``(band,
    chunk)``, accumulated over the blocks; the vertical 2-tap selector
    ``rowsel (band, chunk)`` blends the sum with a sublane reduction.
    The blocks outside ``blocks`` hold none of the tile's taps, so their
    products would be exactly zero and are skipped: the MXU work follows
    the tile's footprint, not the window.  Taps outside the strip select
    all-zero one-hot entries and vanish — with the zero border this is
    the exact zero-outside-detector semantics.  The ``1/w²`` weight is
    folded in; returns the f32 ``(ty, chunk)`` contribution.

    ``get_blocks`` is a zero-arg callable (wait on the strip DMA, return
    a :func:`_window_blocks` reader) invoked once the tap coordinates are
    built, so the copy overlaps that arithmetic.
    """
    fx = jnp.floor(ix)
    fy = jnp.floor(iy)
    sx = ix - fx
    sy = iy - fy
    # Padded-relative tap coordinates (+1: pad offset).
    rel_c = fx.astype(jnp.int32) + 1 - c0
    rel_r = fy.astype(jnp.int32) + 1 - r0
    rw2 = r * r
    kb_lo, kb_hi = blocks
    biota = jax.lax.broadcasted_iota(jnp.int32, (band, chunk), 0)
    liota = jax.lax.broadcasted_iota(jnp.int32, (_LANE, chunk), 0)
    yiota = jax.lax.broadcasted_iota(jnp.int32, (ty, chunk), 0)
    block = get_blocks()
    out = jnp.zeros((ty, chunk), jnp.float32)
    for y in range(ty):
        row = slice(y, y + 1)

        def contract(kb, colmix, c=rel_c[row], s=sx[row]):
            # MXU: horizontal interpolation of every strip row of block
            # kb at once; c - kb*128 is the tap's lane within the block.
            c = c - kb * _LANE
            colsel = jnp.where(liota == c, 1.0 - s,
                               jnp.where(liota == c + 1, s, 0.0))
            return colmix + jax.lax.dot_general(
                block(kb), colsel, (((1,), (0,)), ((), ())),
                precision=_PRECISION, preferred_element_type=jnp.float32)

        colmix = jax.lax.fori_loop(kb_lo, kb_hi + 1, contract,
                                   jnp.zeros((band, chunk), jnp.float32))
        rowsel = jnp.where(biota == rel_r[row], 1.0 - sy[row],
                           jnp.where(biota == rel_r[row] + 1, sy[row], 0.0))
        val = jnp.sum(colmix * rowsel, axis=0, keepdims=True) * rw2[row]
        out = jnp.where(yiota == y, val, out)
    return out


def backproject_kernel(A_ref, img_ref, *refs,
                       o_mm, n_u, n_v, ty, chunk, band, width,
                       quantized=False):
    """One grid step: back-project one projection into a (1, TY, CHUNK)
    volume tile.

    Refs: ``A_ref`` (3,4) f32 in SMEM; ``img_ref`` zero-padded projection
    in ANY/HBM; with ``quantized=True`` a ``(rows, 2)`` per-row scale
    block in VMEM follows (``img_ref`` then holds int8 codes); then the
    aliased ``vol_in/out`` volume tile in VMEM, ``strip_ref`` VMEM
    scratch, ``sem`` DMA semaphore.  ``band``/``width`` are the DMA
    window dims (:func:`strip_window`).
    """
    scl_ref = None
    if quantized:
        scl_ref, *refs = refs
    vol_in_ref, vol_out_ref, strip_ref, sem = refs
    z = pl.program_id(0)
    y0 = (pl.program_id(1) * ty).astype(jnp.float32)
    x0 = (pl.program_id(2) * chunk).astype(jnp.float32)
    A = _read_A(A_ref)

    ix, iy, w, r = _part1_tile(A, o_mm, z, y0, x0, ty, chunk)
    active = _tile_active(ix, iy, w, n_u, n_v)
    r0, c0, cols = _strip_origin(
        A, o_mm, z, y0, x0, n_u=n_u, n_v=n_v, ty=ty, chunk=chunk,
        band=band, width=width, pad_rows=img_ref.shape[0],
        pad_cols=img_ref.shape[1], row_tile=_row_tile(img_ref))

    @pl.when(active)
    def _():
        # --- Part 2: one strip DMA replaces 4*TY*CHUNK gathers ----------
        copy = pltpu.make_async_copy(
            img_ref.at[pl.ds(r0, band), pl.ds(c0, width)], strip_ref, sem)
        copy.start()

        def strip():
            copy.wait()
            return _window_blocks(lambda cols: strip_ref[:, cols], scl_ref,
                                  r0, band)

        contrib = _tile_contrib(strip, ix, iy, r, r0, c0,
                                _col_blocks(cols, c0, width), ty=ty,
                                chunk=chunk, band=band)
        # --- Part 3: inverse-square-law weighted accumulate -------------
        vol_out_ref[...] = vol_in_ref[...] + contrib.astype(
            vol_in_ref.dtype)[None]

    @pl.when(jnp.logical_not(active))
    def _():
        vol_out_ref[...] = vol_in_ref[...]


def backproject_kernel_db(A_ref, img_ref, *refs,
                          o_mm, n_u, n_v, ty, chunk, band, width,
                          grid_dims, depth=2, quantized=False):
    """Double-buffered variant: the strip DMA for grid step ``k+1`` is
    issued before step ``k``'s compute, generalised to a ``depth``-slot
    rotation running ``depth - 1`` fetches ahead.

    KNC had no usable gather prefetch (the paper found
    ``vgatherpf0dps`` blocking and scalar prefetch too expensive,
    section 6.4); on TPU the strip origin is *computed* geometry, so
    future tiles' DMAs can be launched any number of steps ahead into a
    ``(depth, band, width)`` scratch.  Step 0 primes the first
    ``depth - 1`` fetches; step ``k`` then issues the fetch for step
    ``k + depth - 1`` (whose slot was drained at step ``k - 1``) and
    waits on its own — the same rotation ledger as the batched
    :func:`backproject_kernel_batch_db` at ``pbatch = 1``, so a tuned
    ``db_depth`` means one thing on both paths.  Producer and consumer
    both take the origin from :func:`_strip_origin`, so they agree by
    construction.
    """
    scl_ref = None
    if quantized:
        scl_ref, *refs = refs
    vol_in_ref, vol_out_ref, strip_ref, sems = refs
    nz, ny, nc = grid_dims
    z = pl.program_id(0)
    yb = pl.program_id(1)
    cb = pl.program_id(2)
    step = (z * ny + yb) * nc + cb
    total = nz * ny * nc
    slot = jax.lax.rem(step, depth)
    y0 = (yb * ty).astype(jnp.float32)
    x0 = (cb * chunk).astype(jnp.float32)

    pad_rows = img_ref.shape[0]
    pad_cols = img_ref.shape[1]
    row_tile = _row_tile(img_ref)
    A = _read_A(A_ref)

    def origin(zi, yi, ci):
        return _strip_origin(
            A, o_mm, zi, (yi * ty).astype(jnp.float32),
            (ci * chunk).astype(jnp.float32), n_u=n_u, n_v=n_v, ty=ty,
            chunk=chunk, band=band, width=width, pad_rows=pad_rows,
            pad_cols=pad_cols, row_tile=row_tile)

    def start_dma(t):
        cn = jax.lax.rem(t, nc)
        rest = jax.lax.div(t, nc)
        yn = jax.lax.rem(rest, ny)
        zn = jax.lax.div(rest, ny)
        r0n, c0n, _ = origin(zn, yn, cn)
        s = jax.lax.rem(t, depth)
        pltpu.make_async_copy(
            img_ref.at[pl.ds(r0n, band), pl.ds(c0n, width)],
            strip_ref.at[s], sems.at[s]).start()

    ix, iy, w, r = _part1_tile(A, o_mm, z, y0, x0, ty, chunk)
    active = _tile_active(ix, iy, w, n_u, n_v)
    r0, c0, cols = origin(z, yb, cb)

    # First step primes the whole lookahead window.
    @pl.when(step == 0)
    def _():
        for d in range(min(depth - 1, total)):
            start_dma(jnp.int32(d))

    # Refill the slot step-1 just drained with step + depth - 1's strip.
    @pl.when(step + (depth - 1) < total)
    def _():
        start_dma(step + (depth - 1))

    def wait_strip():
        pltpu.make_async_copy(
            img_ref.at[pl.ds(r0, band), pl.ds(c0, width)],
            strip_ref.at[slot], sems.at[slot]).wait()

    @pl.when(active)
    def _():
        def strip():
            wait_strip()
            return _window_blocks(lambda cols: strip_ref[slot, :, cols],
                                  scl_ref, r0, band)

        contrib = _tile_contrib(strip, ix, iy, r, r0, c0,
                                _col_blocks(cols, c0, width), ty=ty,
                                chunk=chunk, band=band)
        vol_out_ref[...] = vol_in_ref[...] + contrib.astype(
            vol_in_ref.dtype)[None]

    @pl.when(jnp.logical_not(active))
    def _():
        # The prefetched strip for this inactive tile must still be
        # consumed so the semaphore balances.
        wait_strip()
        vol_out_ref[...] = vol_in_ref[...]


def backproject_kernel_batch(zrow_ref, A_ref, imgs_ref, *refs,
                             o_mm, n_u, n_v, ty, chunk, band, width,
                             pbatch, quantized=False):
    """Projection-batched grid step: the ``(1, ty, chunk)`` volume tile
    stays resident in VMEM while an in-kernel ``fori_loop`` folds in
    ``pbatch`` projections — the inverted loop nest (DESIGN.md §7).

    Refs: ``zrow_ref`` the scalar-prefetched ``(z0, row0)`` int32 pair
    (:func:`backproject_volume_pallas_batch`; the grid's first axis runs
    over the slab's planes, and plane ``i`` is global plane ``z0 + i``);
    ``A_ref`` stacked ``(pbatch, 3, 4)`` f32 in SMEM; ``imgs_ref``
    stacked zero-padded projections ``(pbatch, rows, cols)`` in ANY/HBM;
    with ``quantized=True`` a ``(pbatch, rows, 2)`` scale block in VMEM
    follows (``imgs_ref`` then holds int8 codes); then the aliased
    ``vol_in/out`` volume tile, ``strip_ref`` ``(2, band, width)`` VMEM
    scratch, ``acc_ref`` ``(ty, chunk)`` f32 accumulator, ``sems`` 2
    DMA semaphores.

    The volume tile is loaded once and stored once per ``pbatch``
    projections — volume HBM traffic drops by the batch factor versus
    the per-projection kernels.  Per in-kernel projection ``p``,
    projection ``p+1``'s strip (address from the corner-based
    :func:`_strip_origin`) is prefetched into the other half of a 2-slot
    rotation while ``p``'s contribution computes.  Every projection's
    strip is DMA'd and waited unconditionally (clamped origins are
    always in-bounds) so the semaphores balance; off-detector
    projections contribute zero through the all-zero one-hot entries
    and the ``r²`` mask.
    """
    scl_ref = None
    if quantized:
        scl_ref, *refs = refs
    vol_in_ref, vol_out_ref, strip_ref, acc_ref, sems = refs
    z = zrow_ref[0] + pl.program_id(0)
    y0 = (pl.program_id(1) * ty).astype(jnp.float32)
    x0 = (pl.program_id(2) * chunk).astype(jnp.float32)
    pad_rows = imgs_ref.shape[1]
    pad_cols = imgs_ref.shape[2]
    row_tile = _row_tile(imgs_ref)

    def origin(p):
        return _strip_origin(
            _read_A(A_ref, p), o_mm, z, y0, x0, n_u=n_u, n_v=n_v, ty=ty,
            chunk=chunk, band=band, width=width, pad_rows=pad_rows,
            pad_cols=pad_cols, row_tile=row_tile)

    def start_dma(p, r0, c0, slot):
        pltpu.make_async_copy(
            imgs_ref.at[p, pl.ds(r0, band), pl.ds(c0, width)],
            strip_ref.at[slot], sems.at[slot]).start()

    acc_ref[...] = vol_in_ref[0].astype(jnp.float32)
    start_dma(0, *origin(0)[:2], 0)

    def body(p, _):
        # Projection p's strip is in flight; the wait recomputes the
        # origin its issuer used.
        r0, c0, cols = origin(p)
        slot = jax.lax.rem(p, 2)

        # Prefetch projection p+1's strip into the other slot while p's
        # contribution computes.  The clamped index keeps the SMEM read
        # in-bounds on the last iteration; the DMA only starts when a
        # next projection exists.
        pn = jnp.minimum(p + 1, pbatch - 1)
        r0n, c0n, _ = origin(pn)

        @pl.when(p + 1 < pbatch)
        def _():
            start_dma(pn, r0n, c0n, 1 - slot)

        A = _read_A(A_ref, p)
        ix, iy, w, r = _part1_tile(A, o_mm, z, y0, x0, ty, chunk)
        active = _tile_active(ix, iy, w, n_u, n_v)

        def wait_strip():
            pltpu.make_async_copy(
                imgs_ref.at[p, pl.ds(r0, band), pl.ds(c0, width)],
                strip_ref.at[slot], sems.at[slot]).wait()

        @pl.when(active)
        def _():
            def strip():
                wait_strip()
                return _window_blocks(
                    lambda cols: strip_ref[slot, :, cols], scl_ref, r0,
                    band, p)

            acc_ref[...] += _tile_contrib(
                strip, ix, iy, r, r0, c0, _col_blocks(cols, c0, width),
                ty=ty, chunk=chunk, band=band)

        @pl.when(jnp.logical_not(active))
        def _():
            wait_strip()               # balance the unconditional DMA

        return 0

    jax.lax.fori_loop(0, pbatch, body, 0)
    vol_out_ref[...] = acc_ref[...].astype(vol_out_ref.dtype)[None]


def backproject_kernel_batch_db(zrow_ref, A_ref, imgs_ref, *refs,
                                o_mm, n_u, n_v, ty, chunk, band, width,
                                pbatch, depth, grid_dims, quantized=False):
    """Deep-pipelined batched grid step: the strip DMA stream runs
    ``depth - 1`` fetches ahead of compute through a ``depth``-slot
    rotation, across *both* the in-kernel projection ``fori_loop`` and
    the plane/tile grid loop.

    The plain batch kernel's pipeline drains at every grid-step
    boundary: projection 0 of tile ``k+1`` only starts its DMA once tile
    ``k`` is fully folded, so each of the ``nz·ny·nc`` steps eats one
    cold strip latency.  Here every strip fetch lives on one global
    sequence ``t = step·pbatch + p``; iteration ``t`` issues the DMA for
    ``t + depth - 1`` (its target slot was consumed at iteration
    ``t - 1``, so the rotation never overwrites a live strip) and the
    strip addresses of *future tiles* are plain geometry via the
    corner-based :func:`_strip_origin` — nothing about a tile has to be
    resident to prefetch for it.  ``depth=2`` is the classical double
    buffer without the per-step drain; deeper pipelines keep more
    fetches in flight, which pays once a single strip latency exceeds
    one projection's compute.

    Refs as :func:`backproject_kernel_batch`, except ``strip_ref`` is
    ``(depth, band, width)`` and ``sems`` ``depth`` DMA semaphores.  The
    sequence runs over the slab's own grid; a future tile's strip is
    placed at its global plane.
    Issue/wait counts balance by construction: exactly one DMA is
    issued and one waited per sequence index (`t < total` guards both
    ends), and every wait recomputes the same origin the issuer used.
    """
    scl_ref = None
    if quantized:
        scl_ref, *refs = refs
    vol_in_ref, vol_out_ref, strip_ref, acc_ref, sems = refs
    nz, ny, nc = grid_dims
    zb = zrow_ref[0]                   # the slab's first global plane
    zl = pl.program_id(0)
    z = zb + zl
    yb = pl.program_id(1)
    cb = pl.program_id(2)
    step = (zl * ny + yb) * nc + cb
    t0 = step * pbatch
    total = nz * ny * nc * pbatch
    y0 = (yb * ty).astype(jnp.float32)
    x0 = (cb * chunk).astype(jnp.float32)
    pad_rows = imgs_ref.shape[1]
    pad_cols = imgs_ref.shape[2]
    row_tile = _row_tile(imgs_ref)

    def origin(A, zi, yi, xi):
        return _strip_origin(A, o_mm, zi, yi, xi, n_u=n_u, n_v=n_v, ty=ty,
                             chunk=chunk, band=band, width=width,
                             pad_rows=pad_rows, pad_cols=pad_cols,
                             row_tile=row_tile)

    def start_dma(t):
        """Issue the strip fetch for global sequence index ``t`` —
        decode (tile, projection), compute the corner origin, copy into
        slot ``t % depth``."""
        s = jax.lax.div(t, pbatch)
        p = jax.lax.rem(t, pbatch)
        cn = jax.lax.rem(s, nc)
        rest = jax.lax.div(s, nc)
        yn = jax.lax.rem(rest, ny)
        zn = jax.lax.div(rest, ny)
        r0, c0, _ = origin(_read_A(A_ref, p), zb + zn,
                           (yn * ty).astype(jnp.float32),
                           (cn * chunk).astype(jnp.float32))
        slot = jax.lax.rem(t, depth)
        pltpu.make_async_copy(
            imgs_ref.at[p, pl.ds(r0, band), pl.ds(c0, width)],
            strip_ref.at[slot], sems.at[slot]).start()

    # The first step primes the whole lookahead window; later steps
    # inherit their leading strips from their predecessors' prefetches.
    @pl.when(step == 0)
    def _():
        for d in range(min(depth - 1, total)):
            start_dma(jnp.int32(d))

    acc_ref[...] = vol_in_ref[0].astype(jnp.float32)

    def body(p, _):
        t = t0 + p
        # Refill the slot iteration t-1 just drained with strip
        # t + depth - 1 (possibly a future tile's) before this
        # iteration's compute, so the copy overlaps it.
        @pl.when(t + (depth - 1) < total)
        def _():
            start_dma(t + (depth - 1))

        A = _read_A(A_ref, p)
        ix, iy, w, r = _part1_tile(A, o_mm, z, y0, x0, ty, chunk)
        active = _tile_active(ix, iy, w, n_u, n_v)
        # t always belongs to *this* tile, so its origin is current-tile
        # geometry — the issuer (iteration t - depth + 1) computed the
        # identical corner origin, producer and consumer agreeing by
        # construction.
        r0, c0, cols = origin(A, z, y0, x0)
        slot = jax.lax.rem(t, depth)

        def wait_strip():
            pltpu.make_async_copy(
                imgs_ref.at[p, pl.ds(r0, band), pl.ds(c0, width)],
                strip_ref.at[slot], sems.at[slot]).wait()

        @pl.when(active)
        def _():
            def strip():
                wait_strip()
                return _window_blocks(
                    lambda cols: strip_ref[slot, :, cols], scl_ref, r0,
                    band, p)

            acc_ref[...] += _tile_contrib(
                strip, ix, iy, r, r0, c0, _col_blocks(cols, c0, width),
                ty=ty, chunk=chunk, band=band)

        @pl.when(jnp.logical_not(active))
        def _():
            wait_strip()               # balance the unconditional DMA
        return 0

    jax.lax.fori_loop(0, pbatch, body, 0)
    vol_out_ref[...] = acc_ref[...].astype(vol_out_ref.dtype)[None]


def backproject_kernel_batch_shared(zrow_ref, A_ref, imgs_ref, *refs,
                                    o_mm, n_u, n_v, ty, chunk, band,
                                    width, pbatch, quantized=False):
    """Shared-superset-window batched grid step: ONE window DMA per
    (volume tile, projection group) instead of ``pbatch`` strip fetches.

    Adjacent angles' strips over one tile overlap heavily, so the group
    is served from a single superset window anchored at the elementwise
    *minimum* of the members' corner origins (:func:`_strip_origin` per
    projection; each is already clamped in-bounds and tile-aligned, so
    the minimum is too).  The DMA moves a ``(pbatch, band, width)``
    slab — same total pixel area only when the members coincide, but
    always a ``pbatch``× cut in DMA *descriptors*.  Coverage is NOT
    checked here: ops.py sizes/validates ``(band, width)`` against the
    host planner's :func:`repro.core.clipping.shared_window_requirement`
    — an undersized window would drop taps silently, so the wrapper
    raises before this kernel ever runs.

    Refs as :func:`backproject_kernel_batch`, except the scratch is one
    ``(pbatch, band, width)`` window slab and a single DMA semaphore.
    """
    scl_ref = None
    if quantized:
        scl_ref, *refs = refs
    vol_in_ref, vol_out_ref, win_ref, acc_ref, sem = refs
    z = zrow_ref[0] + pl.program_id(0)
    y0 = (pl.program_id(1) * ty).astype(jnp.float32)
    x0 = (pl.program_id(2) * chunk).astype(jnp.float32)
    pad_rows = imgs_ref.shape[1]
    pad_cols = imgs_ref.shape[2]

    def origin(A):
        return _strip_origin(
            A, o_mm, z, y0, x0, n_u=n_u, n_v=n_v, ty=ty, chunk=chunk,
            band=band, width=width, pad_rows=pad_rows, pad_cols=pad_cols,
            row_tile=_row_tile(imgs_ref))

    r0s = c0s = None
    for p in range(pbatch):
        r0p, c0p, _ = origin(_read_A(A_ref, p))
        r0s = r0p if r0s is None else jnp.minimum(r0s, r0p)
        c0s = c0p if c0s is None else jnp.minimum(c0s, c0p)

    copy = pltpu.make_async_copy(
        imgs_ref.at[pl.ds(0, pbatch), pl.ds(r0s, band), pl.ds(c0s, width)],
        win_ref, sem)
    copy.start()
    acc_ref[...] = vol_in_ref[0].astype(jnp.float32)   # overlaps the DMA
    copy.wait()

    def body(p, _):
        A = _read_A(A_ref, p)
        ix, iy, w, r = _part1_tile(A, o_mm, z, y0, x0, ty, chunk)
        active = _tile_active(ix, iy, w, n_u, n_v)

        # The member's own tap columns, in the shared window's blocks.
        blocks = _col_blocks(origin(A)[2], c0s, width)

        @pl.when(active)
        def _():
            acc_ref[...] += _tile_contrib(
                lambda: _window_blocks(lambda cols: win_ref[p, :, cols],
                                       scl_ref, r0s, band, p),
                ix, iy, r, r0s, c0s, blocks, ty=ty, chunk=chunk, band=band)
        return 0

    jax.lax.fori_loop(0, pbatch, body, 0)
    vol_out_ref[...] = acc_ref[...].astype(vol_out_ref.dtype)[None]


def _check_window(padded_shape, band, width):
    rows, cols = padded_shape[-2:]
    if rows < band or cols < width:
        raise ValueError(
            f"padded projection {tuple(padded_shape)} is smaller than the "
            f"({band}, {width}) strip window")


def backproject_volume_pallas(volume, padded_img, A, *, o_mm, n_u, n_v,
                              ty=8, chunk=128, band=16, width=512,
                              double_buffer=False, db_depth=2, scales=None,
                              interpret=False):
    """``pallas_call`` wrapper: one projection into the whole volume.

    ``volume``: (L, L, L) f32; ``padded_img``: zero-padded projection,
    row/col counts already rounded up by ops.py so the strip window
    (:func:`strip_window` of ``(band, width)``) always fits.  Returns the
    updated volume (input aliased).  ``double_buffer=True`` selects the
    DMA-prefetching variant (``db_depth`` slots in rotation, same ledger
    as the batched variant).

    ``scales`` selects the int8 wire: ``padded_img`` holds int8 codes
    and ``scales`` the ``(rows, 2)`` f32 per-row scale/offset block
    (built by ops.py from :func:`repro.quant.quantize_rows`), kept
    VMEM-resident for the whole call via a constant-index BlockSpec.
    """
    L = volume.shape[0]
    assert L % ty == 0 and L % chunk == 0
    grid = (L, L // ty, L // chunk)
    quantized = scales is not None
    band, width = strip_window(band, width, padded_img.dtype.itemsize)
    _check_window(padded_img.shape, band, width)

    vol_spec = pl.BlockSpec((1, ty, chunk), lambda z, y, x: (z, y, x))
    if double_buffer:
        depth = int(db_depth)
        if depth < 2:
            raise ValueError(
                f"db_depth={db_depth}: the pipelined kernel needs an "
                f"in-flight slot rotation of at least 2")
        kernel = functools.partial(
            backproject_kernel_db, o_mm=o_mm, n_u=n_u, n_v=n_v,
            ty=ty, chunk=chunk, band=band, width=width, grid_dims=grid,
            depth=depth, quantized=quantized)
        scratch = [pltpu.VMEM((depth, band, width), padded_img.dtype),
                   pltpu.SemaphoreType.DMA((depth,))]
        name = f"backproject_strip_db{depth}"
    else:
        kernel = functools.partial(
            backproject_kernel, o_mm=o_mm, n_u=n_u, n_v=n_v,
            ty=ty, chunk=chunk, band=band, width=width,
            quantized=quantized)
        scratch = [pltpu.VMEM((band, width), padded_img.dtype),
                   pltpu.SemaphoreType.DMA]
        name = "backproject_strip"

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),       # A (3, 4)
        pl.BlockSpec(memory_space=pl.ANY),           # padded image (HBM)
    ]
    args = [A, padded_img]
    if quantized:
        # Whole scale block resident in VMEM (constant index map).
        in_specs.append(pl.BlockSpec(scales.shape, lambda z, y, x: (0, 0)))
        args.append(scales)
        name += "_int8"
    in_specs.append(vol_spec)                        # volume tile in
    args.append(volume)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=vol_spec,
        out_shape=jax.ShapeDtypeStruct(volume.shape, volume.dtype),
        scratch_shapes=scratch,
        input_output_aliases={len(args) - 1: 0},
        interpret=interpret,
        name=name,
    )(*args)


def backproject_volume_pallas_batch(volume, padded_imgs, A_stack, *, o_mm,
                                    n_u, n_v, ty=8, chunk=128, band=16,
                                    width=512, double_buffer=False,
                                    db_depth=2, shared_window=False,
                                    scales=None, z0=0, row0=0,
                                    planes=None, interpret=False):
    """``pallas_call`` wrapper: one *batch* of projections into a volume
    or a z-slab of one, volume tile resident across the in-kernel
    projection loop.

    ``volume`` is ``(rows, L, L)``: the kernel folds its ``planes``
    planes from row ``row0`` on (default: all of them), and those hold
    the global planes ``z0, z0 + 1, ...`` of the ``L³`` volume, so one
    slab of a z-sharded volume, or one slot of a stack of slabs seen as
    ``(slots * planes, L, L)``, folds in place.  ``z0`` and ``row0`` are
    runtime scalars, prefetched to SMEM: one compiled kernel serves every
    slab and slot of a shape, and ``z0 = row0 = 0`` over a whole cube is
    the cube's kernel.  ``padded_imgs``: stacked zero-padded projections
    ``(pbatch, rows, cols)`` (rows/cols already rounded up by ops.py);
    ``A_stack``: ``(pbatch, 3, 4)`` matrices.  Returns the updated volume
    (input aliased: rows outside the folded planes keep their values).
    Volume HBM traffic per call: one load + one store of the folded
    planes — a ``pbatch``× cut versus ``pbatch`` calls of
    :func:`backproject_volume_pallas`.

    ``double_buffer=True`` selects the deep DMA pipeline
    (:func:`backproject_kernel_batch_db`, ``db_depth`` slots in
    rotation, in-flight depth ``db_depth - 1`` across the plane loop);
    ``shared_window=True`` the one-DMA-per-group superset-window scheme
    (:func:`backproject_kernel_batch_shared` — here ``band``/``width``
    are the *superset* dims ops.py sized against the group planner).
    The variants are exclusive — asking for both raises rather than
    silently preferring one, because a tuned decision named exactly one.

    ``scales`` selects the int8 wire exactly as in
    :func:`backproject_volume_pallas`, stacked ``(pbatch, rows, 2)``.
    """
    L = volume.shape[-1]
    planes = volume.shape[0] if planes is None else int(planes)
    pbatch = int(A_stack.shape[0])
    assert volume.shape[1:] == (L, L) and volume.shape[0] >= planes
    assert L % ty == 0 and L % chunk == 0
    assert padded_imgs.shape[0] == pbatch
    grid = (planes, L // ty, L // chunk)
    quantized = scales is not None
    band, width = strip_window(band, width, padded_imgs.dtype.itemsize)
    _check_window(padded_imgs.shape, band, width)
    zrow = jnp.stack([jnp.asarray(z0, jnp.int32),
                      jnp.asarray(row0, jnp.int32)])

    vol_spec = pl.BlockSpec((1, ty, chunk),
                            lambda z, y, x, zr: (zr[1] + z, y, x))
    if shared_window and double_buffer:
        raise ValueError(
            f"batch kernel variants are exclusive: got double_buffer="
            f"{double_buffer}, shared_window={shared_window}; a tuned "
            f"decision names exactly one")

    if shared_window:
        kernel = functools.partial(
            backproject_kernel_batch_shared, o_mm=o_mm, n_u=n_u, n_v=n_v,
            ty=ty, chunk=chunk, band=band, width=width, pbatch=pbatch,
            quantized=quantized)
        name = f"backproject_strip_batch_shared_p{pbatch}"
        scratch = [pltpu.VMEM((pbatch, band, width), padded_imgs.dtype),
                   pltpu.VMEM((ty, chunk), jnp.float32),
                   pltpu.SemaphoreType.DMA]
    else:
        if double_buffer:
            n_slots = int(db_depth)
            if n_slots < 2:
                raise ValueError(
                    f"db_depth={db_depth}: the pipelined batch kernel "
                    f"needs an in-flight slot rotation of at least 2")
            kernel = functools.partial(
                backproject_kernel_batch_db, o_mm=o_mm, n_u=n_u, n_v=n_v,
                ty=ty, chunk=chunk, band=band, width=width, pbatch=pbatch,
                depth=n_slots, grid_dims=grid, quantized=quantized)
            name = f"backproject_strip_batch_db{n_slots}_p{pbatch}"
        else:
            kernel = functools.partial(
                backproject_kernel_batch, o_mm=o_mm, n_u=n_u, n_v=n_v,
                ty=ty, chunk=chunk, band=band, width=width, pbatch=pbatch,
                quantized=quantized)
            n_slots = 2
            name = f"backproject_strip_batch_p{pbatch}"
        scratch = [pltpu.VMEM((n_slots, band, width), padded_imgs.dtype),
                   pltpu.VMEM((ty, chunk), jnp.float32),
                   pltpu.SemaphoreType.DMA((n_slots,))]

    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),       # A stack (P, 3, 4)
        pl.BlockSpec(memory_space=pl.ANY),           # padded images (HBM)
    ]
    args = [zrow, A_stack, padded_imgs]              # zrow: prefetched
    if quantized:
        # Whole (P, rows, 2) scale block VMEM-resident per call.
        in_specs.append(
            pl.BlockSpec(scales.shape, lambda z, y, x, zr: (0, 0, 0)))
        args.append(scales)
        name += "_int8"
    in_specs.append(vol_spec)                        # volume tile in
    args.append(volume)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=vol_spec, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(volume.shape, volume.dtype),
        input_output_aliases={len(args) - 1: 0},
        interpret=interpret,
        name=name,
    )(*args)
