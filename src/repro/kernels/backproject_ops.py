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

from repro.core.backproject import (DEFAULT_PBATCH, GeomStatic,
                                    strip_wire_dtype)
from repro.core.clipping import (_round8, _round128, plan_strips,
                                 shared_window_requirement)
from repro.core.geometry import Geometry

from .backproject import (SUBLANE, backproject_volume_pallas,
                          backproject_volume_pallas_batch, strip_window)

__all__ = ["pallas_backproject_one", "pallas_backproject_batch",
           "validate_strip_config", "shared_window_dims", "clamp_tiles"]


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


def validate_strip_config(geom: Geometry, A: np.ndarray, *, ty: int,
                          chunk: int, band: int, width: int) -> None:
    """Host-side check that (band, width) covers every tile footprint.

    A tile spans ``ty`` lines x ``chunk`` voxels; per-line strip needs are
    computed exactly by the planner (monotone-beam property), and adjacent
    lines' strips are merged by taking min/max origins.  Raises with the
    required sizes if the static config is too small — silent tap loss is
    never possible.
    """
    plan = plan_strips(geom, A, chunk=chunk)
    r0 = plan.r0.astype(np.int64)
    c0 = plan.c0.astype(np.int64)
    # Merge ty adjacent lines: worst-case span = max over the group of
    # (origin + required extent) - min origin.
    L = geom.L
    g = r0.reshape(L, L // ty, ty, -1)
    span_r = g.max(axis=2) - g.min(axis=2) + plan.required_band
    gc = c0.reshape(L, L // ty, ty, -1)
    span_c = gc.max(axis=2) - gc.min(axis=2) + plan.required_width
    need_band, need_width = int(span_r.max()), int(span_c.max())
    if band < need_band or width < need_width:
        raise ValueError(
            f"strip config (band={band}, width={width}) does not cover the "
            f"tile footprint; need at least (band={need_band}, "
            f"width={need_width}) for ty={ty}, chunk={chunk}")


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
        validate_strip_config(geom, np.asarray(A, np.float64), ty=ty,
                              chunk=chunk, band=band, width=width)
    return _run(jnp.asarray(volume), jnp.asarray(image),
                jnp.asarray(A, jnp.float32), gs, ty, chunk, band, width,
                double_buffer, int(db_depth), strip_dtype, _interpret())


@functools.partial(
    jax.jit,
    static_argnames=("gs", "ty", "chunk", "band", "width", "pbatch",
                     "double_buffer", "db_depth", "shared_window",
                     "strip_dtype", "interpret"))
def _run_batched(volume, images, mats, gs: GeomStatic, ty, chunk, band,
                 width, pbatch, double_buffer, db_depth, shared_window,
                 strip_dtype, interpret):
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
            shared_window=shared_window, scales=scl, interpret=interpret)

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
    if need is None:
        need = shared_window_requirement(geom, mats64, ty=ty, chunk=chunk,
                                         pbatch=pbatch)
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
                             strategy: str = "fixed"):
    """Add a stack of projections to ``volume``, ``pbatch`` per kernel
    launch, with the volume tile resident in VMEM across the in-kernel
    projection loop (DESIGN.md §7).

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
        mats64 = np.asarray(mats, np.float64).reshape(-1, 3, 4)
        key = (gs, ty, chunk, band, width,
               hashlib.sha1(mats64.tobytes()).hexdigest())
        if key not in _VALIDATED_STACKS:
            for A in mats64:
                validate_strip_config(geom, A, ty=ty, chunk=chunk,
                                      band=band, width=width)
            if len(_VALIDATED_STACKS) >= 4096:
                _VALIDATED_STACKS.clear()
            _VALIDATED_STACKS.add(key)
    return _run_batched(jnp.asarray(volume), images, mats_f32, gs, ty,
                        chunk, band, width, pbatch, double_buffer,
                        int(db_depth), shared_window, strip_dtype,
                        _interpret())
