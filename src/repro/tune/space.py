"""Autotuner search space: candidate (strategy, tile-parameter) points.

The paper's empirical question — which gather/interpolation scheme wins on
a given chip — maps here to a compact grid over the five jnp strategies
(DESIGN.md §2) plus the Pallas kernel variants, each with the tile
parameters that govern its locality/width trade-off (``chunk``/``band``/
``width`` for strips, ``group``/``gband``/``gwidth`` for micro-windows,
``ty``/``double_buffer`` for the kernel).  Every family also
spans the ``pbatch`` axis — how many projections fold into the volume per
volume pass (DESIGN.md §7): the loop-nest inversion trades volume HBM
traffic (÷pbatch) against working-set pressure, so the right depth is a
chip property exactly like the gather scheme.  The space is small by
design: the sweep runs at benchmark time on real hardware, and per
Hofmann et al. the *ordering* shifts per microarchitecture, not the
plausible-region boundaries.
"""

from __future__ import annotations

from typing import NamedTuple

from repro.analysis.lint.budget import (VMEM_BUDGET_BYTES,
                                        batch_vmem_estimate)
from repro.core.backproject import GeomStatic
from repro.kernels.backproject_ops import clamp_tiles

__all__ = ["Candidate", "jnp_candidates", "pallas_candidates",
           "default_space", "pallas_batch_fits_vmem"]

# Kept as an alias for external readers; the value (and the whole byte
# model) lives in repro.analysis.lint.budget so the tuner's candidate
# screen and the lint budget pass can never drift.
_VMEM_BUDGET_BYTES = VMEM_BUDGET_BYTES

# pbatch depths proposed per candidate family (clamped to n_proj at
# sweep/run time; 1 = the classical per-projection nest).
_PBATCHES = (1, 4)


def pallas_batch_fits_vmem(gs: GeomStatic, *, pbatch: int, ty: int,
                           chunk: int, band: int, width: int,
                           depth: int = 2, itemsize: int = 4) -> bool:
    """Conservative VMEM budget check for a batched kernel candidate.

    Counts every in-flight projection strip at full ``pbatch`` depth or
    the DMA pipeline's ``depth``-slot rotation, whichever is larger
    (the plain batch kernel holds 2 slots, the pipelined variant
    ``db_depth``, and an ANY-space promotion may keep more resident),
    the aliased volume tile pair plus the f32 accumulator, the one-hot
    selector temporaries ``rowsel (ty·chunk, band)`` / ``colsel
    (ty·chunk, width)``, and — for the 1-byte wire — the ``(P, 2,
    rows)`` f32 scale sideband.  A candidate that fails here is never
    proposed — an OOM'd sweep point would abort the whole tune run on
    device.  Delegates to :func:`repro.analysis.lint.budget
    .batch_vmem_estimate`: the lint budget pass and this screen are one
    implementation.
    """
    return batch_vmem_estimate(gs, pbatch=pbatch, ty=ty, chunk=chunk,
                               band=band, width=width, depth=depth,
                               itemsize=itemsize).fits


class Candidate(NamedTuple):
    """One sweep point: a strategy name plus its static options.

    ``strategy`` is one of ``repro.core.backproject.STRATEGIES`` or
    ``"pallas"``; ``opts`` is a sorted ``(key, value)`` tuple so candidates
    are hashable and stable as cache-file keys.  ``opts`` may carry
    ``pbatch`` — the projection batch depth, consumed by the batch-major
    drivers rather than the ``sample_*`` kernels.
    """

    strategy: str
    opts: tuple

    @classmethod
    def of(cls, strategy: str, **opts) -> "Candidate":
        return cls(strategy, tuple(sorted(opts.items())))

    @property
    def label(self) -> str:
        if not self.opts:
            return self.strategy
        txt = ",".join(f"{k}={v}" for k, v in self.opts)
        return f"{self.strategy}[{txt}]"

    @property
    def pbatch(self) -> int:
        return int(dict(self.opts).get("pbatch", 1))


def jnp_candidates(gs: GeomStatic,
                   pbatches: tuple[int, ...] = _PBATCHES
                   ) -> list[Candidate]:
    """Candidate grid for the five jnp strategies, clamped to ``gs``.

    The tile grid is crossed with the ``pbatch`` axis: the batched loop
    nest changes the strategies' memory behaviour (volume traffic ÷
    pbatch, ``pbatch`` detector images hot at once), so the winner must
    be measured per depth, not assumed.
    """
    L = gs.L
    bases = [Candidate.of("scalar"), Candidate.of("gather")]
    for vb in (256, 512):
        bases.append(Candidate.of("onehot", vox_block=min(vb, L * L)))
    for chunk, band, width in ((32, 16, 128), (64, 16, 256)):
        bases.append(Candidate.of(
            "strip", chunk=min(chunk, L), band=min(band, gs.n_v + 2),
            width=min(width, gs.n_u + 2)))
    for group, gband, gwidth in ((8, 8, 64), (8, 8, 32), (16, 8, 128)):
        bases.append(Candidate.of(
            "strip2", group=min(group, L), gband=min(gband, gs.n_v + 2),
            gwidth=min(gwidth, gs.n_u + 2)))
    # The wire-dtype axis on the best strip window: bf16 halves strip
    # bytes at identical tap semantics (f32 accumulate); int8 halves
    # them again via per-row affine codes (repro.quant), paying a
    # one-time encode per projection.  Both must compete.
    bases.append(Candidate.of(
        "strip2", group=min(8, L), gband=min(8, gs.n_v + 2),
        gwidth=min(64, gs.n_u + 2), strip_dtype="bfloat16"))
    bases.append(Candidate.of(
        "strip2", group=min(8, L), gband=min(8, gs.n_v + 2),
        gwidth=min(64, gs.n_u + 2), strip_dtype="int8"))
    cands = [Candidate.of(b.strategy, **dict(b.opts), pbatch=pb)
             for b in bases for pb in pbatches]
    # De-dup clamped collisions on tiny geometries.
    seen, out = set(), []
    for c in cands:
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def pallas_candidates(gs: GeomStatic,
                      pbatches: tuple[int, ...] = _PBATCHES
                      ) -> list[Candidate]:
    """Kernel variants at a geometry-clamped base tile: plain /
    double-buffer per-projection, plus the projection-batched kernel
    crossed ``pbatch × {plain, db}`` at every depth that fits the VMEM
    budget — the batch path honors the full tuned config surface, so
    every variant competes at every batch depth.  The deepest fitting
    ``pbatch`` also proposes a 4-deep DMA rotation (``db_depth=4``).

    The base tile is TPU block-legal (``ty`` 8 or the whole axis,
    ``chunk`` 128 or the whole axis) and its ``(192, 1152)`` strip
    covers every tile footprint of the RabbitCT sweep at L=512 (the
    planner needs ``(190, 1125)`` over all 496 angles);
    ``clamp_tiles`` shrinks it to small detectors.
    """
    base = dict(ty=8 if gs.L % 8 == 0 else gs.L,
                chunk=128 if gs.L % 128 == 0 else gs.L, band=192,
                width=1152)
    cands = [
        Candidate.of("pallas", **base),
        Candidate.of("pallas", double_buffer=True, **base),
    ]
    batched = [pb for pb in pbatches
               if pb > 1 and pallas_batch_fits_vmem(gs, pbatch=pb, **base)]
    for pb in batched:
        cands.append(Candidate.of("pallas", pbatch=pb, **base))
        cands.append(Candidate.of("pallas", pbatch=pb, double_buffer=True,
                                  db_depth=2, **base))
        # Narrow-wire axes on the plain batch kernel: bf16 halves strip
        # DMA bytes, int8 halves them again (per-row affine codes, 1-byte
        # scratch — the VMEM screen at itemsize=1 admits it wherever the
        # f32 config fits).
        cands.append(Candidate.of("pallas", pbatch=pb,
                                  strip_dtype="bfloat16", **base))
        if pallas_batch_fits_vmem(gs, pbatch=pb, itemsize=1, **base):
            cands.append(Candidate.of("pallas", pbatch=pb,
                                      strip_dtype="int8", **base))
        # Shared superset window: one DMA per projection group.  The
        # window dims auto-size from the group planner at run time; the
        # VMEM screen assumes up to 2x the base strip dims per slab,
        # clamped to the detector as the wrapper clamps them.
        _, _, sband, swidth = clamp_tiles(gs, base["ty"], base["chunk"],
                                          2 * base["band"],
                                          2 * base["width"])
        if pallas_batch_fits_vmem(gs, pbatch=pb, ty=base["ty"],
                                  chunk=base["chunk"], band=sband,
                                  width=swidth, depth=pb):
            cands.append(Candidate.of("pallas", pbatch=pb,
                                      shared_window=True, **base))
            cands.append(Candidate.of("pallas", pbatch=pb,
                                      shared_window=True,
                                      strip_dtype="bfloat16", **base))
            cands.append(Candidate.of("pallas", pbatch=pb,
                                      shared_window=True,
                                      strip_dtype="int8", **base))
    if batched:
        pb = max(batched)
        if pallas_batch_fits_vmem(gs, pbatch=pb, depth=4, **base):
            cands.append(Candidate.of("pallas", pbatch=pb,
                                      double_buffer=True, db_depth=4,
                                      **base))
    return cands


def default_space(gs: GeomStatic,
                  include_pallas: bool = True) -> list[Candidate]:
    cands = jnp_candidates(gs)
    if include_pallas:
        cands += pallas_candidates(gs)
    return cands
