"""VMEM/SMEM byte model for the Pallas kernel configs — THE model.

One implementation of the per-config working-set accounting, used by
both the autotuner's candidate screen (:func:`repro.tune.space
.pallas_batch_fits_vmem` delegates here) and the lint budget pass, so
the two can never drift: a config the tuner admits is a config the
linter prices with the same bytes, and vice versa (PR 6's hard-coded
traffic-model tile is the bug class this kills).

The model mirrors what the wrappers actually allocate
(``repro.kernels.backproject_ops`` / ``backproject.py``).  Every strip
is a DMA *window*: the ``(band, width)`` footprint grown to
:func:`repro.kernels.backproject.strip_window` (one extra
``(sublane, 128)`` tile per axis, because window origins are
tile-aligned).

* **strip slots** — ``max(pbatch, depth) · window · itemsize``.
  The plain batch kernel rotates 2 slots, the pipelined variant
  ``db_depth``, the shared-window kernel one ``(pbatch, band, width)``
  slab; the screen prices the larger of the two (the tuner's
  historical conservative rule).
* **volume tile** — the aliased in/out ``(1, ty, chunk)`` f32 blocks,
  each double-buffered by the grid pipeline, plus the f32 accumulator:
  ``5 · ty · chunk · 4``.
* **one-hot selectors** — per tile row, :func:`_tile_contrib` holds
  ``colsel (width, chunk)`` and ``rowsel (band, chunk)`` f32 with their
  int32 iotas, and the f32 strip the MXU reads:
  ``2 · chunk · (band + width) · 4 + band · width · 4`` at window dims.
* **int8 scale sideband** — the ``(pbatch, rows, 2)`` f32 scale/offset
  block is VMEM-resident for the whole call (constant BlockSpec,
  double-buffered like every blocked input), its 2-wide minor dim
  padded to the 128-lane tile: ``2 · pbatch · rows · 128 · 4`` with
  ``rows`` the *padded* row count.
* **SMEM** — the ``(pbatch, 3, 4)`` f32 matrix stack (reported, never
  binding: SMEM is KBs and the stack is tiny).

``compiled.memory_analysis()`` of a kernel program reports HBM
arguments and temporaries only (its temp bytes are 0 for these
kernels), so it says nothing about VMEM; the v5e compile in
``tests/test_tpu_compile.py`` is the check that the configs the tuner
proposes fit the chip.
"""

from __future__ import annotations

import dataclasses

from repro.core.backproject import GeomStatic

__all__ = ["VMEM_BUDGET_BYTES", "WIRE_ITEMSIZE", "VmemEstimate",
           "batch_vmem_estimate", "estimate_for_pallas_config",
           "screen_candidate_spaces"]

# Usable per-core VMEM budget for candidate screening.  Half the 16 MB
# physical VMEM: the grid pipeline needs headroom for the in-flight
# volume tiles and the compiler's own temporaries.  (Moved here from
# repro.tune.space — the tuner now reads it from the model.)
VMEM_BUDGET_BYTES = 8 * 2 ** 20

# Strip wire itemsize per ``strip_dtype`` option — the same table
# ``repro.core.backproject.strip_wire_dtype`` validates against.
WIRE_ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}

def _padded_rows(gs: GeomStatic, band: int, itemsize: int) -> int:
    """Padded detector row count for a wire itemsize — the row shape
    the ``(P, rows, 2)`` scale sideband is allocated at
    (``backproject_ops._padded_dims``)."""
    from repro.kernels.backproject_ops import _padded_dims

    return _padded_dims(gs.n_v, gs.n_u, band, 128, itemsize)[0]


@dataclasses.dataclass(frozen=True)
class VmemEstimate:
    """Per-config VMEM/SMEM byte accounting, term by term."""

    strip_bytes: int
    tile_bytes: int
    onehot_bytes: int
    scale_bytes: int
    smem_bytes: int
    budget: int = VMEM_BUDGET_BYTES

    @property
    def vmem_total(self) -> int:
        return (self.strip_bytes + self.tile_bytes + self.onehot_bytes
                + self.scale_bytes)

    @property
    def fits(self) -> bool:
        return self.vmem_total <= self.budget

    def as_dict(self) -> dict:
        return {**dataclasses.asdict(self), "vmem_total": self.vmem_total,
                "fits": self.fits}


def batch_vmem_estimate(gs: GeomStatic, *, pbatch: int, ty: int,
                        chunk: int, band: int, width: int, depth: int = 2,
                        itemsize: int | None = None,
                        strip_dtype: str = "float32") -> VmemEstimate:
    """Byte model for one batched-kernel configuration.

    ``itemsize`` overrides the ``strip_dtype``-derived wire width (the
    tuner's historical calling convention); the ``(P, 2, rows)`` f32
    scale sideband is counted whenever the wire is 1 byte — the int8
    path always carries it.
    """
    if itemsize is None:
        try:
            itemsize = WIRE_ITEMSIZE[str(strip_dtype)]
        except KeyError:
            raise ValueError(
                f"unknown strip_dtype {strip_dtype!r}; want one of "
                f"{tuple(WIRE_ITEMSIZE)}") from None
    from repro.kernels.backproject import strip_window

    wband, wwidth = strip_window(band, width, itemsize)
    strips = max(pbatch, depth) * wband * wwidth * itemsize
    tile = 5 * ty * chunk * 4
    onehot = 2 * chunk * (wband + wwidth) * 4 + wband * wwidth * 4
    scales = (2 * pbatch * _padded_rows(gs, band, itemsize) * 128 * 4
              if itemsize == 1 else 0)
    smem = pbatch * 3 * 4 * 4
    return VmemEstimate(strip_bytes=strips, tile_bytes=tile,
                        onehot_bytes=onehot, scale_bytes=scales,
                        smem_bytes=smem)


def estimate_for_pallas_config(gs: GeomStatic,
                               cfg: dict) -> VmemEstimate:
    """Price a tuned/cached Pallas config dict (``_PALLAS_KEYS`` shape).

    Derives the slot depth from the variant flags exactly as the
    wrappers do: ``db_depth`` slots when ``double_buffer``, a
    ``pbatch``-deep slab when ``shared_window`` (at the explicit
    ``shared_band``/``shared_width`` when pinned, else the 2×-base
    screen the tuner applies before the group planner sizes the real
    slab), 2 rotation slots otherwise.  The tile parameters are clamped
    through :func:`repro.kernels.backproject_ops.clamp_tiles` — the
    model prices the config the kernel would *run*, not the raw dict.
    """
    from repro.kernels.backproject_ops import clamp_tiles

    ty, chunk, band, width = clamp_tiles(
        gs, int(cfg.get("ty", 8)), int(cfg.get("chunk", 128)),
        int(cfg.get("band", 16)), int(cfg.get("width", 512)))
    pbatch = max(1, int(cfg.get("pbatch", 1)))
    strip_dtype = str(cfg.get("strip_dtype", "float32"))
    if cfg.get("shared_window", False):
        band = int(cfg.get("shared_band") or 2 * band)
        width = int(cfg.get("shared_width") or 2 * width)
        _, _, band, width = clamp_tiles(gs, ty, chunk, band, width)
        depth = pbatch
    elif cfg.get("double_buffer", False):
        depth = int(cfg.get("db_depth", 2))
    else:
        depth = 2
    return batch_vmem_estimate(gs, pbatch=pbatch, ty=ty, chunk=chunk,
                               band=band, width=width, depth=depth,
                               strip_dtype=strip_dtype)


# ----------------------------------------------------------------------
# Lint pass: every config the repo can propose must fit the budget
# ----------------------------------------------------------------------

# Geometry scales the budget pass screens the candidate generator at:
# tiny (the test/CI shapes), mid, and the RabbitCT production case.
_SCREEN_SCALES = (8, 32, 512)


def screen_candidate_spaces(extra_configs=()):
    """Budget-screen every Pallas candidate the tuner can propose.

    The generator's own VMEM check and this model are now the same
    function, so a violation here means the *derived* config (after
    ``clamp_tiles`` / shared-window sizing) outgrew what the raw
    candidate was screened at — exactly the drift class this pass
    exists to catch.  ``extra_configs`` adds ``(label, GeomStatic,
    config_dict)`` triples (cache files, CLI ``--tuned-config``) to
    the screen.

    Returns ``(findings, checked)``.
    """
    from repro.core.geometry import default_geometry
    from repro.tune.space import pallas_candidates

    from .common import Finding

    findings, checked = [], 0
    for L in _SCREEN_SCALES:
        gs = GeomStatic.of(default_geometry().scaled(L))
        for cand in pallas_candidates(gs):
            est = estimate_for_pallas_config(gs, dict(cand.opts))
            checked += 1
            if not est.fits:
                findings.append(Finding(
                    "budget", "candidate-over-vmem",
                    f"L={L}:{cand.label}",
                    f"derived working set {est.vmem_total} B exceeds "
                    f"the {est.budget} B screen "
                    f"(strips={est.strip_bytes}, tile={est.tile_bytes}, "
                    f"onehot={est.onehot_bytes}, "
                    f"scales={est.scale_bytes})"))
    for label, gs, cfg in extra_configs:
        est = estimate_for_pallas_config(gs, dict(cfg))
        checked += 1
        if not est.fits:
            findings.append(Finding(
                "budget", "config-over-vmem", str(label),
                f"working set {est.vmem_total} B exceeds the "
                f"{est.budget} B budget (strips={est.strip_bytes}, "
                f"tile={est.tile_bytes}, onehot={est.onehot_bytes}, "
                f"scales={est.scale_bytes})"))
    return findings, checked
