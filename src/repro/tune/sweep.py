"""Sweep driver: time every candidate strategy/config on this backend.

The measurement mirrors ``benchmarks/fig1_single_device`` (projections
into an ``L^3`` volume, median of a few runs via :func:`timing.time_fn`)
so tuned decisions and benchmark rows are directly comparable.  A
candidate carrying ``pbatch`` is timed through the batch-major drivers on
a ``pbatch``-deep projection stack and normalised to **us per
projection**, so depths compete on one scale with the classical
per-projection nest.  Candidates whose static windows cannot cover the
geometry's tap footprint, or that the VMEM screen rejects, are *skipped
with a recorded reason* rather than timed — a config the validator
rejects would produce silently wrong voxels, and a tuner must never
select one.  Nothing else is skipped: a candidate that fails to compile
or run raises.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backproject import (STRATEGIES, GeomStatic,
                                    backproject_batch, backproject_one,
                                    validate_strip_opts)
from repro.core.geometry import Geometry, projection_matrices, \
    projection_matrix

from .cache import device_identity
from .space import Candidate, default_space
from .timing import time_fn

__all__ = ["Timing", "SweepResult", "sweep_strategies"]


@dataclasses.dataclass(frozen=True)
class Timing:
    """One measured sweep point (``us_per_call`` = us per *projection*)."""

    label: str
    strategy: str
    opts: tuple
    us_per_call: float
    gups: float                     # billions of voxel updates / second

    def as_dict(self) -> dict:
        return {"label": self.label, "strategy": self.strategy,
                "opts": dict(self.opts), "us_per_call": self.us_per_call,
                "gups": self.gups}


@dataclasses.dataclass
class SweepResult:
    geom_key: tuple
    backend: str
    device_kind: str
    timings: list[Timing]
    skipped: list[tuple[str, str]]  # (candidate label, reason)

    def best(self, strategies: tuple[str, ...] | None = None):
        pool = [t for t in self.timings
                if strategies is None or t.strategy in strategies]
        return min(pool, key=lambda t: t.us_per_call) if pool else None


def _default_problem(geom: Geometry):
    """One mid-sweep projection of white noise (access-pattern-faithful;
    the timings do not depend on image content)."""
    rng = np.random.default_rng(0)
    image = jnp.asarray(rng.standard_normal((geom.n_v, geom.n_u)),
                        jnp.float32)
    theta = float(geom.angles[geom.n_proj // 2])
    A = jnp.asarray(projection_matrix(geom, theta), jnp.float32)
    return image, A


def _batch_problem(geom: Geometry, image, pbatch: int):
    """A ``pbatch``-deep stack around the mid-sweep angle: distinct
    matrices (faithful strip-origin churn), one noise image replicated."""
    k0 = max(0, geom.n_proj // 2 - pbatch // 2)
    thetas = [float(geom.angles[min(k0 + i, geom.n_proj - 1)])
              for i in range(pbatch)]
    mats = jnp.asarray(np.stack([projection_matrix(geom, th)
                                 for th in thetas]), jnp.float32)
    images = jnp.broadcast_to(image, (pbatch,) + image.shape)
    return images, mats


def _screen(strategy: str, geom: Geometry, gs: GeomStatic, mats_all,
            pbatch: int, opts: dict) -> None:
    """Raise ``ValueError`` if the planner or the VMEM screen rejects a
    candidate — the only reasons a sweep may skip one."""
    if strategy in STRATEGIES:
        validate_strip_opts(geom, mats_all, strategy, opts)
        return
    from repro.kernels.backproject_ops import (clamp_tiles,
                                               shared_window_dims,
                                               validate_strip_config)
    from .space import pallas_batch_fits_vmem
    ty, chunk, band, width = clamp_tiles(
        gs, opts.get("ty", 8), opts.get("chunk", 128),
        opts.get("band", 16), opts.get("width", 512))
    if opts.get("shared_window", False):
        # Size the superset window over the *full* matrix set (what
        # reconstruct-time resolution will see) and screen it against
        # the VMEM budget — the planner-tight dims can exceed the base
        # strip's.
        pb_eff = max(1, min(pbatch, geom.n_proj))
        sband, swidth = shared_window_dims(
            geom, mats_all, ty=ty, chunk=chunk, pbatch=pb_eff,
            shared_band=opts.get("shared_band"),
            shared_width=opts.get("shared_width"))
        itemsize = {"bfloat16": 2, "int8": 1}.get(
            str(opts.get("strip_dtype")), 4)
        if not pallas_batch_fits_vmem(
                gs, pbatch=pb_eff, ty=ty, chunk=chunk, band=sband,
                width=swidth, depth=pb_eff, itemsize=itemsize):
            raise ValueError(
                f"shared window ({sband}, {swidth}) x pbatch={pb_eff} "
                f"exceeds the VMEM budget")
        return
    for A_i in mats_all:
        validate_strip_config(geom, A_i, ty=ty, chunk=chunk, band=band,
                              width=width)


def sweep_strategies(geom: Geometry, *, image=None, A=None,
                     space: list[Candidate] | None = None,
                     include_pallas: bool | None = None,
                     warmup: int = 1, iters: int = 3,
                     min_total_s: float | None = None) -> SweepResult:
    """Time every valid candidate for ``geom`` on the current backend.

    ``include_pallas=None`` auto-selects: the kernel is timed only where
    it compiles (TPU) — interpreter-mode timings would be meaningless.
    ``min_total_s`` overrides :func:`time_fn`'s adaptive floor (pass 0
    to pin the sample count to ``iters`` exactly — cheap smoke sweeps).
    """
    tkw = {} if min_total_s is None else {"min_total_s": min_total_s}
    gs = GeomStatic.of(geom)
    backend = jax.default_backend()
    if include_pallas is None:
        include_pallas = backend == "tpu"
    if space is None:
        space = default_space(gs, include_pallas=include_pallas)
    if image is None or A is None:
        image, A = _default_problem(geom)
    # A decision is persisted for the *geometry*, so candidate windows
    # must cover the footprint at every projection angle — the timing
    # matrix alone could admit a config that loses taps (or fails
    # validation) at the sweep extremes once reconstruct() runs the
    # full set.
    mats_all = np.asarray(projection_matrices(geom), np.float64)
    vol0 = jnp.zeros((gs.L,) * 3, jnp.float32)

    timings: list[Timing] = []
    skipped: list[tuple[str, str]] = []
    for cand in space:
        opts = dict(cand.opts)
        pbatch = max(1, int(opts.pop("pbatch", 1)))
        if cand.strategy not in STRATEGIES and cand.strategy != "pallas":
            raise ValueError(f"unknown candidate strategy "
                             f"{cand.strategy!r}")
        # Only the planner and the VMEM screen may reject a candidate;
        # anything the timed run raises (a kernel the compiler refuses
        # included) propagates.
        try:
            _screen(cand.strategy, geom, gs, mats_all, pbatch, opts)
        except ValueError as e:
            skipped.append((cand.label, str(e)))
            continue
        if cand.strategy in STRATEGIES:
            if pbatch == 1:
                t = time_fn(backproject_one, vol0, image, A, geom,
                            strategy=cand.strategy, warmup=warmup,
                            iters=iters, **tkw, **opts)
            else:
                images, mats = _batch_problem(geom, image, pbatch)
                t = time_fn(backproject_batch, vol0, images, mats, geom,
                            strategy=cand.strategy, pbatch=pbatch,
                            warmup=warmup, iters=iters, **tkw,
                            **opts) / pbatch
        else:
            from repro.kernels.backproject_ops import (
                pallas_backproject_batch, pallas_backproject_one)
            if pbatch == 1:
                t = time_fn(pallas_backproject_one, vol0, image, A, geom,
                            warmup=warmup, iters=iters, **tkw, **opts)
            else:
                images, mats = _batch_problem(geom, image, pbatch)
                t = time_fn(pallas_backproject_batch, vol0, images, mats,
                            geom, pbatch=pbatch, validate=False,
                            warmup=warmup, iters=iters, **tkw,
                            **opts) / pbatch
        timings.append(Timing(
            label=cand.label, strategy=cand.strategy, opts=cand.opts,
            us_per_call=t * 1e6, gups=gs.L ** 3 / t / 1e9))

    backend, device_kind = device_identity(backend)
    return SweepResult(geom_key=tuple(gs), backend=backend,
                       device_kind=device_kind,
                       timings=timings, skipped=skipped)
