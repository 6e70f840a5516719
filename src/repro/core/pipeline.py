"""Distributed reconstruction pipeline (shard_map over the production mesh).

Decomposition (DESIGN.md §5):

* volume z-planes are sharded over the ``data`` mesh axis — the direct
  analogue of the paper's OpenMP plane decomposition ("the voxel volume is
  segmented into voxel planes that can be processed independently");
* the projection set is sharded over the ``model`` axis (and over ``pod``
  when present): each rank back-projects its projection subset into its
  full local z-slab, then the slabs are ``psum``-reduced over the
  projection axes.  Back projection is a sum over projections, so this is
  exact.

Collectives per reconstruction: one ``psum`` of the local volume slab per
projection-sharded axis — ``(L^3 / data_shards) * 4`` bytes, the quantity
the roofline term in ``benchmarks/fig2_scaling.py`` is built from.
Projection images are small (4.8 MB at RabbitCT scale) and stay local to
their rank; nothing else moves.

Each rank's slab update runs the batch-major loop nest of
:func:`repro.core.backproject._reconstruct_batched` (DESIGN.md §7): the
local slab streams through memory ``ceil(n_proj_local / pbatch)`` times
instead of ``n_proj_local`` times — the same P× traffic cut as the
single-device path, per rank, on top of the psum structure.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.dist.sharding import (ShardingRules, logical_to_spec,
                                 shard_constraint, sharding_context)

from .backproject import (GeomStatic, _reconstruct_batched,
                          validate_strip_opts)
from .filtering import apply_filter, make_filter_plan
from .geometry import Geometry

__all__ = ["sharded_reconstruct", "reconstruct_shards"]


def reconstruct_shards(local_projs, local_mats, gs: GeomStatic, plan,
                       local_volume, *, z0=None):
    """Per-rank body: back-project the local projection subset.

    ``plan`` is the resolved :class:`repro.dispatch.ExecutionPlan`
    (strategy, sample options, and batch depth in one static object —
    build one with ``ExecutionPlan.explicit(...)`` or resolve via the
    dispatcher).  ``local_volume`` may be a z-slab of the full volume;
    ``z0`` is the slab's first *global* z index (default 0 — a
    full-volume or first-slab caller).  It used to be hard-coded to 0,
    so any caller handing this body a non-first slab back-projected the
    wrong planes.
    """
    if z0 is None:
        z0 = jnp.int32(0)
    return _reconstruct_batched(local_projs, local_mats, local_volume, gs,
                                plan, jnp.asarray(z0, jnp.int32))


def sharded_reconstruct(projections, matrices, geom: Geometry, mesh: Mesh,
                        *, strategy: str = "strip2",
                        volume_axis: str = "data",
                        proj_axes: tuple[str, ...] = ("model",),
                        pbatch: int | None = None,
                        prefiltered: bool = True,
                        short_scan: bool | None = None,
                        **opts):
    """Reconstruct on a device mesh.

    ``projections``: ``(n_proj, n_v, n_u)`` filtered images.  ``n_proj``
    must divide by the product of ``proj_axes`` sizes, and ``geom.L`` by
    the ``volume_axis`` size.  Returns the full ``(L, L, L)`` volume with
    sharding ``P(volume_axis)`` on z.

    ``prefiltered=False`` takes *raw* line integrals instead: each rank
    FDK-filters its own projection subset on-device inside the
    ``shard_map`` body (cosine + Parker + ramp, DESIGN.md §8) before
    back-projecting, so the preprocessing stage scales out with the
    ``proj`` axes.  Parker weights are selected by *global angle index*
    — the full ``(n_proj, n_u)`` weight table is sharded along the
    projection axis exactly like the projections, so every rank weights
    its subset by the angles it actually holds.  (Filtering a non-prefix
    subset used to be impossible without silent mis-weighting:
    ``filter_projections`` handed any subset the first-k-angles
    weights.)

    ``strategy="auto"`` resolves through the process dispatcher
    (:mod:`repro.dispatch`) exactly like
    :func:`repro.core.backproject.reconstruct` — resolution (including
    the tuned ``pbatch``) happens here, host-side, before the
    ``shard_map`` closure is built, so every rank runs one identical
    plan.
    """
    from repro.dispatch import get_dispatcher

    gs = GeomStatic.of(geom)
    plan = get_dispatcher().resolve(geom, strategy, opts, pbatch=pbatch)
    validate_strip_opts(geom, matrices, plan.strategy, plan.jnp_opts())
    proj_shards = 1
    for ax in proj_axes:
        proj_shards *= mesh.shape[ax]
    z_shards = mesh.shape[volume_axis]
    if projections.shape[0] % proj_shards:
        raise ValueError(
            f"n_proj={projections.shape[0]} not divisible by "
            f"projection shards {proj_shards}")
    if gs.L % z_shards:
        raise ValueError(f"L={gs.L} not divisible by {z_shards} z-shards")

    fplan = None
    pw_full = None
    if not prefiltered:
        if projections.shape[0] != geom.n_proj:
            raise ValueError(
                f"prefiltered=False filters by global angle index, so the "
                f"raw stack must be the full scan: got "
                f"{projections.shape[0]} projections for "
                f"n_proj={geom.n_proj}")
        fplan = make_filter_plan(geom, short_scan)
        # The Parker table is sharded along the projection axis exactly
        # like the projections, so rank k filters its subset with the
        # weights of the angles it holds (ones = no short-scan weights).
        pw_full = (fplan.parker if fplan.parker is not None
                   else jnp.ones((geom.n_proj, geom.n_u), jnp.float32))

    # The CT decomposition in logical axes (repro.dist.sharding):
    # ``vol`` (z-planes, the paper's OpenMP plane split) and ``proj``.
    rules = ShardingRules(vol=(volume_axis,), proj=tuple(proj_axes))
    proj_spec = logical_to_spec(("proj",), rules, mesh)
    vol_spec = logical_to_spec(("vol",), rules, mesh)

    def slab_body(local_projs, local_mats, local_volume):
        # z offset of this rank's slab: planes are contiguous per shard.
        idx = jax.lax.axis_index(volume_axis)
        slab = local_volume.shape[0]
        z0 = idx * slab
        # The slab becomes varying over the projection axes once local
        # contributions are added; mark the carry accordingly (shard_map
        # varying-manual-axes typing).
        local_volume = jax.lax.pcast(local_volume, tuple(proj_axes),
                                     to="varying")
        partial = reconstruct_shards(local_projs, local_mats, gs, plan,
                                     local_volume, z0=z0)
        # Sum the projection-sharded partial volumes.
        for ax in proj_axes:
            partial = jax.lax.psum(partial, ax)
        return partial

    if prefiltered:
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(proj_spec, proj_spec, vol_spec),
            out_specs=vol_spec)
        def run(local_projs, local_mats, local_volume):
            return slab_body(local_projs, local_mats, local_volume)
    else:
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(proj_spec, proj_spec, proj_spec, vol_spec),
            out_specs=vol_spec)
        def run(local_projs, local_mats, local_pw, local_volume):
            local_projs = apply_filter(local_projs, fplan, local_pw)
            return slab_body(local_projs, local_mats, local_volume)

    with sharding_context(mesh, rules):
        # shard_constraint is the placement mechanism here: the
        # logical-axis annotation idiom, not a parallel device_put path.
        volume = shard_constraint(
            jnp.zeros((gs.L, gs.L, gs.L), dtype=jnp.float32),
            ("vol", None, None))
        projections = shard_constraint(jnp.asarray(projections),
                                       ("proj", None, None))
        matrices = shard_constraint(jnp.asarray(matrices, jnp.float32),
                                    ("proj", None, None))
        if prefiltered:
            return run(projections, matrices, volume)
        pw_full = shard_constraint(pw_full, ("proj", None))
        return run(projections, matrices, pw_full, volume)
