"""Streamed reconstruction engine: slot-based continuous batching for CT.

The paper's production setting is a C-arm that delivers projections *as a
stream* — end-to-end latency is set by how much of the filter and
back-projection work overlaps the acquisition, not by the back projection
alone (Treibig et al., arXiv:1104.5243).  This engine is the CT analogue
of :class:`repro.serving.engine.ServingEngine`:

* fixed ``n_slots`` concurrent reconstructions share one resident volume
  stack ``(n_slots, L, L, L)`` and one jitted fold step;
* an arriving chunk is FDK-filtered **on device the moment it arrives**,
  with Parker weights selected by its explicit *angle indices* (the
  ``filter_projections(..., angle_indices=...)`` contract — arrival order
  never has to match angle order);
* filtered projections accumulate in a per-scan staging buffer and are
  folded ``pbatch`` at a time through the batch-major loop nest
  (:func:`repro.core.backproject._backproject_batch_body`), so a chunk
  pays one volume pass, not one pass per projection (DESIGN.md §7/§8);
* every tick folds *all* ready slots in one vmapped+masked jitted call —
  B scans in flight cost one compiled step, mirroring the LM engine's
  ``_masked_decode_step`` slot discipline;
* finished scans retire, their slot is zeroed and immediately refilled
  from the admission queue (continuous batching);
* with a ``mesh`` the slot stack is z-sharded over the mesh's volume
  axis (``P(None, vol)``, the paper's plane decomposition): each chunk
  is placed on every chip and filtered there, and each chip folds every
  view into its own slab with the Pallas batch kernel, in place (the
  stack is donated), so no reduction is ever needed (DESIGN.md §5).

Summation order within a volume follows arrival order, so a streamed
result matches the one-shot :func:`repro.core.backproject.reconstruct`
of the same projection set to fp32 rounding (~1e-5), not bit-for-bit.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.backproject import (GeomStatic, _backproject_batch_body,
                                    validate_strip_opts)
from repro.core.filtering import FilterPlan, apply_filter, make_filter_plan
from repro.core.geometry import Geometry

__all__ = ["ProjectionChunk", "ScanState", "ReconstructionEngine"]


@functools.partial(jax.jit,
                   static_argnames=("pad", "n_u", "n_proj", "scale"))
def _filter_chunk(projs, idx, cosw, hf, parker, pad, n_u, n_proj, scale):
    """On-device per-chunk FDK filter with angle-indexed Parker rows.

    Module-level jit: the compile cache is keyed on (chunk shape, plan
    statics), so every engine over the same geometry shares one trace
    per chunk size.
    """
    plan = FilterPlan(pad=pad, n_u=n_u, n_proj=n_proj, scale=scale,
                      hf=hf, cosw=cosw, parker=parker)
    pw = parker[idx] if parker is not None else None
    return apply_filter(projs, plan, pw)


@functools.partial(jax.jit, static_argnames=("gs", "plan"))
def _fold_slots(volumes, images, mats, mask, gs, plan):
    """One engine tick on device: fold a ``pbatch``-deep batch into every
    masked-in slot volume.

    ``volumes`` is ``(B, L, L, L)``, ``images`` ``(B, pbatch, n_v,
    n_u)``, ``mats`` ``(B, pbatch, 3, 4)``, ``mask`` ``(B,)`` bool;
    ``plan`` the resolved :class:`repro.dispatch.ExecutionPlan`.  The
    per-slot body is the batch-major volume pass of DESIGN.md §7 vmapped
    over slots; masked-out slots keep their volume bit-identical (their
    staged images are zero anyway, but the merge makes the guarantee
    unconditional — same idiom as the LM engine's masked decode step).
    """

    def one(vol, imgs, ms):
        return _backproject_batch_body(vol, imgs, ms, gs, plan,
                                       jnp.int32(0))

    new = jax.vmap(one)(volumes, images, mats)
    return jnp.where(mask[:, None, None, None], new, volumes)


@functools.partial(jax.jit, static_argnames=("gs", "kernel", "mesh", "axis"),
                   donate_argnums=0)
def _fold_slabs(volumes, slot, images, mats, gs, kernel, mesh, axis):
    """Fold one ``pbatch`` batch into slot ``slot`` of a z-sharded stack.

    Every chip folds the replicated batch into its own ``(S, L, L)``
    slab of the slot with the Pallas batch kernel, at its slab's first
    global plane; the stack is donated and the kernel aliases it, so the
    slot is updated in place and nothing else is copied.  ``kernel`` is
    the kernel's keyword options as sorted items.  Returns the stack and
    a small array read from the fold's output (one value per chip),
    which a caller may wait on: the stack itself is donated by the next
    fold.
    """
    from repro.kernels.backproject_ops import pallas_backproject_batch

    def body(vols, slot, imgs, ms):
        z0 = jax.lax.axis_index(axis) * vols.shape[1]
        vols = pallas_backproject_batch(vols, imgs, ms, gs, validate=False,
                                        z0=z0, slot=slot, **dict(kernel))
        mark = jax.lax.dynamic_slice(vols, (slot, 0, 0, 0), (1, 1, 1, 1))
        return vols, mark.reshape(1)

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P(None, axis), P(), P(), P()),
        out_specs=(P(None, axis), P(axis)), check_vma=False)(
            volumes, slot, images, mats)


@functools.partial(jax.jit, static_argnames=("mesh", "axis", "take"),
                   donate_argnums=0)
def _clear_slab_slot(volumes, slot, mesh, axis, take):
    """Zero slot ``slot`` of a z-sharded stack in place (the stack is
    donated); with ``take`` also return the slot's volume as it was,
    sharded ``P(vol)``."""

    def body(vols, slot):
        zero = jnp.zeros(vols.shape[1:], vols.dtype)
        out = jax.lax.dynamic_update_index_in_dim(vols, zero, slot, 0)
        if not take:
            return out
        return jax.lax.dynamic_index_in_dim(vols, slot, 0,
                                            keepdims=False), out

    out_specs = ((P(axis), P(None, axis)) if take else P(None, axis))
    return jax.shard_map(body, mesh=mesh, in_specs=(P(None, axis), P()),
                         out_specs=out_specs, check_vma=False)(volumes,
                                                               slot)


def _slab_axis(mesh, L: int) -> str:
    """The mesh axis the volume's z-planes are split over (the ``vol``
    logical axis); raises where the mesh asks for a split the engine
    does not make."""
    from repro.dist.sharding import ShardingRules, logical_to_spec

    rules = ShardingRules()
    axis = logical_to_spec(("vol",), rules, mesh)[0]
    if not isinstance(axis, str):
        raise ValueError(
            f"the mesh engine splits the volume over one mesh axis "
            f"{rules.vol}; mesh axes {mesh.axis_names} give {axis!r}")
    other = {a: n for a, n in dict(mesh.shape).items()
             if a != axis and n > 1}
    if other:
        raise ValueError(
            f"the mesh engine splits only the volume's z-planes (over "
            f"{axis!r}); a split over {other} (the projections) is not "
            f"supported: use a mesh whose other axes have size 1")
    if L % mesh.shape[axis]:
        raise ValueError(f"L={L} does not split into {mesh.shape[axis]} "
                         f"z-slabs")
    return axis


def _bytes_in_use(arr):
    """The device allocator's ``bytes_in_use`` where ``arr`` lives, or
    ``None`` where the backend reports none."""
    stats = next(iter(arr.devices())).memory_stats() or {}
    return stats.get("bytes_in_use")


@dataclasses.dataclass(frozen=True)
class ProjectionChunk:
    """One typed arrival payload: ``k`` raw projections with their
    matrices and global angle indices.

    The one submit currency shared by :meth:`ReconstructionEngine.submit`
    and the front door (:class:`repro.serving.ct_frontdoor.CTFrontDoor`).
    ``projections`` is ``(k, n_v, n_u)`` (or a single ``(n_v, n_u)``
    image), ``matrices`` ``(k, 3, 4)`` (or one ``(3, 4)``), and
    ``angle_indices`` the ``k`` *global* angle indices (or a scalar) —
    raw line integrals, filtered by the consumer on arrival.
    """

    projections: object
    matrices: object
    angle_indices: object

    @property
    def n(self) -> int:
        """Number of projections carried."""
        shape = np.shape(self.projections)
        return 1 if len(shape) == 2 else int(shape[0])

    def arrays(self, sharding=None):
        """Normalise to ``(k, n_v, n_u) f32, (k, 3, 4) f64, (k,) i32``;
        ``sharding`` places the projections (default: JAX's default
        device)."""
        if sharding is None:
            projs = jnp.asarray(self.projections, jnp.float32)
        else:
            projs = jax.device_put(
                np.asarray(self.projections, np.float32), sharding)
        if projs.ndim == 2:
            projs = projs[None]
        mats = np.asarray(self.matrices, np.float64).reshape(-1, 3, 4)
        idx = np.atleast_1d(np.asarray(self.angle_indices, np.int32))
        return projs, mats, idx


# The deprecated positional ``submit(sid, projection, matrix, angle_index)``
# form warns exactly once per process — every further call is silent, so a
# chunk-per-chunk streaming loop does not drown the log.
_POSITIONAL_SUBMIT_WARNED = False


@dataclasses.dataclass
class ScanState:
    """One reconstruction in flight (the CT analogue of ``Request``)."""

    sid: int
    n_proj: int                       # projections this scan will deliver
    received: int = 0
    folded: int = 0
    # Staged (filtered image, matrix) pairs awaiting a volume pass.
    pending: list = dataclasses.field(default_factory=list)
    volume: jnp.ndarray | None = None  # set at retirement
    done: bool = False

    @property
    def complete(self) -> bool:
        """All projections submitted (folds may still be outstanding)."""
        return self.received >= self.n_proj


class ReconstructionEngine:
    """Accept projection chunks in arrival order; serve volumes.

    ``submit(sid, projection, matrix, angle_index)`` takes one ``(n_v,
    n_u)`` projection (scalar ``angle_index``) or a ``(k, n_v, n_u)``
    chunk (``angle_index`` array of k global angle indices) — raw line
    integrals, filtered here on arrival.  ``strategy="auto"`` resolves
    through the process dispatcher exactly like ``reconstruct`` —
    including in-situ first-call selection (the timing problem is
    synthesized from the geometry, so resolution happens here at
    construction, before any projection arrives); when the resolved
    plan's tuned Pallas batch kernel beat the jnp nest
    (``plan.use_pallas``), the fold step runs that kernel per ready
    slot instead of the vmapped jnp body.  Strip windows are validated
    against the host planner per submitted chunk (memoised), so an
    undersized window raises instead of dropping taps.

    ``mesh`` z-shards the slot stack over the mesh's volume axis
    (:class:`repro.dist.sharding.ShardingRules` ``vol``, the ``data``
    axis), one slab of ``L / n`` planes per chip, and every fold runs
    the plan's Pallas batch kernel on each chip's slab in place.  It
    needs a plan that folds with the kernel; a mesh that also splits
    another axis (the projections) raises.
    """

    def __init__(self, geom: Geometry, *, n_slots: int = 4,
                 strategy: str = "strip2", pbatch: int | None = None,
                 short_scan: bool | None = None, validate: bool = True,
                 auto_step: bool = True, plan=None, mesh=None, **opts):
        self.geom = geom
        self.gs = GeomStatic.of(geom)
        if plan is None:
            from repro.dispatch import get_dispatcher

            plan = get_dispatcher().resolve(geom, strategy, opts,
                                            pbatch=pbatch)
        # ``self.plan`` is the *filter* plan (pre-dates the dispatcher);
        # the execution plan lives under ``exec_plan``.
        self.exec_plan = plan
        self.strategy = plan.strategy
        self.opts = plan.jnp_opts()
        # Tuned kernel fold: only taken when the measured evidence says
        # the Pallas batch kernel beat the jnp nest for this key.
        self._pallas_kwargs = (plan.pallas_opts() if plan.use_pallas
                               else None)
        if pbatch is not None:
            eff = int(pbatch)
        elif self._pallas_kwargs is not None:
            # The kernel decision was timed at its own batch depth.
            eff = int(self._pallas_kwargs.get("pbatch", plan.pbatch))
        else:
            eff = plan.pbatch
        self.pbatch = max(1, eff)
        if self._pallas_kwargs is not None:
            self._pallas_kwargs["pbatch"] = self.pbatch
        self.validate = validate
        self.auto_step = auto_step
        self.n_slots = int(n_slots)
        self.plan = make_filter_plan(geom, short_scan)
        self.mesh = mesh
        self.shards = 1
        shape = (self.n_slots,) + (geom.L,) * 3
        if mesh is None:
            self._volumes = jnp.zeros(shape, jnp.float32)
            self._zero_image = jnp.zeros((geom.n_v, geom.n_u), jnp.float32)
        else:
            self._init_mesh(mesh, shape)
        # On a mesh, a small array of the latest fold's output: what a
        # caller waits on for a fold, since the next fold donates the
        # stack.  ``None`` off a mesh and before the first fold.
        self.last_fold = None
        self.slot_scan: list[int | None] = [None] * self.n_slots
        self.scans: dict[int, ScanState] = {}
        self.queue: list[int] = []
        self.slot_history: list[tuple[int, int]] = []  # (slot, sid)
        self.stats = {"folds": 0, "fold_ticks": 0, "retired": 0,
                      "pallas_folds": 0, "aborted": 0}
        self._next_sid = 0

    def _init_mesh(self, mesh, shape) -> None:
        """Place the engine's state on ``mesh``: the slot stack z-sharded
        (created there, never whole on one chip), the filter's tables
        and the zero image replicated."""
        from repro.kernels.backproject_ops import clamp_tiles

        if self._pallas_kwargs is None:
            raise ValueError(
                "the mesh engine folds with the Pallas batch kernel: give "
                "it a plan with use_pallas set")
        if self._pallas_kwargs.get("shared_window"):
            raise ValueError(
                "the mesh engine folds each slab with the strip kernels; "
                "the shared-window variant sizes its window on the host "
                "per fold and is not supported")
        self._axis = _slab_axis(mesh, self.geom.L)
        self.shards = int(mesh.shape[self._axis])
        rep = NamedSharding(mesh, P())
        self._replicated = rep
        self._volumes = jax.jit(
            functools.partial(jnp.zeros, shape, jnp.float32),
            out_shardings=NamedSharding(mesh, P(None, self._axis)))()
        self._zero_image = jax.device_put(
            np.zeros((self.geom.n_v, self.geom.n_u), np.float32), rep)
        self.plan = self.plan._replace(**{
            k: jax.device_put(getattr(self.plan, k), rep)
            for k in ("hf", "cosw", "parker")
            if getattr(self.plan, k) is not None})
        kw = self._pallas_kwargs
        self._tiles = dict(zip(("ty", "chunk", "band", "width"), clamp_tiles(
            self.gs, int(kw.get("ty", 8)), int(kw.get("chunk", 128)),
            int(kw.get("band", 16)), int(kw.get("width", 512)))))
        self._kernel = tuple(sorted({**kw, **self._tiles}.items()))

    # ------------------------------------------------------------------
    # Admission
    # ------------------------------------------------------------------
    def begin_scan(self, n_proj: int | None = None) -> int:
        """Register a new reconstruction; returns its scan id.

        The scan occupies a volume slot immediately when one is free,
        else it queues (its chunks are still filtered and staged on
        arrival) until a running scan retires — continuous batching.

        ``n_proj=None`` means a full scan (``geom.n_proj``).  An explicit
        non-positive count is a caller bug and raises — a truthiness
        check here once turned ``n_proj=0`` into a silent full scan.
        """
        if n_proj is not None and int(n_proj) <= 0:
            raise ValueError(
                f"begin_scan: n_proj must be positive, got {n_proj!r} "
                f"(pass None for a full scan)")
        sid = self._next_sid
        self._next_sid += 1
        self.scans[sid] = ScanState(
            sid=sid,
            n_proj=int(n_proj) if n_proj is not None else self.geom.n_proj)
        self.queue.append(sid)
        self._admit()
        return sid

    def _free_slots(self):
        return [i for i, s in enumerate(self.slot_scan) if s is None]

    def _admit(self):
        for slot in self._free_slots():
            if not self.queue:
                break
            sid = self.queue.pop(0)
            self.slot_scan[slot] = sid
            self.slot_history.append((slot, sid))

    # ------------------------------------------------------------------
    # Arrival path
    # ------------------------------------------------------------------
    def submit(self, sid: int, chunk, matrix=None, angle_index=None):
        """Stage one :class:`ProjectionChunk` of scan ``sid``.

        Filters on device now — with the Parker rows of the *submitted
        angle indices* — and stages the result for the next fold tick.
        Arrival order is free: chunks may be shuffled, interleaved
        across scans, and split arbitrarily.

        The blessed form is ``submit(sid, ProjectionChunk(...))``.  The
        pre-facade positional form ``submit(sid, projection, matrix,
        angle_index)`` still works as a thin shim but emits one
        ``DeprecationWarning`` per process.
        """
        global _POSITIONAL_SUBMIT_WARNED
        if not isinstance(chunk, ProjectionChunk):
            if matrix is None or angle_index is None:
                raise TypeError(
                    "submit takes a ProjectionChunk (or the deprecated "
                    "positional (projection, matrix, angle_index) triple)")
            if not _POSITIONAL_SUBMIT_WARNED:
                _POSITIONAL_SUBMIT_WARNED = True
                warnings.warn(
                    "submit(sid, projection, matrix, angle_index) is "
                    "deprecated; pass submit(sid, ProjectionChunk("
                    "projection, matrix, angle_index))",
                    DeprecationWarning, stacklevel=2)
            chunk = ProjectionChunk(chunk, matrix, angle_index)
        elif matrix is not None or angle_index is not None:
            raise TypeError(
                "submit(sid, ProjectionChunk) takes no separate matrix/"
                "angle_index arguments")
        scan = self.scans[sid]
        if scan.done:
            raise ValueError(f"scan {sid} already finished")
        if self.mesh is None:
            projs, mats, idx = chunk.arrays()
        else:
            with obs.span("engine.put", units=chunk.n):
                projs, mats, idx = chunk.arrays(self._replicated)
        k = projs.shape[0]
        if mats.shape[0] != k or idx.shape != (k,):
            raise ValueError(
                f"chunk of {k} projection(s) needs {k} matrices and {k} "
                f"angle indices; got {mats.shape[0]} and {idx.shape}")
        if idx.min() < 0 or idx.max() >= self.geom.n_proj:
            raise ValueError(
                f"angle indices must lie in [0, {self.geom.n_proj})")
        if scan.received + k > scan.n_proj:
            raise ValueError(
                f"scan {sid} declared {scan.n_proj} projections; "
                f"{scan.received + k} submitted")
        if self.validate and self._pallas_kwargs is None:
            # The kernel fold path validates its own tile config at fold
            # time (pallas_backproject_batch(validate=...)).
            validate_strip_opts(self.geom, mats, self.strategy, self.opts)
        with obs.span("engine.filter", units=k):
            filt = _filter_chunk(
                projs, jnp.asarray(idx), self.plan.cosw, self.plan.hf,
                self.plan.parker, pad=self.plan.pad, n_u=self.plan.n_u,
                n_proj=self.plan.n_proj, scale=self.plan.scale)
        mats32 = np.asarray(mats, np.float32)
        for i in range(k):
            scan.pending.append((filt[i], mats32[i]))
        scan.received += k
        if self.auto_step:
            self.step()

    # ------------------------------------------------------------------
    # Fold path
    # ------------------------------------------------------------------
    def _take_batch(self, scan: ScanState):
        """Up to ``pbatch`` staged projections, zero-padded to depth.

        Padding images are zero (their contribution is exactly 0.0) and
        padding matrices repeat a real, validated matrix so the strip
        planner's coverage guarantee extends to the pad rows.
        """
        take = scan.pending[:self.pbatch]
        del scan.pending[:self.pbatch]
        imgs = [img for img, _ in take]
        mats = [m for _, m in take]
        while len(imgs) < self.pbatch:
            imgs.append(self._zero_image)
            mats.append(mats[0])
        return jnp.stack(imgs), np.stack(mats), len(take)

    def step(self) -> bool:
        """One engine tick: fold every ready slot, retire finished scans.

        A slot is *ready* when it holds a full ``pbatch`` of staged
        projections, or its scan is complete (the sub-``pbatch``
        remainder folds zero-padded — same compiled step, DESIGN.md §8).
        All ready slots fold in one vmapped jitted call.  Returns True
        when any fold or retirement happened.
        """
        self._admit()
        ready = []
        for slot, sid in enumerate(self.slot_scan):
            if sid is None:
                continue
            scan = self.scans[sid]
            if len(scan.pending) >= self.pbatch \
                    or (scan.complete and scan.pending):
                ready.append((slot, scan))
        progressed = False
        if ready:
            n = sum(min(self.pbatch, len(scan.pending)) for _, scan in ready)
            with obs.span("engine.fold", units=n, slots=len(ready),
                          shards=self.shards) as rec:
                if rec is not None:
                    rec["bytes_in_use_entry"] = _bytes_in_use(self._volumes)
                if self.mesh is not None:
                    self._fold_mesh(ready)
                elif self._pallas_kwargs is not None:
                    self._fold_kernel(ready)
                else:
                    self._fold_jnp(ready)
                if rec is not None:
                    rec["bytes_in_use_exit"] = _bytes_in_use(self._volumes)
            self.stats["fold_ticks"] += 1
            progressed = True
        progressed |= self._retire()
        return progressed

    def _fold_kernel(self, ready) -> None:
        """Tuned kernel fold: the Pallas batch winner, one launch per
        ready slot (zero-padded staging contributes exactly 0, so the
        static batch shape is shared with the jnp path)."""
        from repro.kernels.backproject_ops import pallas_backproject_batch

        for slot, scan in ready:
            imgs, ms, n = self._take_batch(scan)
            vol = pallas_backproject_batch(
                self._volumes[slot], imgs, ms, self.geom,
                validate=self.validate, **self._pallas_kwargs)
            self._volumes = self._volumes.at[slot].set(vol)
            scan.folded += n
            self.stats["folds"] += n
            self.stats["pallas_folds"] += n

    def _fold_mesh(self, ready) -> None:
        """Mesh fold: per ready slot, the host planner checks the batch
        over the whole cube, then every chip folds it into its own slab
        of the slot, in place (:func:`_fold_slabs`)."""
        from repro.kernels.backproject_ops import validate_batch

        for slot, scan in ready:
            imgs, ms, n = self._take_batch(scan)
            if self.validate:
                validate_batch(self.geom, ms, slabs=self.shards,
                               **self._tiles)
            self._volumes, self.last_fold = _fold_slabs(
                self._volumes, jnp.int32(slot), imgs, ms, gs=self.gs,
                kernel=self._kernel, mesh=self.mesh, axis=self._axis)
            scan.folded += n
            self.stats["folds"] += n
            self.stats["pallas_folds"] += n

    def _fold_jnp(self, ready) -> None:
        """All ready slots in one vmapped, slot-masked jitted call."""
        images = [self._zero_image[None].repeat(self.pbatch, axis=0)
                  ] * self.n_slots
        mats = [np.broadcast_to(np.eye(3, 4, dtype=np.float32),
                                (self.pbatch, 3, 4))] * self.n_slots
        mask = np.zeros((self.n_slots,), bool)
        for slot, scan in ready:
            imgs, ms, n = self._take_batch(scan)
            images[slot] = imgs
            mats[slot] = ms
            mask[slot] = True
            scan.folded += n
            self.stats["folds"] += n
        self._volumes = _fold_slots(
            self._volumes, jnp.stack(images), jnp.asarray(np.stack(mats)),
            jnp.asarray(mask), self.gs, self.exec_plan)

    def _clear_slot(self, slot: int, take: bool = False):
        """Zero slot ``slot``; with ``take`` return its volume first."""
        if self.mesh is None:
            vol = self._volumes[slot] if take else None
            self._volumes = self._volumes.at[slot].set(0.0)
            return vol
        out = _clear_slab_slot(self._volumes, jnp.int32(slot),
                               mesh=self.mesh, axis=self._axis, take=take)
        vol, self._volumes = out if take else (None, out)
        return vol

    def _retire(self) -> bool:
        any_retired = False
        for slot, sid in enumerate(self.slot_scan):
            if sid is None:
                continue
            scan = self.scans[sid]
            if scan.complete and not scan.pending:
                scan.volume = self._clear_slot(slot, take=True)
                scan.done = True
                self.slot_scan[slot] = None
                self.stats["retired"] += 1
                any_retired = True
                del self.slot_history[:-4096]   # bound a long-lived server
        if any_retired:
            self._admit()
        return any_retired

    # ------------------------------------------------------------------
    # Results
    # ------------------------------------------------------------------
    def drain(self, max_ticks: int = 100_000) -> int:
        """Fold until no slot can make progress; returns ticks run.

        Scans that have not submitted all their projections keep their
        sub-``pbatch`` staging buffers — drain never forces a partial
        scan to a (wrong) early result.
        """
        ticks = 0
        while ticks < max_ticks and self.step():
            ticks += 1
        return ticks

    def result(self, sid: int, pop: bool = False) -> jnp.ndarray:
        """The finished ``(L, L, L)`` volume of scan ``sid``.

        ``pop=True`` releases the scan's state after fetching — a
        long-running server must do one of ``pop``/:meth:`release` per
        scan, or retired volumes (``L³·4`` bytes each) accumulate in
        ``self.scans`` forever.
        """
        scan = self.scans[sid]
        if not scan.done:
            raise ValueError(
                f"scan {sid} not finished: {scan.received}/{scan.n_proj} "
                f"submitted, {len(scan.pending)} staged"
                + ("" if scan.complete else " (more submissions expected)"))
        vol = scan.volume
        if pop:
            self.release(sid)
        return vol

    def release(self, sid: int) -> None:
        """Drop a *finished* scan's state (and its retained volume)."""
        scan = self.scans.get(sid)
        if scan is None:
            return
        if not scan.done:
            raise ValueError(f"scan {sid} still active; cannot release")
        del self.scans[sid]

    def abort_scan(self, sid: int) -> None:
        """Drop scan ``sid`` mid-flight (the front door's cancel path).

        Staged projections are discarded, the scan's slot (if it holds
        one) is retired and zeroed, and the freed slot refills from the
        admission queue immediately.  The next occupant starts from the
        same all-zero volume a fresh slot gets, so abort-then-reuse is
        bit-clean.  Unknown (or already-released) sids raise; aborting a
        *finished* scan just drops its retained volume.
        """
        scan = self.scans.pop(sid, None)
        if scan is None:
            raise ValueError(f"abort_scan: unknown scan {sid}")
        if sid in self.queue:
            self.queue.remove(sid)
        for slot, owner in enumerate(self.slot_scan):
            if owner == sid:
                self._clear_slot(slot)
                self.slot_scan[slot] = None
        scan.pending.clear()
        scan.done = True
        self.stats["aborted"] += 1
        self._admit()

    @property
    def active(self) -> int:
        """Scans currently holding slots or queued."""
        return sum(s is not None for s in self.slot_scan) + len(self.queue)

    @property
    def free_slots(self) -> int:
        """Slots an admission would get *right now* (empty slots not
        already claimed by the engine's own FIFO queue) — the capacity
        signal the front door's policies schedule against."""
        empty = sum(s is None for s in self.slot_scan)
        return max(0, empty - len(self.queue))
