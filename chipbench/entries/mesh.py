"""Scanners streaming whole scans into ``CTFrontDoor`` over a device mesh.

The traffic keys and the scanners' behaviour are those of the
``frontdoor`` entry: ``scanners`` closed-loop clients, one C-arm each,
send whole scans of all the acquisition's views in acquisition order in
``ProjectionChunk``s of ``chunk`` raw views, at most one fold ahead of
the device, and once the window's seconds are up a scanner completes
the fold batch it has started and stops.  The configuration's
``deployment.mesh`` gives the mesh (``data`` x ``model`` chips); the
front door's engine splits its volumes over it in z-slabs, one per
chip.

What differs from the one-chip entry is what the program's state allows:

* the engine donates its slot stack to every fold, so a scanner waits on
  the small array each fold returns (``engine.last_fold``) and never
  keeps a stack;
* a volume is sharded over the chips, so the checked voxels are read on
  each chip from its own slab and summed over the chips (each voxel
  lives on one chip, so the sum adds only zeros to it): no volume, and
  no stack, is gathered onto one chip.

A program whose engine takes no mesh cannot run the cell: ``build``
raises at once.
"""

from __future__ import annotations

import asyncio
import collections
import inspect
import time

import numpy as np

from harness.program import execution_plan, program_geometry

POLL_S = 0.0005          # how often a waiting scanner looks at its fold
AHEAD = 1                # folds a scanner may have queued behind the running one


class SlabSampler:
    """Reads a z-sharded volume ``(L, L, L)``, or one slot of a z-sharded
    stack ``(n, L, L, L)``, at fixed voxels: each chip gathers the voxels
    inside its slab by their three indices (a flat index would reshape,
    and so copy, the slab) and the chips' readings are summed."""

    def __init__(self, vox: np.ndarray, mesh, axis: str):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.vox = jax.device_put(np.asarray(vox, np.int32),
                                  NamedSharding(mesh, P()))

        def read(local, first, vox):
            planes = local.shape[-3]
            z = vox[:, 0] - jax.lax.axis_index(axis) * planes
            inside = (z >= 0) & (z < planes)
            z = jnp.clip(z, 0, planes - 1)
            vals = (local[first, z, vox[:, 1], vox[:, 2]] if local.ndim == 4
                    else local[z, vox[:, 1], vox[:, 2]])
            return jax.lax.psum(jnp.where(inside, vals, 0.0), axis)

        def shard(spec):
            return jax.jit(jax.shard_map(
                read, mesh=mesh, in_specs=(spec, P(), P()), out_specs=P(),
                check_vma=False))

        self._take = shard(P(axis))
        self._take_slot = shard(P(None, axis))

    def __call__(self, vol):
        import jax.numpy as jnp

        return self._take(vol, jnp.int32(0), self.vox)

    def slot(self, volumes, slot: int):
        """``volumes[slot]`` at the voxels, with no copy of the slot."""
        import jax.numpy as jnp

        return self._take_slot(volumes, jnp.int32(slot), self.vox)


class Entry:
    def __init__(self, cell, acq, raw, mats, seed, sampler):
        self.acq, self.raw, self.mats = acq, raw, mats
        L = acq.L
        self.vox = np.stack(np.unravel_index(np.asarray(sampler.idx),
                                             (L, L, L)), axis=1)
        self.sampler = None
        self.spans = None
        t, dep = cell.traffic, cell.config["deployment"]
        self.scanners, self.chunk = int(t["scanners"]), int(t["chunk"])
        self.n_slots = int(dep["n_slots"])
        self.max_pending, self.policy = int(dep["max_pending"]), \
            dep["policy"]
        self.mesh_shape = dict(dep["mesh"])
        self.geom = program_geometry(acq)
        self.plan = execution_plan(cell.config["plan"])
        depth = int(dict(self.plan.pallas or {}).get("pbatch",
                                                     self.plan.pbatch))
        self.depth = depth
        if depth % self.chunk and self.chunk % depth:
            raise ValueError(f"chunks of {self.chunk} do not align with "
                             f"folds of {depth}")
        if acq.n_proj % depth:
            raise ValueError(f"{acq.n_proj} views do not split into folds "
                             f"of {depth}")
        self.door = None
        self.started = 0
        self.done: list = []       # (views, samples) of finished scans
        self.open: list = []       # (ticket, views submitted) at the close

    def build(self) -> None:
        from repro.api import CTFrontDoor
        from repro.launch.mesh import make_local_mesh
        from repro.streaming import ReconstructionEngine

        if "mesh" not in inspect.signature(ReconstructionEngine).parameters:
            raise RuntimeError("the program has no mesh engine: "
                               "ReconstructionEngine takes no mesh")
        mesh = make_local_mesh(data=int(self.mesh_shape["data"]),
                               model=int(self.mesh_shape["model"]))
        self.door = CTFrontDoor(self.geom, n_slots=self.n_slots,
                                max_pending=self.max_pending,
                                policy=self.policy, plan=self.plan,
                                mesh=mesh)
        self.sampler = SlabSampler(self.vox, mesh,
                                   self.engine._volumes.sharding.spec[1])

    @property
    def engine(self):
        return self.door._backend.engine

    def warm_up(self) -> None:
        """Every program the window runs, once: a scan of one fold's
        depth per scanner, submitted in the window's chunks, from the
        end of the sweep, which a window does not reach; and the
        samplers."""
        import jax

        views = np.arange(self.acq.n_proj - self.depth, self.acq.n_proj)
        vols = asyncio.run(self._gather(
            [self._warm_scan(i, views) for i in range(self.scanners)]))
        jax.block_until_ready([self.sampler(v) for v in vols]
                              + [self.sampler.slot(self.engine._volumes, 0)])

    async def _warm_scan(self, i, views):
        from repro.api import ProjectionChunk

        ticket = await self.door.open_scan(tenant=f"scanner-{i}",
                                           n_proj=len(views))
        for k in range(0, len(views), self.chunk):
            v = views[k:k + self.chunk]
            await self.door.submit(ticket, ProjectionChunk(
                self.raw[v], self.mats[v], v))
        return await self.door.result(ticket)

    def window(self, seconds: float, spans) -> dict:
        import jax

        self.spans = spans
        t0 = time.perf_counter()
        asyncio.run(self._gather([self._scanner(i, t0 + seconds)
                                  for i in range(self.scanners)]))
        with spans.span("wait_volume"):
            jax.block_until_ready([s for _, s in self.done]
                                  + [self.engine._volumes])
        views = sum(len(v) for v, _ in self.done) + \
            sum(n for _, n in self.open)
        return {"window_s": time.perf_counter() - t0, "views": views,
                "passes": len(self.done) + len(self.open)}

    def results(self):
        out = [(v, np.asarray(s, np.float64)) for v, s in self.done]
        eng = self.engine
        for ticket, n in self.open:
            if ticket.state != "active":
                raise RuntimeError(f"scan {ticket.tid} is {ticket.state} "
                                   f"at the close, not active")
            slot = eng.slot_scan.index(ticket.sid)
            out.append((np.arange(n), np.asarray(
                self.sampler.slot(eng._volumes, slot), np.float64)))
        return out

    @staticmethod
    async def _gather(coros):
        return await asyncio.gather(*coros)

    async def _scanner(self, i: int, deadline: float) -> None:
        from repro.api import ProjectionChunk

        n = self.acq.n_proj
        door = self.door
        folds = collections.deque()     # what each fold returned
        while True:
            self.started += 1
            with self.spans.span("open_scan"):
                ticket = await door.open_scan(tenant=f"scanner-{i}",
                                              n_proj=n)
            sent = 0
            while sent < n and (time.perf_counter() < deadline
                                or sent % self.depth):
                v = np.arange(sent, min(sent + self.chunk, n))
                part = ProjectionChunk(self.raw[v], self.mats[v], v)
                with self.spans.span("submit", len(v)):
                    await door.submit(ticket, part)
                sent += len(v)
                mark = self.engine.last_fold
                if mark is not None and (not folds or mark is not folds[-1]):
                    folds.append(mark)
                while len(folds) > AHEAD + 1:
                    head = folds.popleft()
                    with self.spans.span("wait_fold"):
                        while not head.is_ready():
                            await asyncio.sleep(POLL_S)
            if sent < n:
                if sent:
                    self.open.append((ticket, sent))
                return
            with self.spans.span("result"):
                vol = await door.result(ticket)
            with self.spans.span("wait_volume"):
                while not vol.is_ready():
                    await asyncio.sleep(POLL_S)
            self.done.append((np.arange(n), self.sampler(vol)))
            if time.perf_counter() >= deadline:
                return
