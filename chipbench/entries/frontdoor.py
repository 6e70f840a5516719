"""Scanners streaming whole scans into ``CTFrontDoor``.

Traffic keys: ``scanners`` (closed-loop clients, one C-arm each) and
``chunk`` (raw views per ``ProjectionChunk``).  Each scanner opens a
scan of all the acquisition's views with ``CTFrontDoor.open_scan``,
submits them in acquisition order, awaits ``result`` once the last view
is in, and opens its next scan.  A scanner runs at most one fold ahead
of the device: after a submit that starts a fold it waits until the
fold before it has finished, as a scanner with a bounded buffer would.
Once the window's seconds are up a scanner submits only what completes
the fold batch it has started, and stops.  Every seed does the same work in the same order; the seed
changes the phantom and the checked voxels.

An answer is a scan's volume: from ``result`` for a scan that finished
inside the window, and, for the scan still open at the close, the
volume of its slot in the engine as the timed folds left it, holding
every view the scanner submitted.  The slot is read from the engine's
own state (``engine.slot_scan``, ``engine._volumes``), since the front
door serves no volume before a scan's last view.
"""

from __future__ import annotations

import asyncio
import collections
import time

import numpy as np

from harness.program import execution_plan, program_geometry

POLL_S = 0.0005          # how often a waiting scanner looks at its volume
AHEAD = 1                # folds a scanner may have queued behind the running one


class Entry:
    def __init__(self, cell, acq, raw, mats, seed, sampler):
        self.acq, self.raw, self.mats = acq, raw, mats
        self.sampler = sampler
        self.spans = None
        t, dep = cell.traffic, cell.config["deployment"]
        self.scanners, self.chunk = int(t["scanners"]), int(t["chunk"])
        self.n_slots = int(dep["n_slots"])
        self.max_pending, self.policy = int(dep["max_pending"]), \
            dep["policy"]
        self.geom = program_geometry(acq)
        self.plan = execution_plan(cell.config["plan"])
        depth = self.plan.pbatch
        if self.plan.use_pallas and self.plan.pallas:
            depth = int(dict(self.plan.pallas).get("pbatch", depth))
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

        self.door = CTFrontDoor(self.geom, n_slots=self.n_slots,
                                max_pending=self.max_pending,
                                policy=self.policy, plan=self.plan)

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
        folds = collections.deque()     # the engine's volumes, one per fold
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
                vols = self.engine._volumes
                if not folds or vols is not folds[-1]:
                    folds.append(vols)
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
