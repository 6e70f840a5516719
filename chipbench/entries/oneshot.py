"""A stored scan reconstructed one-shot, through ``filter_projections``
and ``reconstruct(plan=..., volume=...)``.

Traffic keys: ``views_per_call``.  The scan is reconstructed in calls of
that many views spread evenly over the sweep, accumulating one volume;
the seed orders the calls (a permutation of all offsets, cycled) and
never sizes them.  A call is issued once the call before the previous
one has finished, so the host runs at most one call ahead of the device.
The window's answer is the accumulated volume.
"""

from __future__ import annotations

import time

import numpy as np

from harness.acq import scan_angles
from harness.program import execution_plan, program_geometry, schedule


class Entry:
    def __init__(self, cell, acq, raw, mats, seed, sampler):
        self.acq, self.raw, self.mats = acq, raw, mats
        self.sampler = sampler
        self.spans = None
        self.per = int(cell.traffic["views_per_call"])
        self.geom = program_geometry(acq)
        self.plan = execution_plan(cell.config["plan"])
        self.order = schedule(seed, self.per, acq.n_proj)
        self._next = 0
        self.started = 0
        self.views: list = []
        self.volume = None

    def build(self) -> None:
        import jax.numpy as jnp

        self.volume = jnp.zeros((self.acq.L,) * 3, jnp.float32)

    def _views(self, off) -> np.ndarray:
        return scan_angles(int(off), self.per, self.acq.n_proj)

    def warm_up(self) -> None:
        """One call, with the offset a window reaches last, on a volume
        of zeros that is then dropped; and the sampler."""
        import jax
        import jax.numpy as jnp

        vol = self._call(jnp.zeros_like(self.volume),
                         self._views(self.order[-1]))
        jax.block_until_ready(self.sampler(vol))

    def _call(self, volume, views):
        from repro.api import filter_projections, reconstruct

        filt = filter_projections(self.raw[views], self.geom,
                                  angle_indices=views)
        return reconstruct(filt, self.mats[views], self.geom,
                           plan=self.plan, volume=volume)

    def window(self, seconds: float, spans) -> dict:
        self.spans = spans
        t0 = time.perf_counter()
        deadline = t0 + seconds
        prev = None
        while time.perf_counter() < deadline:
            v = self._views(self.order[self._next % len(self.order)])
            self._next += 1
            self.started += 1
            with spans.span("oneshot_call", len(v)):
                self.volume = self._call(self.volume, v)
            self.views.append(v)
            if prev is not None:
                with spans.span("wait_call"):
                    prev.block_until_ready()
            prev = self.volume
        with spans.span("wait_call"):
            self.volume.block_until_ready()
        views = sum(len(v) for v in self.views)
        return {"window_s": time.perf_counter() - t0, "views": views,
                "passes": len(self.views)}

    def results(self):
        if not self.views:
            return []
        return [(np.concatenate(self.views),
                 np.asarray(self.sampler(self.volume), np.float64))]
