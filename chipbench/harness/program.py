"""What the entries share when they call the program: its geometry and
pinned plan built from a configuration, the seeded order of one-shot
calls, and the sampler that reads a volume at the checked voxels.
"""

from __future__ import annotations

import numpy as np

from .acq import Acq


def program_geometry(acq: Acq):
    from repro.api import Geometry

    return Geometry(n_u=acq.n_u, n_v=acq.n_v, du=acq.du, dv=acq.dv,
                    sid=acq.sid, sdd=acq.sdd, L=acq.L,
                    voxel_mm=acq.voxel_mm, n_proj=acq.n_proj,
                    sweep=acq.sweep)


def execution_plan(plan: dict):
    from repro.api import ExecutionPlan

    pallas = plan.get("pallas")
    return ExecutionPlan(
        strategy=plan["strategy"], opts=tuple(sorted(plan.get("opts",
                                                              {}).items())),
        pbatch=int(plan["pbatch"]),
        pallas=tuple(sorted(pallas.items())) if pallas else None,
        use_pallas=bool(plan["use_pallas"]))


def schedule(seed: int, per: int, n_proj: int) -> np.ndarray:
    """The order of call offsets: a permutation of all of them."""
    if n_proj % per:
        raise ValueError(f"{n_proj} views do not split into calls of {per}")
    return np.random.default_rng([seed, 1]).permutation(n_proj // per)


class Sampler:
    """Reads a volume, or one slot of a stack of volumes, at fixed voxels,
    on the device, in one program."""

    def __init__(self, vox: np.ndarray, L: int):
        import jax
        import jax.numpy as jnp

        flat = (vox[:, 0] * L + vox[:, 1]) * L + vox[:, 2]
        self.idx = jnp.asarray(flat, jnp.int32)
        self.size = L ** 3
        self._take = jax.jit(lambda vol, idx: vol.reshape(-1)[idx])
        self._take_slot = jax.jit(
            lambda vols, slot, idx, size: vols.reshape(-1)[slot * size
                                                           + idx],
            static_argnums=3)

    def __call__(self, vol):
        return self._take(vol, self.idx)

    def slot(self, volumes, slot: int):
        """``volumes[slot]`` at the voxels, with no copy of the slot."""
        import jax.numpy as jnp

        return self._take_slot(volumes, jnp.int32(slot), self.idx,
                               self.size)
