"""Pallas TPU kernels (validated with interpret=True on CPU).

The back projection kernel ships the required triple: the
``pl.pallas_call`` kernels with explicit BlockSpec/VMEM tiling
(:mod:`.backproject`), the jit'd ops wrappers (:mod:`.backproject_ops`),
and a pure-jnp oracle (:mod:`.backproject_ref`).
"""

from .backproject_ops import (  # noqa: F401
    pallas_backproject_batch,
    pallas_backproject_one,
)
