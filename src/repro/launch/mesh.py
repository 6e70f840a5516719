"""Device mesh construction.

A function, not a module-level constant: importing this module never
touches jax device state (device count is locked at first use).  All
sharding is expressed against the mesh's axis *names*
(:mod:`repro.dist.sharding`), so the same code runs on a four-chip host
or a multi-device CPU test process.
"""

from __future__ import annotations

import jax

__all__ = ["make_local_mesh"]


def make_local_mesh(data: int | None = None, model: int = 1):
    """Mesh ``("data", "model")`` over whatever devices exist."""
    n = jax.device_count()
    if data is None:
        data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
