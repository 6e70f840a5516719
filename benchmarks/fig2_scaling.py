"""Paper Fig. 2 analogue: full-system scaling of the reconstruction.

The paper scales across cores (93% parallel efficiency, "highly
core-bound").  Here: ``shard_map`` reconstruction over an N-device mesh
(subprocess with fake CPU devices so the parent process keeps 1 device),
volume z-planes over ``data`` x projections over ``model`` — plus the
collective-bytes model for the production mesh from the same code path.
This is a CPU figure on fake devices: each child pins the CPU backend
(a chip belongs to the one process that opened it, and the parent holds
it), and a child that fails fails the module.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from .common import bench_size, emit

_CHILD = textwrap.dedent("""
    import os, sys, json, time
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=%(ndev)d")
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from repro.api import Geometry, filter_projections, sharded_reconstruct
    from repro.core.phantom import make_dataset
    from repro.launch.mesh import make_local_mesh

    L, n_proj = %(L)d, %(n_proj)d
    geom = Geometry().scaled(L, n_proj=n_proj)
    projs, mats, ref = make_dataset(geom)
    filt = np.asarray(filter_projections(projs, geom))
    mesh = make_local_mesh(data=%(data)d, model=%(model)d)
    def run():
        return sharded_reconstruct(filt, mats, geom, mesh,
                                   strategy="gather")
    out = run(); jax.block_until_ready(out)       # compile+warm
    t0 = time.perf_counter()
    out = run(); jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(json.dumps({"dt": dt,
                      "sum": float(jnp.sum(out))}))
""")


def run(L: int | None = None, n_proj: int | None = None):
    L = bench_size(48, 16) if L is None else L
    n_proj = bench_size(8, 4) if n_proj is None else n_proj
    results = {}
    for ndev, data, model in [(1, 1, 1), (2, 2, 1), (4, 2, 2),
                              (8, 4, 2)]:
        script = _CHILD % {"ndev": ndev, "L": L, "n_proj": n_proj,
                           "data": data, "model": model}
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        out = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=600,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        if out.returncode != 0:
            raise RuntimeError(
                f"fig2 child ndev={ndev} exited {out.returncode}: "
                f"{out.stderr.strip()[-2000:]}")
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        results[ndev] = rec
        base = results.get(1, rec)["dt"]
        # Single host CPU: ideal scaling is flat wall time (devices share
        # one core); the check is correctness + collective plumbing, the
        # paper-style efficiency number is meaningful on real chips.
        emit(f"fig2/ndev={ndev}", rec["dt"] * 1e6,
             f"checksum={rec['sum']:.2f} rel_time={rec['dt'] / base:.2f} "
             f"mesh={data}x{model}")


if __name__ == "__main__":
    run()
