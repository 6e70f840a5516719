"""The z-sharded engine: the slab kernel, the mesh fold, its counter.

The back projection kernel folds a z-slab of the volume at the slab's
first global plane ``z0``, a runtime scalar; one slot of a stack of
slabs folds in place.  ``ReconstructionEngine(mesh=...)`` and
``CTFrontDoor(mesh=...)`` split their volumes over the mesh's ``data``
axis and each device folds every view into its own slab.  The mesh
tests run in a child process with four CPU devices, so that this
process keeps JAX at one.
"""

import hashlib
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _col_blocks import CASES
from repro import obs
from repro.core import Geometry, filter_projections
from repro.core.phantom import make_dataset
from repro.kernels import backproject_ops
from repro.kernels.backproject_ops import (pallas_backproject_batch,
                                           tile_col_blocks, validate_batch)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GEOM = Geometry().scaled(16, n_proj=6)
_DS = make_dataset(GEOM)
FILT = np.asarray(filter_projections(_DS[0], GEOM))
TILES = dict(ty=8, chunk=16, band=16, width=128, pbatch=2)
VARIANTS = {"plain": {}, "db2": dict(double_buffer=True, db_depth=2),
            "shared": dict(shared_window=True)}
# sha1 of the cube kernel's output on VOL0 over all six views, for each
# variant, as the kernel computed it before slabs existed.
CUBE_SHA1 = "a477f49ee7653c76256e64bb5701b1a351bb2310"
VOL0 = jnp.asarray(np.random.default_rng(0).standard_normal(
    (16, 16, 16)).astype(np.float32))


def _fold(volume, n=6, **kw):
    return np.asarray(pallas_backproject_batch(
        volume, FILT[:n], _DS[1][:n], GEOM, **TILES, **kw))


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_cube_kernel_is_bitwise_unchanged(variant):
    out = _fold(VOL0, **VARIANTS[variant])
    assert hashlib.sha1(out.tobytes()).hexdigest() == CUBE_SHA1


@pytest.mark.parametrize("variant", ["plain", "db2"])
@pytest.mark.parametrize("z0", [0, 4, 8, 12])
def test_slab_kernel_folds_the_cube_planes(variant, z0):
    cube = _fold(VOL0, 4, **VARIANTS[variant])
    slab = _fold(VOL0[z0:z0 + 4], 4, z0=z0, **VARIANTS[variant])
    np.testing.assert_array_equal(slab, cube[z0:z0 + 4])


def test_stack_slot_folds_in_place_and_leaves_the_other_slot():
    stack = jnp.stack([VOL0[8:12], -VOL0[8:12]])
    out = _fold(stack, 4, z0=8, slot=1)
    np.testing.assert_array_equal(out[0], np.asarray(stack[0]))
    np.testing.assert_array_equal(out[1], _fold(-VOL0[8:12], 4, z0=8))


@pytest.mark.parametrize("slabs", [2, 4])
def test_max_slab_counter_is_the_host_rule(tmp_path, monkeypatch, slabs):
    """``mesh.col_blocks_max_slab`` is the largest slab's sum of the host
    rule's contracted blocks over the stack, and ``kernel.col_blocks``
    still counts the whole cube."""
    c = CASES["seam"]
    mats = c.mats()
    per_slab = np.zeros(slabs, np.int64)
    for A in mats:
        active, _, lo, hi = tile_col_blocks(c.gs, A, **c.tiles)
        per_slab += np.where(active, hi - lo + 1, 0).reshape(
            slabs, -1).sum(axis=1)
    monkeypatch.setattr(backproject_ops, "_VALIDATED_STACKS", set())
    with jax.profiler.trace(str(tmp_path)):
        validate_batch(c.geom, mats, slabs=slabs, **c.tiles)
    s = obs.summary()
    got = s["mesh.col_blocks_max_slab"]["units"]
    assert got == per_slab.max()
    assert slabs * got >= s["kernel.col_blocks"]["units"] >= got
    assert s["kernel.col_blocks"]["units"] == per_slab.sum()


def test_one_slab_counts_no_max(tmp_path, monkeypatch):
    c = CASES["seam"]
    monkeypatch.setattr(backproject_ops, "_VALIDATED_STACKS", set())
    with jax.profiler.trace(str(tmp_path)):
        validate_batch(c.geom, c.mats(), **c.tiles)
    s = obs.summary()
    assert "mesh.col_blocks_max_slab" not in s
    assert s["kernel.col_blocks"]["units"] > 0


# ----------------------------------------------------------------------
# Four devices, in a child process
# ----------------------------------------------------------------------

CHILD = """
import asyncio
from jax.sharding import PartitionSpec as P
from repro import obs
from repro.api import CTFrontDoor, ProjectionChunk
from repro.core import Geometry, filter_projections, reconstruct
from repro.core.phantom import make_dataset
from repro.dispatch import ExecutionPlan
from repro.launch.mesh import make_local_mesh
from repro.streaming import ReconstructionEngine

GEOM = Geometry().scaled(16, n_proj=6)
projs, mats, _ = make_dataset(GEOM)
filt = np.asarray(filter_projections(projs, GEOM))
oracle = np.asarray(reconstruct(filt, mats, GEOM))
oracle2 = np.asarray(reconstruct(filt[:2], mats[:2], GEOM))
plan = ExecutionPlan(strategy="gather", pbatch=2, use_pallas=True,
                     pallas=(("band", 16), ("chunk", 16), ("pbatch", 2),
                             ("ty", 8), ("width", 128)))
order = np.random.default_rng(3).permutation(GEOM.n_proj)
out = {}

def stream(eng):
    # A scan in shuffled chunks, retired; a second scan admitted into the
    # zeroed slot; a third aborted mid-scan and its slot reused.
    sid = eng.begin_scan(n_proj=GEOM.n_proj)
    for c in (order[:3], order[3:5], order[5:]):
        eng.submit(sid, ProjectionChunk(projs[c], mats[c], c))
    eng.drain()
    first = eng.result(sid, pop=True)
    gone = eng.begin_scan(n_proj=GEOM.n_proj)
    eng.submit(gone, ProjectionChunk(projs[:4], mats[:4], np.arange(4)))
    eng.abort_scan(gone)
    sid = eng.begin_scan(n_proj=2)
    eng.submit(sid, ProjectionChunk(projs[:2], mats[:2], np.arange(2)))
    eng.drain()
    return first, eng.result(sid, pop=True)

single = stream(ReconstructionEngine(GEOM, n_slots=1, plan=plan))
eng = ReconstructionEngine(GEOM, n_slots=1, plan=plan,
                           mesh=make_local_mesh(data=4, model=1))
out["stack_spec"] = str(eng._volumes.sharding.spec)
out["stack_shards"] = len(eng._volumes.sharding.device_set)
mesh_out = stream(eng)
out["volume_spec"] = str(mesh_out[0].sharding.spec)
out["mesh_vs_single"] = max(float(np.abs(np.asarray(a) - np.asarray(b)).max())
                            for a, b in zip(mesh_out, single))
out["vs_oracle"] = max(float(np.abs(np.asarray(mesh_out[0]) - oracle).max()),
                       float(np.abs(np.asarray(mesh_out[1]) - oracle2).max()))
out["scale"] = float(np.abs(oracle).max())
out["stats"] = [eng.stats["retired"], eng.stats["aborted"],
                eng.stats["pallas_folds"]]

async def door_scan():
    fd = CTFrontDoor(GEOM, n_slots=2, plan=plan,
                     mesh=make_local_mesh(data=4, model=1))
    ticket = await fd.open_scan(n_proj=GEOM.n_proj)
    for c0 in range(0, GEOM.n_proj, 2):
        idx = order[c0:c0 + 2]
        await fd.submit(ticket, ProjectionChunk(projs[idx], mats[idx], idx))
    return np.asarray(await fd.result(ticket))

import tempfile
with jax.profiler.trace(tempfile.mkdtemp()):
    vol = asyncio.run(door_scan())
out["door_vs_oracle"] = float(np.abs(vol - oracle).max())
s = obs.summary()
out["put_units"] = s.get("engine.put", {}).get("units")
out["fold_shards"] = sorted({r[5].get("shards") for r in obs.recorded()
                             if r[0] == "engine.fold"})

try:
    ReconstructionEngine(GEOM, n_slots=1, plan=plan,
                         mesh=make_local_mesh(data=2, model=2))
    out["proj_split"] = "accepted"
except ValueError as e:
    out["proj_split"] = str(e)
try:
    ReconstructionEngine(GEOM, n_slots=1, pbatch=2, strategy="gather",
                         mesh=make_local_mesh(data=4, model=1))
    out["jnp_plan"] = "accepted"
except ValueError as e:
    out["jnp_plan"] = str(e)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four():
    script = textwrap.dedent(f"""
        import os, sys, json
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {os.path.join(ROOT, 'src')!r})
        import jax, jax.numpy as jnp, numpy as np
    """) + CHILD
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_mesh_stack_is_z_sharded(four):
    assert four["stack_spec"] == str(jax.sharding.PartitionSpec(None,
                                                                "data"))
    assert four["stack_shards"] == 4
    assert four["volume_spec"] == str(jax.sharding.PartitionSpec("data"))


def test_mesh_engine_matches_single_engine_and_oracle(four):
    assert four["mesh_vs_single"] == 0.0
    assert four["vs_oracle"] <= 1e-5 * max(1.0, four["scale"])
    assert four["stats"] == [2, 1, 10]


def test_mesh_front_door_matches_oracle_and_records_spans(four):
    assert four["door_vs_oracle"] <= 1e-5 * max(1.0, four["scale"])
    assert four["put_units"] == GEOM.n_proj
    assert four["fold_shards"] == [4]


def test_mesh_with_a_projection_split_raises(four):
    assert "not supported" in four["proj_split"]
    assert "'model'" in four["proj_split"]


def test_mesh_engine_needs_the_kernel_plan(four):
    assert "Pallas batch kernel" in four["jnp_plan"]
