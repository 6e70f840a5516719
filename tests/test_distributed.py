"""Multi-device behaviour (subprocess with fake CPU devices, so the main
test process keeps jax at 1 device)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import ShardingRules, logical_to_spec, valid_spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_child(ndev: int, body: str, timeout=560):
    script = textwrap.dedent(f"""
        import os, sys, json
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={ndev}")
        sys.path.insert(0, {str(os.path.join(ROOT, 'src'))!r})
        import jax, jax.numpy as jnp, numpy as np
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout,
                         cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_sharded_reconstruct_matches_single_device():
    rec = _run_child(4, """
        from repro.core import Geometry, filter_projections, reconstruct
        from repro.core.phantom import make_dataset
        from repro.core.pipeline import sharded_reconstruct
        from repro.launch.mesh import make_local_mesh
        geom = Geometry().scaled(16, n_proj=4)
        projs, mats, ref = make_dataset(geom)
        filt = np.asarray(filter_projections(projs, geom))
        mesh = make_local_mesh(data=2, model=2)
        out = sharded_reconstruct(filt, mats, geom, mesh,
                                  strategy="gather")
        single = reconstruct(filt, mats, geom, strategy="gather")
        print(json.dumps({
            "diff": float(jnp.max(jnp.abs(out - single))),
            "sum": float(jnp.sum(out))}))
    """)
    assert rec["diff"] < 1e-5
    assert rec["sum"] != 0.0


def test_sharded_prefiltered_false_weights_nonprefix_ranks():
    """prefiltered=False on a real 2x2 mesh: rank 1 of the proj axis
    holds a *non-prefix* angle subset, so a correct result proves the
    in-shard filter used angle-indexed Parker rows (the old prefix
    contract would have weighted ranks > 0 with rank 0's angles)."""
    rec = _run_child(4, """
        from repro.core import Geometry, filter_projections, reconstruct
        from repro.core.phantom import make_dataset
        from repro.core.pipeline import sharded_reconstruct
        from repro.launch.mesh import make_local_mesh
        geom = Geometry().scaled(16, n_proj=4)
        projs, mats, ref = make_dataset(geom)
        mesh = make_local_mesh(data=2, model=2)
        out = sharded_reconstruct(projs, mats, geom, mesh,
                                  prefiltered=False)
        filt = np.asarray(filter_projections(projs, geom))
        single = reconstruct(filt, mats, geom)
        print(json.dumps({
            "max_abs_diff": float(jnp.max(jnp.abs(out - single))),
            "nonzero": bool(jnp.any(out != 0.0)),
        }))
    """)
    assert rec["nonzero"]
    assert rec["max_abs_diff"] < 1e-5


def test_compress_psum_error_feedback():
    """int8-compressed all-reduce converges to the true mean via EF."""
    rec = _run_child(4, """
        from functools import partial
        from repro.dist.collectives import compress_psum
        mesh = jax.make_mesh((4,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        from jax.sharding import PartitionSpec as P

        @partial(jax.shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                 out_specs=(P("data"), P("data")))
        def step(g, e):
            out, new_e = compress_psum({"g": g}, "data", {"g": e})
            return out["g"], new_e["g"]

        key = jax.random.PRNGKey(0)
        g = jax.random.normal(key, (4, 64)) * 3.0
        true_mean = jnp.mean(g, axis=0)
        e = jnp.zeros((4, 64))
        # accumulate EF over repeated reductions of the same gradient:
        # the running average of compressed means converges to the truth.
        acc = jnp.zeros((64,))
        n = 8
        for _ in range(n):
            out, e = step(g, e)
            acc = acc + out[0]
        err_one = float(jnp.max(jnp.abs(out[0] - true_mean)))
        err_avg = float(jnp.max(jnp.abs(acc / n - true_mean)))
        print(json.dumps({"err_one": err_one, "err_avg": err_avg,
                          "scale": float(jnp.max(jnp.abs(true_mean)))}))
    """)
    # single-shot int8 error bounded by quantisation step; EF average
    # must beat it by a wide margin.
    assert rec["err_one"] < 0.1 * rec["scale"] + 0.05
    assert rec["err_avg"] < rec["err_one"] / 2


def test_bucketed_psum_exact():
    rec = _run_child(2, """
        from functools import partial
        from repro.dist.collectives import bucketed_psum
        from jax.sharding import PartitionSpec as P
        mesh = jax.make_mesh((2,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        tree = {"a": jnp.arange(8.0).reshape(2, 4),
                "b": jnp.ones((2, 3)), "c": jnp.full((2, 1), 2.0)}

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(jax.tree.map(lambda _: P("data"), tree),),
                 out_specs=jax.tree.map(lambda _: P("data"), tree))
        def red(t):
            return bucketed_psum(t, "data", min_bucket_bytes=16)

        out = red(tree)
        ref = jax.tree.map(lambda x: jnp.broadcast_to(
            x.sum(0, keepdims=True), x.shape), tree)
        diff = max(float(jnp.max(jnp.abs(a - b)))
                   for a, b in zip(jax.tree.leaves(out),
                                   jax.tree.leaves(ref)))
        print(json.dumps({"diff": diff}))
    """)
    assert rec["diff"] == 0.0


class FakeMesh:
    axis_names = ("pod", "data", "model")
    shape = {"pod": 2, "data": 16, "model": 16}


def test_logical_to_spec_prunes_missing_axes():
    rules = ShardingRules()
    spec = logical_to_spec(("vol", None, "proj"), rules, FakeMesh())
    assert spec == P("data", None, ("pod", "model"))

    class PodlessMesh:
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    spec = logical_to_spec(("vol", None, "proj"), rules, PodlessMesh())
    assert spec == P("data", None, "model")


def test_valid_spec_drops_indivisible():
    spec = logical_to_spec(("proj", "vol"), ShardingRules(), FakeMesh())
    assert spec == P(("pod", "model"), "data")
    assert valid_spec((64, 32), spec, FakeMesh()) == spec
    # 496 views do not split 32 ways -> the projection dim replicates.
    assert valid_spec((496, 32), spec, FakeMesh()) == P(None, "data")
    # 8 planes over 16 shards replicate, and the tail is trimmed.
    assert valid_spec((64, 8), spec, FakeMesh()) == P(("pod", "model"))
