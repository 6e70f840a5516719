"""Ahead-of-time compiles for a TPU v5e chip, without the chip.

Interpret mode on the CPU checks what the kernels compute, not whether
Mosaic accepts them: misaligned slices, unsupported relayouts and VMEM
overruns only show up in the TPU compiler.  These tests compile the
programs the chip runs — every Pallas variant the tuner can pick, on all
three strip wires, with the tuner's tiles at RabbitCT widths (L=512,
1248x960 detector), and the jnp ``strip2`` reconstruction body — for a
described ``v5e:2x2`` topology.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and a worker that described
it at collection would leave the others nothing to collect.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.analysis.lint.budget import estimate_for_pallas_config
from repro.core.backproject import GeomStatic, _reconstruct_jit
from repro.core.geometry import Geometry
from repro.dispatch.plan import ExecutionPlan
from repro.kernels import backproject_ops
from repro.tune.space import pallas_candidates

GEOM = Geometry()                  # RabbitCT: L=512, 1248x960 detector
GS = GeomStatic.of(GEOM)
PBATCH = 4

VARIANTS = {
    "plain": {},
    "db2": dict(double_buffer=True, db_depth=2),
    "batch": dict(pbatch=PBATCH),
    "batch_db2": dict(pbatch=PBATCH, double_buffer=True, db_depth=2),
    "batch_db4": dict(pbatch=PBATCH, double_buffer=True, db_depth=4),
    "batch_shared": dict(pbatch=PBATCH, shared_window=True),
}
WIRES = ("float32", "bfloat16", "int8")


def _base_tiles():
    """The tile every kernel candidate at RabbitCT widths shares."""
    opts = dict(pallas_candidates(GS)[0].opts)
    return {k: opts[k] for k in ("ty", "chunk", "band", "width")}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # Without this the TPU compiler writes its logs under /tmp.
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "no TPU
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to a persistent cache
    # but cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_tuner_variants_are_all_compiled_here():
    """Every kernel variant the tuner proposes at RabbitCT widths is one
    of the compiled cases below, at the tiles compiled there."""
    base = _base_tiles()
    flags = ("pbatch", "double_buffer", "db_depth", "shared_window")
    cases = [{k: v for k, v in c.items() if k in flags}
             for c in VARIANTS.values()]
    for cand in pallas_candidates(GS):
        opts = dict(cand.opts)
        assert {k: opts[k] for k in base} == base
        variant = {k: v for k, v in opts.items() if k in flags}
        if variant.get("double_buffer") and "db_depth" not in variant:
            variant["db_depth"] = 2
        assert variant in cases, cand.label
        assert opts.get("strip_dtype", "float32") in WIRES


@pytest.mark.parametrize("wire", WIRES)
@pytest.mark.parametrize("variant", list(VARIANTS))
def test_kernel_compiles_for_v5e(one_chip, variant, wire):
    opts = dict(VARIANTS[variant])
    pbatch = opts.pop("pbatch", 1)
    ty, chunk, band, width = backproject_ops.clamp_tiles(
        GS, **_base_tiles())
    cfg = dict(ty=ty, chunk=chunk, band=band, width=width, pbatch=pbatch,
               strip_dtype=wire, **opts)
    if opts.get("shared_window"):
        # The superset window at the base tile's dims: the group planner
        # sizes the real one at run time.
        cfg.update(shared_band=band, shared_width=width)
    assert estimate_for_pallas_config(GS, cfg).fits

    vol = _sds((GS.L,) * 3, jnp.float32, one_chip)
    if pbatch == 1:
        fn = functools.partial(
            backproject_ops._run, gs=GS, ty=ty, chunk=chunk, band=band,
            width=width, double_buffer=opts.get("double_buffer", False),
            db_depth=opts.get("db_depth", 2), strip_dtype=wire,
            interpret=False)
        args = (vol, _sds((GS.n_v, GS.n_u), jnp.float32, one_chip),
                _sds((3, 4), jnp.float32, one_chip))
    else:
        fn = functools.partial(
            backproject_ops._run_batched, gs=GS, ty=ty, chunk=chunk,
            band=band, width=width, pbatch=pbatch,
            double_buffer=opts.get("double_buffer", False),
            db_depth=opts.get("db_depth", 2),
            shared_window=opts.get("shared_window", False),
            strip_dtype=wire, interpret=False)
        args = (vol, _sds((pbatch, GS.n_v, GS.n_u), jnp.float32, one_chip),
                _sds((pbatch, 3, 4), jnp.float32, one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_strip2_reconstruct_compiles_for_v5e(one_chip):
    """The jnp path ``reconstruct(strategy="strip2")`` runs at L=512 with
    the full detector, 32 projections at the default batch depth."""
    n_proj = 32
    plan = ExecutionPlan.explicit("strip2", {}, PBATCH)
    compiled = _reconstruct_jit.lower(
        _sds((n_proj, GS.n_v, GS.n_u), jnp.float32, one_chip),
        _sds((n_proj, 3, 4), jnp.float32, one_chip),
        _sds((GS.L,) * 3, jnp.float32, one_chip), GS, plan).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2 ** 30
