"""Dispatch layer: ExecutionPlan + Dispatcher (DESIGN.md §11).

Covers the three resolution outcomes — cache hit, in-situ first-call
selection, structured fallback — plus the plan's hash-equality contract
and the streaming engine's tuned-kernel fold.  The suite-wide conftest
forces ``REPRO_DISPATCH_INSITU=0``; tests that exercise selection opt
back in with ``Dispatcher(insitu=True)``.
"""

import json
import logging
import os
from pathlib import Path

import numpy as np
import pytest

from repro.core import Geometry, filter_projections, reconstruct
from repro.core.backproject import DEFAULT_PBATCH, GeomStatic
from repro.core.phantom import make_dataset
from repro.dispatch import (Dispatcher, ExecutionPlan, get_dispatcher,
                            insitu_candidates, set_dispatcher)
from repro.tune import (TUNE_SCHEMA_VERSION, TunedConfig,
                        clear_memory_cache, device_identity, store_tuned)
from repro.tune.sweep import SweepResult, Timing

GEOM = Geometry().scaled(16, n_proj=4)
GS = GeomStatic.of(GEOM)


@pytest.fixture(autouse=True)
def _isolated_tune_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path / "tune"))
    clear_memory_cache()
    yield tmp_path / "tune"
    clear_memory_cache()


@pytest.fixture(scope="module")
def ct_case():
    projs, mats, _ = make_dataset(GEOM)
    filt = np.asarray(filter_projections(projs, GEOM))
    return filt, mats


def _fake_sweep_result():
    gather = Timing(label="gather[pbatch=2]", strategy="gather",
                    opts=(("pbatch", 2),), us_per_call=11.0, gups=1.0)
    strip2 = Timing(label="strip2[pbatch=4]", strategy="strip2",
                    opts=(("pbatch", 4),), us_per_call=22.0, gups=1.0)
    return SweepResult(geom_key=tuple(GS), backend="cpu",
                       device_kind="cpu", timings=[gather, strip2],
                       skipped=[])


# ----------------------------------------------------------------------
# ExecutionPlan
# ----------------------------------------------------------------------

def test_plan_hash_equality_across_construction_paths():
    """Identical configurations hash equal no matter how the plan was
    built — the property that keeps one compiled executable per
    configuration."""
    backend, device_kind = device_identity()
    cfg = TunedConfig(strategy="strip2", opts={"pbatch": 2},
                      backend=backend, device_kind=device_kind,
                      us_per_call=1.0)
    a = ExecutionPlan.explicit("strip2", pbatch=2)
    b = ExecutionPlan.from_tuned(cfg)
    assert a == b and hash(a) == hash(b)
    assert {a: "compiled"}[b] == "compiled"
    assert a.label == "strip2@p2"


def test_plan_explicit_validates_strictly():
    with pytest.raises(ValueError, match="auto"):
        ExecutionPlan.explicit("fastest")
    # A known key the named strategy does not accept is a caller bug.
    with pytest.raises(ValueError, match="gband"):
        ExecutionPlan.explicit("onehot", {"gband": 8})
    # A key no strategy accepts is a typo.
    with pytest.raises(ValueError, match="unknown option"):
        ExecutionPlan.explicit("strip2", {"gbnad": 8})


def test_plan_from_tuned_merges_and_flags_kernel():
    backend, device_kind = device_identity()
    cfg = TunedConfig(strategy="strip2", opts={"group": 8, "pbatch": 2},
                      backend=backend, device_kind=device_kind,
                      us_per_call=10.0,
                      pallas={"ty": 8, "chunk": 16, "band": 16,
                              "width": 128, "pbatch": 2},
                      pallas_us=5.0)
    plan = ExecutionPlan.from_tuned(cfg, {"gband": 16})
    assert plan.strategy == "strip2" and plan.pbatch == 2
    assert plan.jnp_opts() == {"group": 8, "gband": 16}
    assert plan.use_pallas and plan.pallas_opts()["ty"] == 8
    # Kernel slower than the jnp nest -> carried but not taken.
    slower = ExecutionPlan.from_tuned(
        TunedConfig(strategy="strip2", opts={}, backend=backend,
                    device_kind=device_kind, us_per_call=10.0,
                    pallas={"ty": 8, "chunk": 16, "band": 16,
                            "width": 128}, pallas_us=50.0))
    assert slower.pallas is not None and not slower.use_pallas


# ----------------------------------------------------------------------
# Fallback (selection unavailable)
# ----------------------------------------------------------------------

def test_fallback_warns_once_with_key_and_matches_strip2(ct_case, caplog):
    """Untuned + in-situ disabled: one structured warning naming the
    cache key, then the pre-dispatch strip2 default bit-for-bit."""
    filt, mats = ct_case
    d = Dispatcher(insitu=False)
    from repro.tune import cache_key
    key = cache_key(GS, d.backend, d.device_kind)
    with caplog.at_level(logging.WARNING, logger="repro.dispatch"):
        plan = d.resolve(GEOM)
        d.resolve(GEOM)                      # warn-once per (surface, key)
    warns = [r for r in caplog.records if "falling back" in r.message]
    assert len(warns) == 1
    assert key in warns[0].message
    assert "REPRO_DISPATCH_INSITU" in warns[0].message
    assert plan == ExecutionPlan.explicit("strip2")
    set_dispatcher(d)
    a = np.asarray(reconstruct(filt, mats, GEOM, strategy="auto"))
    b = np.asarray(reconstruct(filt, mats, GEOM, strategy="strip2"))
    np.testing.assert_array_equal(a, b)


def test_resolve_kernel_fallback_and_hit(caplog):
    d = Dispatcher(insitu=False)
    with caplog.at_level(logging.WARNING, logger="repro.dispatch"):
        assert d.resolve_kernel(GEOM) is None
    assert any("falling back" in r.message for r in caplog.records)
    backend, device_kind = device_identity()
    store_tuned(GS, TunedConfig(
        strategy="strip2", opts={}, backend=backend,
        device_kind=device_kind, us_per_call=1.0,
        pallas={"ty": 8, "chunk": 16, "band": 16, "width": 128,
                "double_buffer": True, "db_depth": 3}))
    tiles = Dispatcher(insitu=False).resolve_kernel(GEOM)
    assert tiles["double_buffer"] and tiles["db_depth"] == 3


# ----------------------------------------------------------------------
# In-situ first-call selection
# ----------------------------------------------------------------------

def test_insitu_shortlist_is_deterministic():
    a = insitu_candidates(GS, topk=6)
    b = insitu_candidates(GS, topk=6)
    assert [c.label for c in a] == [c.label for c in b]
    strategies = [c.strategy for c in a]
    assert strategies[0] == "strip2"
    assert len(a) <= 6 and len(set(map(id, a))) == len(a)
    with_pallas = insitu_candidates(GS, topk=6, include_pallas=True)
    assert any(c.strategy == "pallas" for c in with_pallas)
    assert all(c.pbatch > 1 for c in with_pallas
               if c.strategy == "pallas")


def test_insitu_selects_persists_and_never_retimes(tmp_path, caplog):
    """Miss -> one sweep over the shortlist, winner persisted as a
    schema-current cache file; every later resolve (same or fresh
    dispatcher) is a lookup with zero timing calls."""
    calls = []

    def fake_sweep(geom, *, space, warmup, iters, min_total_s):
        calls.append((len(space), warmup, iters, min_total_s))
        return _fake_sweep_result()

    d = Dispatcher(insitu=True, sweep_fn=fake_sweep)
    with caplog.at_level(logging.INFO, logger="repro.dispatch"):
        plan = d.resolve(GEOM)
    assert len(calls) == 1
    assert calls[0][1:] == (1, 1, 0.0)       # warmup=1, iters=1, pinned
    assert plan == ExecutionPlan.explicit("gather", pbatch=2)
    sel = [r for r in caplog.records if "in-situ selection" in r.message]
    assert len(sel) == 1 and "winner=gather" in sel[0].message

    files = list(Path(os.environ["REPRO_TUNE_DIR"]).glob("*.json"))
    assert len(files) == 1
    data = json.loads(files[0].read_text())
    assert data["version"] == TUNE_SCHEMA_VERSION
    assert data["strategy"] == "gather"
    assert data["opts"]["pbatch"] == 2
    assert len(data["timings"]) == 2         # evidence rides along

    # Same dispatcher: memo hit.
    assert d.resolve(GEOM) == plan and len(calls) == 1

    # Fresh dispatcher (fresh process stand-in): disk hit, no timing.
    def boom(*a, **k):
        raise AssertionError("re-timed a cached key")

    clear_memory_cache()
    d2 = Dispatcher(insitu=True, sweep_fn=boom)
    assert d2.resolve(GEOM) == plan

    # A bare GeomStatic cannot be timed -> still served from the cache.
    assert d2.resolve(GS) == plan


def test_insitu_plan_matches_offline_tuned_path_bitwise(ct_case):
    """Acceptance: the in-situ winner reconstructs bit-for-bit with the
    explicitly-named winner (same plan object, same jit cache entry)."""
    filt, mats = ct_case
    d = Dispatcher(insitu=True,
                   sweep_fn=lambda g, **k: _fake_sweep_result())
    set_dispatcher(d)
    a = np.asarray(reconstruct(filt, mats, GEOM, strategy="auto"))
    b = np.asarray(reconstruct(filt, mats, GEOM, strategy="gather",
                               pbatch=2))
    np.testing.assert_array_equal(a, b)


def test_insitu_real_sweep_end_to_end(ct_case, caplog):
    """One real (untimed-fast) selection on this backend: times the
    shortlist, persists a loadable decision, and auto then matches the
    explicit call of whatever won."""
    filt, mats = ct_case
    d = Dispatcher(insitu=True, topk=2, include_pallas=False)
    with caplog.at_level(logging.INFO, logger="repro.dispatch"):
        plan = d.resolve(GEOM)
    assert any("in-situ selection" in r.message for r in caplog.records)
    assert plan.strategy in ("strip2", "gather")
    assert len(list(Path(os.environ["REPRO_TUNE_DIR"]).glob("*.json"))) \
        == 1
    set_dispatcher(d)
    a = np.asarray(reconstruct(filt, mats, GEOM, strategy="auto"))
    b = np.asarray(reconstruct(filt, mats, GEOM, strategy=plan.strategy,
                               pbatch=plan.pbatch, **plan.jnp_opts()))
    np.testing.assert_array_equal(a, b)


def test_env_flag_gates_insitu(monkeypatch):
    """REPRO_DISPATCH_INSITU=0 (the conftest default here) disables
    selection; flipping it on enables it without constructor args."""
    calls = []

    def fake_sweep(geom, **kw):
        calls.append(1)
        return _fake_sweep_result()

    d = Dispatcher(sweep_fn=fake_sweep)          # insitu=None -> env
    assert d.resolve(GEOM).strategy == "strip2" and not calls
    monkeypatch.setenv("REPRO_DISPATCH_INSITU", "1")
    assert Dispatcher(sweep_fn=fake_sweep).resolve(GEOM).strategy \
        == "gather"
    assert len(calls) == 1


# ----------------------------------------------------------------------
# Streaming engine: tuned kernel fold
# ----------------------------------------------------------------------

def test_engine_runs_tuned_pallas_batch_plan(ct_case):
    """A cached decision whose Pallas batch kernel beat the jnp nest
    makes the engine fold through that kernel (stats prove it), with
    streamed-vs-oneshot parity at fp32 rounding."""
    from repro.streaming.engine import ReconstructionEngine

    filt, mats = ct_case
    backend, device_kind = device_identity()
    store_tuned(GS, TunedConfig(
        strategy="strip2", opts={}, backend=backend,
        device_kind=device_kind, us_per_call=100.0,
        pallas={"ty": 8, "chunk": 16, "band": 16, "width": 128,
                "pbatch": 2},
        pallas_us=10.0))
    projs, pmats, _ = make_dataset(GEOM)
    eng = ReconstructionEngine(GEOM, n_slots=1, strategy="auto")
    assert eng.exec_plan.use_pallas and eng.pbatch == 2
    sid = eng.begin_scan()
    for i in range(GEOM.n_proj):
        eng.submit(sid, projs[i], pmats[i], i)
    eng.drain()
    out = np.asarray(eng.result(sid, pop=True))
    assert eng.stats["pallas_folds"] == GEOM.n_proj
    ref = np.asarray(reconstruct(filt, mats, GEOM))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_engine_untuned_fold_unchanged(ct_case):
    """No kernel decision -> the vmapped jnp fold, zero pallas folds."""
    from repro.streaming.engine import ReconstructionEngine

    filt, mats = ct_case
    projs, pmats, _ = make_dataset(GEOM)
    eng = ReconstructionEngine(GEOM, n_slots=1, strategy="auto")
    assert eng.exec_plan.use_pallas is False
    sid = eng.begin_scan()
    for i in range(GEOM.n_proj):
        eng.submit(sid, projs[i], pmats[i], i)
    eng.drain()
    out = np.asarray(eng.result(sid, pop=True))
    assert eng.stats["pallas_folds"] == 0
    ref = np.asarray(reconstruct(filt, mats, GEOM))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_process_dispatcher_is_singleton():
    d = get_dispatcher()
    assert get_dispatcher() is d
    other = Dispatcher(insitu=False)
    assert set_dispatcher(other) is d
    assert get_dispatcher() is other
