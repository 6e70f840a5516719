"""Autotuner subsystem: sweep, cache, strategy="auto", jit-cache stability.

Every test isolates the on-disk cache in a tmp dir (``REPRO_TUNE_DIR``)
and drops the in-process memo, so decisions never leak between tests or
from a developer's ``.repro_tune/``.
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Geometry, filter_projections, reconstruct
from repro.core.backproject import (GeomStatic, STRATEGIES,
                                    _reconstruct_jit)
from repro.core.phantom import make_dataset
from repro.kernels.backproject_ops import pallas_backproject_one
from repro.tune import (Candidate, TunedConfig, autotune, clear_memory_cache,
                        device_identity, load_tuned, store_tuned,
                        sweep_strategies)

GEOM = Geometry().scaled(16, n_proj=4)
GS = GeomStatic.of(GEOM)


@pytest.fixture(autouse=True)
def _isolated_tune_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path / "tune"))
    clear_memory_cache()
    yield
    clear_memory_cache()


@pytest.fixture(scope="module")
def ct_case():
    projs, mats, _ = make_dataset(GEOM)
    filt = np.asarray(filter_projections(projs, GEOM))
    return filt, mats


def test_auto_untuned_matches_strip2_bitwise(ct_case):
    """Acceptance: untuned auto == strip2 defaults, bit for bit."""
    filt, mats = ct_case
    a = np.asarray(reconstruct(filt, mats, GEOM, strategy="auto"))
    b = np.asarray(reconstruct(filt, mats, GEOM, strategy="strip2"))
    np.testing.assert_array_equal(a, b)


def test_auto_follows_tuned_cache(ct_case):
    """A stored decision redirects auto (bitwise vs the explicit call)."""
    filt, mats = ct_case
    backend, device_kind = device_identity()
    cfg = TunedConfig(strategy="gather", opts={}, backend=backend,
                      device_kind=device_kind, us_per_call=1.0)
    store_tuned(GS, cfg)
    a = np.asarray(reconstruct(filt, mats, GEOM, strategy="auto"))
    b = np.asarray(reconstruct(filt, mats, GEOM, strategy="gather"))
    np.testing.assert_array_equal(a, b)
    c = np.asarray(reconstruct(filt, mats, GEOM, strategy="strip2"))
    assert not np.array_equal(a, c)


def test_auto_filters_mismatched_caller_opts(ct_case):
    """Options written for the fallback strategy must not crash when the
    cache tuned a different one (sample_onehot(gband=...) TypeError) —
    and the shed is *loud*: a RuntimeWarning names the dropped key."""
    filt, mats = ct_case
    backend, device_kind = device_identity()
    cfg = TunedConfig(strategy="onehot", opts={"vox_block": 64},
                      backend=backend, device_kind=device_kind,
                      us_per_call=1.0)
    store_tuned(GS, cfg)
    with pytest.warns(RuntimeWarning, match="gband"):
        a = np.asarray(reconstruct(filt, mats, GEOM, strategy="auto",
                                   gband=8))
    b = np.asarray(reconstruct(filt, mats, GEOM, strategy="onehot",
                               vox_block=64))
    np.testing.assert_array_equal(a, b)


def test_unknown_caller_opt_raises(ct_case):
    """A typo'd option is an error, not a silent no-op."""
    filt, mats = ct_case
    with pytest.raises(ValueError, match="unknown option"):
        reconstruct(filt, mats, GEOM, strategy="strip2", gbnad=8)


def test_autotune_sweeps_and_persists_roundtrip():
    cfg = autotune(GEOM, include_pallas=False, warmup=0, iters=1)
    assert cfg.strategy in STRATEGIES
    assert cfg.us_per_call > 0
    # Every timed candidate carries comparable numbers.
    assert len(cfg.timings) >= 5
    assert all(t["us_per_call"] > 0 and t["gups"] > 0
               for t in cfg.timings)
    clear_memory_cache()                      # force the disk path
    back = load_tuned(GS)
    assert back is not None
    assert (back.strategy, back.opts) == (cfg.strategy, cfg.opts)


def test_sweep_skips_undersized_windows():
    """A candidate the planner rejects is skipped, never timed."""
    bad = Candidate.of("strip2", group=8, gband=2, gwidth=8)
    ok = Candidate.of("gather")
    res = sweep_strategies(GEOM, space=[bad, ok], include_pallas=False,
                           warmup=0, iters=1)
    assert [t.strategy for t in res.timings] == ["gather"]
    assert len(res.skipped) == 1
    assert "does not cover" in res.skipped[0][1]


def test_stale_schema_versions_are_ignored(tmp_path):
    """A ``.repro_tune/`` file from an older schema (no ``version``, or a
    mismatched one) must be treated as untuned — never misread into the
    new dataclass (a v1 decision timed a loop nest that no longer
    exists)."""
    import os
    from pathlib import Path

    from repro.tune import TUNE_SCHEMA_VERSION, cache_key, load_tuned

    d = Path(os.environ["REPRO_TUNE_DIR"])
    d.mkdir(parents=True, exist_ok=True)
    backend, device_kind = device_identity()
    key = cache_key(GS, backend, device_kind)

    # v1-era file: no version field at all.
    v1 = {"strategy": "gather", "opts": {}, "backend": backend,
          "device_kind": device_kind, "us_per_call": 1.0}
    (d / f"{key}.json").write_text(json.dumps(v1))
    assert load_tuned(GS) is None

    # Future/mismatched version.
    v1["version"] = TUNE_SCHEMA_VERSION + 1
    (d / f"{key}.json").write_text(json.dumps(v1))
    clear_memory_cache()
    assert load_tuned(GS) is None

    # Current version loads.
    v1["version"] = TUNE_SCHEMA_VERSION
    (d / f"{key}.json").write_text(json.dumps(v1))
    clear_memory_cache()
    cfg = load_tuned(GS)
    assert cfg is not None and cfg.strategy == "gather"


def test_autotune_persists_current_version_and_pbatch():
    cfg = autotune(GEOM, include_pallas=False, warmup=0, iters=1)
    from repro.tune import TUNE_SCHEMA_VERSION

    assert cfg.version == TUNE_SCHEMA_VERSION
    # Every jnp candidate carries the pbatch axis now; the winner's
    # depth is what reconstruct(strategy="auto") will run.
    assert "pbatch" in cfg.opts and cfg.pbatch >= 1
    assert any(t["opts"].get("pbatch", 1) > 1 for t in cfg.timings)


def test_cache_file_is_json_keyed_on_device(tmp_path, monkeypatch):
    import jax
    cfg = autotune(GEOM, include_pallas=False, warmup=0, iters=1)
    files = list((tmp_path / "tune").glob("*.json"))
    assert len(files) == 1
    name = files[0].name
    assert f"L{GEOM.L}" in name and jax.default_backend() in name
    data = json.loads(files[0].read_text())
    assert data["strategy"] == cfg.strategy


def test_reconstruct_jit_cache_is_stable(ct_case):
    """Repeated reconstruct() calls must not recompile (the old inline
    ``@jax.jit`` closure recompiled on every invocation)."""
    filt, mats = ct_case
    reconstruct(filt, mats, GEOM, strategy="gather")
    size_after_first = _reconstruct_jit._cache_size()
    for _ in range(3):
        reconstruct(filt, mats, GEOM, strategy="gather")
    assert _reconstruct_jit._cache_size() == size_after_first


def test_pallas_auto_uses_tuned_tiles(ct_case):
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    img, A = jnp.asarray(filt[0]), jnp.asarray(mats[0])

    # Untuned: auto falls back to the passed parameters.
    out_auto = pallas_backproject_one(vol0, img, A, GEOM, ty=4, chunk=8,
                                      band=16, width=128, strategy="auto")
    out_fix = pallas_backproject_one(vol0, img, A, GEOM, ty=4, chunk=8,
                                     band=16, width=128)
    np.testing.assert_array_equal(np.asarray(out_auto), np.asarray(out_fix))

    # Tuned: auto picks the cached tile config (db variant here).
    backend, device_kind = device_identity()
    cfg = TunedConfig(strategy="strip2", opts={}, backend=backend,
                      device_kind=device_kind, us_per_call=1.0,
                      pallas={"ty": 8, "chunk": 16, "band": 16,
                              "width": 128, "double_buffer": True})
    store_tuned(GS, cfg)
    out_auto = pallas_backproject_one(vol0, img, A, GEOM, strategy="auto")
    out_fix = pallas_backproject_one(vol0, img, A, GEOM, ty=8, chunk=16,
                                     band=16, width=128,
                                     double_buffer=True)
    np.testing.assert_array_equal(np.asarray(out_auto), np.asarray(out_fix))

    with pytest.raises(ValueError, match="fixed.*auto|auto.*fixed"):
        pallas_backproject_one(vol0, img, A, GEOM, strategy="strip")


def test_pallas_batch_auto_honors_tuned_variant_flags(ct_case):
    """The batch path runs the kernel a tuned decision was timed on:
    ``double_buffer``/``db_depth`` resolve to the pipelined batch
    variant — bitwise against the explicit call, with no shed-the-flag
    warning left anywhere (warnings are errors here)."""
    import warnings

    from repro.kernels.backproject_ops import pallas_backproject_batch

    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    backend, device_kind = device_identity()
    cfg = TunedConfig(strategy="strip2", opts={}, backend=backend,
                      device_kind=device_kind, us_per_call=1.0,
                      pallas={"ty": 8, "chunk": 16, "band": 16,
                              "width": 128, "double_buffer": True,
                              "db_depth": 3, "pbatch": 2})
    store_tuned(GS, cfg)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out = pallas_backproject_batch(vol0, filt, mats, GEOM,
                                       strategy="auto")
    ref = pallas_backproject_batch(vol0, filt, mats, GEOM, ty=8, chunk=16,
                                   band=16, width=128, pbatch=2,
                                   double_buffer=True, db_depth=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_pallas_one_auto_resolves_tuned_db_depth(ct_case):
    """The single-projection path resolves ``db_depth`` with the
    ``double_buffer`` flag (the depth is part of the timed pipeline
    shape, and both paths share one rotation ledger).  The result is
    schedule-invariant, so the honoring is proven through the depth
    validation: a tuned sub-2 depth reaches the kernel selection and
    raises there."""
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    img, A = jnp.asarray(filt[0]), jnp.asarray(mats[0])
    backend, device_kind = device_identity()
    pallas = {"ty": 8, "chunk": 16, "band": 16, "width": 128,
              "double_buffer": True, "db_depth": 4}
    store_tuned(GS, TunedConfig(strategy="strip2", opts={},
                                backend=backend, device_kind=device_kind,
                                us_per_call=1.0, pallas=pallas))
    out_auto = pallas_backproject_one(vol0, img, A, GEOM, strategy="auto")
    out_fix = pallas_backproject_one(vol0, img, A, GEOM, ty=8, chunk=16,
                                     band=16, width=128,
                                     double_buffer=True, db_depth=4)
    np.testing.assert_array_equal(np.asarray(out_auto),
                                  np.asarray(out_fix))

    clear_memory_cache()
    store_tuned(GS, TunedConfig(strategy="strip2", opts={},
                                backend=backend, device_kind=device_kind,
                                us_per_call=1.0,
                                pallas={**pallas, "db_depth": 1}))
    with pytest.raises(ValueError, match="db_depth"):
        pallas_backproject_one(vol0, img, A, GEOM, strategy="auto")


def test_pallas_batch_candidates_cross_variants():
    """The batched candidate family spans pbatch × {plain, db}, every
    db candidate naming its ``db_depth`` so the timed value is the
    persisted value, and deep-rotation candidates pass the depth-aware
    VMEM check."""
    from repro.tune.space import pallas_batch_fits_vmem, pallas_candidates

    cands = [dict(c.opts) for c in pallas_candidates(GS)]
    batched = [c for c in cands if c.get("pbatch", 1) > 1]
    assert any(c.get("double_buffer") for c in batched)
    assert any(not c.get("double_buffer") for c in batched)
    for c in batched:
        if c.get("double_buffer"):
            assert c["db_depth"] >= 2
            assert pallas_batch_fits_vmem(
                GS, pbatch=c["pbatch"], ty=c["ty"], chunk=c["chunk"],
                band=c["band"], width=c["width"], depth=c["db_depth"])
        assert not (c.get("double_buffer") and c.get("shared_window"))


def test_candidate_space_spans_new_axes():
    """The v4 axes compete: the jnp family proposes a bf16-wire strip2,
    the kernel family proposes bf16 and shared-window batch variants,
    and no candidate combines the shared slab with db."""
    from repro.tune.space import jnp_candidates, pallas_candidates

    jnp_opts = [dict(c.opts) for c in jnp_candidates(GS)]
    assert any(c.get("strip_dtype") == "bfloat16" for c in jnp_opts)
    cands = [dict(c.opts) for c in pallas_candidates(GS)]
    assert any(c.get("strip_dtype") == "bfloat16"
               and not c.get("shared_window") for c in cands)
    shared = [c for c in cands if c.get("shared_window")]
    assert shared and any(c.get("strip_dtype") == "bfloat16"
                          for c in shared)
    for c in shared:
        assert not c.get("double_buffer")


def test_sweep_times_or_skips_shared_and_bf16():
    """A sweep over the new axes either times each candidate or skips it
    with a recorded reason — never crashes, never times an invalid
    config (the VMEM screen re-runs at the planner-tight shared dims)."""
    from repro.tune.space import Candidate
    from repro.tune.sweep import sweep_strategies

    geom = Geometry().scaled(16, n_proj=4)
    space = [
        Candidate.of("strip2", group=8, gband=8, gwidth=64,
                     strip_dtype="bfloat16", pbatch=2),
        Candidate.of("pallas", ty=8, chunk=16, band=16, width=128,
                     pbatch=2, strip_dtype="bfloat16"),
        Candidate.of("pallas", ty=8, chunk=16, band=16, width=128,
                     pbatch=2, shared_window=True),
        Candidate.of("pallas", ty=8, chunk=16, band=16, width=128,
                     pbatch=2, shared_window=True,
                     strip_dtype="bfloat16"),
    ]
    res = sweep_strategies(geom, space=space, include_pallas=True,
                           warmup=0, iters=1, min_total_s=0)
    assert len(res.timings) + len(res.skipped) == len(space)
    timed = {t.label for t in res.timings}
    assert any("strip2" in lbl for lbl in timed)
    for lbl, reason in res.skipped:
        assert reason


def test_resolve_strategy_passes_strip_dtype(tmp_path, monkeypatch):
    """``strip_dtype`` survives auto resolution for the strip families —
    a tuned bf16 decision must actually run bf16."""
    from repro.tune.cache import resolve_strategy

    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path / "tune"))
    clear_memory_cache()
    backend, device_kind = device_identity()
    cfg = TunedConfig(strategy="strip2",
                      opts={"strip_dtype": "bfloat16", "pbatch": 2},
                      backend=backend, device_kind=device_kind,
                      us_per_call=1.0)
    store_tuned(GS, cfg)
    strategy, opts = resolve_strategy(GS)
    clear_memory_cache()
    assert strategy == "strip2"
    assert opts["strip_dtype"] == "bfloat16"


def test_sharded_reconstruct_auto(ct_case):
    """auto resolves host-side before shard_map (1x1 mesh, bitwise)."""
    from repro.core.pipeline import sharded_reconstruct
    from repro.launch.mesh import make_local_mesh

    filt, mats = ct_case
    mesh = make_local_mesh(data=1, model=1)
    a = np.asarray(sharded_reconstruct(filt, mats, GEOM, mesh,
                                       strategy="auto"))
    b = np.asarray(reconstruct(filt, mats, GEOM, strategy="strip2"))
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
