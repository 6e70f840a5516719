"""Kernel contract checker (DESIGN.md §13): ledger, budget, hygiene,
cache audit, Dispatcher wiring, and the CLI's exit-code contract.

The seeded known-bad fixtures under ``tests/lint_fixtures/`` are the
true-positive half of the suite; the clean-tree runs are the
false-positive gate.
"""

import json
import logging
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis.lint import (VMEM_BUDGET_BYTES, ReplayCase,
                                 audit_cache_file, audit_tuned_config,
                                 batch_vmem_estimate, check_source,
                                 replay, replay_fixture,
                                 run_cache_audit_pass, run_hygiene_pass)
from repro.analysis.lint.cache_audit import geometry_for, parse_cache_key
from repro.core import Geometry, reconstruct
from repro.core.backproject import GeomStatic
from repro.tune import TunedConfig, clear_memory_cache, store_tuned
from repro.tune.space import pallas_batch_fits_vmem

REPO = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"

GEOM = Geometry().scaled(16, n_proj=4)
GS = GeomStatic.of(GEOM)


@pytest.fixture(autouse=True)
def _isolated_tune_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path / "tune"))
    clear_memory_cache()
    yield tmp_path / "tune"
    clear_memory_cache()


# ----------------------------------------------------------------------
# DMA-ledger replay
# ----------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    ReplayCase("batch_p4", "batch", pbatch=4),
    ReplayCase("batch_p3", "batch", pbatch=3),       # remainder tail
    ReplayCase("batch_db_p4_d3", "batch_db", pbatch=4, depth=3),
    ReplayCase("single_db_d2", "single_db", depth=2),
    ReplayCase("batch_shared_p4", "batch_shared", pbatch=4),
    ReplayCase("batch_int8_p4", "batch", pbatch=4, quantized=True),
], ids=lambda c: c.name)
def test_ledger_clean_on_real_kernels(case):
    """The repo kernels replay with balanced ledgers at the promised
    pipeline depth."""
    ledger = replay(case)
    assert ledger.raw_findings == []
    assert ledger.issues == ledger.waits > 0
    assert ledger.max_in_flight == case.promised


def test_ledger_flags_unbalanced_fixture():
    findings, ledger = replay_fixture(
        str(FIXTURES / "bad_ledger_kernel.py"))
    rules = {f.rule for f in findings}
    assert "unwaited-dma" in rules
    assert ledger.issues > ledger.waits


def test_ledger_flags_wait_before_issue():
    """A kernel that waits on a semaphore nobody signalled is flagged."""
    import numpy as np

    import jax.numpy as jnp  # noqa: F401

    def kernel(A_ref, img_ref, vol_in_ref, vol_out_ref, strip_ref, sem,
               *, o_mm, n_u, n_v, ty, chunk, band, width,
               quantized=False):
        import repro.kernels.backproject as K

        K.pltpu.make_async_copy(
            img_ref.at[K.pl.ds(0, band), K.pl.ds(0, width)],
            strip_ref, sem).wait()
        vol_out_ref[...] = np.asarray(vol_in_ref[...])

    ledger = replay(ReplayCase("waits-first", "single"),
                    kernel_fn=kernel)
    assert {"wait-before-issue"} == {r for r, _ in ledger.raw_findings}


# ----------------------------------------------------------------------
# VMEM budget model — one implementation behind the tuner screen
# ----------------------------------------------------------------------

def test_fits_vmem_delegates_to_budget_model(monkeypatch):
    """``pallas_batch_fits_vmem`` is the budget model — patch the model
    and the tuner screen follows."""
    import repro.tune.space as space

    params = dict(pbatch=4, ty=8, chunk=16, band=16, width=128)
    assert space.pallas_batch_fits_vmem(GS, **params)

    class _Never:
        fits = False

    monkeypatch.setattr(space, "batch_vmem_estimate",
                        lambda *a, **k: _Never())
    assert not space.pallas_batch_fits_vmem(GS, **params)


def test_fits_vmem_equals_model_across_grid():
    for pbatch in (1, 4, 16):
        for depth in (2, 4):
            for itemsize in (4, 2, 1):
                for band, width in ((16, 128), (968, 1280)):
                    got = pallas_batch_fits_vmem(
                        GS, pbatch=pbatch, ty=8, chunk=32, band=band,
                        width=width, depth=depth, itemsize=itemsize)
                    est = batch_vmem_estimate(
                        GS, pbatch=pbatch, ty=8, chunk=32, band=band,
                        width=width, depth=depth, itemsize=itemsize)
                    assert got == est.fits
                    assert est.budget == VMEM_BUDGET_BYTES


def test_budget_sublane_table_matches_kernel_ops():
    """The byte model prices the padded rows and strip windows the
    kernel wrappers actually allocate, for every wire."""
    from repro.analysis.lint import budget as budget_mod
    from repro.kernels import backproject_ops
    from repro.kernels.backproject import SUBLANE

    for itemsize in SUBLANE:
        rows = backproject_ops._padded_dims(GS.n_v, GS.n_u, 16, 128,
                                            itemsize)[0]
        assert budget_mod._padded_rows(GS, 16, itemsize) == rows
        assert rows % SUBLANE[itemsize] == 0


def test_budget_int8_counts_scale_sideband():
    """The 1-byte wire carries a (P, rows, 2) f32 sideband at
    sublane-32 padded rows, lane-padded to 128 and double-buffered;
    wider wires carry none."""
    from repro.kernels.backproject import strip_window

    kw = dict(pbatch=4, ty=8, chunk=16, band=16, width=128)
    f32 = batch_vmem_estimate(GS, itemsize=4, **kw)
    int8 = batch_vmem_estimate(GS, itemsize=1, **kw)
    assert f32.scale_bytes == 0
    wband, wwidth = strip_window(16, 128, 1)       # (64, 256)
    rows = max(wband, GS.n_v + 2)
    rows += (-rows) % 32
    assert int8.scale_bytes == 2 * 4 * rows * 128 * 4
    assert int8.strip_bytes == 4 * wband * wwidth


def test_budget_screens_candidate_generator():
    from repro.analysis.lint.budget import screen_candidate_spaces

    findings, checked = screen_candidate_spaces()
    assert findings == [] and checked > 0


# ----------------------------------------------------------------------
# Trace hygiene
# ----------------------------------------------------------------------

def _rules(src):
    return [f.rule for f in check_source("<t>", textwrap.dedent(src))]


def test_hygiene_flags_jit_in_fn():
    assert _rules("""
        import jax
        def hot(x):
            return jax.jit(lambda y: y + 1)(x)
        """) == ["jit-in-fn"]


def test_hygiene_allows_self_assigned_and_module_jit():
    assert _rules("""
        import jax
        step = jax.jit(lambda y: y)
        class Engine:
            def __init__(self):
                self._step = jax.jit(lambda y: y + 1)
        """) == []


def test_hygiene_pragma_suppresses():
    assert _rules("""
        import jax
        def once(step):
            return jax.jit(step)  # lint: ok(jit-in-fn)
        """) == []


def test_hygiene_flags_warn_without_stacklevel():
    assert _rules("""
        import warnings
        def f():
            warnings.warn("boom", RuntimeWarning)
        """) == ["warn-stacklevel"]
    assert _rules("""
        import warnings
        def f():
            warnings.warn("boom", RuntimeWarning, stacklevel=2)
        """) == []


def test_hygiene_flags_mutable_default():
    assert _rules("""
        def f(x, opts={}):
            return opts
        """) == ["mutable-default"]


def test_hygiene_flags_nonhashable_static():
    found = _rules("""
        import jax
        from functools import partial
        @partial(jax.jit, static_argnames=("opts",))
        def f(x, opts={}):
            return x
        """)
    assert "nonhashable-static" in found


def test_hygiene_flags_unused_import():
    assert _rules("""
        import os
        import numpy as np
        from typing import Sequence
        def f(x):
            return np.asarray(x)
        """) == ["unused-import", "unused-import"]


def test_hygiene_unused_import_exemptions():
    """__all__ re-exports, redundant aliases, noqa, __future__, and the
    pragma are all deliberate — none is a finding."""
    assert _rules("""
        from __future__ import annotations
        from .core import reconstruct, Geometry
        from .tune import autotune as autotune
        import repro.kernels  # noqa: F401 (side-effect registration)
        import repro.serving  # lint: ok(unused-import)
        __all__ = ["reconstruct", "Geometry"]
        """) == []


def test_hygiene_unused_import_sees_attribute_roots():
    """``import a.b`` binds ``a``; use through ``a.b.c`` counts."""
    assert _rules("""
        import os.path
        def f(p):
            return os.path.join(p, "x")
        """) == []
    assert _rules("""
        import os.path
        """) == ["unused-import"]


def test_hygiene_clean_tree_is_the_false_positive_gate():
    res = run_hygiene_pass(str(REPO / "src"))
    assert res.findings == []
    assert res.checked == len(list((REPO / "src").rglob("*.py")))


# ----------------------------------------------------------------------
# Tuned-cache audit
# ----------------------------------------------------------------------

def test_parse_cache_key_roundtrip():
    from repro.tune.cache import cache_key

    key = cache_key(GS, "cpu", "cpu")
    parsed = parse_cache_key(key)
    assert parsed is not None
    gs, backend, device = parsed
    assert gs == GS and backend == "cpu" and device == "cpu"
    assert parse_cache_key("not-a-cache-key") is None
    assert geometry_for(gs) is not None


def test_audit_flags_overflow_fixture():
    findings = audit_cache_file(
        FIXTURES / "overflow_tune"
        / "ct-L16-u39-v30-O-120-MM16--cpu--cpu.json")
    assert [f.rule for f in findings] == ["planner-invalid"]
    assert "VMEM budget" in findings[0].detail


def test_audit_flags_stale_fixture():
    findings = audit_cache_file(
        FIXTURES / "stale_tune"
        / "ct-L16-u39-v30-O-120-MM16--cpu--cpu.json")
    assert [f.rule for f in findings] == ["stale-schema"]


def test_audit_flags_undersized_window_via_planner():
    cfg = TunedConfig(strategy="strip2",
                      opts={"group": 8, "gband": 2, "gwidth": 8,
                            "pbatch": 4},
                      backend="cpu", device_kind="cpu", us_per_call=1.0)
    reasons = audit_tuned_config(GS, cfg, geom=GEOM)
    assert any("planner" in r for r in reasons)


def test_audit_clean_config_has_no_reasons():
    cfg = TunedConfig(strategy="strip2",
                      opts={"group": 8, "gband": 32, "gwidth": 41,
                            "pbatch": 4},
                      backend="cpu", device_kind="cpu", us_per_call=1.0,
                      pallas={"ty": 8, "chunk": 16, "band": 32,
                              "width": 128, "pbatch": 4})
    assert audit_tuned_config(GS, cfg, geom=GEOM) == []


def test_audit_pass_flags_corrupt_and_misnamed(tmp_path):
    d = tmp_path / "tune"
    d.mkdir()
    (d / "ct-L16-u39-v30-O-120-MM16--cpu--cpu.json").write_text("{nope")
    (d / "leftover.json").write_text("{}")
    res = run_cache_audit_pass(d)
    assert sorted(f.rule for f in res.findings) == ["corrupt-file",
                                                    "unparseable-key"]
    assert res.checked == 2


def test_audit_pass_empty_dir_is_clean(tmp_path):
    res = run_cache_audit_pass(tmp_path / "nothing-here")
    assert res.findings == [] and res.checked == 0 and res.notes


# ----------------------------------------------------------------------
# Dispatcher wiring: stale cached config -> warn once + re-select
# ----------------------------------------------------------------------

def test_dispatcher_rejects_planner_invalid_cache(caplog):
    from repro.dispatch import Dispatcher
    from repro.tune.sweep import SweepResult, Timing

    bad = TunedConfig(
        strategy="strip2",
        opts={"group": 8, "gband": 32, "gwidth": 41, "pbatch": 4},
        backend="cpu", device_kind="cpu", us_per_call=1.0,
        pallas={"ty": 8, "chunk": 16, "band": 32, "width": 128,
                "pbatch": 1024})        # over the VMEM budget
    store_tuned(GS, bad)

    def fake_sweep(geom, **kw):
        return SweepResult(
            geom_key=tuple(GS), backend="cpu", device_kind="cpu",
            timings=[Timing(label="gather[pbatch=4]", strategy="gather",
                            opts=(("pbatch", 4),), us_per_call=9.0,
                            gups=1.0)],
            skipped=[])

    d = Dispatcher(insitu=True, sweep_fn=fake_sweep, backend="cpu",
                   device_kind="cpu")
    with caplog.at_level(logging.WARNING, logger="repro.dispatch"):
        plan = d.resolve(GEOM)
        d.resolve(GEOM)
    audit_warnings = [r for r in caplog.records
                      if "fails the current planner" in r.getMessage()]
    assert len(audit_warnings) == 1       # one structured warning
    msg = audit_warnings[0].getMessage()
    assert "ct-L16-u39-v30-O-120-MM16--cpu--cpu" in msg   # names the key
    assert ".json" in msg                                 # ...and file
    assert "VMEM budget" in msg                           # ...and reason
    # Resolution fell back to in-situ selection, not the stale window.
    assert plan.strategy == "gather"


def test_dispatcher_accepts_planner_valid_cache(caplog):
    from repro.dispatch import Dispatcher

    good = TunedConfig(
        strategy="strip2",
        opts={"group": 8, "gband": 32, "gwidth": 41, "pbatch": 4},
        backend="cpu", device_kind="cpu", us_per_call=1.0)
    store_tuned(GS, good)
    d = Dispatcher(insitu=False, backend="cpu", device_kind="cpu")
    with caplog.at_level(logging.WARNING, logger="repro.dispatch"):
        plan = d.resolve(GEOM)
    assert plan.strategy == "strip2"
    assert not [r for r in caplog.records
                if "fails the current planner" in r.getMessage()]


# ----------------------------------------------------------------------
# Runtime retrace counter
# ----------------------------------------------------------------------

def test_retrace_counter_one_compile_per_plan(retrace_counter):
    from repro.core import backproject as core_bp
    from repro.core.phantom import make_dataset

    geom = Geometry().scaled(16, n_proj=7)   # shape unique to this test
    projs, mats, _ = make_dataset(geom)
    counter = retrace_counter(core_bp._reconstruct_jit)
    reconstruct(projs, mats, geom, strategy="strip2")
    first = counter.delta()
    assert first == 1                  # one plan, one compile
    reconstruct(projs, mats, geom, strategy="strip2")
    assert counter.delta() == first    # same plan: zero retraces


# ----------------------------------------------------------------------
# CLI exit codes (subprocess)
# ----------------------------------------------------------------------

def _run_cli(*args, tmp_json=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    env.pop("REPRO_TUNE_DIR", None)
    cmd = [sys.executable, "-m", "repro.analysis.lint", *args]
    if tmp_json is not None:
        cmd += ["--json", str(tmp_json)]
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=600)
    report = json.loads(proc.stdout)
    return proc.returncode, report


def test_cli_nonzero_on_bad_ledger_fixture():
    code, report = _run_cli(
        "--passes", "ledger",
        "--kernel-fixture", str(FIXTURES / "bad_ledger_kernel.py"))
    assert code == 1 and not report["ok"]
    assert any(f["rule"] == "unwaited-dma" for f in report["findings"])


def test_cli_nonzero_on_overflow_fixture():
    code, report = _run_cli("--passes", "cache", "--tune-dir",
                            str(FIXTURES / "overflow_tune"))
    assert code == 1 and not report["ok"]
    assert any(f["rule"] == "planner-invalid"
               for f in report["findings"])


def test_cli_nonzero_on_stale_fixture():
    code, report = _run_cli("--passes", "cache", "--tune-dir",
                            str(FIXTURES / "stale_tune"))
    assert code == 1 and not report["ok"]
    assert any(f["rule"] == "stale-schema" for f in report["findings"])


def test_cli_clean_tree_exits_zero(tmp_path):
    """Acceptance: the full checker on the clean tree — zero findings,
    exit 0, and every pass actually checked something."""
    code, report = _run_cli("--fail-on-findings",
                            tmp_json=tmp_path / "lint.json")
    assert code == 0
    assert report["ok"] and report["findings"] == []
    by_name = {p["pass"]: p for p in report["passes"]}
    assert set(by_name) == {"ledger", "budget", "hygiene", "cache"}
    for name in ("ledger", "budget", "hygiene"):
        assert by_name[name]["checked"] > 0, f"{name} pass was vacuous"
    assert json.loads((tmp_path / "lint.json").read_text()) == report
