"""Benchmark harness: --only validation and the --json perf trajectory."""

import json

import pytest

from benchmarks import common
from benchmarks import run as bench_run
from repro.core.backproject import STRATEGIES
from repro.tune import clear_memory_cache


def test_only_typo_lists_modules_and_exits_nonzero(capsys):
    """An unknown --only name must not print a lone CSV header and
    exit 0 (the old behaviour); it lists valid modules and fails."""
    with pytest.raises(SystemExit) as exc:
        bench_run.main(["--only", "fig1_single_devise"])
    assert exc.value.code == 2
    captured = capsys.readouterr()
    assert "unknown module" in captured.err
    for name, _ in bench_run.MODULES:
        assert name in captured.err
    assert "name,us_per_call" not in captured.out


def test_known_only_name_is_accepted():
    # Argument validation only: any name in MODULES must clear the
    # check; we don't execute it here.
    names = [n for n, _ in bench_run.MODULES]
    assert "fig1_single_device" in names
    assert "table5_traffic" in names


def test_only_comma_list_rejects_any_bad_name(capsys):
    """CI passes a comma-separated --only; one bad name fails the whole
    invocation with the module list, same as the single-name case."""
    with pytest.raises(SystemExit) as exc:
        bench_run.main(["--only", "fig1_single_device,not_a_module"])
    assert exc.value.code == 2
    assert "unknown module" in capsys.readouterr().err


def test_json_trajectory_from_tiny_fig1(tmp_path, monkeypatch):
    """The harness writes BENCH-style json: per-strategy us/call,
    voxel-updates/s, and the autotuner's chosen config — and appends on
    the next run instead of overwriting."""
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path / "tune"))
    clear_memory_cache()
    monkeypatch.setattr(common, "TINY_ENV", True)
    path = tmp_path / "bench.json"

    bench_run.main(["--only", "fig1_single_device", "--json", str(path)])
    doc = json.loads(path.read_text())
    assert len(doc["runs"]) == 1
    run0 = doc["runs"][0]
    assert run0["meta"]["tiny"] is True
    assert run0["meta"]["failures"] == 0

    rows = {r["name"]: r for r in run0["rows"]}
    for strat in STRATEGIES + ("auto",):
        row = rows[f"fig1/{strat}"]
        assert row["us_per_call"] > 0
        assert row["fields"]["gups"] > 0          # voxel-updates/s

    tuned = run0["extras"]["tuned_config"]
    assert tuned["strategy"] in STRATEGIES
    assert rows["fig1/auto"]["fields"]["chosen"] == tuned["strategy"]
    assert len(tuned["timings"]) >= 5
    # The decision carries the pbatch axis and the current schema
    # version (acceptance: tuned config includes pbatch).
    assert tuned["opts"].get("pbatch", 0) >= 1
    from repro.tune import TUNE_SCHEMA_VERSION
    assert tuned["version"] == TUNE_SCHEMA_VERSION
    # The batched loop nest is benchmarked at several *effective*
    # depths (requested depths clamp to the tiny n_proj).
    batch_rows = [r for n, r in rows.items()
                  if n.startswith("fig1/batch/p")]
    assert len(batch_rows) >= 2
    assert all(r["us_per_call"] > 0 for r in batch_rows)
    assert any(r["fields"]["pbatch"] > 1 for r in batch_rows)

    # Second run appends a trajectory entry with *fresh* rows (main()
    # resets the collection state, so nothing from run 1 replays).
    bench_run.main(["--only", "fig1_single_device", "--json", str(path)])
    doc = json.loads(path.read_text())
    assert len(doc["runs"]) == 2
    assert len(doc["runs"][1]["rows"]) == len(run0["rows"])


def test_table5_traffic_models_pbatch_reduction(tmp_path, monkeypatch):
    """table5 commits the volume-traffic model: the chosen-pbatch row's
    modelled bytes are the sequential bytes divided by the chosen
    depth (acceptance criterion)."""
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path / "tune"))
    clear_memory_cache()
    monkeypatch.setattr(common, "TINY_ENV", True)
    path = tmp_path / "bench.json"
    bench_run.main(["--only", "fig1_single_device,table5_traffic",
                    "--json", str(path)])
    run0 = json.loads(path.read_text())["runs"][0]
    assert run0["meta"]["failures"] == 0
    assert run0["meta"]["modules"] == ["fig1_single_device",
                                      "table5_traffic"]

    traffic = run0["extras"]["table5_traffic"]
    chosen = traffic["chosen_pbatch"]
    assert chosen >= 1
    from benchmarks.table5_traffic import volume_bytes

    L, n_proj = traffic["L"], traffic["n_proj"]
    assert traffic["volume_bytes_seq"] == volume_bytes(L, n_proj, 1)
    assert traffic["volume_bytes_chosen"] == volume_bytes(L, n_proj,
                                                          chosen)
    rows = {r["name"]: r for r in run0["rows"]}
    row = rows["table5/chosen"]
    assert row["fields"]["pbatch"] == chosen
    assert row["fields"]["vol_reduction"] == pytest.approx(
        traffic["volume_bytes_seq"] / traffic["volume_bytes_chosen"])


# ----------------------------------------------------------------------
# Regression gate (benchmarks/check_regression.py)
# ----------------------------------------------------------------------

def _traj(path, us_by_name, backend="cpu", device_kind="cpu", tiny=True):
    entry = {
        "timestamp": "2026-01-01T00:00:00Z",
        "meta": {"backend": backend, "device_kind": device_kind,
                 "tiny": tiny, "failures": 0, "modules": []},
        "rows": [{"name": n, "us_per_call": us, "derived": "",
                  "fields": {}} for n, us in us_by_name.items()],
        "extras": {},
    }
    import pathlib
    p = pathlib.Path(path)
    doc = {"runs": []}
    if p.is_file():
        doc = json.loads(p.read_text())
    doc["runs"].append(entry)
    p.write_text(json.dumps(doc))


def test_regression_gate_passes_within_threshold(tmp_path):
    from benchmarks import check_regression

    base, fresh = tmp_path / "base.json", tmp_path / "fresh.json"
    _traj(base, {"fig1/gather": 1000.0})
    _traj(fresh, {"fig1/gather": 2500.0})     # 2.5x < 4x: noise budget
    check_regression.main(["--baseline", str(base), "--fresh", str(fresh),
                           "--threshold", "4.0", "--min-us", "200"])


def test_regression_gate_fails_past_threshold(tmp_path, capsys):
    from benchmarks import check_regression

    base, fresh = tmp_path / "base.json", tmp_path / "fresh.json"
    _traj(base, {"fig1/gather": 1000.0, "fig1/strip2": 500.0})
    _traj(fresh, {"fig1/gather": 5000.0, "fig1/strip2": 600.0})
    with pytest.raises(SystemExit) as exc:
        check_regression.main(["--baseline", str(base), "--fresh",
                               str(fresh), "--threshold", "4.0",
                               "--min-us", "200"])
    assert exc.value.code == 1
    out = capsys.readouterr().out
    assert "REGRESSION fig1/gather" in out
    assert "fig1/strip2" not in out.replace("compared", "")


def test_regression_gate_skips_noise_rows_and_compares_latest(tmp_path):
    """µs-scale rows below --min-us never fail the gate, and the
    baseline is the *latest* committed entry for the identity."""
    from benchmarks import check_regression

    base, fresh = tmp_path / "base.json", tmp_path / "fresh.json"
    _traj(base, {"fig1/gather": 50.0})        # old slow entry
    _traj(base, {"fig1/gather": 10.0})        # latest entry: 10us
    _traj(fresh, {"fig1/gather": 1000.0})     # 100x but below min-us
    check_regression.main(["--baseline", str(base), "--fresh", str(fresh),
                           "--threshold", "4.0", "--min-us", "200"])


def test_regression_gate_noise_floor_is_symmetric(tmp_path, capsys):
    """Sub-floor medians are incomparable noise in *both* directions: a
    fresh row above the floor must never fail against a sub-floor
    baseline (the ratio is all baseline jitter), and a sub-floor fresh
    row against an above-floor baseline is skipped, not scored."""
    from benchmarks import check_regression

    base, fresh = tmp_path / "base.json", tmp_path / "fresh.json"
    _traj(base, {"fig1/a": 150.0,       # sub-floor baseline
                 "fig1/b": 1000.0})     # above-floor baseline
    _traj(fresh, {"fig1/a": 4000.0,     # 26x "regression" vs noise
                  "fig1/b": 80.0})      # sub-floor fresh
    check_regression.main(["--baseline", str(base), "--fresh", str(fresh),
                           "--threshold", "2.5", "--min-us", "200"])
    out = capsys.readouterr().out
    assert "no regressions" in out
    assert "compared 0 row(s)" in out


def test_regression_gate_vacuous_without_matching_identity(tmp_path,
                                                           capsys):
    from benchmarks import check_regression

    base, fresh = tmp_path / "base.json", tmp_path / "fresh.json"
    _traj(base, {"fig1/gather": 1000.0}, device_kind="TPU v5e")
    _traj(fresh, {"fig1/gather": 99999.0})
    check_regression.main(["--baseline", str(base), "--fresh", str(fresh)])
    assert "vacuously" in capsys.readouterr().out


def test_regression_gate_rejects_empty_fresh(tmp_path):
    from benchmarks import check_regression

    fresh = tmp_path / "fresh.json"
    with pytest.raises(SystemExit) as exc:
        check_regression.main(["--baseline", str(tmp_path / "b.json"),
                               "--fresh", str(fresh)])
    assert exc.value.code == 2


def test_regression_gate_fails_when_all_baseline_rows_dropped(tmp_path,
                                                              capsys):
    """A fresh run whose benchmark modules crashed emits no comparable
    rows; that used to sail through as 'no regressions'.  Zero rows
    compared with baseline rows expected = gate failure, and the missing
    rows are named."""
    from benchmarks import check_regression

    base, fresh = tmp_path / "base.json", tmp_path / "fresh.json"
    _traj(base, {"fig1/gather": 1000.0, "fig4/ttfv/b1": 5000.0})
    _traj(fresh, {"other/row": 10.0})     # module crashed: rows dropped
    with pytest.raises(SystemExit) as exc:
        check_regression.main(["--baseline", str(base), "--fresh",
                               str(fresh), "--min-us", "200"])
    assert exc.value.code == 1
    out = capsys.readouterr()
    assert "MISSING fig1/gather" in out.out
    assert "MISSING fig4/ttfv/b1" in out.out
    assert "zero rows compared" in out.err


def test_regression_gate_reports_partially_missing_rows(tmp_path, capsys):
    """Rows above --min-us that vanished are reported even when other
    rows still compare (and pass); noise-floor rows are not."""
    from benchmarks import check_regression

    base, fresh = tmp_path / "base.json", tmp_path / "fresh.json"
    _traj(base, {"fig1/gather": 1000.0, "fig4/ttfv/b1": 5000.0,
                 "fig1/tiny": 10.0})
    _traj(fresh, {"fig1/gather": 1100.0})
    check_regression.main(["--baseline", str(base), "--fresh", str(fresh),
                           "--min-us", "200"])
    out = capsys.readouterr().out
    assert "MISSING fig4/ttfv/b1" in out
    assert "fig1/tiny" not in out          # below the noise floor
    assert "no regressions" in out


def test_tiny_does_not_latch_across_inprocess_runs(monkeypatch):
    """--tiny must not leak into a later in-process main() without the
    flag (RESULTS/EXTRAS were reset; TINY silently stayed True)."""
    seen = []

    class Probe:                           # a module that records TINY
        @staticmethod
        def run():
            seen.append(common.TINY)

    monkeypatch.setattr(bench_run, "MODULES", [("probe", Probe)])
    monkeypatch.setattr(common, "TINY", False)
    monkeypatch.setattr(common, "TINY_ENV", False)
    with pytest.raises(SystemExit):
        bench_run.main(["--tiny", "--only", "nonexistent_module"])
    assert common.TINY is False            # parse failed before assign
    bench_run.main(["--tiny", "--only", "probe"])
    assert common.TINY is True
    bench_run.main(["--only", "probe"])
    assert common.TINY is False            # assigned, not latched
    # The REPRO_BENCH_TINY env opt-in survives the per-run assignment.
    monkeypatch.setattr(common, "TINY_ENV", True)
    bench_run.main(["--only", "probe"])
    assert common.TINY is True
    assert seen == [True, False, True]
