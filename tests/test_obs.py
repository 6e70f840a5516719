"""Program spans and counters (``repro.obs``).

Spans record only while a profiler capture runs, nest through the span
open around them, total to self time per name, start afresh with each
capture, and match the ``repro.*`` events of the profiler's own trace
one to one.  The per-view readers of the chip benchmark's host metrics
read that record, and read ``None`` where it holds nothing of theirs.
"""

import glob
import importlib.util
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from repro import obs

REPO = Path(__file__).resolve().parents[1]
READERS = {"planner_ms_per_view": ("planner.check", "total_s"),
           "filter_dispatch_ms_per_view": ("engine.filter", "total_s"),
           "fold_dispatch_ms_per_view": ("engine.fold", "self_s"),
           "frontdoor_self_ms_per_view": ("frontdoor.submit", "self_s")}


def _nested():
    with obs.span("outer", units=3, scan=7) as rec:
        rec["seen"] = True
        with obs.span("inner", units=2):
            time.sleep(0.004)
        with obs.span("inner", units=1):
            time.sleep(0.002)
        obs.count("hits", 5)
        time.sleep(0.003)


def _xplane_events(log_dir):
    """``(name, duration_ns)`` of the ``repro.*`` host events of the
    capture written under ``log_dir``, in start order."""
    from jax.profiler import ProfileData

    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    rows = [(e.start_ns, e.name[len(obs.PREFIX):], e.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(obs.PREFIX)]
    return [(name, dur) for _, name, dur in sorted(rows)]


def test_no_capture_records_nothing():
    before, totals = obs.recorded(), obs.summary()
    with obs.span("outside", units=4) as rec:
        assert rec is None
    obs.count("outside", 2)
    assert obs.recorded() == before
    assert obs.summary() == totals


def test_capture_records_nesting_parents_and_self_time(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        _nested()
    spans = obs.recorded()
    assert [(n, p, u) for n, p, _, _, u, _ in spans] == [
        ("inner", "outer", 2), ("inner", "outer", 1), ("outer", None, 3)]
    assert spans[-1][5] == {"scan": 7, "seen": True}
    s = obs.summary()
    assert s["inner"]["count"] == 2 and s["inner"]["units"] == 3
    assert s["inner"]["self_s"] == s["inner"]["total_s"] >= 0.006
    outer = s["outer"]
    assert outer["self_s"] == pytest.approx(
        outer["total_s"] - s["inner"]["total_s"])
    assert 0.003 <= outer["self_s"] < outer["total_s"]
    assert s["hits"] == {"count": 1, "units": 5, "total_s": 0.0,
                         "self_s": 0.0}


def test_second_capture_starts_a_fresh_record(tmp_path):
    with jax.profiler.trace(str(tmp_path / "a")):
        _nested()
    with jax.profiler.trace(str(tmp_path / "b")):
        with obs.span("only"):
            pass
    assert [r[0] for r in obs.recorded()] == ["only"]
    assert set(obs.summary()) == {"only"}


def test_spans_match_the_profiler_trace(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        _nested()
        with obs.span("after"):
            time.sleep(0.001)
    mine = sorted(obs.recorded(), key=lambda r: r[2])
    events = _xplane_events(tmp_path)
    assert [n for n, *_ in mine] == [n for n, _ in events]
    for (name, _, t0, t1, _, _), (_, dur) in zip(mine, events):
        assert abs((t1 - t0) - dur) < 0.5e6, name


def _reader(name):
    path = REPO / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(submits, views):
    return SimpleNamespace(
        work={"views": views, "window_s": 1.0},
        spans=SimpleNamespace(by_name={"submit": [(0.1, 1)] * submits}))


@pytest.fixture()
def readers(monkeypatch):
    monkeypatch.syspath_prepend(str(REPO / "chipbench"))
    return {name: _reader(name) for name in READERS}


@pytest.fixture()
def front_door_record(tmp_path):
    """A capture holding two front-door submits of 3 views in all."""
    with jax.profiler.trace(str(tmp_path)):
        for units in (2, 1):
            with obs.span("frontdoor.submit", units=units):
                with obs.span("planner.check", units=units):
                    time.sleep(0.001)
                with obs.span("engine.filter", units=units):
                    pass
                with obs.span("engine.fold", units=units):
                    with obs.span("planner.check", units=units):
                        time.sleep(0.001)
                    time.sleep(0.002)
    return obs.summary()


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_reads_the_window_per_view(metric, readers,
                                          front_door_record):
    name, field = READERS[metric]
    got = readers[metric](_run(submits=2, views=3))
    assert got == pytest.approx(1e3 * front_door_record[name][field] / 3)
    assert got >= 0


def test_readers_add_up_to_the_front_door(readers, front_door_record):
    total = sum(readers[m](_run(2, 3)) for m in READERS)
    assert total == pytest.approx(
        1e3 * front_door_record["frontdoor.submit"]["total_s"] / 3)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_none_without_this_windows_submits(metric, readers,
                                                     front_door_record,
                                                     monkeypatch):
    read = readers[metric]
    assert read(_run(submits=0, views=3)) is None     # no front door
    assert read(_run(submits=5, views=3)) is None     # an older record
    assert read(_run(submits=2, views=0)) is None
    # A program without repro.obs, as before it existed.
    monkeypatch.delattr(sys.modules["repro"], "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(_run(submits=2, views=3)) is None
