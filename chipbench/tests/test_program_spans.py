"""The readers of the program's own spans, in whole traced runs on the
CPU.

A capture on the CPU holds no TPU operations, so the reduction of the
device trace is stood in for by an empty one; the program's spans are
recorded during the window's capture as on the chip.  In a scan cell
the four readers read the front door's host time, split; in the
one-shot cell, which submits nothing to the front door, they read
nothing.
"""

import time

import pytest

from harness import runner

SPLIT = ("planner_ms_per_view", "filter_dispatch_ms_per_view",
         "fold_dispatch_ms_per_view", "frontdoor_self_ms_per_view")


@pytest.fixture()
def cpu_trace(monkeypatch):
    monkeypatch.setattr(runner, "_trace_numbers", lambda d, layers, chips: {
        "busy_s": 0.0, "layer_s": {k: 0.0 for k in layers},
        "device_ops": [], "idle_gaps": []})


def traced(root, cell):
    result = runner.execute(root, cell, seed=2 ** 33 + 7, seconds=0.5,
                            trace=True, t_start=time.perf_counter(),
                            require_chip=False)
    assert result["correct"], result
    return {k: v["value"] for k, v in result["metrics"].items()}


def test_scan_cell_splits_the_submit_span(tiny_root, cpu_trace):
    m = traced(tiny_root, "tiny.scan")
    assert all(m[k] >= 0 for k in SPLIT)
    assert m["fold_dispatch_ms_per_view"] > 0
    # The benchmark's span around each submit also holds the await after
    # the program's span: a little more, never less.
    split = sum(m[k] for k in SPLIT)
    assert 0.8 * m["submit_host_ms"] <= split <= m["submit_host_ms"]


def test_oneshot_cell_reads_nothing(tiny_root, cpu_trace):
    traced(tiny_root, "tiny.scan")          # a record the next run must
    m = traced(tiny_root, "tiny.oneshot")   # not read as its own
    assert not set(SPLIT) & set(m)
