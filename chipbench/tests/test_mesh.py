"""The mesh cell: its readers, a whole run on four CPU devices, and its
refusal of a program without a mesh engine.

The whole run goes to a child process with four CPU devices, so that
this process keeps JAX at one; the device trace is stood in for there
as in ``test_program_spans.py``, with some back projection time.
"""

import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

from conftest import BENCH, REPO, TINY_GEOMETRY, tiny_config
from harness.acq import Acq
from harness.cell import _module, load_cell, reader
from harness.tracing import Spans

NEW = ("slab_bp_ms_per_proj", "slab_bp_roofline", "slab_block_skew",
       "mesh_put_ms_per_view")
PEAK = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}


def test_cell_files_resolve():
    c = load_cell("rabbitct-1024.mesh", REPO)
    assert c.chips == 4 and c.traffic["entry"] == "mesh"
    assert c.config["deployment"]["mesh"] == {"data": 4, "model": 1}
    assert c.config["geometry"]["L"] % c.chips == 0
    assert set(NEW) <= {m["name"] for m in c.per_layer}
    assert callable(_module("entries", "mesh", REPO).Entry)


def _run(summary, layer_s=None, views=28, submits=7, peak=PEAK):
    spans = Spans()
    spans.by_name["submit"] = [(0.1, 4)] * submits
    cell = SimpleNamespace(chips=4)
    acq = Acq.from_config(json.loads(
        (BENCH / "configs" / "rabbitct-1024.json").read_text())["geometry"])
    trace = None if layer_s is None else {"layer_s": layer_s,
                                          "busy_s": 1.0}
    return SimpleNamespace(cell=cell, acq=acq, spans=spans, trace=trace,
                           peak=peak, work={"views": views, "passes": 1,
                                            "window_s": 40.0})


def test_readers_read_a_synthetic_run(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "summary", lambda: {
        "frontdoor.submit": {"count": 7, "units": 28, "total_s": 1.0,
                             "self_s": 0.1},
        "engine.put": {"count": 7, "units": 28, "total_s": 0.28,
                       "self_s": 0.28},
        "kernel.col_blocks": {"count": 28, "units": 1000},
        "mesh.col_blocks_max_slab": {"count": 7, "units": 300}})
    run = _run(None, {"backprojection": 28.0, "filter": 0.1})
    got = {m: reader(m, REPO)(run) for m in NEW}
    assert got["slab_bp_ms_per_proj"] == pytest.approx(1000.0)
    # 28 views of 1248x960 and one read and write of 1024^3, over four
    # chips' bandwidth, in 28 s.
    moved = 4 * (28 * 1248 * 960 + 2 * 1024 ** 3)
    assert got["slab_bp_roofline"] == pytest.approx(
        100 * moved / 4 / 819e9 / 28.0)
    assert got["slab_block_skew"] == pytest.approx(1.2)
    assert got["mesh_put_ms_per_view"] == pytest.approx(10.0)


def test_readers_read_nothing_in_an_empty_run(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "summary", lambda: {})
    run = _run(None, None, views=0, submits=0, peak=None)
    assert all(reader(m, REPO)(run) is None for m in NEW)
    # A record of another window's submits is not this one's.
    monkeypatch.setattr(obs, "summary", lambda: {
        "frontdoor.submit": {"count": 3, "units": 12},
        "engine.put": {"count": 3, "units": 12, "total_s": 0.1},
        "kernel.col_blocks": {"count": 12, "units": 10},
        "mesh.col_blocks_max_slab": {"count": 3, "units": 4}})
    run = _run(None, {"backprojection": 0.0, "filter": 0.0})
    assert all(reader(m, REPO)(run) is None for m in NEW)


def test_entry_refuses_a_program_without_a_mesh_engine(monkeypatch):
    import repro.streaming as streaming

    class OneChipEngine:
        def __init__(self, geom, *, n_slots=4, plan=None, **opts):
            pass

    monkeypatch.setattr(streaming, "ReconstructionEngine", OneChipEngine)
    c = load_cell("rabbitct-1024.mesh", REPO)
    acq = Acq.from_config(c.config["geometry"])
    sampler = SimpleNamespace(idx=[0, 1, 2])
    e = _module("entries", "mesh", REPO).Entry(c, acq, None, None, 1,
                                               sampler)
    t = time.perf_counter()
    with pytest.raises(RuntimeError, match="no mesh engine"):
        e.build()
    assert time.perf_counter() - t < 5


CHILD = """
import time
from pathlib import Path
from harness import runner
runner._trace_numbers = lambda d, layers, chips: {
    "busy_s": 0.5, "layer_s": {"filter": 0.01, "backprojection": 0.2},
    "device_ops": [], "idle_gaps": []}
res = runner.execute(Path(ROOT), "tiny.mesh", seed=2 ** 33 + 9,
                     seconds=1.0, trace=True, t_start=time.perf_counter(),
                     require_chip=False)
print(json.dumps(res))
"""


def test_tiny_mesh_cell_runs_on_four_devices(tiny_root):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    cfg = tiny_config("tinymesh", dict(TINY_GEOMETRY, n_proj=16))
    cfg["deployment"] = {"chips": 4, "mesh": {"data": 4, "model": 1},
                         "n_slots": 2, "max_pending": 16, "policy": "fifo"}
    d = tiny_root / "chipbench"
    (d / "configs" / "tinymesh.json").write_text(json.dumps(cfg))
    (d / "limits" / "tiny.mesh.json").write_text(json.dumps(
        {"max_rel_err": {"limit": 5e-4}}))
    bench["workloads"].append({"name": "tiny.mesh", "config": "tinymesh",
                               "traffic": "mesh-scan-1x4", "chips": 4,
                               "why": "test"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    script = textwrap.dedent(f"""
        import os, sys, json
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {str(BENCH)!r})
        sys.path.insert(0, {str(REPO / 'src')!r})
        ROOT = {str(tiny_root)!r}
    """) + textwrap.dedent(CHILD)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=str(tiny_root))
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"], res
    assert res["device"]["count"] == 4
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # Scans of 16 views: one finishes inside the window, the next is read
    # from its slot.
    assert res["attempted"] >= 16
    assert m["slab_bp_ms_per_proj"] > 0
    assert m["slab_block_skew"] >= 1.0
    assert m["mesh_put_ms_per_view"] > 0 and m["planner_ms_per_view"] > 0
    assert "slab_bp_roofline" not in m          # no peak off the chip
