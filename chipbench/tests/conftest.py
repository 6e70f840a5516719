"""A tiny checkout for running cells on the CPU.

``tiny_root`` builds a directory laid out like a checkout: a
``BENCHMARK.json`` with four tiny cells (the front door with the Pallas
kernel in interpret mode, on scans longer than a window and on scans of
8 views that finish inside it; the one-shot jnp path; and the one-shot
path on RabbitCT's whole detector, where the control's rounding shows),
their configuration, traffic and limit files, the real entries and
metric readers, and a link to the program's ``src``.
"""

import atexit
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))
# Runs in these tests keep their compiled programs out of the checkout,
# in a directory removed when the test process exits.
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _CACHE = tempfile.mkdtemp(prefix="chipbench-tests-cache-")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _CACHE
    atexit.register(shutil.rmtree, _CACHE, ignore_errors=True)

# RabbitCT's field of view and fan, on a 40x32 detector and a 16^3
# volume of 16 mm voxels, 64 views over the 200 degree short scan.
TINY_GEOMETRY = {"n_u": 40, "n_v": 32, "du_mm": 9.984, "dv_mm": 9.6,
                 "sid_mm": 750.0, "sdd_mm": 1200.0, "L": 16,
                 "voxel_mm": 16.0, "n_proj": 64, "sweep_deg": 200.0}


# RabbitCT's whole detector over a 16^3 volume of 16 mm voxels.
WIDE_GEOMETRY = {"n_u": 1248, "n_v": 960, "du_mm": 0.32, "dv_mm": 0.32,
                 "sid_mm": 750.0, "sdd_mm": 1200.0, "L": 16,
                 "voxel_mm": 16.0, "n_proj": 64, "sweep_deg": 200.0}


def limit_of(cell: str) -> float:
    path = BENCH / "limits" / f"{cell}.json"
    return json.loads(path.read_text())["max_rel_err"]["limit"]


def tiny_config(name="tiny", geometry=TINY_GEOMETRY):
    return {
        "name": name, "geometry": dict(geometry),
        "deployment": {"chips": 1, "n_slots": 2, "max_pending": 16,
                       "policy": "fifo"},
        "plan": {"strategy": "gather", "opts": {}, "pbatch": 4,
                 "use_pallas": True,
                 "pallas": {"ty": 8, "chunk": 16, "band": 40, "width": 128,
                            "pbatch": 4, "strip_dtype": "float32"}},
    }


@pytest.fixture()
def tiny_root(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    # (cell, configuration, mix, limit)
    cells = [("tiny.scan", "tiny", "scan-1x1", 5e-4),
             ("short.scan", "short", "scan-1x4", 5e-4),
             ("tiny.oneshot", "tiny", "oneshot-8", 5e-4),
             ("wide.oneshot", "wide", "oneshot-8",
              limit_of("rabbitct-256.oneshot"))]
    bench["configs"] = [{"name": n, "source": "test",
                         "file": f"chipbench/configs/{n}.json",
                         "reduced": [], "why": "test"}
                        for n in ("tiny", "short", "wide")]
    bench["workloads"] = [{"name": n, "config": c, "traffic": t,
                           "chips": 1, "why": "test"}
                          for n, c, t, _ in cells]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    d = tmp_path / "chipbench"
    for sub in ("configs", "limits"):
        (d / sub).mkdir(parents=True)
    for sub in ("metrics", "entries", "traffic"):
        shutil.copytree(BENCH / sub, d / sub)
    (d / "configs" / "tiny.json").write_text(json.dumps(tiny_config()))
    (d / "configs" / "short.json").write_text(json.dumps(
        tiny_config("short", dict(TINY_GEOMETRY, n_proj=8))))
    (d / "configs" / "wide.json").write_text(json.dumps(
        tiny_config("wide", WIDE_GEOMETRY)))
    for name, _, _, limit in cells:
        (d / "limits" / f"{name}.json").write_text(json.dumps(
            {"max_rel_err": {"limit": limit}}))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "src").symlink_to(REPO / "src")
    return tmp_path
