"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout's root lists the cells
(``workloads``); a cell names a configuration and a traffic mix, and
the harness finds each as a file of its own:

* ``chipbench/configs/<config>.json``: the acquisition, the deployment
  and the pinned execution plan;
* ``chipbench/traffic/<traffic>.json``: the parameters of the mix, among
  them ``entry``, the way the load goes into the program;
* ``chipbench/entries/<entry>.py``: the one generator of each way in,
  which reads any mix that names it;
* ``chipbench/limits/<cell>.json``: the limit of each number the
  correctness check compares, with the readings it was set from;
* ``chipbench/metrics/<metric>.py``: one reader per per-layer metric.

Adding a cell, a mix or a metric adds files and entries; it edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path


def bench_dir(root: Path) -> Path:
    """Where a checkout keeps the benchmark's files."""
    return root / "chipbench"


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    root: Path

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def applies(metric: dict, cell: str) -> bool:
    ws = metric.get("workloads")
    return ws is None or cell in ws


def load_cell(name: str, root: Path) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files."""
    bench = _json(root / "BENCHMARK.json")
    d = bench_dir(root)
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r}; known: {known}")
    w = found[0]
    return Cell(
        name=name, workload=w,
        config=_json(d / "configs" / f"{w['config']}.json"),
        traffic=_json(d / "traffic" / f"{w['traffic']}.json"),
        limits=_json(d / "limits" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name)],
        root=root)


def _module(kind: str, name: str, root: Path):
    path = bench_dir(root) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: Path):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    return _module("metrics", metric, root).read


def entry(cell: Cell):
    """The ``Entry`` class of ``entries/<entry>.py`` that the cell's mix
    names."""
    return _module("entries", cell.traffic["entry"], cell.root).Entry
