"""Every cell of BENCHMARK.json resolves from its files by name."""

import json
import re

import pytest

from conftest import REPO
from harness.cell import entry, load_cell, reader

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(cell):
    c = load_cell(cell, REPO)
    assert c.config["name"] == c.workload["config"]
    assert callable(entry(c))
    assert set(c.traffic["layers"]) == {"filter", "backprojection"}
    assert 0 < c.limits["max_rel_err"]["limit"] < 1
    assert {m["name"] for m in c.end_to_end} == {"gups", "peak_hbm_gb",
                                                 "setup_s"}
    for m in c.per_layer:
        assert callable(reader(m["name"], REPO))


def test_names_and_files():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for cfg in BENCH["configs"]:
        assert (REPO / cfg["file"]).is_file()
        data = json.loads((REPO / cfg["file"]).read_text())
        assert data["reduced"] == cfg["reduced"]
    moves = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in moves
        assert set(m.get("workloads", cells)) <= cells
