"""The trace reduction on a small recorded trace with known numbers."""

import pytest

from harness.tracing import module_at, reduce, union

MS = 1e6     # nanoseconds

# Device operations (module, op, start, duration) on one TPU and the
# benchmark's host annotations (name, start, duration), in ns.
EVENTS = [
    ("jit__filter_chunk", "fusion.1", 0 * MS, 2 * MS),
    ("jit__run_batched", "backproject_strip_batch_p4", 2 * MS, 10 * MS),
    ("jit__run_batched", "backproject_strip_batch_p4", 5 * MS, 1 * MS),
    ("jit__filter_chunk", "fusion.1", 15 * MS, 2 * MS),
    ("jit_take", "gather", 20 * MS, 1 * MS),
]
HOST = [("submit", 11 * MS, 5 * MS), ("wait_volume", 0, 30 * MS)]
LAYERS = {"filter": "jit__filter_chunk/",
          "backprojection": "backproject_strip_batch"}


def test_union_merges_overlaps():
    assert union([(5, 6), (0, 2), (2, 12), (15, 17)]) == \
        [(0, 12), (15, 17)]


def test_reduce_known_numbers():
    r = reduce(EVENTS, HOST, LAYERS)
    # Busy: [0, 12] + [15, 17] + [20, 21] ms.
    assert r["busy_s"] == pytest.approx(15e-3)
    assert r["layer_s"]["filter"] == pytest.approx(4e-3)
    # The 5-6 ms operation lies inside the 2-12 ms one, as a loop's body
    # lies inside its while: the layer counts that time once.
    assert r["layer_s"]["backprojection"] == pytest.approx(10e-3)
    assert r["device_ops"][0] == ["jit__run_batched/"
                                  "backproject_strip_batch_p4",
                                  pytest.approx(11e-3)]
    # Gap 12-15 ms has its middle in the submit span; 17-20 ms only in
    # wait_volume.
    assert dict(r["idle_gaps"]) == {"submit": pytest.approx(3e-3),
                                    "wait_volume": pytest.approx(3e-3)}


def test_reduce_nothing():
    assert reduce([], HOST, LAYERS) == {}


def test_module_at_names_the_covering_program():
    modules = [(0.0, 10.0, "jit__filter_chunk"),
               (12.0, 5.0, "jit__run_batched")]
    assert module_at(3.0, modules) == "jit__filter_chunk"
    assert module_at(12.0, modules) == "jit__run_batched"
    assert module_at(11.0, modules) == ""
