"""A whole run on the CPU at a tiny size, sound and with faults planted.

The look for a chip is skipped; everything else of a run is driven as
on the chip: data from the seed, warm-up, the window, the check against
the float64 reference.  A sound run is correct; a run whose timed path
is broken underneath is not, for each fault a cell can have: a fold
that returns its volume unchanged, half of each batch left out, and an
answer altered where it is produced.
"""

import time

import pytest

from harness.runner import execute

import repro.core.backproject as core_bp
import repro.kernels.backproject_ops as kernel_ops


def run(root, cell):
    return execute(root, cell, seed=2 ** 33 + 5, seconds=0.5, trace=False,
                   t_start=time.perf_counter(), require_chip=False)


@pytest.mark.parametrize("cell", ["tiny.scan", "short.scan",
                                  "tiny.oneshot"])
def test_sound_run_is_correct(tiny_root, cell):
    result = run(tiny_root, cell)
    assert result["correct"], result
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"gups", "setup_s"}
    assert list(result)[-1] == "checks"
    if cell == "short.scan":
        # Scans of 8 views: at least one finishes inside the window.
        assert result["attempted"] >= 8


def unchanged(fn):
    def broken(volume, images, mats, *a, **k):
        return volume
    return broken


def half_batch(fn):
    def broken(volume, images, mats, *a, **k):
        h = max(1, images.shape[0] // 2)
        return fn(volume, images[:h], mats[:h], *a, **k)
    return broken


def altered(fn):
    def broken(volume, images, mats, *a, **k):
        return fn(volume, images, mats, *a, **k) * 1.01
    return broken


FAULTS = {"unchanged": unchanged, "half_batch": half_batch,
          "altered": altered}
PATHS = {"tiny.scan": (kernel_ops, "pallas_backproject_batch"),
         "tiny.oneshot": (core_bp, "_reconstruct_jit")}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", sorted(PATHS))
def test_fault_is_caught(tiny_root, monkeypatch, cell, fault):
    mod, name = PATHS[cell]
    monkeypatch.setattr(mod, name, FAULTS[fault](getattr(mod, name)))
    result = run(tiny_root, cell)
    assert not result["correct"], result
    assert result["failed"] >= 1
    c = result["checks"]["max_rel_err"]
    assert c["value"] > c["limit"]
