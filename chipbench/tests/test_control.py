"""The control, put in the program's place, makes a run not correct.

The control is the float32 reference with its projective transform at
``Precision.HIGH`` (three bfloat16 passes, written out so that the CPU
rounds as the chip does).  On RabbitCT's whole detector, where pixel
coordinates reach ~1250, that transform misplaces taps by hundredths of
a pixel.  A whole run of a one-shot cell on that detector, with the
control in the program's place, reads above the one-shot cell's limit
and reports ``correct: false``; the same run with the program is
correct; and the same control code at ``HIGHEST`` lands within the
limit.
"""

import time

import numpy as np

from harness import phantom
from harness.acq import Acq, projection_matrices
from harness.reference import Reference, control_values
from harness.runner import execute, rel_err, voxel_sample

from conftest import WIDE_GEOMETRY, limit_of

SEED = 2 ** 33 + 11


def run(root, control):
    return execute(root, "wide.oneshot", seed=SEED, seconds=0.5,
                   trace=False, t_start=time.perf_counter(),
                   require_chip=False, control=control)


def test_control_run_is_not_correct(tiny_root):
    result = run(tiny_root, control=True)
    c = result["checks"]["max_rel_err"]
    assert not result["correct"], result
    assert result["failed"] == result["attempted"] > 0
    assert c["value"] > c["limit"] == limit_of("rabbitct-256.oneshot")


def test_program_run_is_correct(tiny_root):
    result = run(tiny_root, control=False)
    assert result["correct"], result


def test_control_at_highest_passes():
    acq = Acq.from_config(WIDE_GEOMETRY)
    views = np.arange(3, acq.n_proj, 8)
    raw = phantom.generate(acq, SEED)
    mats = projection_matrices(acq)
    vox = voxel_sample(SEED, acq.L)
    ref = Reference(raw, mats, vox, acq)(views)
    err = rel_err(control_values(raw[views], views, mats[views], vox, acq,
                                 high=False), ref)
    assert err < limit_of("rabbitct-256.oneshot"), err
