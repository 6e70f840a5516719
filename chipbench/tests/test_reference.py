"""The copied float64 reference agrees with the program at a tiny size."""

import numpy as np

from conftest import TINY_GEOMETRY
from harness import phantom
from harness.acq import Acq, projection_matrices
from harness.reference import Reference, filter64, reference_values
from harness.runner import rel_err, voxel_sample
from test_phantom import program_geometry

from repro.api import ExecutionPlan, filter_projections, reconstruct


def test_filter_matches_program():
    acq = Acq.from_config(TINY_GEOMETRY)
    views = np.array([1, 20, 40, 60])
    raw = phantom.generate(acq, seed=5, views=views)
    got = np.asarray(filter_projections(raw, program_geometry(acq),
                                        angle_indices=views))
    want = filter64(raw, views, acq)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_reference_matches_program_volume():
    acq = Acq.from_config(TINY_GEOMETRY)
    views = np.arange(0, 64, 8)
    raw = phantom.generate(acq, seed=6, views=views)
    mats = projection_matrices(acq)[views]
    geom = program_geometry(acq)
    vol = reconstruct(filter_projections(raw, geom, angle_indices=views),
                      mats, geom, plan=ExecutionPlan("scalar", pbatch=4))
    vox = voxel_sample(6, acq.L, 512)
    got = np.asarray(vol, np.float64)[vox[:, 0], vox[:, 1], vox[:, 2]]
    want = reference_values(raw, views, mats, vox, acq)
    assert rel_err(got, want) <= 1e-5


def test_reference_by_view_sums_the_same():
    acq = Acq.from_config(TINY_GEOMETRY)
    raw = phantom.generate(acq, seed=5)
    mats = projection_matrices(acq)
    vox = voxel_sample(5, acq.L)
    views = np.arange(7, acq.n_proj, 6)
    ref = Reference(raw, mats, vox, acq)
    np.testing.assert_allclose(
        ref(views), reference_values(raw[views], views, mats[views], vox,
                                     acq), rtol=1e-12, atol=1e-12)
    assert sorted(ref.share) == views.tolist()
