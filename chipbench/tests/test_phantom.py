"""The device generator matches the program's numpy projector."""

import numpy as np

from conftest import TINY_GEOMETRY
from harness import phantom
from harness.acq import Acq, projection_matrices

from repro.core.geometry import Geometry, projection_matrix
from repro.core.phantom import forward_project


def program_geometry(acq):
    return Geometry(n_u=acq.n_u, n_v=acq.n_v, du=acq.du, dv=acq.dv,
                    sid=acq.sid, sdd=acq.sdd, L=acq.L,
                    voxel_mm=acq.voxel_mm, n_proj=acq.n_proj,
                    sweep=acq.sweep)


def test_generator_matches_forward_project():
    acq = Acq.from_config(TINY_GEOMETRY)
    views = np.array([0, 7, 33, 63])
    got = phantom.generate(acq, seed=None, views=views)
    want = forward_project(program_geometry(acq),
                           angles=acq.angles[views])
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale


def test_seed_moves_the_phantom_and_repeats():
    acq = Acq.from_config(TINY_GEOMETRY)
    a = phantom.generate(acq, seed=2 ** 40 + 1, views=[3])
    b = phantom.generate(acq, seed=2 ** 40 + 1, views=[3])
    c = phantom.generate(acq, seed=2 ** 40 + 2, views=[3])
    assert np.array_equal(a, b)
    assert not np.allclose(a, c)


def test_matrices_match_the_program():
    acq = Acq.from_config(TINY_GEOMETRY)
    g = program_geometry(acq)
    want = np.stack([projection_matrix(g, float(t)) for t in g.angles])
    np.testing.assert_allclose(projection_matrices(acq), want,
                               rtol=1e-12, atol=1e-9)
