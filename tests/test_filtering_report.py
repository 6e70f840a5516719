"""FDK filtering properties: ramp, cosine and Parker weights, subsets."""

import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Geometry
from repro.core.filtering import (cosine_weights, filter_projections,
                                  parker_weights, ramlak_kernel)


def test_ramp_kills_dc():
    """The ramp filter has zero DC response: a constant projection
    filters to ~0 away from the linear-convolution boundary."""
    geom = Geometry().scaled(16)
    const = np.ones((1, geom.n_v, geom.n_u), np.float32)
    out = np.asarray(filter_projections(const, geom, short_scan=False))
    noise = np.random.default_rng(0).normal(
        size=(1, geom.n_v, geom.n_u)).astype(np.float32)
    outn = np.asarray(filter_projections(noise, geom, short_scan=False))
    interior = np.abs(out[0, :, 4:-4]).max()
    assert interior < 0.05 * np.abs(outn).max()


def test_ramlak_kernel_structure():
    h = ramlak_kernel(16, du=1.0)
    k = np.arange(-8, 8)
    assert h[k == 0] == 0.25
    assert (h[(np.abs(k) % 2 == 0) & (k != 0)] == 0).all()
    assert (h[np.abs(k) % 2 == 1] < 0).all()


def test_cosine_weights_bounded_and_centered():
    geom = Geometry().scaled(16)
    w = cosine_weights(geom)
    assert w.max() <= 1.0 + 1e-6
    iv, iu = np.unravel_index(np.argmax(w), w.shape)
    assert abs(iu - geom.cu) <= 1 and abs(iv - geom.cv) <= 1


def test_parker_weights_full_scan_constant():
    geom = dataclasses.replace(Geometry().scaled(16), sweep=2 * math.pi)
    pw = parker_weights(geom)
    assert np.allclose(pw, 1.0)


def test_parker_weights_short_scan_shape():
    geom = Geometry().scaled(16)         # 200-degree C-arm
    pw = parker_weights(geom)
    assert pw.shape == (geom.n_proj, geom.n_u)
    assert pw.min() >= 0.0
    # Ramp-up at the start of the sweep: first projection nearly zero.
    assert pw[0].max() < 0.2
    # Plateau in the middle of the sweep near the constant-2 level
    # (the factor-2 compensates the retained FDK 1/2 — filtering.py).
    assert abs(pw[geom.n_proj // 2].mean() - 2.0) < 0.2


def test_nonprefix_subset_matches_full_stack_rows():
    """The filtering-contract fix: a shuffled, non-prefix subset with
    explicit angle_indices filters identically to the matching rows of
    the full-stack result.  (The old code silently applied the *first k*
    angles' Parker weights to any k-subset — wrong for every non-prefix
    subset a streamed or proj-sharded caller sends.)"""
    geom = Geometry().scaled(16, n_proj=8)
    rng = np.random.default_rng(3)
    projs = rng.normal(size=(8, geom.n_v, geom.n_u)).astype(np.float32)
    full = np.asarray(filter_projections(projs, geom))
    idx = np.array([6, 2, 5])                    # shuffled, non-prefix
    sub = np.asarray(filter_projections(projs[idx], geom,
                                        angle_indices=idx))
    np.testing.assert_array_equal(sub, full[idx])
    # And the old prefix guess is demonstrably NOT those rows (Parker
    # ramp-up weights at angles 0..2 differ from angles 6/2/5).
    prefix = np.asarray(filter_projections(projs[idx], geom,
                                           angle_indices=np.arange(3)))
    assert np.abs(prefix - sub).max() > 1e-3


def test_mismatched_subset_without_indices_raises():
    """A short-scan subset must say which angles it holds — guessing is
    the silent mis-weighting bug."""
    geom = Geometry().scaled(16, n_proj=8)
    projs = np.ones((3, geom.n_v, geom.n_u), np.float32)
    with pytest.raises(ValueError, match="angle_indices"):
        filter_projections(projs, geom)
    # Explicitly opting out of Parker weighting still works.
    out = filter_projections(projs, geom, short_scan=False)
    assert out.shape == projs.shape
    # And a full-length stack keeps the no-indices convenience path.
    full = np.ones((8, geom.n_v, geom.n_u), np.float32)
    assert filter_projections(full, geom).shape == full.shape


def test_single_projection_scalar_angle_index():
    geom = Geometry().scaled(16, n_proj=8)
    projs = np.random.default_rng(0).normal(
        size=(8, geom.n_v, geom.n_u)).astype(np.float32)
    full = np.asarray(filter_projections(projs, geom))
    one = np.asarray(filter_projections(projs[5], geom, angle_indices=5))
    assert one.shape == (geom.n_v, geom.n_u)
    np.testing.assert_array_equal(one, full[5])
    with pytest.raises(ValueError, match=r"\[0, 8\)"):
        filter_projections(projs[5], geom, angle_indices=9)
    with pytest.raises(ValueError, match="shape"):
        filter_projections(projs[:2], geom, angle_indices=np.arange(3))
