"""Pallas back projection kernel: shape/dtype sweep vs the pure-jnp oracle.

Required kernel validation: sweep shapes and dtypes, assert_allclose
against backproject_ref (interpret=True on CPU).
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Geometry, filter_projections
from repro.core.backproject import GeomStatic
from repro.core.geometry import projection_matrix
from repro.core.phantom import make_dataset
from repro.kernels.backproject_ops import (pallas_backproject_one,
                                           validate_strip_config)
from repro.kernels.backproject_ref import backproject_volume_ref

from _col_blocks import CASES, assert_case_holds, assert_matches_oracle


def _problem(L, n_proj=2):
    geom = Geometry().scaled(L, n_proj=n_proj)
    projs, mats, _ = make_dataset(geom)
    filt = np.asarray(filter_projections(projs, geom))
    return geom, filt, mats


@pytest.mark.parametrize("L,ty,chunk,band,width", [
    (16, 4, 16, 16, 128),
    (16, 8, 8, 16, 128),
    (32, 8, 32, 16, 128),
    (32, 4, 16, 24, 256),
])
def test_kernel_shape_sweep(L, ty, chunk, band, width):
    geom, filt, mats = _problem(L)
    gs = GeomStatic.of(geom)
    vol0 = jnp.zeros((L,) * 3, jnp.float32)
    out_k = pallas_backproject_one(vol0, filt[0], mats[0], geom, ty=ty,
                                   chunk=chunk, band=band, width=width,
                                   validate=True)
    out_r = backproject_volume_ref(vol0, filt[0], mats[0], gs)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("variant", [{"double_buffer": True},
                                     {"double_buffer": True,
                                      "db_depth": 4}])
def test_kernel_variants_match_oracle(variant):
    """Double-buffer (classical and deep rotation) vs the oracle."""
    geom, filt, mats = _problem(32, n_proj=4)
    gs = GeomStatic.of(geom)
    vol0 = jnp.zeros((32,) * 3, jnp.float32)
    k = 2                      # mid-sweep (projection 0 is Parker~0)
    out = pallas_backproject_one(vol0, filt[k], mats[k], geom, ty=8,
                                 chunk=32, band=16, width=128, **variant)
    ref = backproject_volume_ref(vol0, filt[k], mats[k], gs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("variant", [{}, {"double_buffer": True},
                                     {"double_buffer": True,
                                      "db_depth": 3}])
def test_kernel_variants_border_rays_vs_scalar_oracle(variant):
    """Interpret-mode parity of both variants on the border-ray
    geometry of tests/test_strategy_sweep.py: taps straddling the
    detector edge must blend with implicit zeros in the kernel too."""
    from repro.core.backproject import backproject_one

    geom = Geometry().scaled(16, n_proj=8, n_u=24, n_v=18)
    rng = np.random.default_rng(3)
    image = jnp.asarray(rng.standard_normal((geom.n_v, geom.n_u)),
                        jnp.float32)
    A = jnp.asarray(projection_matrix(geom, 1.1), jnp.float32)
    vol0 = jnp.zeros((geom.L,) * 3, jnp.float32)
    ref = np.asarray(backproject_one(vol0, image, A, geom,
                                     strategy="scalar"))
    out = np.asarray(pallas_backproject_one(
        vol0, image, A, geom, ty=8, chunk=16, band=16, width=128,
        validate=True, **variant))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    # Border geometry must exercise both zero and nonzero voxels.
    assert (ref == 0.0).any() and (ref != 0.0).any()


@pytest.mark.parametrize("img_dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtype_sweep(img_dtype):
    geom, filt, mats = _problem(16)
    gs = GeomStatic.of(geom)
    vol0 = jnp.zeros((16,) * 3, jnp.float32)
    img = jnp.asarray(filt[0], img_dtype)
    out_k = pallas_backproject_one(vol0, img, mats[0], geom, ty=4,
                                   chunk=16, band=16, width=128)
    out_r = backproject_volume_ref(vol0, img.astype(jnp.float32),
                                   mats[0], gs)
    tol = 1e-5 if img_dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out_k), np.asarray(out_r),
        rtol=tol, atol=tol * float(jnp.max(jnp.abs(out_r))))


def test_kernel_int8_wire_differs_but_bounded():
    """int8 per-row affine codes on the kernel wire (plain / db):
    observably different from f32 (the quantisation is real),
    within ~2% of the volume scale (the post-gather f32 dequant +
    f32-accumulate contract), and **bitwise identical across variants**
    — every variant dequantises the same codes with the same per-row
    scales, so DMA shape must not change the arithmetic."""
    geom, filt, mats = _problem(32, n_proj=4)
    vol0 = jnp.zeros((32,) * 3, jnp.float32)
    k = 2                      # mid-sweep (projection 0 is Parker~0)
    base = dict(ty=8, chunk=32, band=16, width=128)
    f32 = np.asarray(pallas_backproject_one(vol0, filt[k], mats[k],
                                            geom, **base))
    scale = float(np.abs(f32).max())
    outs = []
    for variant in ({}, {"double_buffer": True}):
        i8 = np.asarray(pallas_backproject_one(
            vol0, filt[k], mats[k], geom, strip_dtype="int8", **base,
            **variant))
        assert not np.array_equal(i8, f32), \
            f"int8 wire was a no-op under {variant}"
        assert float(np.abs(i8 - f32).max()) < 0.02 * scale
        outs.append(i8)
    for other in outs[1:]:
        np.testing.assert_array_equal(outs[0], other)


@pytest.mark.parametrize("wire", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("variant", [{}, {"double_buffer": True,
                                          "db_depth": 3}],
                         ids=["plain", "db3"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_column_block_seams_vs_oracle(case, variant, wire):
    """The kernel contracts only the window's 128-column blocks that hold
    the tile's taps: a tap pair across a block boundary (``seam``) and a
    tile spanning every block (``wide``) still match the oracle."""
    c = CASES[case]
    images, mats = c.images(), c.mats()
    out = jnp.zeros((c.geom.L,) * 3, jnp.float32)
    for k in range(len(mats)):
        assert_case_holds(case, k)
        out = pallas_backproject_one(out, images[k], mats[k], c.geom,
                                     strip_dtype=wire, validate=True,
                                     **c.tiles, **variant)
    assert_matches_oracle(out, c, images, mats, wire)


def test_kernel_accumulates_over_projections():
    geom, filt, mats = _problem(16, n_proj=3)
    gs = GeomStatic.of(geom)
    vol_k = jnp.zeros((16,) * 3, jnp.float32)
    vol_r = jnp.zeros((16,) * 3, jnp.float32)
    for k in range(3):
        vol_k = pallas_backproject_one(vol_k, filt[k], mats[k], geom,
                                       ty=4, chunk=16, band=16, width=128)
        vol_r = backproject_volume_ref(vol_r, filt[k], mats[k], gs)
    np.testing.assert_allclose(np.asarray(vol_k), np.asarray(vol_r),
                               rtol=1e-4, atol=1e-4)


def test_validate_rejects_undersized_strips():
    geom, filt, mats = _problem(32)
    with pytest.raises(ValueError, match="does not cover"):
        validate_strip_config(geom, np.asarray(mats[0], np.float64),
                              ty=32, chunk=32, band=8, width=128)


def test_gather_kernel_sweep():
    """One-hot gather kernel vs oracle across shapes/dtypes."""
    import jax
    from repro.kernels.gather_kernel_ops import pallas_onehot_gather
    from repro.kernels.gather_ref import gather_ref
    key = jax.random.PRNGKey(1)
    for V, D, N, dt in [(300, 32, 17, jnp.float32),
                        (1024, 128, 512, jnp.float32),
                        (513, 64, 100, jnp.bfloat16)]:
        table = jax.random.normal(key, (V, D), jnp.float32).astype(dt)
        ids = jax.random.randint(key, (N,), -2, V + 2)
        out = pallas_onehot_gather(table, ids)
        ref = gather_ref(table, ids)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=1e-5, atol=1e-5)
