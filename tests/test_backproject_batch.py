"""Projection-batched (volume-resident) back projection vs the
sequential scalar oracle.

The loop-nest inversion (DESIGN.md §7) must not change semantics: for
every strategy, every ``pbatch`` — including ``pbatch ∤ n_proj``
remainders and border-ray geometries — the batched reconstruction
matches the sequential scalar-oracle reconstruction to fp32 rounding
(≤1e-5).  Accumulation order *within* a batch differs by construction
(contributions sum before the plane update), which is exactly what the
tolerance is for.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Geometry, filter_projections, reconstruct
from repro.core.backproject import (DEFAULT_PBATCH, STRATEGIES, GeomStatic,
                                    backproject_batch, backproject_one)
from repro.core.geometry import projection_matrix, projection_matrices
from repro.core.phantom import make_dataset
from repro.kernels.backproject_ops import pallas_backproject_batch
from repro.kernels.backproject_ref import backproject_volume_ref

from _col_blocks import CASES, assert_case_holds, assert_matches_oracle

GEOM = Geometry().scaled(16, n_proj=5)           # 5: prime vs pbatch 2, 3
GS = GeomStatic.of(GEOM)


@pytest.fixture(scope="module")
def ct_case():
    projs, mats, _ = make_dataset(GEOM)
    filt = np.asarray(filter_projections(projs, GEOM))
    return filt, np.asarray(mats, np.float32)


@pytest.fixture(scope="module")
def scalar_sequential(ct_case):
    filt, mats = ct_case
    return np.asarray(reconstruct(filt, mats, GEOM, strategy="scalar",
                                  pbatch=1))


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("pbatch", [2, 3])       # both 5 % pbatch != 0
def test_batched_matches_sequential_oracle(ct_case, scalar_sequential,
                                           strategy, pbatch):
    filt, mats = ct_case
    out = np.asarray(reconstruct(filt, mats, GEOM, strategy=strategy,
                                 pbatch=pbatch))
    np.testing.assert_allclose(out, scalar_sequential, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("pbatch", [1, 4, 5, 7])
def test_batch_depth_sweep_strip2(ct_case, scalar_sequential, pbatch):
    """Depth sweep for the default strategy: exact divisor (5), clamp
    past n_proj (7), divisor-with-remainder (4), sequential (1)."""
    filt, mats = ct_case
    out = np.asarray(reconstruct(filt, mats, GEOM, strategy="strip2",
                                 pbatch=pbatch))
    np.testing.assert_allclose(out, scalar_sequential, rtol=1e-5,
                               atol=1e-5)


def test_batched_border_rays():
    """Geometry whose rays straddle the detector edge: the batched path
    must blend edge taps with implicit zeros exactly like the
    sequential scalar oracle (n_proj=5, pbatch=2 remainder)."""
    geom = Geometry().scaled(16, n_proj=5, n_u=24, n_v=18)
    rng = np.random.default_rng(3)
    imgs = rng.standard_normal(
        (geom.n_proj, geom.n_v, geom.n_u)).astype(np.float32)
    mats = np.asarray(projection_matrices(geom), np.float32)
    ref = np.asarray(reconstruct(imgs, mats, geom, strategy="scalar",
                                 pbatch=1))
    assert (ref == 0.0).any() and (ref != 0.0).any()
    for strategy in ("scalar", "gather", "strip2"):
        out = np.asarray(reconstruct(imgs, mats, geom, strategy=strategy,
                                     pbatch=2))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_backproject_batch_accumulates_onto_volume(ct_case):
    """backproject_batch adds onto a non-zero volume like repeated
    backproject_one calls."""
    filt, mats = ct_case
    rng = np.random.default_rng(11)
    vol0 = jnp.asarray(rng.standard_normal((16, 16, 16)), jnp.float32)
    seq = vol0
    for k in range(3):
        seq = backproject_one(seq, filt[k], mats[k], GEOM,
                              strategy="gather")
    out = backproject_batch(vol0, filt[:3], mats[:3], GEOM,
                            strategy="gather", pbatch=3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(seq),
                               rtol=1e-5, atol=1e-5)


def test_sharded_batched_matches_single_device(ct_case):
    """Explicit pbatch threads through the shard_map slab path bit-for-
    bit on a 1x1 mesh (same batched helper, same depth)."""
    from repro.core.pipeline import sharded_reconstruct
    from repro.launch.mesh import make_local_mesh

    filt, mats = ct_case
    mesh = make_local_mesh(data=1, model=1)
    out = np.asarray(sharded_reconstruct(filt, mats, GEOM, mesh,
                                         strategy="gather", pbatch=3))
    single = np.asarray(reconstruct(filt, mats, GEOM, strategy="gather",
                                    pbatch=3))
    np.testing.assert_array_equal(out, single)


def test_tuned_pbatch_resolves_through_auto(ct_case, tmp_path, monkeypatch):
    """A tuned decision carrying ``pbatch`` redirects auto bitwise."""
    from repro.tune import (TunedConfig, clear_memory_cache,
                            device_identity, store_tuned)

    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path / "tune"))
    clear_memory_cache()
    filt, mats = ct_case
    backend, device_kind = device_identity()
    cfg = TunedConfig(strategy="gather", opts={"pbatch": 3},
                      backend=backend, device_kind=device_kind,
                      us_per_call=1.0)
    store_tuned(GS, cfg)
    assert cfg.pbatch == 3
    a = np.asarray(reconstruct(filt, mats, GEOM, strategy="auto"))
    b = np.asarray(reconstruct(filt, mats, GEOM, strategy="gather",
                               pbatch=3))
    clear_memory_cache()
    np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------------
# Pallas batch kernel (interpret mode on CPU)
# ----------------------------------------------------------------------

def _pallas_ref(filt, mats, n):
    vol = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    for k in range(n):
        vol = backproject_volume_ref(vol, filt[k], mats[k], GS)
    return np.asarray(vol)


@pytest.mark.parametrize("pbatch", [1, 2, 3, 5])
def test_pallas_batch_matches_ref(ct_case, pbatch):
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    out = pallas_backproject_batch(vol0, filt, mats, GEOM, ty=4, chunk=16,
                                   band=16, width=128, pbatch=pbatch)
    np.testing.assert_allclose(np.asarray(out), _pallas_ref(filt, mats, 5),
                               rtol=1e-5, atol=1e-5)


def test_pallas_batch_border_rays():
    """Kernel-path zero-outside semantics across an in-kernel projection
    loop with a pbatch remainder."""
    geom = Geometry().scaled(16, n_proj=8, n_u=24, n_v=18)
    rng = np.random.default_rng(3)
    imgs = rng.standard_normal((3, geom.n_v, geom.n_u)).astype(np.float32)
    mats = np.stack([projection_matrix(geom, th)
                     for th in (0.7, 1.1, 2.9)]).astype(np.float32)
    vol0 = jnp.zeros((geom.L,) * 3, jnp.float32)
    ref = vol0
    for k in range(3):
        ref = backproject_one(ref, imgs[k], mats[k], geom,
                              strategy="scalar")
    out = pallas_backproject_batch(vol0, imgs, mats, geom, ty=8, chunk=16,
                                   band=16, width=128, pbatch=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(ref) == 0.0).any() and (np.asarray(ref) != 0.0).any()


@pytest.mark.parametrize("variant", [
    dict(double_buffer=True, db_depth=2),
    dict(double_buffer=True, db_depth=3),
    dict(double_buffer=True, db_depth=4),
], ids=["db2", "db3", "db4"])
@pytest.mark.parametrize("pbatch", [2, 5])   # 5 % 2 != 0: remainder batch
def test_pallas_batch_variants_match_ref(ct_case, variant, pbatch):
    """Interpret-mode parity of the db batch variant (depth 2 and
    deeper) against the per-projection oracle, full-divisor and
    remainder depths."""
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    out = pallas_backproject_batch(vol0, filt, mats, GEOM, ty=4, chunk=16,
                                   band=16, width=128, pbatch=pbatch,
                                   **variant)
    np.testing.assert_allclose(np.asarray(out), _pallas_ref(filt, mats, 5),
                               rtol=1e-5, atol=1e-5)


def test_pallas_batch_db_bitwise_vs_plain(ct_case):
    """The DMA pipeline moves *when* strips are fetched, never what is
    computed: every depth's result is bit-for-bit the plain batch
    kernel's (same contributions, same accumulation order)."""
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    plain = np.asarray(pallas_backproject_batch(
        vol0, filt, mats, GEOM, ty=4, chunk=16, band=16, width=128,
        pbatch=2))
    for depth in (2, 3, 4):
        db = np.asarray(pallas_backproject_batch(
            vol0, filt, mats, GEOM, ty=4, chunk=16, band=16, width=128,
            pbatch=2, double_buffer=True, db_depth=depth))
        np.testing.assert_array_equal(db, plain)


@pytest.mark.parametrize("variant", [
    dict(double_buffer=True, db_depth=3),
    dict(shared_window=True),
], ids=["db3", "shared"])
def test_pallas_batch_variants_border_rays(variant):
    """Zero-outside semantics of the db and shared variants across an in-kernel
    projection loop with a pbatch remainder on edge-straddling rays."""
    geom = Geometry().scaled(16, n_proj=8, n_u=24, n_v=18)
    rng = np.random.default_rng(3)
    imgs = rng.standard_normal((3, geom.n_v, geom.n_u)).astype(np.float32)
    mats = np.stack([projection_matrix(geom, th)
                     for th in (0.7, 1.1, 2.9)]).astype(np.float32)
    vol0 = jnp.zeros((geom.L,) * 3, jnp.float32)
    ref = vol0
    for k in range(3):
        ref = backproject_one(ref, imgs[k], mats[k], geom,
                              strategy="scalar")
    out = pallas_backproject_batch(vol0, imgs, mats, geom, ty=8, chunk=16,
                                   band=16, width=128, pbatch=2, **variant)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(ref) == 0.0).any() and (np.asarray(ref) != 0.0).any()


@pytest.mark.parametrize("wire", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("variant", [
    dict(),
    dict(double_buffer=True, db_depth=3),
    dict(shared_window=True),
], ids=["batch", "batch_db3", "batch_shared"])
@pytest.mark.parametrize("case", list(CASES))
def test_pallas_batch_column_block_seams_vs_oracle(case, variant, wire):
    """Column-block skipping in every batch variant: a tap pair across a
    128-column block boundary (``seam``) and a tile spanning every block
    of the window (``wide``), two projections folded in one call."""
    for k in range(2):
        assert_case_holds(case, k)
    c = CASES[case]
    images, mats = c.images(), c.mats()
    vol0 = jnp.zeros((c.geom.L,) * 3, jnp.float32)
    out = pallas_backproject_batch(vol0, images, mats, c.geom, pbatch=2,
                                   strip_dtype=wire, **c.tiles, **variant)
    assert_matches_oracle(out, c, images, mats, wire)


def test_pallas_batch_variant_flags_are_loud(ct_case):
    """Impossible variant combinations raise instead of silently
    preferring one: both variants at once, and a sub-2 pipeline depth."""
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    with pytest.raises(ValueError, match="exclusive"):
        pallas_backproject_batch(vol0, filt, mats, GEOM, ty=4, chunk=16,
                                 band=16, width=128, pbatch=2,
                                 shared_window=True, double_buffer=True)
    with pytest.raises(ValueError, match="db_depth"):
        pallas_backproject_batch(vol0, filt, mats, GEOM, ty=4, chunk=16,
                                 band=16, width=128, pbatch=2,
                                 double_buffer=True, db_depth=1)


def test_pallas_batch_validates_stack(ct_case):
    """Undersized strips are rejected for *every* projection of the
    stack before any device work."""
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    with pytest.raises(ValueError, match="does not cover"):
        pallas_backproject_batch(vol0, filt, mats, GEOM, ty=16, chunk=16,
                                 band=8, width=128, pbatch=2)


def test_pallas_batch_auto_uses_tuned_pbatch(ct_case, tmp_path,
                                             monkeypatch):
    from repro.tune import (TunedConfig, clear_memory_cache,
                            device_identity, store_tuned)

    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path / "tune"))
    clear_memory_cache()
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    backend, device_kind = device_identity()
    cfg = TunedConfig(strategy="strip2", opts={}, backend=backend,
                      device_kind=device_kind, us_per_call=1.0,
                      pallas={"ty": 4, "chunk": 16, "band": 16,
                              "width": 128, "pbatch": 2})
    store_tuned(GS, cfg)
    out_auto = pallas_backproject_batch(vol0, filt, mats, GEOM,
                                        strategy="auto")
    out_fix = pallas_backproject_batch(vol0, filt, mats, GEOM, ty=4,
                                       chunk=16, band=16, width=128,
                                       pbatch=2)
    clear_memory_cache()
    np.testing.assert_array_equal(np.asarray(out_auto),
                                  np.asarray(out_fix))


def _write_cache_file(tmp_path, pallas, version):
    """A raw on-disk tune-cache JSON (the path a fresh process resolves
    through), bypassing store_tuned so the version field is exactly what
    the test says it is."""
    import json
    import os
    from pathlib import Path

    from repro.tune import cache_key, device_identity

    backend, device_kind = device_identity()
    d = Path(os.environ["REPRO_TUNE_DIR"])
    d.mkdir(parents=True, exist_ok=True)
    doc = {"strategy": "strip2", "opts": {}, "backend": backend,
           "device_kind": device_kind, "us_per_call": 1.0,
           "pallas": pallas, "pallas_us": 1.0, "timings": [],
           "version": version}
    (d / f"{cache_key(GS, backend, device_kind)}.json").write_text(
        json.dumps(doc))


@pytest.mark.parametrize("variant", [
    {"double_buffer": True, "db_depth": 3},
    {"shared_window": True},
], ids=["db", "shared"])
def test_tuned_batch_flags_resolve_from_v3_cache_file(ct_case, tmp_path,
                                                      monkeypatch,
                                                      variant):
    """A cache file carrying ``double_buffer``/``shared_window`` redirects
    the batch path to the matching variant — bit-for-bit against both
    the explicit variant call and the plain batch kernel — and the old
    shed-the-flag warning never fires (warnings are errors here)."""
    import warnings

    from repro.tune import TUNE_SCHEMA_VERSION, clear_memory_cache

    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path / "tune"))
    clear_memory_cache()
    tiles = {"ty": 4, "chunk": 16, "band": 16, "width": 128}
    _write_cache_file(tmp_path, {**tiles, "pbatch": 2, **variant},
                      TUNE_SCHEMA_VERSION)
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out_auto = pallas_backproject_batch(vol0, filt, mats, GEOM,
                                            strategy="auto")
    out_fix = pallas_backproject_batch(vol0, filt, mats, GEOM, pbatch=2,
                                       **tiles, **variant)
    plain = pallas_backproject_batch(vol0, filt, mats, GEOM, pbatch=2,
                                     **tiles)
    clear_memory_cache()
    np.testing.assert_array_equal(np.asarray(out_auto),
                                  np.asarray(out_fix))
    # Neither variant changes the arithmetic, only its schedule — the
    # pipeline moves fetches, the shared slab only moves the window
    # origin, so the one-hot terms it adds are identically zero.
    np.testing.assert_array_equal(np.asarray(out_auto), np.asarray(plain))


def test_v2_cache_file_is_ignored_not_misread(ct_case, tmp_path,
                                              monkeypatch):
    """A v2-era cache file (its variant flags were timed against a batch
    path that shed them) must read as *untuned* — auto falls back to the
    caller's parameters, bit-for-bit, with no warning."""
    import warnings

    from repro.tune import clear_memory_cache, load_tuned

    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path / "tune"))
    clear_memory_cache()
    _write_cache_file(tmp_path, {"ty": 4, "chunk": 16, "band": 16,
                                 "width": 128, "pbatch": 2,
                                 "double_buffer": True}, version=2)
    assert load_tuned(GS) is None
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out_auto = pallas_backproject_batch(vol0, filt, mats, GEOM,
                                            ty=4, chunk=16, band=16,
                                            width=128, strategy="auto")
    out_fix = pallas_backproject_batch(vol0, filt, mats, GEOM, ty=4,
                                       chunk=16, band=16, width=128)
    clear_memory_cache()
    np.testing.assert_array_equal(np.asarray(out_auto),
                                  np.asarray(out_fix))


def test_fold_projections_chunked_shuffled_and_slab(ct_case,
                                                    scalar_sequential):
    """The incremental-fold entry point: shuffled chunk folds cover the
    set once and match the one-shot reconstruction; a traced z0 folds
    into the right slab; undersized strip windows raise (same planner
    guard as reconstruct)."""
    from repro.core.backproject import fold_projections

    filt, mats = ct_case
    order = np.random.default_rng(11).permutation(GEOM.n_proj)
    vol = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    for chunk in (order[:2], order[2:5]):
        vol = fold_projections(vol, filt[chunk], mats[chunk], GEOM,
                               strategy="scalar", pbatch=2)
    np.testing.assert_allclose(np.asarray(vol), scalar_sequential,
                               atol=1e-5, rtol=1e-5)

    full = np.asarray(reconstruct(filt, mats, GEOM))
    half = GEOM.L // 2
    slab = fold_projections(jnp.zeros((half,) + (GEOM.L,) * 2,
                                      jnp.float32),
                            filt, mats, GEOM, z0=half)
    np.testing.assert_array_equal(np.asarray(slab), full[half:])

    with pytest.raises(ValueError, match="window"):
        fold_projections(vol, filt, mats, GEOM, strategy="strip2",
                         gband=2, gwidth=4)


def test_default_pbatch_is_sane():
    assert DEFAULT_PBATCH >= 1


# ----------------------------------------------------------------------
# Shared superset window (one group DMA per volume tile, DESIGN.md §10)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("pbatch", [1, 2, 3, 5])
def test_pallas_batch_shared_matches_ref(ct_case, pbatch):
    """Group-superset windows move *where* pixels are fetched from, not
    which taps contribute: parity with the per-projection oracle at a
    divisor depth, remainder depths, and the degenerate pbatch=1."""
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    out = pallas_backproject_batch(vol0, filt, mats, GEOM, ty=4, chunk=16,
                                   band=16, width=128, pbatch=pbatch,
                                   shared_window=True)
    np.testing.assert_allclose(np.asarray(out), _pallas_ref(filt, mats, 5),
                               rtol=1e-5, atol=1e-5)


def test_pallas_batch_shared_bitwise_vs_plain(ct_case):
    """At equal pbatch the shared kernel accumulates the same
    contributions in the same order as the plain batch kernel — the
    superset window only re-bases the in-window offsets."""
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    plain = np.asarray(pallas_backproject_batch(
        vol0, filt, mats, GEOM, ty=4, chunk=16, band=16, width=128,
        pbatch=2))
    shared = np.asarray(pallas_backproject_batch(
        vol0, filt, mats, GEOM, ty=4, chunk=16, band=16, width=128,
        pbatch=2, shared_window=True))
    np.testing.assert_array_equal(shared, plain)


def test_pallas_batch_shared_border_rays():
    """Zero-outside semantics through the shared slab: edge-straddling
    rays with a pbatch remainder."""
    geom = Geometry().scaled(16, n_proj=8, n_u=24, n_v=18)
    rng = np.random.default_rng(3)
    imgs = rng.standard_normal((3, geom.n_v, geom.n_u)).astype(np.float32)
    mats = np.stack([projection_matrix(geom, th)
                     for th in (0.7, 1.1, 2.9)]).astype(np.float32)
    # The host planner sizes the superset from the *submitted* matrices,
    # so hand it the same geometry object reconstruct would see.
    vol0 = jnp.zeros((geom.L,) * 3, jnp.float32)
    ref = vol0
    for k in range(3):
        ref = backproject_one(ref, imgs[k], mats[k], geom,
                              strategy="scalar")
    out = pallas_backproject_batch(vol0, imgs, mats, geom, ty=8, chunk=16,
                                   band=16, width=128, pbatch=2,
                                   shared_window=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert (np.asarray(ref) == 0.0).any() and (np.asarray(ref) != 0.0).any()


@pytest.mark.parametrize("dtype,rel", [("bfloat16", 0.005),
                                       ("int8", 0.02)])
def test_pallas_batch_narrow_wire_differs_but_bounded(ct_case, dtype, rel):
    """Narrow wires on the batch kernel (plain and shared): observably
    different from f32 (the conversion is real) yet within a small
    fraction of the volume scale — the f32-accumulate contract,
    adversarial form.  bf16 rounds the tap values (~0.5%); int8 moves
    per-row affine codes dequantised after the gather (~2%)."""
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    f32 = np.asarray(pallas_backproject_batch(
        vol0, filt, mats, GEOM, ty=4, chunk=16, band=16, width=128,
        pbatch=2))
    scale = float(np.abs(f32).max())
    for flags in (dict(), dict(shared_window=True)):
        vq = np.asarray(pallas_backproject_batch(
            vol0, filt, mats, GEOM, ty=4, chunk=16, band=16, width=128,
            pbatch=2, strip_dtype=dtype, **flags))
        assert not np.array_equal(vq, f32)
        assert float(np.abs(vq - f32).max()) < rel * scale


def test_pallas_batch_int8_variants_agree_bitwise(ct_case):
    """Every batch variant (plain / shared / db) dequantises the same
    codes with the same per-row scales — the DMA shape must not change
    the int8 arithmetic, so all three agree bit-for-bit."""
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    outs = []
    for flags in (dict(), dict(shared_window=True),
                  dict(double_buffer=True)):
        outs.append(np.asarray(pallas_backproject_batch(
            vol0, filt, mats, GEOM, ty=4, chunk=16, band=16, width=128,
            pbatch=2, strip_dtype="int8", **flags)))
    for other in outs[1:]:
        np.testing.assert_array_equal(outs[0], other)


def test_pallas_batch_shared_is_exclusive(ct_case):
    """The shared slab owns the window layout — combining it with the
    DMA pipeline must raise, not silently pick one."""
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    for bad in (dict(double_buffer=True), dict(double_buffer=True,
                                              db_depth=4)):
        with pytest.raises(ValueError, match="exclusive"):
            pallas_backproject_batch(vol0, filt, mats, GEOM, ty=4,
                                     chunk=16, band=16, width=128,
                                     pbatch=2, shared_window=True, **bad)


def test_pallas_batch_shared_undersized_dims_raise(ct_case):
    """Explicit shared dims below the planner's group-superset
    requirement must raise before any device work — an undersized slab
    would drop taps silently."""
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    with pytest.raises(ValueError, match="shared window"):
        pallas_backproject_batch(vol0, filt, mats, GEOM, ty=4, chunk=16,
                                 band=16, width=128, pbatch=2,
                                 shared_window=True, shared_band=8,
                                 shared_width=128)


def test_pallas_batch_shared_needs_full_geometry(ct_case):
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    with pytest.raises(ValueError, match="Geometry"):
        pallas_backproject_batch(vol0, filt, mats, GS, ty=4, chunk=16,
                                 band=16, width=128, pbatch=2,
                                 shared_window=True)


def test_tuned_shared_window_resolves_from_cache(ct_case, tmp_path,
                                                 monkeypatch):
    """A v4 tuned decision carrying ``shared_window``/``strip_dtype``
    redirects auto to the shared bf16 kernel bit-for-bit."""
    from repro.tune import TUNE_SCHEMA_VERSION, clear_memory_cache

    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path / "tune"))
    clear_memory_cache()
    tiles = {"ty": 4, "chunk": 16, "band": 16, "width": 128}
    _write_cache_file(tmp_path, {**tiles, "pbatch": 2,
                                 "shared_window": True,
                                 "strip_dtype": "bfloat16"},
                      TUNE_SCHEMA_VERSION)
    filt, mats = ct_case
    vol0 = jnp.zeros((GEOM.L,) * 3, jnp.float32)
    out_auto = pallas_backproject_batch(vol0, filt, mats, GEOM,
                                        strategy="auto")
    out_fix = pallas_backproject_batch(vol0, filt, mats, GEOM, pbatch=2,
                                       shared_window=True,
                                       strip_dtype="bfloat16", **tiles)
    clear_memory_cache()
    np.testing.assert_array_equal(np.asarray(out_auto),
                                  np.asarray(out_fix))


def test_v3_cache_file_is_ignored_not_misread(ct_case, tmp_path,
                                              monkeypatch):
    """A v3-era decision predates the strip_dtype/shared_window axes —
    its "best" never competed against them, so it must read as untuned
    rather than freeze the old design space."""
    from repro.tune import clear_memory_cache, load_tuned

    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path / "tune"))
    clear_memory_cache()
    _write_cache_file(tmp_path, {"ty": 4, "chunk": 16, "band": 16,
                                 "width": 128, "pbatch": 2}, version=3)
    assert load_tuned(GS) is None
    clear_memory_cache()
