"""Streamed reconstruction engine: arrival-order freedom, slot reuse.

The acceptance claim: a streamed reconstruction (projections submitted
in shuffled-order chunks with explicit angle indices) matches the
one-shot ``reconstruct`` of the same filtered stack to <= 1e-5, and B
concurrent scans over fewer slots all converge to the same volume
(continuous batching).
"""

import numpy as np
import pytest

from repro.core import Geometry, filter_projections, reconstruct
from repro.core.phantom import make_dataset
from repro.streaming import ProjectionChunk, ReconstructionEngine

GEOM = Geometry().scaled(16, n_proj=6)
_DS = make_dataset(GEOM)


def _oracle():
    projs, mats, _ = _DS
    filt = np.asarray(filter_projections(projs, GEOM))
    return np.asarray(reconstruct(filt, mats, GEOM))


REF = _oracle()


def test_streamed_shuffled_chunks_match_one_shot():
    projs, mats, _ = _DS
    eng = ReconstructionEngine(GEOM, n_slots=2, pbatch=4)
    sid = eng.begin_scan(n_proj=GEOM.n_proj)
    order = np.random.default_rng(7).permutation(GEOM.n_proj)
    # Ragged shuffled chunks, including a single-projection submit with
    # a scalar angle index.
    for chunk in (order[:3], order[3:5]):
        eng.submit(sid, projs[chunk], mats[chunk], chunk)
    last = int(order[5])
    eng.submit(sid, projs[last], mats[last], last)
    eng.drain()
    out = np.asarray(eng.result(sid))
    assert np.abs(out).max() > 0
    np.testing.assert_allclose(out, REF, atol=1e-5, rtol=1e-5)


def test_streamed_remainder_not_divisible_by_pbatch():
    """n_proj % pbatch != 0: the remainder folds zero-padded to the same
    compiled step, contributing exactly its own projections."""
    projs, mats, _ = _DS
    eng = ReconstructionEngine(GEOM, n_slots=1, pbatch=4)
    sid = eng.begin_scan(n_proj=GEOM.n_proj)       # 6 = 4 + 2 remainder
    idx = np.arange(GEOM.n_proj)
    eng.submit(sid, projs, mats, idx)
    eng.drain()
    np.testing.assert_allclose(np.asarray(eng.result(sid)), REF,
                               atol=1e-5, rtol=1e-5)
    assert eng.stats["folds"] == GEOM.n_proj


def test_multi_volume_continuous_batching_reuses_slots():
    """3 scans over 2 slots: the third admits only after a retirement,
    every result matches the oracle, and a freed slot is reused."""
    projs, mats, _ = _DS
    eng = ReconstructionEngine(GEOM, n_slots=2, pbatch=4)
    sids = [eng.begin_scan(n_proj=GEOM.n_proj) for _ in range(3)]
    assert eng.active == 3
    assert [s for s, _ in eng.slot_history] == [0, 1]  # third queued
    for i in range(GEOM.n_proj):                  # interleaved arrival
        for sid in sids:
            eng.submit(sid, projs[i], mats[i], i)
    eng.drain()
    assert eng.stats["retired"] == 3 and eng.active == 0
    for sid in sids:
        np.testing.assert_allclose(np.asarray(eng.result(sid)), REF,
                                   atol=1e-5, rtol=1e-5)
    slots = [s for s, _ in eng.slot_history]
    assert len(slots) == 3 and len(set(slots)) < len(slots)  # reuse
    # Retired slots were zeroed: a fresh 4th scan reconstructs cleanly.
    sid = eng.begin_scan(n_proj=GEOM.n_proj)
    eng.submit(sid, projs, mats, np.arange(GEOM.n_proj))
    eng.drain()
    np.testing.assert_allclose(np.asarray(eng.result(sid)), REF,
                               atol=1e-5, rtol=1e-5)


def test_engine_rejects_bad_submissions():
    projs, mats, _ = _DS
    eng = ReconstructionEngine(GEOM, n_slots=1, pbatch=4)
    sid = eng.begin_scan(n_proj=2)
    with pytest.raises(ValueError, match="angle ind"):
        eng.submit(sid, projs[0], mats[0], GEOM.n_proj)   # out of range
    with pytest.raises(ValueError, match="matrices"):
        eng.submit(sid, projs[:2], mats[:1], np.arange(2))
    with pytest.raises(ValueError, match="not finished"):
        eng.result(sid)
    with pytest.raises(ValueError, match="declared"):
        eng.submit(sid, projs[:3], mats[:3], np.arange(3))  # 3 > 2
    eng.submit(sid, projs[:2], mats[:2], np.arange(2))
    eng.drain()
    assert eng.scans[sid].done
    with pytest.raises(ValueError, match="finished"):
        eng.submit(sid, projs[2], mats[2], 2)           # post-retirement


def test_begin_scan_zero_n_proj_is_loud_not_full():
    """Regression: ``begin_scan(n_proj=0)`` used to fall through a
    truthiness check (``n_proj or geom.n_proj``) and silently register a
    *full* scan — a caller bug that would then block retirement forever
    waiting for projections nobody declared.  Zero and negative counts
    raise; only ``None`` means "full scan"."""
    eng = ReconstructionEngine(GEOM, n_slots=1, pbatch=4)
    with pytest.raises(ValueError, match="n_proj"):
        eng.begin_scan(n_proj=0)
    with pytest.raises(ValueError, match="n_proj"):
        eng.begin_scan(n_proj=-3)
    sid = eng.begin_scan(n_proj=None)
    assert eng.scans[sid].n_proj == GEOM.n_proj
    sid2 = eng.begin_scan(n_proj=2)
    assert eng.scans[sid2].n_proj == 2


def test_result_pop_releases_scan_state():
    """A long-running server must be able to drop retired volumes:
    result(pop=True) / release() evict the ScanState."""
    projs, mats, _ = _DS
    eng = ReconstructionEngine(GEOM, n_slots=1, pbatch=4)
    sid = eng.begin_scan(n_proj=2)
    with pytest.raises(ValueError, match="still active"):
        eng.release(sid)
    eng.submit(sid, projs[:2], mats[:2], np.arange(2))
    eng.drain()
    vol = eng.result(sid, pop=True)
    assert vol.shape == (GEOM.L,) * 3
    assert sid not in eng.scans
    eng.release(sid)                  # idempotent after eviction


def test_streamed_auto_strategy_resolves(tmp_path, monkeypatch):
    """strategy='auto' goes through the tuner cache like reconstruct
    (untuned fallback: strip2 — same result as the default engine)."""
    monkeypatch.setenv("REPRO_TUNE_DIR", str(tmp_path))
    from repro.tune import clear_memory_cache

    clear_memory_cache()
    projs, mats, _ = _DS
    eng = ReconstructionEngine(GEOM, n_slots=1, strategy="auto")
    assert eng.strategy == "strip2"
    sid = eng.begin_scan(n_proj=GEOM.n_proj)
    eng.submit(sid, projs, mats, np.arange(GEOM.n_proj))
    eng.drain()
    np.testing.assert_allclose(np.asarray(eng.result(sid)), REF,
                               atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------------------
# Spans (repro.obs)
# ----------------------------------------------------------------------

def test_engine_spans_under_a_capture(tmp_path):
    """With the jnp fold, the engine records a filter span per submit
    and fold spans whose views add up to the views submitted; the
    planner check of a first-seen stack sits outside both."""
    import jax

    from repro import obs

    projs, mats, _ = _DS
    eng = ReconstructionEngine(GEOM, n_slots=2, pbatch=4)
    sid = eng.begin_scan(n_proj=GEOM.n_proj)
    with jax.profiler.trace(str(tmp_path)):
        for c in (np.arange(0, 3), np.arange(3, 6)):
            eng.submit(sid, ProjectionChunk(projs[c], mats[c], c))
        eng.drain()                 # the remainder of 2 folds here
    spans = obs.recorded()
    s = obs.summary()
    assert s["engine.filter"]["count"] == 2
    assert s["engine.filter"]["units"] == GEOM.n_proj
    assert s["engine.fold"]["count"] == 2
    assert s["engine.fold"]["units"] == GEOM.n_proj
    assert {p for n, p, *_ in spans if n != "planner.check"} == {None}
    folds = [a for n, _, _, _, _, a in spans if n == "engine.fold"]
    assert all(a["slots"] == 1 and "bytes_in_use_entry" in a
               and "bytes_in_use_exit" in a for a in folds)
    np.testing.assert_allclose(np.asarray(eng.result(sid)), REF,
                               atol=1e-5, rtol=1e-5)


def test_second_identical_stack_is_a_memo_hit(tmp_path):
    """The planner checks a stack once: the same matrices again count a
    ``planner.memo_hit`` and record no ``planner.check``."""
    import jax

    from repro import obs

    projs, mats, _ = _DS
    mats = mats.copy()
    mats[:, 2, 3] += 1e-3          # a stack no other test has checked
    eng = ReconstructionEngine(GEOM, n_slots=1, pbatch=4)
    c = np.arange(GEOM.n_proj)
    with jax.profiler.trace(str(tmp_path / "first")):
        eng.submit(eng.begin_scan(), ProjectionChunk(projs, mats, c))
    assert obs.summary()["planner.check"]["units"] == GEOM.n_proj
    assert "planner.memo_hit" not in obs.summary()
    with jax.profiler.trace(str(tmp_path / "again")):
        eng.submit(eng.begin_scan(), ProjectionChunk(projs, mats, c))
    s = obs.summary()
    assert "planner.check" not in s
    assert s["planner.memo_hit"] == {"count": 1, "units": GEOM.n_proj,
                                     "total_s": 0.0, "self_s": 0.0}

