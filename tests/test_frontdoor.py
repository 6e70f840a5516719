"""Multi-tenant front door: admission policies, backpressure, abort.

Three layers of claim (DESIGN.md §14):

* **Policy properties** — the admission policies are pure functions of
  (pending, context), so their scheduling guarantees hold as properties:
  SRSF's linear aging bounds starvation, the deadline policy is exactly
  least-slack order, fair share always serves the least-loaded tenant.
* **Tier contracts** — a full house raises :class:`Backpressure` with a
  positive ``retry_after``; over-declared submission is loud; a
  cancelled ticket raises :class:`ScanAborted`; abort-then-reuse of a
  slot is bit-clean (the next scan through that slot matches the
  oracle to the same tolerance as a fresh engine).
* **End to end** — N clients interleaving chunk streams through one
  event loop all converge to the one-shot ``reconstruct`` volume, under
  every policy, and the z-sharded engine on the trivial 1x1 mesh
  matches too.
"""

import asyncio

import numpy as np
import pytest

from _prop import given, settings, st
from repro.api import (Backpressure, CTFrontDoor, DeadlinePolicy,
                       FairSharePolicy, FIFOPolicy, Geometry,
                       PolicyContext, ProjectionChunk, ScanAborted,
                       SRSFPolicy, filter_projections, reconstruct)
from repro.core.phantom import make_dataset
from repro.serving.ct_frontdoor import POLICIES, ScanTicket, _resolve_policy

GEOM = Geometry().scaled(16, n_proj=6)
_DS = make_dataset(GEOM)


def _oracle():
    projs, mats, _ = _DS
    filt = np.asarray(filter_projections(projs, GEOM))
    return np.asarray(reconstruct(filt, mats, GEOM))


REF = _oracle()


def _ticket(tid, *, n_proj=8, tenant="default", arrived=0.0,
            deadline=None):
    return ScanTicket(tid=tid, tenant=tenant, n_proj=n_proj,
                      deadline=deadline, arrived=arrived)


def _ctx(now=0.0, active=None, admitted=None, est_proj_s=0.0):
    return PolicyContext(now=now, active=active or {},
                         admitted=admitted or {}, est_proj_s=est_proj_s)


async def _stream(fd, projs, mats, *, chunk=3, tenant="default"):
    ticket = await fd.open_scan(tenant=tenant, n_proj=GEOM.n_proj)
    order = np.arange(GEOM.n_proj)
    for c0 in range(0, GEOM.n_proj, chunk):
        idx = order[c0:c0 + chunk]
        await fd.submit(ticket, ProjectionChunk(projs[idx], mats[idx],
                                                idx))
    return np.asarray(await fd.result(ticket))


# ----------------------------------------------------------------------
# Policy properties
# ----------------------------------------------------------------------

@given(long=st.integers(10, 500), wait=st.floats(0.0, 1000.0),
       aging=st.floats(0.1, 10.0))
@settings(max_examples=40, deadline=None)
def test_srsf_aging_bounds_starvation(long, wait, aging):
    """A scan that has waited past ``(its remaining - shortest
    remaining) / aging`` seconds outranks every fresh short arrival —
    SRSF with aging > 0 cannot starve it indefinitely."""
    short = 5
    pending = (_ticket(0, n_proj=long, arrived=-wait),
               _ticket(1, n_proj=short, arrived=0.0))
    pick = SRSFPolicy(aging=aging).select(pending, _ctx(now=0.0))
    aged_key = long - aging * wait          # the policy's own key
    if aged_key <= short:                   # waited past the bound
        assert pick == 0                    # (ties keep arrival order)
    else:
        assert pick == 1            # fresh short scan still preferred


def test_srsf_without_wait_is_shortest_first():
    pending = (_ticket(0, n_proj=50), _ticket(1, n_proj=3),
               _ticket(2, n_proj=20))
    assert SRSFPolicy().select(pending, _ctx()) == 1


@given(d0=st.floats(1.0, 100.0), d1=st.floats(1.0, 100.0),
       n0=st.integers(1, 200), n1=st.integers(1, 200),
       rate=st.floats(0.0, 0.5))
@settings(max_examples=40, deadline=None)
def test_deadline_policy_is_least_slack_order(d0, d1, n0, n1, rate):
    """The pick always has minimal slack = deadline - now - work left
    at the measured rate; a no-deadline ticket never beats one with a
    deadline."""
    pending = (_ticket(0, n_proj=n0, deadline=d0),
               _ticket(1, n_proj=n1, deadline=d1),
               _ticket(2, n_proj=1, deadline=None))
    ctx = _ctx(now=0.0, est_proj_s=rate)
    pick = DeadlinePolicy().select(pending, ctx)
    slack = [d0 - n0 * rate, d1 - n1 * rate, float("inf")]
    assert pick != 2
    assert slack[pick] == min(slack)


def test_fair_share_serves_least_loaded_tenant():
    pending = (_ticket(0, tenant="hog"), _ticket(1, tenant="hog"),
               _ticket(2, tenant="quiet"))
    ctx = _ctx(active={"hog": 2}, admitted={"hog": 7, "quiet": 1})
    assert FairSharePolicy().select(pending, ctx) == 2
    # All else equal, total admissions break the tie.
    ctx = _ctx(active={}, admitted={"hog": 7, "quiet": 1})
    assert FairSharePolicy().select(pending, ctx) == 2


def test_every_policy_is_fifo_among_equals():
    """Identical tickets: min keeps the first minimum, so every policy
    degrades to arrival order."""
    pending = tuple(_ticket(i) for i in range(4))
    for name, cls in POLICIES.items():
        assert cls().select(pending, _ctx()) == 0, name


def test_policy_resolution():
    assert isinstance(_resolve_policy("FIFO"), FIFOPolicy)
    p = SRSFPolicy(aging=2.0)
    assert _resolve_policy(p) is p
    with pytest.raises(ValueError, match="unknown admission policy"):
        _resolve_policy("lifo")
    with pytest.raises(TypeError):
        _resolve_policy(42)
    with pytest.raises(ValueError, match="aging"):
        SRSFPolicy(aging=-1.0)


# ----------------------------------------------------------------------
# Tier contracts: backpressure, bounds, cancellation, slot hygiene
# ----------------------------------------------------------------------

def test_full_house_raises_backpressure_with_hint():
    projs, mats, _ = _DS

    async def scenario():
        fd = CTFrontDoor(GEOM, n_slots=1, max_pending=2, pbatch=4)
        # 1 active + 2 pending = full house; the 4th arrival bounces.
        for _ in range(3):
            await fd.open_scan(n_proj=GEOM.n_proj)
        assert fd.active == 1 and fd.pending == 2
        with pytest.raises(Backpressure) as ei:
            await fd.open_scan(n_proj=GEOM.n_proj)
        assert ei.value.retry_after > 0
        assert fd.stats["rejected"] == 1
        # An explicit retry_after override is honoured verbatim.
        fd2 = CTFrontDoor(GEOM, n_slots=1, max_pending=1,
                          retry_after=7.5, pbatch=4)
        await fd2.open_scan()
        await fd2.open_scan()
        with pytest.raises(Backpressure) as ei:
            await fd2.open_scan()
        assert ei.value.retry_after == 7.5

    asyncio.run(scenario())


def test_over_declared_submission_is_loud():
    projs, mats, _ = _DS

    async def scenario():
        fd = CTFrontDoor(GEOM, n_slots=1, pbatch=4)
        ticket = await fd.open_scan(n_proj=4)
        idx = np.arange(3)
        await fd.submit(ticket, ProjectionChunk(projs[idx], mats[idx],
                                                idx))
        with pytest.raises(ValueError, match="declared 4"):
            await fd.submit(ticket, ProjectionChunk(projs[3:5], mats[3:5],
                                                    np.arange(3, 5)))
        with pytest.raises(TypeError, match="ProjectionChunk"):
            await fd.submit(ticket, projs[:1])

    asyncio.run(scenario())


def test_cancel_pending_and_active_raises_scan_aborted():
    projs, mats, _ = _DS

    async def scenario():
        fd = CTFrontDoor(GEOM, n_slots=1, max_pending=4, pbatch=4)
        active = await fd.open_scan(n_proj=GEOM.n_proj)
        queued = await fd.open_scan(n_proj=GEOM.n_proj)
        assert active.state == "active" and queued.state == "pending"
        assert await fd.cancel(queued)
        with pytest.raises(ScanAborted):
            await fd.result(queued)
        idx = np.arange(2)
        await fd.submit(active, ProjectionChunk(projs[idx], mats[idx],
                                                idx))
        assert await fd.cancel(active)
        with pytest.raises(ScanAborted):
            await fd.result(active)
        assert not await fd.cancel(active)      # already settled
        assert fd.stats["cancelled"] == 2
        # Settled tickets refuse further chunks.
        with pytest.raises(ValueError, match="aborted"):
            await fd.submit(active, ProjectionChunk(projs[idx],
                                                    mats[idx], idx))
        return fd

    fd = asyncio.run(scenario())
    assert fd.active == 0 and fd.pending == 0
    assert fd.free_slots == 1                   # the slot came back


def test_abort_then_reuse_is_bit_clean():
    """A half-streamed scan aborted mid-flight leaves no residue: the
    next scan through the freed slot matches the oracle exactly as a
    fresh engine would."""
    projs, mats, _ = _DS

    async def scenario():
        fd = CTFrontDoor(GEOM, n_slots=1, pbatch=4)
        poisoned = await fd.open_scan(n_proj=GEOM.n_proj)
        idx = np.arange(4)
        await fd.submit(poisoned, ProjectionChunk(projs[idx] * 1e3,
                                                  mats[idx], idx))
        await fd.cancel(poisoned)
        return await _stream(fd, projs, mats)

    out = asyncio.run(scenario())
    np.testing.assert_allclose(out, REF, atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------------------
# End to end
# ----------------------------------------------------------------------

@pytest.mark.parametrize("policy", sorted(POLICIES))
def test_interleaved_clients_converge_under_every_policy(policy):
    projs, mats, _ = _DS

    async def scenario():
        fd = CTFrontDoor(GEOM, n_slots=2, max_pending=8, policy=policy,
                         pbatch=4)
        outs = await asyncio.gather(*(
            _stream(fd, projs, mats, chunk=c, tenant=t)
            for c, t in ((2, "a"), (3, "b"), (6, "a"), (1, "c"))))
        return outs, fd.stats

    outs, stats = asyncio.run(scenario())
    assert stats["completed"] == 4
    for out in outs:
        np.testing.assert_allclose(out, REF, atol=1e-5, rtol=1e-5)


def test_deadline_policy_admits_tightest_slo_first():
    """With one slot busy and three queued, the freed slot goes to the
    ticket whose deadline is soonest — not the first arrival."""
    projs, mats, _ = _DS

    async def scenario():
        fd = CTFrontDoor(GEOM, n_slots=1, max_pending=8,
                         policy="deadline", pbatch=4)
        blocker = await fd.open_scan(n_proj=GEOM.n_proj)
        loose = await fd.open_scan(n_proj=GEOM.n_proj, deadline=1e9)
        tight = await fd.open_scan(n_proj=GEOM.n_proj, deadline=1.0)
        none = await fd.open_scan(n_proj=GEOM.n_proj)
        await fd.cancel(blocker)                # frees the slot
        assert tight.state == "active"
        assert loose.state == "pending" and none.state == "pending"

    asyncio.run(scenario())


def test_sharded_backend_identity_mesh_matches_oracle():
    """``mesh=`` streams through the z-sharded engine: on the trivial 1x1
    mesh, shuffled chunks and a partial scan (no longer refused) fold
    with the kernel and match the oracle."""
    from repro.dispatch import ExecutionPlan
    from repro.launch.mesh import make_local_mesh

    projs, mats, _ = _DS
    mesh = make_local_mesh(data=1, model=1)
    plan = ExecutionPlan(strategy="gather", pbatch=2, use_pallas=True,
                         pallas=(("band", 16), ("chunk", 16),
                                 ("pbatch", 2), ("ty", 8),
                                 ("width", 128)))

    async def scenario():
        fd = CTFrontDoor(GEOM, mesh=mesh, n_slots=1, plan=plan)
        assert fd._backend.engine.mesh is mesh
        part = await fd.open_scan(n_proj=3)
        await fd.submit(part, ProjectionChunk(projs[:3], mats[:3],
                                              np.arange(3)))
        await fd.result(part)
        ticket = await fd.open_scan(n_proj=GEOM.n_proj)
        order = np.random.default_rng(3).permutation(GEOM.n_proj)
        for c0 in range(0, GEOM.n_proj, 2):
            idx = order[c0:c0 + 2]
            await fd.submit(ticket, ProjectionChunk(projs[idx],
                                                    mats[idx], idx))
        return np.asarray(await fd.result(ticket))

    out = asyncio.run(scenario())
    np.testing.assert_allclose(out, REF, atol=1e-5, rtol=1e-5)


# ----------------------------------------------------------------------
# Spans (repro.obs)
# ----------------------------------------------------------------------

def _traced_scan(tmp_path, mats, **door_opts):
    """One scan of ``mats`` through a front door, in chunks of 2, under
    a profiler capture; returns the spans and the volume."""
    import jax

    from repro import obs

    projs = _DS[0]
    fd = CTFrontDoor(GEOM, n_slots=1, **door_opts)

    async def scenario():
        ticket = await fd.open_scan(n_proj=GEOM.n_proj)
        for c0 in range(0, GEOM.n_proj, 2):
            idx = np.arange(c0, c0 + 2)
            await fd.submit(ticket, ProjectionChunk(projs[idx], mats[idx],
                                                    idx))
        return await fd.result(ticket)

    with jax.profiler.trace(str(tmp_path)):
        vol = asyncio.run(scenario())
    return obs.recorded(), np.asarray(vol)


def _unseen(mats, shift):
    """The matrices moved by a hair, so that no memo has seen them."""
    mats = mats.copy()
    mats[:, 2, 3] += shift
    return mats


def test_frontdoor_spans_hold_the_engine_jnp_path(tmp_path):
    """On the jnp fold, every engine span sits inside
    ``frontdoor.submit``, the fold's views add up to the views submitted,
    and the planner checks the new stack directly under the front door."""
    spans, out = _traced_scan(tmp_path, _unseen(_DS[1], 2e-3), pbatch=4)
    doors = [r for r in spans if r[0] == "frontdoor.submit"]
    assert [r[4] for r in doors] == [2, 2, 2]
    assert {r[1] for r in doors} == {None}
    assert all("scan" in r[5] for r in doors)
    inner = [r for r in spans if r[0] != "frontdoor.submit"]
    assert {(n, p) for n, p, *_ in inner} == {
        ("engine.filter", "frontdoor.submit"),
        ("engine.fold", "frontdoor.submit"),
        ("planner.check", "frontdoor.submit")}
    assert sum(r[4] for r in spans if r[0] == "engine.fold") == GEOM.n_proj
    assert sum(r[4] for r in spans if r[0] == "planner.check") \
        == GEOM.n_proj
    assert 0 < np.abs(out).max()


def test_frontdoor_spans_put_the_planner_inside_the_kernel_fold(tmp_path):
    """With a kernel plan (interpret mode on the CPU), the planner's
    check runs inside ``engine.fold``, and the fold's self time excludes
    it."""
    from repro import obs
    from repro.dispatch import ExecutionPlan

    plan = ExecutionPlan(strategy="gather", pbatch=2, use_pallas=True,
                         pallas=(("band", 16), ("chunk", 16),
                                 ("pbatch", 2), ("ty", 8),
                                 ("width", 128)))
    spans, out = _traced_scan(tmp_path, _unseen(_DS[1], 3e-3), plan=plan)
    checks = [r for r in spans if r[0] == "planner.check"]
    assert checks and {r[1] for r in checks} == {"engine.fold"}
    assert sum(r[4] for r in checks) == GEOM.n_proj
    assert sum(r[4] for r in spans if r[0] == "engine.fold") == GEOM.n_proj
    s = obs.summary()
    assert s["engine.fold"]["self_s"] == pytest.approx(
        s["engine.fold"]["total_s"] - s["planner.check"]["total_s"])
    assert np.isfinite(out).all() and 0 < np.abs(out).max()
