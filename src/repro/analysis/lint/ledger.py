"""DMA-ledger replay: prove every kernel's issue/wait discipline.

Each Pallas variant in :mod:`repro.kernels.backproject` hand-rolls its
strip DMA pipeline — the 2-slot rotation in ``_batch_strip_loop``, the
``depth``-slot ``start_dma``/``wait_strip`` rotations of the ``_db``
kernels, the one-slab copy of the shared-window kernel.  Mosaic checks
none of the invariants these rely on; an unbalanced semaphore or a
slot overwritten while its copy is in flight is silent data corruption
on hardware (and often *passes* in interpret mode, which serialises the
copies).

This pass replays the *actual kernel functions* — not a model of them —
by swapping the module's ``pl``/``pltpu``/``jax`` globals for recording
stubs and running every grid step eagerly:

* refs are numpy-backed (:class:`StubRef`), so indexing/arithmetic run
  for real and out-of-bounds slicing fails loudly;
* ``pltpu.make_async_copy(...).start()/.wait()`` post to a
  :class:`Ledger` keyed by semaphore, with the copy's full
  (source-view, dest-view) descriptor, so producer/consumer *origin
  agreement* is checked, not just counts;
* ``pl.when`` executes its branch iff the (concrete) predicate holds
  and ``jax.lax.fori_loop`` becomes a Python loop, so every issue/wait
  the kernel would perform is observed exactly once per grid step.

Invariants proved per replay (each violation is a finding):

* **balance** — every started copy is awaited exactly once
  (``unwaited-dma``), and no wait fires on an idle semaphore
  (``wait-before-issue``);
* **origin agreement** — a wait's recomputed descriptor matches what
  the issuer posted (``wait-descriptor-mismatch``);
* **slot liveness** — no copy targets a slot whose previous copy is
  still in flight (``slot-overwrite``);
* **depth bounds** — peak in-flight copies stay within the scratch's
  slot count (``in-flight-exceeds-slots``) and reach the depth the
  variant promises (``pipeline-under-depth``): a rotation that never
  fills is a silently-degraded pipeline, PR 5's bug class.

The replay space crosses all five variants with ``db_depth`` ∈
{2, 3, 4}, ``pbatch`` ∈ {4, 3} (3 exercises the ``pbatch ∤ n_proj``
remainder group the batch wrapper dispatches at the tail), and the
quantized (int8 + scale-sideband) ref layout.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import itertools

import numpy as np

import jax
import jax.numpy as jnp

from .common import Finding, PassResult

__all__ = ["StubRef", "Ledger", "ReplayCase", "builtin_cases", "replay",
           "replay_fixture", "run_ledger_pass"]


# ----------------------------------------------------------------------
# Recording stubs for pl / pltpu / jax.lax
# ----------------------------------------------------------------------

class _DS:
    """Concrete stand-in for ``pl.ds``: a (start, size) slice."""

    __slots__ = ("start", "size")

    def __init__(self, start, size):
        self.start = int(start)
        self.size = int(size)

    def as_slice(self):
        return slice(self.start, self.start + self.size)

    def key(self):
        return ("ds", self.start, self.size)


def _norm(idx):
    """Hashable descriptor form of an index tuple."""
    if not isinstance(idx, tuple):
        idx = (idx,)
    out = []
    for i in idx:
        if isinstance(i, _DS):
            out.append(i.key())
        elif i is Ellipsis:
            out.append("...")
        elif isinstance(i, slice):
            out.append(("slice", i.start, i.stop))
        else:
            out.append(int(i))
    return tuple(out)


def _np_index(idx):
    """Numpy indexing form of an index tuple."""
    if not isinstance(idx, tuple):
        idx = (idx,)
    out = []
    for i in idx:
        if isinstance(i, _DS):
            out.append(i.as_slice())
        elif i is Ellipsis or isinstance(i, slice):
            out.append(i)
        else:
            out.append(int(i))
    return tuple(out)


class _View:
    """A ``ref.at[idx]`` view: descriptor for the ledger, data for the
    copy."""

    def __init__(self, ref, idx):
        self.ref = ref
        self.idx = idx

    def descr(self):
        return (self.ref.name, _norm(self.idx))

    def read(self):
        return self.ref.data[_np_index(self.idx)]

    def write(self, val):
        self.ref.data[_np_index(self.idx)] = np.asarray(val)


class _At:
    def __init__(self, ref):
        self.ref = ref

    def __getitem__(self, idx):
        return _View(self.ref, idx)


class StubRef:
    """Numpy-backed stand-in for a Pallas ref (VMEM/SMEM/ANY alike)."""

    def __init__(self, data, name):
        self.data = np.asarray(data)
        self.name = name

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def at(self):
        return _At(self)

    def __getitem__(self, idx):
        return self.data[_np_index(idx)]

    def __setitem__(self, idx, val):
        self.data[_np_index(idx)] = np.asarray(val)


class Ledger:
    """Per-semaphore copy bookkeeping: the contract being proved."""

    def __init__(self):
        self.pending = {}          # sem descriptor -> FIFO of copy descrs
        self.raw_findings = []     # (rule, detail) tuples
        self.in_flight = 0
        self.max_in_flight = 0
        self.issues = 0
        self.waits = 0

    def issue(self, sem_key, descr):
        q = self.pending.setdefault(sem_key, [])
        if q:
            self.raw_findings.append((
                "slot-overwrite",
                f"copy {descr} started on semaphore {sem_key} while "
                f"{q[0]} is still in flight"))
        q.append(descr)
        self.issues += 1
        self.in_flight += 1
        self.max_in_flight = max(self.max_in_flight, self.in_flight)

    def wait(self, sem_key, descr):
        q = self.pending.setdefault(sem_key, [])
        self.waits += 1
        if not q:
            self.raw_findings.append((
                "wait-before-issue",
                f"wait for {descr} on semaphore {sem_key} with no copy "
                f"in flight"))
            return
        got = q.pop(0)
        self.in_flight -= 1
        if got != descr:
            self.raw_findings.append((
                "wait-descriptor-mismatch",
                f"semaphore {sem_key}: issuer posted {got}, waiter "
                f"recomputed {descr}"))

    def finish(self, n_slots, promised):
        for k, q in self.pending.items():
            for d in q:
                self.raw_findings.append((
                    "unwaited-dma",
                    f"copy {d} on semaphore {k} never awaited"))
        if self.max_in_flight > n_slots:
            self.raw_findings.append((
                "in-flight-exceeds-slots",
                f"peak {self.max_in_flight} copies in flight with only "
                f"{n_slots} scratch slot(s)"))
        if promised is not None and self.max_in_flight < promised:
            self.raw_findings.append((
                "pipeline-under-depth",
                f"peak in-flight depth {self.max_in_flight} never "
                f"reached the promised {promised}"))


class _StubCopy:
    def __init__(self, ledger, src, dst, sem):
        self.ledger = ledger
        self.src = src
        self.dst = dst
        self.sem_key = sem.descr() if isinstance(sem, _View) else \
            (sem.name, ())
        self.descr = (self.src.descr(), self.dst.descr())

    def start(self):
        self.ledger.issue(self.sem_key, self.descr)
        # Data moves at start time.  A correct kernel never overwrites a
        # live slot, so eager movement is equivalent; an incorrect one
        # already produced a slot-overwrite finding above.
        self.dst.write(self.src.read())

    def wait(self):
        self.ledger.wait(self.sem_key, self.descr)


class _PLStub:
    """Eager ``pl``: concrete program ids, real slices, executed
    ``when``."""

    def __init__(self):
        self.grid_point = (0, 0, 0)

    def program_id(self, i):
        return jnp.int32(self.grid_point[i])

    @staticmethod
    def ds(start, size):
        return _DS(start, size)

    @staticmethod
    def multiple_of(x, _):
        return x

    @staticmethod
    def when(cond):
        def deco(f):
            if bool(cond):
                f()
            return f
        return deco


class _PltpuStub:
    def __init__(self, ledger):
        self.ledger = ledger

    def make_async_copy(self, src, dst, sem):
        if isinstance(src, StubRef):
            src = _View(src, (Ellipsis,))
        if isinstance(dst, StubRef):
            dst = _View(dst, (Ellipsis,))
        return _StubCopy(self.ledger, src, dst, sem)


class _LaxStub:
    """``jax.lax`` with ``fori_loop`` unrolled to a Python loop so the
    per-iteration DMA side effects are observed, not traced once."""

    def __getattr__(self, name):
        return getattr(jax.lax, name)

    @staticmethod
    def fori_loop(lo, hi, body, init):
        carry = init
        for i in range(int(lo), int(hi)):
            carry = body(jnp.int32(i), carry)
        return carry


class _JaxStub:
    def __init__(self):
        self.lax = _LaxStub()

    def __getattr__(self, name):
        return getattr(jax, name)


@contextlib.contextmanager
def _patched(modules, pl_stub, pltpu_stub):
    """Swap ``pl``/``pltpu``/``jax`` in each module for the stubs."""
    jax_stub = _JaxStub()
    saved = []
    try:
        for mod in modules:
            for name, stub in (("pl", pl_stub), ("pltpu", pltpu_stub),
                               ("jax", jax_stub)):
                if hasattr(mod, name):
                    saved.append((mod, name, getattr(mod, name)))
                    setattr(mod, name, stub)
        yield
    finally:
        for mod, name, val in reversed(saved):
            setattr(mod, name, val)


# ----------------------------------------------------------------------
# Replay driver
# ----------------------------------------------------------------------

# Replay shape: tiny volume, 4 z-planes × 2 y-bands × 1 chunk grid —
# enough steps to wrap every rotation depth several times while keeping
# a full-suite replay in seconds.  ``_BAND``/``_WIDTH`` are DMA window
# dims: tile multiples for every wire (32 rows covers the int8 tile).
_L, _TY, _CHUNK, _BAND, _WIDTH = 8, 4, 8, 32, 128
_ROWS, _COLS = 64, 256
_GRID = (4, 2, 1)


@dataclasses.dataclass(frozen=True)
class ReplayCase:
    """One kernel replay: which variant, at which pipeline shape.

    ``kind`` selects the ref layout and promised depth; ``n_slots`` is
    the scratch rotation size the ledger bounds peak in-flight copies
    by, ``promised`` the depth the variant claims to sustain (``None``
    for variants whose DMAs are conditional on tile activity).
    """

    name: str
    kind: str              # single|single_db|batch|batch_db|batch_shared
    pbatch: int = 1
    depth: int = 2
    quantized: bool = False

    @property
    def n_slots(self) -> int:
        return {"single": 1, "single_db": self.depth, "batch": 2,
                "batch_db": self.depth, "batch_shared": 1}[self.kind]

    @property
    def promised(self):
        steps = _GRID[0] * _GRID[1] * _GRID[2]
        if self.kind == "single":
            return None            # DMA only under the active flag
        if self.kind == "single_db":
            return min(self.depth, steps)
        if self.kind == "batch_db":
            return min(self.depth, steps * self.pbatch)
        if self.kind == "batch_shared":
            return 1
        return 2 if self.pbatch > 1 else 1


def _default_kernel(case: ReplayCase):
    import repro.kernels.backproject as K

    return {"single": K.backproject_kernel,
            "single_db": K.backproject_kernel_db,
            "batch": K.backproject_kernel_batch,
            "batch_db": K.backproject_kernel_batch_db,
            "batch_shared": K.backproject_kernel_batch_shared}[case.kind]


def replay(case: ReplayCase, kernel_fn=None, extra_modules=()) -> Ledger:
    """Drive one kernel variant across the replay grid; return its
    ledger.

    ``kernel_fn`` overrides the repo kernel (fixture stubs);
    ``extra_modules`` are additional modules whose ``pl``/``pltpu``/
    ``jax`` globals must be stubbed (the fixture's own module — repo
    helpers it imports still resolve through
    ``repro.kernels.backproject``'s globals, which are always patched).
    """
    import repro.kernels.backproject as K
    from repro.core.backproject import GeomStatic
    from repro.core.geometry import default_geometry, projection_matrices

    geom = default_geometry().scaled(_L)
    gs = GeomStatic.of(geom)
    mats = np.asarray(projection_matrices(geom), np.float32)
    kernel = kernel_fn if kernel_fn is not None else _default_kernel(case)

    rng = np.random.default_rng(0)
    batched = case.kind.startswith("batch")
    P = case.pbatch
    if case.quantized:
        imgs = rng.integers(-127, 128, size=(P, _ROWS, _COLS),
                            dtype=np.int8)
        scl = np.stack([rng.uniform(0.01, 0.1, (P, _ROWS)),
                        rng.uniform(-1.0, 1.0, (P, _ROWS))],
                       axis=-1).astype(np.float32)    # (P, rows, 2)
    else:
        imgs = rng.standard_normal((P, _ROWS, _COLS)).astype(np.float32)
        scl = None

    ledger = Ledger()
    pl_stub = _PLStub()
    pltpu_stub = _PltpuStub(ledger)

    kwargs = dict(o_mm=(gs.O, gs.MM), n_u=gs.n_u, n_v=gs.n_v, ty=_TY,
                  chunk=_CHUNK, band=_BAND, width=_WIDTH,
                  quantized=case.quantized)
    if batched:
        kwargs["pbatch"] = P
    if case.kind in ("single_db", "batch_db"):
        kwargs.update(depth=case.depth, grid_dims=_GRID)

    if batched:
        A_ref = StubRef(mats[:P], "A")
        img_ref = StubRef(imgs, "imgs")
    else:
        A_ref = StubRef(mats[0], "A")
        img_ref = StubRef(imgs[0], "img")
    scl_ref = None
    if case.quantized:
        scl_ref = StubRef(scl if batched else scl[0], "scl")

    # Scratch persists across grid steps — exactly the dimension the
    # rotation ledgers depend on.
    strip_shape = {"single": (_BAND, _WIDTH),
                   "single_db": (case.depth, _BAND, _WIDTH),
                   "batch": (2, _BAND, _WIDTH),
                   "batch_db": (case.depth, _BAND, _WIDTH),
                   "batch_shared": (P, _BAND, _WIDTH)}[case.kind]
    strip_ref = StubRef(np.zeros(strip_shape, imgs.dtype), "strip")
    acc_ref = StubRef(np.zeros((_TY, _CHUNK), np.float32), "acc")
    sems = StubRef(np.zeros(max(case.n_slots, 1), np.int32), "sems")

    modules = [K] + [m for m in extra_modules if m is not K]
    with _patched(modules, pl_stub, pltpu_stub):
        for z, y, x in itertools.product(*map(range, _GRID)):
            pl_stub.grid_point = (z, y, x)
            vol_in = StubRef(np.zeros((1, _TY, _CHUNK), np.float32),
                             "vol_in")
            vol_out = StubRef(np.zeros((1, _TY, _CHUNK), np.float32),
                              "vol_out")
            refs = [A_ref, img_ref]
            if scl_ref is not None:
                refs.append(scl_ref)
            refs += [vol_in, vol_out, strip_ref]
            if batched:
                refs.append(acc_ref)
            refs.append(sems)
            kernel(*refs, **kwargs)
    ledger.finish(case.n_slots, case.promised)
    return ledger


def builtin_cases() -> list:
    """The full replay space for the repo's five kernel variants."""
    cases = [
        ReplayCase("single", "single"),
        ReplayCase("batch_shared_p4", "batch_shared", pbatch=4),
        ReplayCase("batch_int8_p4", "batch", pbatch=4, quantized=True),
    ]
    for depth in (2, 3, 4):
        cases.append(ReplayCase(f"single_db_d{depth}", "single_db",
                                depth=depth))
    for pb in (4, 3):              # 3: the remainder-group tail shape
        cases.append(ReplayCase(f"batch_p{pb}", "batch", pbatch=pb))
        for depth in (2, 3, 4):
            cases.append(ReplayCase(f"batch_db_p{pb}_d{depth}",
                                    "batch_db", pbatch=pb, depth=depth))
    return cases


def _ledger_findings(name: str, ledger: Ledger) -> list:
    return [Finding("ledger", rule, name, detail)
            for rule, detail in ledger.raw_findings]


def replay_fixture(path: str):
    """Replay a fixture module (``kernel`` callable + ``SPEC`` dict).

    ``SPEC`` carries the :class:`ReplayCase` fields (``kind`` required;
    ``pbatch``/``depth``/``quantized`` optional) — the contract under
    which the fixture kernel claims to operate, which the ledger then
    checks it against.
    """
    spec_obj = importlib.util.spec_from_file_location("_lint_fixture",
                                                      path)
    mod = importlib.util.module_from_spec(spec_obj)
    spec_obj.loader.exec_module(mod)
    spec = dict(mod.SPEC)
    case = ReplayCase(name=spec.get("name", "fixture"),
                      kind=spec["kind"],
                      pbatch=int(spec.get("pbatch", 1)),
                      depth=int(spec.get("depth", 2)),
                      quantized=bool(spec.get("quantized", False)))
    ledger = replay(case, kernel_fn=mod.kernel, extra_modules=(mod,))
    return _ledger_findings(f"{path}:{case.name}", ledger), ledger


def run_ledger_pass(fixture=None, cases=None) -> PassResult:
    """Run the DMA-ledger pass: the builtin suite, or one fixture."""
    findings, notes, checked = [], [], 0
    if fixture is not None:
        fx_findings, ledger = replay_fixture(fixture)
        findings += fx_findings
        checked += 1
        notes.append(f"fixture {fixture}: issues={ledger.issues} "
                     f"waits={ledger.waits} "
                     f"max_in_flight={ledger.max_in_flight}")
        return PassResult("ledger", findings, checked, notes)
    for case in (cases if cases is not None else builtin_cases()):
        ledger = replay(case)
        checked += 1
        findings += _ledger_findings(case.name, ledger)
        notes.append(f"{case.name}: issues={ledger.issues} "
                     f"waits={ledger.waits} "
                     f"max_in_flight={ledger.max_in_flight}")
        if ledger.issues == 0 and case.kind != "single":
            findings.append(Finding(
                "ledger", "vacuous-replay", case.name,
                "replay executed zero DMAs — the case proves nothing"))
    return PassResult("ledger", findings, checked, notes)
