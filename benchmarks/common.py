"""Shared benchmark utilities: timing, CSV+JSON emission, standard problems.

``time_fn`` is the single timing implementation shared with the autotuner
(``repro.tune.timing``) so tuned decisions and benchmark rows are
comparable numbers.  Every ``emit`` row is also collected into
:data:`RESULTS` (with ``key=value`` pairs in the derived column parsed
out) so ``benchmarks.run --json`` can persist a machine-readable
trajectory entry; :func:`record_extra` attaches structured extras such as
the autotuner's chosen config.
"""

from __future__ import annotations

import os

import numpy as np

from repro.core import Geometry, filter_projections
from repro.core.phantom import make_dataset
from repro.tune.timing import time_fn  # noqa: F401  (re-export)

# Tiny mode shrinks every standard problem to CI-sized shapes via
# ``bench_size`` (``benchmarks.run --tiny`` or REPRO_BENCH_TINY=1).
# ``TINY_ENV`` is the immutable env-var default: ``run.main`` *assigns*
# ``TINY`` per invocation (tiny-ness must not latch across in-process
# runs), and the env opt-in has to survive that reset.
TINY_ENV = os.environ.get("REPRO_BENCH_TINY", "0") not in ("", "0")
TINY = TINY_ENV


def bench_size(normal, tiny):
    """Pick the CI-tiny or the paper-representative problem size."""
    return tiny if TINY else normal


RESULTS: list[dict] = []
EXTRAS: dict = {}


def _parse_derived(derived: str) -> dict:
    fields = {}
    for tok in derived.split():
        if "=" not in tok:
            continue
        k, _, v = tok.partition("=")
        try:
            fields[k] = float(v)
        except ValueError:
            fields[k] = v
    return fields


def emit(name: str, us_per_call: float, derived: str = ""):
    RESULTS.append({"name": name, "us_per_call": us_per_call,
                    "derived": derived,
                    "fields": _parse_derived(derived)})
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def record_extra(key: str, value):
    """Attach a structured (JSON-serialisable) extra to this run."""
    EXTRAS[key] = value


_CACHE = {}


def ct_problem(L: int = 64, n_proj: int = 8):
    """Standard CT bench problem: filtered projections + matrices."""
    key = (L, n_proj)
    if key not in _CACHE:
        geom = Geometry().scaled(L, n_proj=n_proj)
        projs, mats, ref = make_dataset(geom)
        filt = np.asarray(filter_projections(projs, geom))
        _CACHE[key] = (geom, filt, mats, ref)
    return _CACHE[key]


STRATEGY_OPTS = {
    "scalar": {},
    "gather": {},
    "onehot": {"vox_block": 512},
    "strip": {"chunk": 32, "band": 16, "width": 128},
    "strip2": {"group": 8, "gband": 8, "gwidth": 64},
}
