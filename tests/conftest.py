"""Shared test fixtures.  NOTE: no XLA_FLAGS here — tests see 1 device
(multi-device tests set the flag in a child process)."""

import jax
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture
def retrace_counter():
    """Runtime half of the trace-hygiene pass (DESIGN.md §13): count
    XLA compilations of jitted entry points via their ``_cache_size()``.

    Usage::

        counter = retrace_counter(core._reconstruct_jit)
        reconstruct(...)           # first call with a new plan
        assert counter.delta() == 1
        reconstruct(...)           # same plan again
        assert counter.delta() == 1    # still: no silent retrace

    A delta above the number of distinct (shape, static-arg) plans
    means something non-hashable or freshly-constructed leaked into a
    jit boundary — the bug class the static ``jit-in-fn`` /
    ``nonhashable-static`` rules guard at source level.
    """

    class _Counter:
        def __init__(self, *fns):
            assert fns, "pass at least one jitted function"
            for f in fns:
                assert hasattr(f, "_cache_size"), (
                    f"{f} is not a jitted function with _cache_size()")
            self.fns = fns
            self.base = [f._cache_size() for f in fns]

        def delta(self) -> int:
            return sum(f._cache_size() - b
                       for f, b in zip(self.fns, self.base))

    return _Counter


@pytest.fixture(autouse=True)
def _dispatch_deterministic(monkeypatch):
    """Keep the suite deterministic: an untuned ``strategy="auto"``
    falls back to strip2 (the pre-dispatch contract) instead of timing
    candidates in situ.  Dispatch tests opt back in explicitly with
    ``Dispatcher(insitu=True)``; any test-installed process dispatcher
    is dropped afterwards so state never leaks across tests."""
    monkeypatch.setenv("REPRO_DISPATCH_INSITU", "0")
    yield
    from repro.dispatch import reset_dispatcher

    reset_dispatcher()
