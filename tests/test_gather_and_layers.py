"""gather_ops strategy equivalence: take, one-hot matmul and auto."""

import jax
import jax.numpy as jnp
import numpy as np
from _prop import given, settings, st

from repro.core.gather_ops import gather, onehot_gather, take_gather


@given(V=st.integers(3, 300), D=st.sampled_from([4, 32]),
       N=st.integers(1, 64), seed=st.integers(0, 10),
       chunk=st.sampled_from([16, 64, 2048]))
@settings(max_examples=40, deadline=None)
def test_gather_impl_equivalence(V, D, N, seed, chunk):
    key = jax.random.PRNGKey(seed)
    table = jax.random.normal(key, (V, D), jnp.float32)
    ids = jax.random.randint(key, (N,), 0, V)
    a = take_gather(table, ids)
    b = onehot_gather(table, ids, chunk=chunk)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)


def test_gather_auto_dispatch():
    key = jax.random.PRNGKey(0)
    small = jax.random.normal(key, (100, 8))
    big = jax.random.normal(key, (5000, 8))
    ids = jnp.asarray([0, 1, 2])
    np.testing.assert_allclose(np.asarray(gather(small, ids, "auto")),
                               np.asarray(take_gather(small, ids)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gather(big, ids, "auto")),
                               np.asarray(take_gather(big, ids)),
                               rtol=1e-6)


def test_onehot_gather_differentiable_scatter_add():
    """d/dtable of onehot gather is the scatter-add (training-safe)."""
    table = jnp.ones((10, 4))
    ids = jnp.asarray([3, 3, 7])

    def f(t):
        return jnp.sum(onehot_gather(t, ids, chunk=4))

    g = jax.grad(f)(table)
    assert float(g[3, 0]) == 2.0 and float(g[7, 0]) == 1.0
    assert float(g[0, 0]) == 0.0
