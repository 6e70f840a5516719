"""Generalised gather strategies — the paper's technique as a library.

The paper's portable lesson is that *how* you materialise a scattered
gather matters more than ISA width: hardware gather (AVX2/IMCI) can lose to
structured loads (AVX/FMA3), and on machines with strong matrix units the
interpolation/selection itself can ride the MXU.  This module exposes that
choice for a plain table lookup, the paper's Table 4 micro-benchmark
(``benchmarks/table4_gather_micro.py``); the back projection strategies
make the same choice for the detector reads
(:mod:`repro.core.backproject`).

``gather_impl`` values:

``take``
    ``table[ids]`` — the XLA gather HLO.  On TPU this is the "hardware
    gather" analogue: correct, compact, and at the mercy of the backend's
    descriptor loop.
``onehot``
    chunked one-hot matmul on the MXU.  ``2 * V * D`` flops per id, but
    zero gather HLOs: the matrix unit plays texture unit.  Wins when the
    table is small/hot (small codebooks) or when gathers would
    serialise; loses asymptotically on large tables.
    Differentiable (the transpose matmul is the scatter-add), which makes
    it the *training-safe* path where scatter performance is the concern.
``auto``
    picks ``take`` for big tables, ``onehot`` under
    :data:`ONEHOT_AUTO_MAX_ROWS` — the measured crossover from
    ``benchmarks/table4_gather_micro.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["gather", "take_gather", "onehot_gather", "ONEHOT_AUTO_MAX_ROWS"]

# Crossover measured by benchmarks/table4_gather_micro.py on the CPU
# backend.
ONEHOT_AUTO_MAX_ROWS = 1024


def take_gather(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Plain XLA gather: ``table[ids]`` with clamped out-of-range ids."""
    return jnp.take(table, ids, axis=0, mode="clip")


@functools.partial(jax.jit, static_argnames=("chunk",))
def onehot_gather(table: jax.Array, ids: jax.Array,
                  chunk: int = 2048) -> jax.Array:
    """One-hot-matmul gather: no gather HLO, all flops on the MXU.

    The table's row axis is processed in ``chunk``-row tiles inside a
    ``fori_loop`` so HLO size and live memory stay flat in ``V``:
    ``out += onehot(ids in tile) @ table[tile]``.
    """
    V, D = table.shape
    flat = ids.reshape(-1)
    n = flat.shape[0]
    chunk = min(chunk, V)
    n_chunks = -(-V // chunk)
    pad_v = n_chunks * chunk - V
    padded = jnp.pad(table, ((0, pad_v), (0, 0))) if pad_v else table
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, chunk), 1)

    def body(c, acc):
        base = c * chunk
        tile = jax.lax.dynamic_slice_in_dim(padded, base, chunk, axis=0)
        oh = (iota == (flat[:, None] - base)).astype(table.dtype)
        return acc + oh @ tile

    out = jax.lax.fori_loop(
        0, n_chunks, body,
        jnp.zeros((n, D), dtype=table.dtype))
    return out.reshape(ids.shape + (D,))


def gather(table: jax.Array, ids: jax.Array, impl: str = "auto",
           chunk: int = 2048) -> jax.Array:
    """Dispatch on ``impl`` in {take, onehot, auto}."""
    if impl == "take":
        return take_gather(table, ids)
    if impl == "onehot":
        return onehot_gather(table, ids, chunk=chunk)
    if impl == "auto":
        if table.shape[0] <= ONEHOT_AUTO_MAX_ROWS:
            return onehot_gather(table, ids, chunk=chunk)
        return take_gather(table, ids)
    raise ValueError(f"unknown gather impl {impl!r}")
