"""Pallas TPU kernel: fused sLSTM recurrence (hillclimb LM-1).

The xlstm train/prefill cells are bound by the sLSTM token scan: XLA
keeps the (c, n, h, m) state and per-step gate tensors in HBM, so every
token pays ~10 state-array reads/writes — the roofline table shows the
memory term 500x above compute.  Unrolling cannot fix it (iteration 1,
refuted: XLA does not fuse across the sequential dependency).  This
kernel does what the XLA schedule cannot:

* state lives in VMEM scratch for the *entire sequence*;
* gate pre-activations stream HBM->VMEM in ``(TB, T_c, 4, TD)`` chunks,
  hidden states stream back per chunk;
* HBM traffic collapses to one read of ``zifo`` + one write of ``h``:
  ``5 * di * 4`` bytes/token instead of ~``40 * di``.

Feature dims are fully elementwise in the sLSTM cell, so the grid tiles
(batch x d_inner) are embarrassingly parallel.  Validated against
``repro.models.ssm.slstm_forward`` in ``tests/test_kernel_slstm.py``
(interpret mode; TPU is the target).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["slstm_kernel", "slstm_pallas"]


def slstm_kernel(r_ref, zifo_ref, hs_ref, c_ref, n_ref, h_ref, m_ref,
                 zbuf, obuf, sem_in, sem_out, *, seq_chunk: int,
                 n_chunks: int, tb: int, td: int):
    """One grid step: the full sequence for a (TB, TD) feature tile."""
    b = pl.program_id(0)
    d = pl.program_id(1)

    c_ref[...] = jnp.zeros_like(c_ref)
    n_ref[...] = jnp.zeros_like(n_ref)
    h_ref[...] = jnp.zeros_like(h_ref)
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    r = r_ref[...]                                        # (4, TD)

    def in_copy(ci):
        return pltpu.make_async_copy(
            zifo_ref.at[pl.ds(b * tb, tb),
                        pl.ds(ci * seq_chunk, seq_chunk),
                        slice(None), pl.ds(d * td, td)],
            zbuf, sem_in)

    def out_copy(ci):
        return pltpu.make_async_copy(
            obuf,
            hs_ref.at[pl.ds(b * tb, tb),
                      pl.ds(ci * seq_chunk, seq_chunk),
                      pl.ds(d * td, td)],
            sem_out)

    def chunk_body(ci, _):
        in_copy(ci).start()
        in_copy(ci).wait()

        def tok(t, _):
            z_in = zbuf[:, t, 0, :].astype(jnp.float32)   # (TB, TD)
            i_in = zbuf[:, t, 1, :].astype(jnp.float32)
            f_in = zbuf[:, t, 2, :].astype(jnp.float32)
            o_in = zbuf[:, t, 3, :].astype(jnp.float32)
            h = h_ref[...]
            zt = jnp.tanh(z_in + r[0] * h)
            ig = i_in + r[1] * h
            fg = f_in + r[2] * h
            og = jax.nn.sigmoid(o_in + r[3] * h)
            logf = -jax.nn.softplus(-fg)
            m = m_ref[...]
            m_new = jnp.maximum(logf + m, ig)
            dec = jnp.exp(logf + m - m_new)
            inc = jnp.exp(ig - m_new)
            c_new = c_ref[...] * dec + inc * zt
            n_new = n_ref[...] * dec + inc
            h_new = og * c_new / jnp.maximum(n_new, 1e-6)
            c_ref[...] = c_new
            n_ref[...] = n_new
            h_ref[...] = h_new
            m_ref[...] = m_new
            obuf[:, t, :] = h_new.astype(obuf.dtype)
            return 0

        jax.lax.fori_loop(0, seq_chunk, tok, 0)
        out_copy(ci).start()
        out_copy(ci).wait()
        return 0

    jax.lax.fori_loop(0, n_chunks, chunk_body, 0)


def slstm_pallas(zifo, r, *, tb: int = 8, td: int = 128,
                 seq_chunk: int = 256, interpret: bool = False):
    """Run the fused recurrence.

    ``zifo``: (B, S, 4, di) gate pre-activations; ``r``: (4, di) diag
    recurrence weights.  Returns hidden states (B, S, di) in
    ``zifo.dtype``.  B, di, S are padded by ops.py to tile multiples.
    """
    B, S, four, di = zifo.shape
    assert four == 4
    assert B % tb == 0 and di % td == 0 and S % seq_chunk == 0, \
        (B, S, di, tb, td, seq_chunk)
    grid = (B // tb, di // td)
    n_chunks = S // seq_chunk

    kernel = functools.partial(
        slstm_kernel, seq_chunk=seq_chunk, n_chunks=n_chunks, tb=tb,
        td=td)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((4, td), lambda b, d: (0, d)),   # r tile
            pl.BlockSpec(memory_space=pl.ANY),            # zifo (HBM)
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),      # hs (HBM)
        out_shape=jax.ShapeDtypeStruct((B, S, di), zifo.dtype),
        scratch_shapes=[
            pltpu.VMEM((tb, td), jnp.float32),            # c
            pltpu.VMEM((tb, td), jnp.float32),            # n
            pltpu.VMEM((tb, td), jnp.float32),            # h
            pltpu.VMEM((tb, td), jnp.float32),            # m
            pltpu.VMEM((tb, seq_chunk, 4, td), zifo.dtype),
            pltpu.VMEM((tb, seq_chunk, td), zifo.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
        name="slstm_fused",
    )(r, zifo)
