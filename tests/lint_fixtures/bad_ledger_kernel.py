"""Seeded known-bad kernel: the last projection's DMA is never awaited.

A batch-style kernel stub for the DMA-ledger replay
(``repro.analysis.lint.ledger.replay_fixture``): it issues one strip
copy per projection through the usual 2-slot rotation but only waits
while a *next* projection exists — the final copy of every grid step
leaks.  On hardware that is a semaphore left signalled into the next
grid step (and a slot overwritten while its copy is in flight); the
ledger must flag it (``unwaited-dma`` at finish, ``slot-overwrite`` /
``wait-descriptor-mismatch`` as later steps reuse the leaked slot).

``pl``/``pltpu``/``jax`` are module globals so the replay harness can
swap in its recording stubs; the module is never imported outside the
lint tests.
"""

import jax  # noqa: F401  (replaced by the replay harness)
import jax.numpy as jnp

pl = None      # patched to the recording stubs by the replay harness
pltpu = None

SPEC = {"name": "unbalanced-batch", "kind": "batch", "pbatch": 4}


def kernel(zrow_ref, A_ref, imgs_ref, vol_in_ref, vol_out_ref, strip_ref,
           acc_ref, sems, *, o_mm, n_u, n_v, ty, chunk, band, width, pbatch,
           quantized=False):
    acc_ref[...] = vol_in_ref[0].astype(jnp.float32)

    def body(p, _):
        slot = jax.lax.rem(p, 2)
        copy = pltpu.make_async_copy(
            imgs_ref.at[p, pl.ds(0, band), pl.ds(0, width)],
            strip_ref.at[slot], sems.at[slot])
        copy.start()

        # BUG under test: the guard skips the wait for the final
        # projection, so its copy is never consumed.
        @pl.when(p + 1 < pbatch)
        def _():
            copy.wait()

        return 0

    jax.lax.fori_loop(0, pbatch, body, 0)
    vol_out_ref[...] = vol_in_ref[...]
