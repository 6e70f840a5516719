"""Paper Fig. 1 analogue: single-device back projection throughput.

GUP/s (billions of voxel updates per second) per gather strategy for one
projection on one device — the paper's single-core SIMD comparison.
(The SMT column of Fig. 1 has no single-device analogue here; latency
hiding is the Pallas grid pipeline, measured structurally in fig3.)

After the per-strategy rows, ``fig1/batch/p*`` times the projection-
batched loop nest (DESIGN.md §7) against the per-projection nest at
several ``pbatch`` depths — same strategy, same projections, only the
volume-residency structure changes.  ``fig1/batch_db/p*`` and
``fig1/batch_shared*/p*`` then time the batched *Pallas kernel* variants
(DESIGN.md §9: deep DMA pipeline, shared superset window) on a smaller
kernel-sized volume — structural numbers in interpret mode off-TPU,
compiled on TPU, comparable within one backend either way.  Then the
autotuner sweeps its candidate space on this geometry (now including
the ``pbatch × {plain, db}`` cross), persists the winner
(``.repro_tune/``), and the ``fig1/auto`` row times ``strategy="auto"``
resolving through that cache — the chosen config lands in the
``--json`` trajectory via ``record_extra``.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.api import autotune, reconstruct
from repro.core.backproject import STRATEGIES, backproject_one
from repro.kernels.backproject_ops import pallas_backproject_batch

from .common import (STRATEGY_OPTS, bench_size, ct_problem, emit,
                     record_extra, time_fn)

PBATCHES = (1, 2, 4)
KERNEL_PBATCHES = (2, 4)


def run(L: int | None = None):
    L = bench_size(96, 16) if L is None else L
    n_proj = bench_size(4, 2)
    geom, filt, mats, _ = ct_problem(L, n_proj=n_proj)
    vol0 = jnp.zeros((L,) * 3, jnp.float32)
    image = jnp.asarray(filt[0])
    A = jnp.asarray(mats[0])
    for strat in STRATEGIES:
        t = time_fn(backproject_one, vol0, image, A, geom,
                    strategy=strat, warmup=1, iters=3,
                    **STRATEGY_OPTS[strat])
        emit(f"fig1/{strat}", t * 1e6,
             f"gups={L ** 3 / t / 1e9:.4f} L={L}")

    # Batched vs per-projection: full n_proj reconstruction per call,
    # pbatch=1 is the classical nest.  gups counts every voxel update.
    # Depths clamp to n_proj (tiny mode) — emit the *effective* depth
    # once, never a duplicate measurement under an inflated label.
    for pb in sorted({min(pb, n_proj) for pb in PBATCHES}):
        t = time_fn(reconstruct, filt, mats, geom, strategy="strip2",
                    pbatch=pb, warmup=1, iters=2,
                    **STRATEGY_OPTS["strip2"])
        emit(f"fig1/batch/p{pb}", t * 1e6,
             f"gups={n_proj * L ** 3 / t / 1e9:.4f} L={L} pbatch={pb} "
             f"nproj={n_proj}")

    # bf16 on the wire at the default batch depth: identical tap
    # semantics at half the strip bytes (f32 accumulate; DESIGN.md §10).
    pb16 = min(4, n_proj)
    t = time_fn(reconstruct, filt, mats, geom, strategy="strip2",
                pbatch=pb16, strip_dtype="bfloat16", warmup=1, iters=2,
                **STRATEGY_OPTS["strip2"])
    emit("fig1/strip2_bf16", t * 1e6,
         f"gups={n_proj * L ** 3 / t / 1e9:.4f} L={L} pbatch={pb16} "
         f"nproj={n_proj}")

    # Batched kernel variants: full n_proj stack per call through the
    # Pallas batch path, db (depth-2 rotation) and the shared window.
    # A smaller volume keeps interpret-mode (off-TPU) rows tractable;
    # the rows compare variants against each other, not against the jnp
    # rows above.
    Lk = bench_size(32, 16)
    geom_k, filt_k, mats_k, _ = ct_problem(Lk, n_proj=n_proj)
    vol0_k = jnp.zeros((Lk,) * 3, jnp.float32)
    tiles = dict(ty=8, chunk=min(32, Lk), band=16, width=128)
    for pb in sorted({min(pb, n_proj) for pb in KERNEL_PBATCHES}):
        for tag, flags in (("batch_db", dict(double_buffer=True,
                                             db_depth=2)),
                           ("batch_shared", dict(shared_window=True)),
                           ("batch_shared_bf16",
                            dict(shared_window=True,
                                 strip_dtype="bfloat16"))):
            # A wider sampling window than the 50 ms default: these rows
            # feed the tightened regression gate, and interpret-mode
            # medians over ~10 samples drift with host contention.
            t = time_fn(pallas_backproject_batch, vol0_k, filt_k, mats_k,
                        geom_k, pbatch=pb, warmup=1, iters=3,
                        min_total_s=0.3, **tiles, **flags)
            emit(f"fig1/{tag}/p{pb}", t * 1e6,
                 f"gups={n_proj * Lk ** 3 / t / 1e9:.4f} L={Lk} "
                 f"pbatch={pb} nproj={n_proj}")

    cfg = autotune(geom, image=image, A=A, warmup=1, iters=3)
    opts = dict(cfg.opts)
    pbatch = int(opts.pop("pbatch", 1))
    if pbatch == 1:
        t = time_fn(backproject_one, vol0, image, A, geom,
                    strategy=cfg.strategy, warmup=1, iters=3, **opts)
    else:
        # Same problem construction as the sweep that picked this
        # config: distinct matrices, so the strip-origin churn (and
        # therefore the cost) matches the number the tuner measured.
        from repro.core.backproject import backproject_batch
        from repro.tune.sweep import _batch_problem

        images, mats_b = _batch_problem(geom, image, pbatch)
        t = time_fn(backproject_batch, vol0, images, mats_b, geom,
                    strategy=cfg.strategy, pbatch=pbatch, warmup=1,
                    iters=3, **opts) / pbatch
    emit("fig1/auto", t * 1e6,
         f"gups={L ** 3 / t / 1e9:.4f} L={L} chosen={cfg.strategy} "
         f"pbatch={pbatch}")
    record_extra("tuned_config", cfg.as_dict())


if __name__ == "__main__":
    run()
