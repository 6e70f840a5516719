#!/usr/bin/env python3
"""Chip smoke test: the reconstruction path on a TPU at RabbitCT widths.

Runs the system through the entry points a user calls, in one process,
and checks every volume against a float64 numpy reference (FDK filter
and Listing 1 of the paper) on a few sampled z-planes:

* phase A, one-shot: ``repro.api.filter_projections`` then
  ``repro.api.reconstruct(strategy="strip2")``;
* phase B, the compiled kernel: ``pallas_backproject_batch`` (plain
  variant, f32 wire) on the same filtered stack;
* phase C, serving: the raw projections streamed through
  ``repro.api.CTFrontDoor`` as ``ProjectionChunk``s of one scan, the
  engine folding with the kernel as a tuned TPU deployment does.

``--four-chips`` instead runs only the mesh path users take for 1024³:
``sharded_reconstruct(prefiltered=False)`` on a 2x2 mesh (``data`` =
z-slabs, ``model`` = projection shards), and shows the volume is spread
over all four devices.

The geometry is RabbitCT's (1248x960 detector, 200° short scan, L=512 on
one chip, L=1024 on four); the projections are the analytic phantom's,
synthesised here.  Each phase runs once, cold, and prints its set-up
(compile) seconds, its wall seconds (ending in ``block_until_ready``,
compile included), the host planner seconds, its error against the
reference and the device's peak bytes so far.  These are set-up facts,
not benchmark numbers.

Without a TPU as JAX's first device, or run outside the repository
checkout, the script exits non-zero and prints no result line.  On
success the last line is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # 2x2 mesh
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

# Tolerance on the largest error over the sampled planes, relative to
# the largest reference magnitude there.  f32 rounding of the projective
# transform moves taps by ~1e-5 px at 1248-px detector coordinates, and
# the ramp-filtered phantom's sharp edges turn that into error: a
# float32 numpy emulation of Listing 1 at the one-chip geometry misses
# the float64 reference by 1.3e-4 of the volume scale.  The bound allows
# about 4x that for the device's own rounding (reciprocal, fused
# multiply-adds, summation order).  A single bf16 pass on the
# interpolation dot rounds the strip values alone to 8 bits, 1.8e-3 of
# the scale at the same geometry, so the bound rejects it.
REL_TOL = 5e-4

# Projections for the one-chip run: the smallest count the check
# accepts.  strip2 (phase A) folds ~6.5M voxel updates/s on one v5e, and
# its host planner validates 8-voxel groups at ~9 s per projection on
# the chip host, so phase A alone is ~8 min of the ~10 min cold run.
N_ONE_CHIP = 16
# Projections for the mesh run: even, so the two projection shards hold
# equal counts.
N_FOUR_CHIPS = 4
CHUNK = 4              # projections per streamed ProjectionChunk


def _log(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------------------
# Float64 reference, independent of the code under test
# ----------------------------------------------------------------------

def reference_filter(raw: np.ndarray, geom) -> np.ndarray:
    """FDK pre-processing in float64: cosine weights, Parker short-scan
    weights, ramp (band-limited Ram-Lak) filter along detector rows by
    linear FFT convolution, and the FDK constant."""
    n_u, n_v = geom.n_u, geom.n_v
    u = (np.arange(n_u) - (n_u - 1) / 2.0) * geom.du
    v = (np.arange(n_v) - (n_v - 1) / 2.0) * geom.dv
    cosw = geom.sdd / np.sqrt(geom.sdd ** 2 + u[None, :] ** 2
                              + v[:, None] ** 2)
    gamma = np.arctan2(u, geom.sdd)
    delta = float(np.abs(gamma).max())
    beta = (geom.angles - geom.angles[0])[:, None]
    assert math.pi + 2 * delta <= geom.sweep < 2 * math.pi, \
        "the reference implements the short-scan case only"
    with np.errstate(invalid="ignore", divide="ignore"):
        up = np.sin(math.pi / 4 * beta / (delta - gamma)) ** 2
        down = np.sin(math.pi / 4 * (math.pi + 2 * delta - beta)
                      / (delta + gamma)) ** 2
    parker = np.ones((geom.n_proj, n_u))
    parker = np.where(beta <= 2 * (delta - gamma), np.nan_to_num(up),
                      parker)
    parker = np.where(beta >= math.pi - 2 * gamma, np.nan_to_num(down),
                      parker)
    parker = np.where(beta > math.pi + 2 * delta, 0.0, parker)
    parker *= 2.0                      # the filter keeps FDK's 1/2
    pad = 1 << (2 * n_u - 1).bit_length()
    k = np.arange(-(pad // 2), pad - pad // 2)
    h = np.zeros(pad)
    h[k == 0] = 1.0 / (4 * geom.du ** 2)
    odd = k % 2 == 1
    h[odd] = -1.0 / (math.pi * k[odd] * geom.du) ** 2
    hf = np.fft.rfft(np.roll(h, -(pad // 2)))
    weighted = raw.astype(np.float64) * cosw * parker[:, None, :]
    ramp = np.fft.irfft(np.fft.rfft(weighted, n=pad, axis=-1) * hf,
                        n=pad, axis=-1)[..., :n_u]
    scale = geom.sweep / geom.n_proj * geom.sdd / (2 * geom.sid) * geom.du
    return ramp * scale


def reference_planes(filtered: np.ndarray, mats: np.ndarray, geom,
                     zs) -> np.ndarray:
    """Listing 1 in float64 on the z-planes ``zs``: project every voxel,
    floor-bilinear sample with zero outside the detector, weight by
    ``1/w²`` and sum over the projections."""
    L = geom.L
    c = geom.O + np.arange(L) * geom.MM
    wy, wx = np.meshgrid(c, c, indexing="ij")
    out = np.zeros((len(zs), L, L))
    for img, A in zip(filtered, mats):
        padded = np.pad(img, 1)
        for i, z in enumerate(zs):
            wz = geom.O + z * geom.MM
            u = A[0, 0] * wx + A[0, 1] * wy + A[0, 2] * wz + A[0, 3]
            v = A[1, 0] * wx + A[1, 1] * wy + A[1, 2] * wz + A[1, 3]
            w = A[2, 0] * wx + A[2, 1] * wy + A[2, 2] * wz + A[2, 3]
            ix, iy = u / w, v / w
            fx, fy = np.floor(ix), np.floor(iy)
            sx, sy = ix - fx, iy - fy
            # Taps outside the detector read the zero border.
            c0 = np.clip(fx + 1, 0, geom.n_u + 1).astype(np.int64)
            c1 = np.clip(fx + 2, 0, geom.n_u + 1).astype(np.int64)
            r0 = np.clip(fy + 1, 0, geom.n_v + 1).astype(np.int64)
            r1 = np.clip(fy + 2, 0, geom.n_v + 1).astype(np.int64)
            bot = (1 - sx) * padded[r0, c0] + sx * padded[r0, c1]
            top = (1 - sx) * padded[r1, c0] + sx * padded[r1, c1]
            out[i] += ((1 - sy) * bot + sy * top) / (w * w)
    return out


def check(name: str, volume, ref: np.ndarray, zs) -> dict:
    got = np.asarray(volume[np.asarray(zs)], np.float64)
    err = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    rel = err / scale
    ok = bool(np.isfinite(got).all() and rel <= REL_TOL)
    _log(f"  {name}: max_abs_err={err!r} max_rel_err={rel!r} "
         f"(tol {REL_TOL}, scale {scale!r}) {'PASS' if ok else 'FAIL'}")
    return {"max_abs_err": err, "max_rel_err": rel, "ok": ok}


# ----------------------------------------------------------------------
# Measurement helpers
# ----------------------------------------------------------------------

class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling (or loading
    from the persistent cache) since the last :meth:`take`."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self._jax = jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self._EVENTS:
            self.seconds += duration

    def take(self) -> float:
        s, self.seconds = self.seconds, 0.0
        return s

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on)


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def run_phase(name, fn, clock, jax, planner_s):
    """Call ``fn`` once, cold; the wall time ends in block_until_ready
    and includes the compile seconds reported beside it."""
    clock.take()
    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    wall = time.perf_counter() - t
    _log(f"phase {name}: compile_s={clock.take()!r} wall_s={wall!r} "
         f"planner_s={planner_s!r} "
         f"peak_device_bytes={peak_bytes(jax.devices()[:1])}")
    return out


# ----------------------------------------------------------------------
# Runs
# ----------------------------------------------------------------------

def synthesize(geom):
    from repro.core.geometry import projection_matrices, projection_matrix
    from repro.core.phantom import forward_project

    t = time.perf_counter()
    raw = forward_project(geom)
    mats = projection_matrices(geom)
    mats64 = np.stack([projection_matrix(geom, float(a))
                       for a in geom.angles])
    _log(f"synthesised {geom.n_proj} projections of {geom.n_v}x"
         f"{geom.n_u} in {time.perf_counter() - t!r} s")
    return raw, mats, mats64


def one_chip(jax, clock, geom) -> bool:
    import jax.numpy as jnp

    from repro.api import (CTFrontDoor, ExecutionPlan, ProjectionChunk,
                           filter_projections, reconstruct)
    from repro.core.backproject import GeomStatic, validate_strip_opts
    from repro.kernels.backproject_ops import (clamp_tiles,
                                               pallas_backproject_batch,
                                               validate_strip_config)
    from repro.tune.space import pallas_candidates

    gs = GeomStatic.of(geom)
    _log(f"geometry: L={geom.L} detector {geom.n_u}x{geom.n_v} sweep 200 "
         f"deg N={geom.n_proj}: N is the smallest allowed; strip2 folds "
         f"about 6.5M voxel updates/s on one v5e and its planner costs "
         f"~9 s per projection on the chip host, so phase A alone takes "
         f"~8 min")
    raw, mats, mats64 = synthesize(geom)
    zs = [geom.L // 8, geom.L // 2 - 1, geom.L // 2, 7 * geom.L // 8]
    t = time.perf_counter()
    ref = reference_planes(reference_filter(raw, geom), mats64, geom, zs)
    _log(f"reference: planes {zs} in {time.perf_counter() - t!r} s")
    results = []

    # --- phase A: one-shot filter + reconstruct ----------------------
    t = time.perf_counter()
    validate_strip_opts(geom, mats, "strip2", {})
    planner = time.perf_counter() - t
    filt = jax.block_until_ready(filter_projections(raw, geom))
    vol = run_phase(
        "A one-shot (filter_projections + reconstruct strip2)",
        lambda: reconstruct(filt, mats, geom, strategy="strip2"),
        clock, jax, planner)
    results.append(check("A", vol, ref, zs))
    del vol

    # --- phase B: the compiled Pallas kernel -------------------------
    # The tuner's base tile, clamped as the entry point clamps it.
    base = dict(pallas_candidates(gs)[0].opts)
    tiles = dict(zip(("ty", "chunk", "band", "width"), clamp_tiles(
        gs, base["ty"], base["chunk"], base["band"], base["width"])))
    t = time.perf_counter()
    for A in np.asarray(mats, np.float64):
        validate_strip_config(geom, A, **tiles)
    planner = time.perf_counter() - t
    vol0 = jnp.zeros((geom.L,) * 3, jnp.float32)

    def kernel(vol0, filt, mats):
        # The stack was validated above for exactly these tiles.
        return pallas_backproject_batch(vol0, filt, mats, geom, **tiles,
                                        validate=False)

    text = jax.jit(kernel).lower(vol0, filt, mats).as_text()
    if "tpu_custom_call" not in text:
        raise RuntimeError("phase B did not lower to a Mosaic kernel: the "
                           "entry point ran in interpret mode")
    _log(f"phase B lowers to tpu_custom_call: compiled, not interpreted; "
         f"tiles {tiles}")
    vol = run_phase("B pallas_backproject_batch (plain, f32 wire)",
                    lambda: kernel(vol0, filt, mats), clock, jax, planner)
    results.append(check("B", vol, ref, zs))
    del vol, vol0

    # --- phase C: the serving front door -----------------------------
    # The engine runs the plan a tuned TPU deployment resolves to: the
    # Pallas batch kernel folds each ready slot.  (Its jnp fold vmaps
    # strip2 over every slot, 2x phase A's minutes at n_slots=2.)  The
    # kernel tiles and matrices are the ones phase B validated.
    plan = ExecutionPlan("strip2", pbatch=CHUNK, use_pallas=True,
                         pallas=tuple(sorted({**tiles,
                                              "pbatch": CHUNK}.items())))
    door = CTFrontDoor(geom, n_slots=2, plan=plan, validate=False)
    chunks = [ProjectionChunk(raw[k:k + CHUNK], mats[k:k + CHUNK],
                              np.arange(k, min(k + CHUNK, geom.n_proj)))
              for k in range(0, geom.n_proj, CHUNK)]

    async def scan():
        ticket = await door.open_scan(tenant="smoke", n_proj=geom.n_proj)
        for ch in chunks:
            await door.submit(ticket, ch)
        return await door.result(ticket)

    vol = run_phase("C CTFrontDoor (n_slots=2, one scan, kernel fold)",
                    lambda: asyncio.run(scan()), clock, jax, 0.0)
    results.append(check("C", vol, ref, zs))
    return all(r["ok"] for r in results)


def four_chips(jax, clock, geom) -> bool:
    from repro.api import sharded_reconstruct
    from repro.launch.mesh import make_local_mesh

    if len(jax.devices()) != 4:
        raise RuntimeError(f"--four-chips needs 4 devices, found "
                           f"{len(jax.devices())}")
    _log(f"geometry: L={geom.L} detector {geom.n_u}x{geom.n_v} sweep 200 "
         f"deg N={geom.n_proj}: even N splits over the 2 projection "
         f"shards; strategy gather (needs no planner pass), ~20M voxel "
         f"updates/s per chip at L=1024")
    raw, mats, mats64 = synthesize(geom)
    half = geom.L // 2
    zs = [half // 4, half - 1, half, half + 3 * half // 4]
    t = time.perf_counter()
    ref = reference_planes(reference_filter(raw, geom), mats64, geom, zs)
    _log(f"reference: planes {zs} (both z-slabs) in "
         f"{time.perf_counter() - t!r} s")
    mesh = make_local_mesh(data=2, model=2)
    vol = run_phase(
        "mesh sharded_reconstruct(prefiltered=False, gather) 2x2",
        lambda: sharded_reconstruct(raw, mats, geom, mesh,
                                    strategy="gather", prefiltered=False),
        clock, jax, 0.0)
    shards = vol.addressable_shards
    spread = sorted({s.device.id for s in shards})
    slabs = sorted({(s.index[0].start or 0) for s in shards})
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in jax.devices()]
    _log(f"volume sharding {vol.sharding.spec} over devices {spread}; "
         f"z-slab starts {slabs}; shard shape {shards[0].data.shape}; "
         f"bytes_in_use per device {in_use}; peak per device "
         f"{peak_bytes(jax.devices())}")
    slab_bytes = half * geom.L * geom.L * 4
    spread_ok = (len(spread) == 4 and len(slabs) == 2
                 and all(b < 2 * slab_bytes for b in in_use))
    _log(f"  spread over 4 devices, each holding about one "
         f"{slab_bytes}-byte slab: {'PASS' if spread_ok else 'FAIL'}")
    return check("mesh", vol, ref, zs)["ok"] and spread_ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-mesh path at L=1024")
    args = ap.parse_args(argv)

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}; run from the "
              f"repository checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX's first device is {dev.platform!r}, not a "
              f"TPU; nothing was run", file=sys.stderr)
        return 1
    from repro.api import Geometry
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    clock = CompileClock(jax)
    _log(f"device: {dev.device_kind} x{len(jax.devices())}, jax "
         f"{jax.__version__}")
    try:
        if args.four_chips:
            # RabbitCT's 1024³ keeps the 256 mm field of view.
            ok = four_chips(jax, clock, Geometry(
                L=1024, voxel_mm=0.25, n_proj=N_FOUR_CHIPS))
        else:
            ok = one_chip(jax, clock, Geometry(n_proj=N_ONE_CHIP))
    finally:
        clock.close()
        cache.close()
    _log(f"{cache.summary()} ({'hit' if cache.hits else 'not hit'})")
    if not ok:
        print("chip_smoke: a phase missed its tolerance", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
