"""One run of one cell: set up, warm up, measure, check, report.

The result is one JSON line on standard output, the last thing printed
there.  Each number the correctness check compares is printed with its
limit as the last lines on standard error, and under ``checks``, the
result's last key.
"""

from __future__ import annotations

import glob
import json
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from . import counts, phantom
from .acq import Acq, projection_matrices
from .cell import entry, load_cell, reader
from .program import Sampler
from .reference import Reference, control_values
from .tracing import CompileClock, Spans, device_events, reduce

# Voxels read from each answer, drawn from the seed once per run.
N_VOXELS = 4096
# Answers compared at most, drawn from the seed among those the window
# produced, so that the float64 reference stays shorter than the window.
MAX_CHECKED = 12


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def voxel_sample(seed: int, L: int, n: int = N_VOXELS) -> np.ndarray:
    """``(n, 3)`` distinct voxel indices ``(z, y, x)``."""
    rng = np.random.default_rng([seed, 2])
    flat = rng.choice(L ** 3, size=min(n, L ** 3), replace=False)
    return np.stack(np.unravel_index(flat, (L, L, L)), axis=1)


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """Largest error over the voxels, relative to the largest reference
    magnitude there; ``inf`` for a non-finite answer."""
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def pick(answers, seed: int) -> list:
    """The answers to compare: all, or a seeded sample of
    :data:`MAX_CHECKED`."""
    if len(answers) <= MAX_CHECKED:
        return list(answers)
    keep = np.random.default_rng([seed, 3]).choice(
        len(answers), MAX_CHECKED, replace=False)
    return [answers[i] for i in sorted(keep.tolist())]


def check(cell, reference, answers) -> dict:
    """Compare each answer ``(views, values)`` with the float64 reference
    over the same views; returns the worst error, its limit, and the
    answers and views that missed it."""
    limit = float(cell.limits["max_rel_err"]["limit"])
    errs = [(rel_err(got, reference(views)), len(views))
            for views, got in answers]
    worst = max((e for e, _ in errs), default=float("inf"))
    return {"max_rel_err": worst, "limit": limit, "checked": len(errs),
            "failed": sum(not e <= limit for e, _ in errs),
            "failed_views": sum(n for e, n in errs if not e <= limit)}


def controlled(answers, raw, mats, vox, acq: Acq) -> list:
    """The same answers computed by the control in the program's place:
    the reference in float32 with its transform at ``HIGH``."""
    return [(views, control_values(raw[views], views, mats[views], vox,
                                   acq)) for views, _ in answers]


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _trace_numbers(trace_dir: str, layers: dict, chips: int):
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise RuntimeError("the profiler wrote no trace")
    per_device, host = device_events(paths[0])
    planes = sorted(per_device)[:chips]
    if not planes:
        raise RuntimeError("the trace holds no TPU operations")
    red = [reduce(per_device[p], host, layers) for p in planes]
    out = dict(red[0])
    out["busy_s"] = sum(r["busy_s"] for r in red) / len(red)
    out["layer_s"] = {k: sum(r["layer_s"][k] for r in red) / len(red)
                      for k in layers}
    return out


def execute(root: Path, workload: str, seed: int, seconds: float,
            trace: bool, *, t_start: float,
            require_chip: bool = True, control: bool = False) -> dict:
    """Run the cell once and return the result line's object.

    ``require_chip=False`` skips the look for a TPU (tests on the CPU);
    otherwise a missing TPU, too few chips or a chip with no row in the
    peaks table raises :class:`NoChip` or ``KeyError`` before any work.
    ``control=True`` puts the control in the program's place once the
    window has closed: the answers the window produced are computed
    again by the control, over the same views at the same voxels, and
    those are what the check compares and the result reports; the
    program's own reading is logged beside it.
    """
    cell = load_cell(workload, root)
    src = root / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    import jax

    devices = jax.devices()
    dev = devices[0]
    peak = None
    if require_chip:
        if dev.platform != "tpu":
            raise NoChip(f"JAX's first device is {dev.platform!r}, not a "
                         f"TPU")
        if len(devices) < cell.chips:
            raise NoChip(f"the cell asks for {cell.chips} chips; JAX found "
                         f"{len(devices)}")
        peak = counts.peaks(dev.device_kind)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    clock = CompileClock(jax)
    try:
        acq = Acq.from_config(cell.config["geometry"])
        t = time.perf_counter()
        raw = phantom.generate(acq, seed)
        mats = projection_matrices(acq)
        _log(f"data: {acq.n_proj} views of {acq.n_v}x{acq.n_u} in "
             f"{time.perf_counter() - t!r} s")
        vox = voxel_sample(seed, acq.L)
        traffic = entry(cell)(cell, acq, raw, mats, seed,
                              Sampler(vox, acq.L))
        traffic.build()
        traffic.warm_up()
        setup_compile_s, setup_programs = clock.take()
        setup_s = time.perf_counter() - t_start
        _log(f"set-up: {setup_s!r} s, compile {setup_compile_s!r} s over "
             f"{setup_programs} programs; {cache.summary()}")

        spans = Spans()
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if trace else None
        try:
            if trace:
                # Device operations and annotations only: the Python
                # tracer would record every call the host makes, slow the
                # window and swell the trace.
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=options)
            work = traffic.window(seconds, spans)
            if trace:
                jax.profiler.stop_trace()
            window_compile_s, window_programs = clock.take()
            mem = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                   for d in devices[:cell.chips]]
            memory_peak = max((m for m in mem if m is not None),
                              default=None)
            answers = traffic.results()
            del traffic                      # the program's state goes
            traced = (_trace_numbers(trace_dir, cell.traffic["layers"],
                                     cell.chips) if trace else None)
        finally:
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
        _log(f"window: {work['window_s']!r} s, {work['views']} views in "
             f"{work['passes']} volume passes; {window_programs} programs "
             f"compiled or loaded inside it ({window_compile_s!r} s)")

        t = time.perf_counter()
        answers = pick(answers, seed)
        reference = Reference(raw, mats, vox, acq)
        verdict = check(cell, reference, answers)
        _log(f"reference: {verdict['checked']} answers over "
             f"{len(reference.share)} views in "
             f"{time.perf_counter() - t!r} s")
        if control:
            _log(f"program max_rel_err value={verdict['max_rel_err']!r}")
            verdict = check(cell, reference,
                            controlled(answers, raw, mats, vox, acq))
    finally:
        clock.close()
        cache.close()

    run = SimpleNamespace(cell=cell, acq=acq, work=work, spans=spans,
                          trace=traced, peak=peak,
                          setup_compile_s=setup_compile_s)
    gups = work["views"] * acq.voxels / work["window_s"] / 1e9
    e2e = {"gups": gups, "setup_s": setup_s,
           "peak_hbm_gb": (memory_peak / 1e9 if memory_peak is not None
                           else None)}
    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = reader(m["name"], root)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": verdict["failed"] == 0 and verdict["checked"] > 0,
              "attempted": work["views"],
              "failed": verdict["failed_views"],
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = work["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["window_programs_compiled"] = window_programs
    result["checks"] = {"max_rel_err": {"value": verdict["max_rel_err"],
                                        "limit": verdict["limit"]}}
    return result


def main(argv, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run one cell of the chip benchmark once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path.cwd()
    try:
        result = execute(root, args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=t_start)
    except Exception:  # noqa: BLE001 — any failure: no result, non-zero
        traceback.print_exc()
        return 1
    c = result["checks"]["max_rel_err"]
    _log(f"check max_rel_err value={c['value']!r} limit={c['limit']!r} "
         f"{'PASS' if result['correct'] else 'FAIL'}")
    print(json.dumps(result), flush=True)
    return 0
