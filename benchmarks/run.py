"""Benchmark harness: one module per paper table/figure.

Emits ``name,us_per_call,derived`` CSV lines.  Mapping to the paper:

==========================  ==============================================
module                      paper artifact
==========================  ==============================================
table2_op_census            Table 2 (instruction count/composition/part)
table3_efficiency           Table 3 (+ section-5 clipping-mask claim)
table4_gather_micro         Table 4 (gather latency vs distribution)
table5_traffic              beyond-paper: volume-HBM-traffic model vs time
fig1_single_device          Fig. 1 (single-core strategy comparison)
fig2_scaling                Fig. 2 (full-system scaling)
fig3_codegen                Fig. 3 (compiler vs hand-structured)
fig4_streaming              beyond-paper: streamed-engine time-to-first-
                            volume + projections/s at B concurrent scans
fig5_serving                beyond-paper: serving-tier TTFV + p50/p99
                            completion latency vs Poisson offered load
dispatch                    beyond-paper: auto-dispatch resolution cost
                            (cold in-situ selection vs warm cache hit)
cycle_model                 Section 6.4 (per-iteration cycle breakdown)
quality                     RabbitCT accuracy score (PSNR)
==========================  ==============================================

``python -m benchmarks.run [--only name[,name...]] [--json PATH] [--tiny]``

``--json PATH`` appends one machine-readable run entry (device meta,
every emitted row with its parsed ``key=value`` fields, and structured
extras such as the autotuner's chosen config) to ``PATH`` — the perf
trajectory file (``BENCH_ct.json``) every future PR extends.  ``--tiny``
shrinks the standard problems to CI-sized shapes.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.compile_cache import enable_compile_cache

from . import common
from . import (ct_hillclimb, cycle_model, dispatch, fig1_single_device,
               fig2_scaling, fig3_codegen, fig4_streaming, fig5_serving,
               quality, table2_op_census, table3_efficiency,
               table4_gather_micro, table5_traffic)

MODULES = [
    ("table2_op_census", table2_op_census),
    ("table3_efficiency", table3_efficiency),
    ("table4_gather_micro", table4_gather_micro),
    ("fig1_single_device", fig1_single_device),
    ("table5_traffic", table5_traffic),
    ("fig2_scaling", fig2_scaling),
    ("fig3_codegen", fig3_codegen),
    ("fig4_streaming", fig4_streaming),
    ("fig5_serving", fig5_serving),
    ("dispatch", dispatch),
    ("cycle_model", cycle_model),
    ("quality", quality),
    ("ct_hillclimb", ct_hillclimb),
]


def _write_json(path: str, ran: list[str], n_fail: int) -> None:
    """Append this run as one trajectory entry to ``path``."""
    from repro.tune import device_identity

    backend, device_kind = device_identity()
    entry = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "meta": {
            "backend": backend,
            "device_kind": device_kind,
            "jax_version": jax.__version__,
            "tiny": common.TINY,
            "modules": ran,
            "failures": n_fail,
        },
        "rows": common.RESULTS,
        "extras": common.EXTRAS,
    }
    p = Path(path)
    doc = {"runs": []}
    if p.is_file():
        try:
            old = json.loads(p.read_text())
            if isinstance(old, dict) and isinstance(old.get("runs"), list):
                doc = old
        except json.JSONDecodeError:
            pass                    # unreadable trajectory: start fresh
    doc["runs"].append(entry)
    p.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    print(f"# wrote {path} ({len(doc['runs'])} run(s))", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run selected modules (comma-separated names)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="append a machine-readable run entry to PATH")
    ap.add_argument("--tiny", action="store_true",
                    help="CI-sized problem shapes")
    args = ap.parse_args(argv)
    names = [n for n, _ in MODULES]
    only = None
    if args.only is not None:
        only = [n.strip() for n in args.only.split(",") if n.strip()]
        bad = [n for n in only if n not in names]
        if bad or not only:
            missing = ", ".join(repr(n) for n in (bad or [args.only]))
            print(f"unknown module {missing}; valid modules: "
                  f"{', '.join(names)}", file=sys.stderr)
            raise SystemExit(2)
    # Assign, don't latch: a prior in-process main(["--tiny"]) must not
    # leak tiny shapes into a later full-size run (RESULTS/EXTRAS were
    # already reset per invocation; TINY was not).
    common.TINY = bool(args.tiny) or common.TINY_ENV
    # Fresh collection state per invocation: a second in-process main()
    # (tests, notebooks) must not replay the previous run's rows/extras
    # into its --json trajectory entry.
    common.RESULTS.clear()
    common.EXTRAS.clear()
    cache = enable_compile_cache()
    print("name,us_per_call,derived")
    n_fail = 0
    ran = []
    try:
        for name, mod in MODULES:
            if only is not None and name not in only:
                continue
            ran.append(name)
            t0 = time.time()
            try:
                mod.run()
                print(f"# {name} done in {time.time() - t0:.1f}s",
                      flush=True)
            except Exception:  # noqa: BLE001 — keep the harness going
                n_fail += 1
                print(f"# {name} FAILED:")
                traceback.print_exc()
        print(f"# {cache.summary()}", flush=True)
    finally:
        cache.close()
    if args.json:
        _write_json(args.json, ran, n_fail)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
