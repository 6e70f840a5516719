"""HLO text analysis: collective bytes, op census, roofline terms.

A compile-time "profiler" (no hardware needed): every roofline term is
derived from ``lowered.compile()`` artifacts —

* ``cost_analysis()``      -> per-device HLO flops + bytes accessed
* ``memory_analysis()``    -> per-device argument/temp/peak bytes
* ``as_text()``            -> collective ops, parsed here into bytes moved

and the paper-methodology op census (Table 2 analogue): classify every HLO
op into memory / shuffle / arithmetic / gather / other, exactly like the
paper classifies x86 instructions.

Hardware constants are TPU v5e per chip: 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (3D-torus link).
"""

from __future__ import annotations

import re
from collections import Counter

__all__ = ["parse_shape_bytes", "collective_bytes", "op_census",
           "roofline_terms", "PEAK_FLOPS", "HBM_BW", "ICI_BW"]

PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link
# Effective-bandwidth derate for gather/scatter element access: TPU has
# no vector-gather hardware (DESIGN.md §2); XLA:TPU lowers row gathers to
# serialised dynamic-slices, sustaining roughly 1/16 of stream bandwidth
# for 4-byte elements (one element per 64B+ transaction).  This plays the
# role of the paper's measured Table-4 gather latencies in the TPU model.
GATHER_DERATE = 16.0

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# Paper Table-2 instruction classes mapped to HLO opcodes.
_CLASS = {
    "memory": {"copy", "dynamic-slice", "dynamic-update-slice", "slice",
               "concatenate", "pad", "parameter", "constant", "iota",
               "broadcast"},
    "gather": {"gather", "scatter"},
    "shuffle": {"transpose", "reshape", "bitcast", "reverse", "select"},
    "arith": {"add", "subtract", "multiply", "divide", "dot", "fusion",
              "exponential", "log", "rsqrt", "sqrt", "maximum", "minimum",
              "compare", "convert", "negate", "power", "tanh", "floor",
              "and", "or", "xor", "reduce", "convolution"},
}


def parse_shape_bytes(typestr: str) -> int:
    """Total bytes of every shape literal in an HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(typestr):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _instr_lines(hlo_text: str):
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" in s and not s.startswith(("HloModule", "ENTRY", "}", "%")):
            yield s
        elif s.startswith("%") and "=" in s:
            yield s


def collective_bytes(hlo_text: str) -> dict:
    """Bytes moved per device by collectives, summed from the HLO.

    Convention: per op we count the *output* shape bytes, doubled for
    all-reduce (ring = reduce-scatter + all-gather).  ``start`` variants
    (async collectives) are counted once; ``done`` ops are skipped.
    Returns ``{op_kind: bytes, ..., "total": bytes}``.
    """
    out: Counter = Counter()
    for line in _instr_lines(hlo_text):
        lhs, _, rhs = line.partition("=")
        rhs = rhs.strip()
        m = re.match(r"(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
                     r"([a-z0-9-]+)", rhs)
        if not m:
            continue
        op = m.group(1)
        base = op.removesuffix("-start")
        if base not in COLLECTIVES or op.endswith("-done"):
            continue
        typestr = rhs[:m.start(1)]
        nbytes = parse_shape_bytes(typestr)
        if base == "all-reduce":
            nbytes *= 2
        out[base] += nbytes
    out["total"] = sum(v for k, v in out.items())
    return dict(out)


def op_census(hlo_text: str) -> dict:
    """Classify HLO ops paper-style: memory/shuffle/arith/gather/other.

    Counts *instruction instances* in the optimised module (fusions count
    once — like one x86 instruction retiring a pipeline of uops).
    """
    census: Counter = Counter()
    ops: Counter = Counter()
    for line in _instr_lines(hlo_text):
        rhs = line.partition("=")[2].strip()
        m = re.match(r"(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
                     r"([a-z0-9-]+)", rhs)
        if not m:
            continue
        op = m.group(1).removesuffix("-start").removesuffix("-done")
        ops[op] += 1
        for cls, names in _CLASS.items():
            if op in names:
                census[cls] += 1
                break
        else:
            census["other"] += 1
    census["total"] = sum(ops.values())
    return {"classes": dict(census), "ops": dict(ops)}


def roofline_terms(flops_dev: float, bytes_dev: float,
                   coll_bytes_dev: float) -> dict:
    """The three §Roofline terms, in seconds per step per device."""
    compute = flops_dev / PEAK_FLOPS
    memory = bytes_dev / HBM_BW
    collective = coll_bytes_dev / ICI_BW
    dominant = max(
        (("compute", compute), ("memory", memory),
         ("collective", collective)), key=lambda kv: kv[1])
    total = max(compute, memory, collective)
    return {
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": collective,
        "dominant": dominant[0],
        "bound_s": total,
    }
