"""Tuned-cache audit: re-validate persisted decisions against today's
planner.

``load_tuned`` already rejects wrong-schema and corrupt files — but
*silently*, by treating them as untuned, and it never re-checks a
schema-valid config against the current planner.  A config tuned before
a planner or kernel change can therefore be schema-v5-clean yet name a
window the planner now proves undersized (silent tap loss, PR 4/5's
bug class), a strategy the resolver would quietly shed options from, or
a working set over the VMEM screen.  This pass makes all of that a lint
finding; the same :func:`audit_tuned_config` runs inside the
``Dispatcher`` at resolve time, where a failing cached config produces
one structured warning and falls back to in-situ selection
(DESIGN.md §11) instead of executing a stale window.
"""

from __future__ import annotations

import json
import re
from pathlib import Path

import numpy as np

from repro.core.backproject import STRATEGIES, GeomStatic

from .budget import WIRE_ITEMSIZE, estimate_for_pallas_config
from .common import Finding, PassResult

__all__ = ["parse_cache_key", "geometry_for", "audit_tuned_config",
           "audit_cache_file", "run_cache_audit_pass"]

# cache_key() layout: ct-L{L}-u{n_u}-v{n_v}-O{O:g}-MM{MM:g}--{backend}--
# {device_kind}.  O/MM are %g floats (may carry '-' or exponents), so
# the geometry fields anchor on their labels, non-greedily.
_KEY_RE = re.compile(
    r"^ct-L(?P<L>\d+)-u(?P<u>\d+)-v(?P<v>\d+)"
    r"-O(?P<O>.+?)-MM(?P<MM>.+?)--(?P<backend>.+?)--(?P<device>.+)$")

# Planner validation is exact-per-matrix; auditing every projection of a
# production scan at resolve time would cost more than the sweep it
# guards.  The footprint extremes move smoothly with angle, so an even
# angular sample bounds them tightly.
_MAX_AUDIT_MATS = 8


def parse_cache_key(stem: str):
    """``(GeomStatic, backend, device_kind)`` from a cache-file stem, or
    ``None`` when the name is not a cache key."""
    m = _KEY_RE.match(stem)
    if not m:
        return None
    try:
        gs = GeomStatic(L=int(m["L"]), n_u=int(m["u"]), n_v=int(m["v"]),
                        O=float(m["O"]), MM=float(m["MM"]))
    except ValueError:
        return None
    return gs, m["backend"], m["device"]


def geometry_for(gs: GeomStatic):
    """Full ``Geometry`` matching ``gs``, when one is reconstructible.

    A cache file stores only the static key, not the full geometry; the
    repo's geometries are all ``default_geometry().scaled(L)``, so that
    round-trip is attempted and verified.  Returns ``None`` when the key
    belongs to some other parameterisation — the audit then runs its
    static checks only.
    """
    from repro.core.geometry import default_geometry

    try:
        geom = default_geometry().scaled(gs.L)
    except Exception:
        return None
    return geom if GeomStatic.of(geom) == gs else None


def _sampled_matrices(geom):
    from repro.core.geometry import projection_matrices

    mats = np.asarray(projection_matrices(geom), np.float64)
    if len(mats) > _MAX_AUDIT_MATS:
        idx = np.linspace(0, len(mats) - 1, _MAX_AUDIT_MATS).astype(int)
        mats = mats[idx]
    return mats


def audit_tuned_config(gs: GeomStatic, cfg, geom=None) -> list:
    """Reasons this TunedConfig must not be replayed; empty when sound.

    Static checks always run (strategy/option-key membership, wire
    dtype, the VMEM byte model); with a full ``geom`` the planner
    re-validates the jnp window and the Pallas tile/shared-window
    coverage exactly as the execution wrappers would.
    """
    from repro.tune.cache import _PALLAS_KEYS, _STRATEGY_KEYS

    reasons = []
    if cfg.strategy not in STRATEGIES:
        reasons.append(f"strategy {cfg.strategy!r} is not a known jnp "
                       f"strategy {STRATEGIES}")
        return reasons
    allowed = _STRATEGY_KEYS[cfg.strategy]
    opts = dict(cfg.opts or {})
    stray = sorted(k for k in opts if k not in allowed)
    if stray:
        reasons.append(f"opts {stray} are not accepted by strategy "
                       f"{cfg.strategy!r} — the resolver would shed them")
    wire = opts.get("strip_dtype", "float32")
    if wire not in WIRE_ITEMSIZE:
        reasons.append(f"opts strip_dtype {wire!r} is not a known wire "
                       f"dtype {tuple(WIRE_ITEMSIZE)}")
    pallas = dict(cfg.pallas or {})
    if pallas:
        stray = sorted(k for k in pallas if k not in _PALLAS_KEYS)
        if stray:
            reasons.append(f"pallas keys {stray} are unknown to the "
                           f"kernel config surface {_PALLAS_KEYS}")
        pwire = pallas.get("strip_dtype", "float32")
        if pwire not in WIRE_ITEMSIZE:
            reasons.append(f"pallas strip_dtype {pwire!r} is not a known "
                           f"wire dtype {tuple(WIRE_ITEMSIZE)}")
        else:
            est = estimate_for_pallas_config(gs, pallas)
            if not est.fits:
                reasons.append(
                    f"pallas config working set {est.vmem_total} B "
                    f"exceeds the {est.budget} B VMEM budget "
                    f"(strips={est.strip_bytes}, tile={est.tile_bytes}, "
                    f"onehot={est.onehot_bytes}, "
                    f"scales={est.scale_bytes})")
    if geom is None:
        return reasons

    mats = _sampled_matrices(geom)
    from repro.core.backproject import validate_strip_opts

    try:
        validate_strip_opts(geom, mats, cfg.strategy,
                            {k: v for k, v in opts.items()
                             if k in allowed})
    except ValueError as e:
        reasons.append(f"jnp window fails the current planner: {e}")
    if pallas and pallas.get("strip_dtype",
                             "float32") in WIRE_ITEMSIZE:
        from repro.kernels.backproject_ops import (clamp_tiles,
                                                   shared_window_dims,
                                                   validate_strip_config)

        ty, chunk, band, width = clamp_tiles(
            gs, int(pallas.get("ty", 8)), int(pallas.get("chunk", 128)),
            int(pallas.get("band", 16)), int(pallas.get("width", 512)))
        for A in mats:
            try:
                validate_strip_config(geom, A, ty=ty, chunk=chunk,
                                      band=band, width=width)
            except ValueError as e:
                reasons.append(
                    f"pallas tile fails the current planner: {e}")
                break
        if pallas.get("shared_window", False):
            try:
                shared_window_dims(
                    geom, mats, ty=ty, chunk=chunk,
                    pbatch=max(1, int(pallas.get("pbatch", 1))),
                    shared_band=pallas.get("shared_band"),
                    shared_width=pallas.get("shared_width"))
            except ValueError as e:
                reasons.append(
                    f"shared window fails the current planner: {e}")
    return reasons


def audit_cache_file(path) -> list:
    """Findings for one ``.repro_tune/`` JSON file."""
    from repro.tune.cache import TUNE_SCHEMA_VERSION, TunedConfig

    path = Path(path)
    where = str(path)
    parsed = parse_cache_key(path.stem)
    if parsed is None:
        return [Finding("cache", "unparseable-key", where,
                        "file name is not a cache key — load_tuned can "
                        "never hit it; delete or re-tune")]
    gs, _backend, _device = parsed
    try:
        data = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        return [Finding("cache", "corrupt-file", where,
                        f"not valid JSON ({e}); load_tuned silently "
                        f"treats this as untuned")]
    if not isinstance(data, dict) \
            or data.get("version") != TUNE_SCHEMA_VERSION:
        return [Finding(
            "cache", "stale-schema", where,
            f"schema version {data.get('version') if isinstance(data, dict) else None!r} "
            f"!= current {TUNE_SCHEMA_VERSION}; load_tuned silently "
            f"ignores it — re-tune or delete")]
    try:
        cfg = TunedConfig(**data)
    except TypeError as e:
        return [Finding("cache", "malformed-config", where,
                        f"fields do not load into TunedConfig ({e})")]
    return [Finding("cache", "planner-invalid", where, reason)
            for reason in audit_tuned_config(gs, cfg,
                                             geom=geometry_for(gs))]


def run_cache_audit_pass(dirpath=None) -> PassResult:
    """Audit every JSON file under the tune dir (default
    ``tune_dir()``)."""
    from repro.tune.cache import tune_dir

    d = Path(dirpath) if dirpath is not None else tune_dir()
    findings, checked, notes = [], 0, []
    if not d.is_dir():
        notes.append(f"tune dir {d} does not exist — nothing cached")
        return PassResult("cache", findings, checked, notes)
    for path in sorted(d.glob("*.json")):
        findings += audit_cache_file(path)
        checked += 1
    if checked == 0:
        notes.append(f"tune dir {d} holds no cache files")
    return PassResult("cache", findings, checked, notes)
