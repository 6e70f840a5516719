"""TunedConfig cache: per-(geometry, backend, device) strategy decisions.

A tuned decision is keyed on ``(GeomStatic, backend, device_kind)`` — the
paper's finding restated as a cache key: the winning gather scheme is a
property of the *chip*, not of the algorithm, so decisions made on one
device kind must never leak to another.  Decisions persist as one JSON
file per key under ``.repro_tune/`` (override with ``REPRO_TUNE_DIR``) so
a sweep paid once amortises across processes; an in-process dict
memoises hits.

``strategy="auto"`` consumers call :func:`resolve_strategy` (jnp paths)
or :func:`resolve_pallas_config` (kernel path); both fall back to the
current hard-coded defaults when the key was never tuned, so ``auto`` is
always safe to request.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
from pathlib import Path

import jax

from repro.core.backproject import STRATEGIES, GeomStatic

__all__ = ["TunedConfig", "DEFAULT_STRATEGY", "TUNE_SCHEMA_VERSION",
           "tune_dir", "cache_key",
           "store_tuned", "load_tuned", "clear_memory_cache",
           "device_identity", "filter_strategy_opts", "resolve_strategy",
           "resolve_pallas_config", "autotune"]

# What "auto" means before anyone has tuned: the repo's historical
# hard-coded default.
DEFAULT_STRATEGY = "strip2"

# Bumped whenever the persisted TunedConfig layout or the semantics of a
# tuned decision change (v2: the ``pbatch`` axis — a v1 decision timed
# the per-projection loop nest, which no longer exists; v3: batched
# kernel candidates carry ``double_buffer``/``db_depth`` and
# the batch path *honors* them — a v2 decision's variant flags were
# timed against a batch path that silently shed them, so replaying one
# would misattribute its numbers; v4: the ``strip_dtype`` and
# ``shared_window`` axes — a v3 decision predates the bf16-wire and
# superset-window variants, so its "best" never competed against them
# and replaying it would freeze the old design space; v5: the
# ``strip_dtype="int8"`` axis — a v4 decision's wire-dtype winner never
# competed against the per-row-affine int8 candidates, and the VMEM
# screen is now itemsize-aware at 1 byte; v6: the micro-window kernel
# variants are gone and kernel tiles are TPU block-legal, so a v5
# decision may name a kernel that no longer exists).  ``load_tuned``
# treats any other version as untuned, so stale ``.repro_tune/`` files
# are *ignored*, never misread into the new dataclass.
TUNE_SCHEMA_VERSION = 6

# ``db_depth`` rides along with ``double_buffer``: the depth is part of
# the timed pipeline shape, and ``shared_band``/``shared_width`` with
# ``shared_window`` (``None`` dims auto-size from the group planner at
# resolve time, so they are usually absent).  ``strip_dtype`` is the
# wire dtype the decision was timed at.
_PALLAS_KEYS = ("ty", "chunk", "band", "width", "double_buffer",
                "db_depth", "shared_window", "shared_band",
                "shared_width", "strip_dtype", "pbatch")

# Options each jnp strategy actually accepts — caller options riding
# along with strategy="auto" are filtered to the *resolved* strategy, so
# a strip2-flavoured option can never reach e.g. sample_onehot(**opts).
# ``pbatch`` is strategy-independent (the batch-major loop nest wraps
# every strategy); ``reconstruct``/``sharded_reconstruct`` pop it before
# options reach any ``sample_*``.
_STRATEGY_KEYS = {
    "scalar": ("pbatch",),
    "gather": ("pbatch",),
    "onehot": ("vox_block", "pbatch"),
    "strip": ("chunk", "band", "width", "strips_per_block", "strip_dtype",
              "pbatch"),
    "strip2": ("group", "gband", "gwidth", "groups_per_block",
               "strip_dtype", "pbatch"),
}

# Every option name *some* jnp strategy accepts.  A caller key outside
# this set is a typo (or an option from a different universe, e.g. a
# Pallas tile key) and raises; a key inside it that the resolved
# strategy does not accept is shed with a warning.
KNOWN_OPTION_KEYS = frozenset(
    k for keys in _STRATEGY_KEYS.values() for k in keys)


def filter_strategy_opts(strategy: str, opts: dict | None, *,
                         strict: bool = False,
                         context: str = "resolve_strategy") -> dict:
    """Filter caller options down to what ``strategy`` accepts — loudly.

    Unknown keys (not accepted by *any* jnp strategy) always raise: a
    typo'd option must never be silently dropped.  Known keys the
    resolved strategy does not accept are shed with a ``RuntimeWarning``
    (``strict=False`` — the ``auto`` path, where the cache may have
    resolved a different strategy than the caller's options were written
    for) or raise (``strict=True`` — an explicitly named strategy, where
    an inapplicable option is a caller bug).
    """
    out, shed = {}, []
    allowed = _STRATEGY_KEYS[strategy]
    for k, v in dict(opts or {}).items():
        if k in allowed:
            out[k] = v
        elif k in KNOWN_OPTION_KEYS:
            shed.append(k)
        else:
            raise ValueError(
                f"{context}: unknown option {k!r} (no jnp strategy "
                f"accepts it); known options: "
                f"{tuple(sorted(KNOWN_OPTION_KEYS))}")
    if shed:
        msg = (f"{context}: option(s) {sorted(shed)} do not apply to "
               f"strategy {strategy!r} (accepts {tuple(allowed)})")
        if strict:
            raise ValueError(msg)
        import warnings

        warnings.warn(msg + "; shedding them", RuntimeWarning,
                      stacklevel=3)
    return out


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """One cached decision plus the sweep evidence behind it."""

    strategy: str                   # best jnp strategy (in STRATEGIES)
    opts: dict                      # its tile options (incl. ``pbatch``)
    backend: str
    device_kind: str
    us_per_call: float              # best jnp median time per projection
    pallas: dict | None = None      # best kernel config, when swept
    pallas_us: float | None = None
    timings: list = dataclasses.field(default_factory=list)
    version: int = TUNE_SCHEMA_VERSION

    @property
    def pbatch(self) -> int:
        """Projection batch depth of the winning jnp decision."""
        from repro.core.backproject import DEFAULT_PBATCH

        return int(self.opts.get("pbatch", DEFAULT_PBATCH))

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def tune_dir() -> Path:
    return Path(os.environ.get("REPRO_TUNE_DIR", ".repro_tune"))


def _sanitize(s: str) -> str:
    return re.sub(r"[^A-Za-z0-9._-]+", "-", s)


def device_identity(backend: str | None = None,
                    device_kind: str | None = None) -> tuple[str, str]:
    """The ``(backend, device_kind)`` pair cache keys and bench metadata
    are built from — one definition so they can never disagree."""
    if backend is None:
        backend = jax.default_backend()
    if device_kind is None:
        dev = jax.devices()[0]
        device_kind = getattr(dev, "device_kind", str(dev))
    return backend, device_kind


def cache_key(gs: GeomStatic, backend: str, device_kind: str) -> str:
    geom = (f"ct-L{gs.L}-u{gs.n_u}-v{gs.n_v}"
            f"-O{gs.O:g}-MM{gs.MM:g}")
    return f"{geom}--{_sanitize(backend)}--{_sanitize(device_kind)}"


_MEM: dict[tuple[str, str], TunedConfig] = {}


def clear_memory_cache() -> None:
    """Drop in-process memoised decisions (tests; tune-dir swaps)."""
    _MEM.clear()


def store_tuned(gs: GeomStatic, cfg: TunedConfig,
                dirpath: str | os.PathLike | None = None) -> Path:
    d = Path(dirpath) if dirpath is not None else tune_dir()
    d.mkdir(parents=True, exist_ok=True)
    key = cache_key(gs, cfg.backend, cfg.device_kind)
    path = d / f"{key}.json"
    path.write_text(json.dumps(cfg.as_dict(), indent=2, sort_keys=True))
    _MEM[(str(d), key)] = cfg
    return path


def load_tuned(gs: GeomStatic, backend: str | None = None,
               device_kind: str | None = None,
               dirpath: str | os.PathLike | None = None
               ) -> TunedConfig | None:
    backend, device_kind = device_identity(backend, device_kind)
    d = Path(dirpath) if dirpath is not None else tune_dir()
    key = cache_key(gs, backend, device_kind)
    hit = _MEM.get((str(d), key))
    if hit is not None:
        return hit
    path = d / f"{key}.json"
    if not path.is_file():
        return None
    try:
        data = json.loads(path.read_text())
        if (not isinstance(data, dict)
                or data.get("version") != TUNE_SCHEMA_VERSION):
            return None             # stale schema: ignored, not misread
        cfg = TunedConfig(**data)
    except (json.JSONDecodeError, TypeError, ValueError):
        return None                 # corrupt cache file: treat as untuned
    _MEM[(str(d), key)] = cfg
    return cfg


# ----------------------------------------------------------------------
# "auto" resolution
# ----------------------------------------------------------------------

def resolve_strategy(gs: GeomStatic, opts: dict | None = None, *,
                     backend: str | None = None,
                     device_kind: str | None = None,
                     dirpath: str | os.PathLike | None = None
                     ) -> tuple[str, dict]:
    """Map ``strategy="auto"`` to a concrete jnp strategy + options.

    Untuned keys fall back to :data:`DEFAULT_STRATEGY` with the caller's
    options untouched, so ``auto`` reproduces today's default behaviour
    bit-for-bit.  Explicitly passed options override tuned ones per key,
    but only those the resolved strategy accepts survive — the cache may
    have tuned a *different* strategy than the one the caller's options
    were written for.  Shedding is loud (:func:`filter_strategy_opts`):
    unknown keys raise, known-but-inapplicable ones warn.
    """
    cfg = load_tuned(gs, backend, device_kind, dirpath)
    if cfg is None or cfg.strategy not in STRATEGIES:
        strategy, merged = DEFAULT_STRATEGY, {}
    else:
        strategy = cfg.strategy
        # Tuned opts always belong to the tuned strategy; filter them
        # defensively (a hand-edited cache file) but never warn on them.
        allowed = _STRATEGY_KEYS[strategy]
        merged = {k: v for k, v in dict(cfg.opts).items() if k in allowed}
    merged.update(filter_strategy_opts(strategy, opts))
    return strategy, merged


def resolve_pallas_config(gs: GeomStatic, *, backend: str | None = None,
                          device_kind: str | None = None,
                          dirpath: str | os.PathLike | None = None
                          ) -> dict | None:
    """Tuned kernel tile config for this key, or ``None`` when untuned."""
    cfg = load_tuned(gs, backend, device_kind, dirpath)
    if cfg is None or not cfg.pallas:
        return None
    return {k: cfg.pallas[k] for k in _PALLAS_KEYS if k in cfg.pallas}


# ----------------------------------------------------------------------
# End-to-end: sweep this geometry, persist the decision
# ----------------------------------------------------------------------

def autotune(geom, *, image=None, A=None, space=None,
             include_pallas: bool | None = None, warmup: int = 1,
             iters: int = 3, min_total_s: float | None = None,
             dirpath: str | os.PathLike | None = None) -> TunedConfig:
    """Sweep ``geom`` on the current backend and cache the winner."""
    from .sweep import sweep_strategies    # lazy: keeps cache import light

    res = sweep_strategies(geom, image=image, A=A, space=space,
                           include_pallas=include_pallas, warmup=warmup,
                           iters=iters, min_total_s=min_total_s)
    best = res.best(STRATEGIES)
    if best is None:
        raise RuntimeError(
            "autotune swept no valid jnp candidate for this geometry; "
            f"skipped: {res.skipped}")
    best_pallas = res.best(("pallas",))
    cfg = TunedConfig(
        strategy=best.strategy, opts=dict(best.opts),
        backend=res.backend, device_kind=res.device_kind,
        us_per_call=best.us_per_call,
        pallas=dict(best_pallas.opts) if best_pallas else None,
        pallas_us=best_pallas.us_per_call if best_pallas else None,
        timings=[t.as_dict() for t in res.timings])
    store_tuned(GeomStatic.of(geom), cfg, dirpath)
    return cfg
