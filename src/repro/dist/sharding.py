"""Logical-axis sharding: names in the CT code, mesh axes at the edge.

Every tensor annotation in this repo is written against *logical* axis
names.  A :class:`ShardingRules` instance maps each logical name to a
tuple of mesh axis names; :func:`logical_to_spec` resolves an annotation
against a concrete mesh, silently pruning mesh axes the mesh does not
have — the same rules lower onto a four-chip host or a 2-device CPU
test mesh without touching the reconstruction code (DESIGN.md §5).

Two special logical names are always replicated: ``None`` and ``"null"``
(the latter used in spec *trees*, where ``None`` would read as an empty
pytree).

:func:`valid_spec` is the divisibility guard: any tensor dimension that
does not divide by the total size of its assigned mesh axes falls back
to replication for that dimension (GSPMD would otherwise pad; we want
exact shards or none).

:func:`sharding_context` + :func:`shard_constraint` give a zero-cost
annotation idiom: ``shard_constraint(x, ("vol", None, None))`` is the
identity outside a context and a ``jax.lax.with_sharding_constraint``
inside one, so single-device tests run the exact same code path as the
sharded reconstruction.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = [
    "ShardingRules",
    "logical_to_spec",
    "valid_spec",
    "sharding_context",
    "shard_constraint",
]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical axis -> mesh axes mapping.

    ``vol`` splits the volume's z-planes (the paper's OpenMP plane
    decomposition); ``proj`` splits the projection set.
    """

    vol: tuple[str, ...] = ("data",)
    proj: tuple[str, ...] = ("pod", "model")


def logical_to_spec(axes, rules: ShardingRules, mesh) -> P:
    """Resolve logical axis names to a PartitionSpec on ``mesh``.

    Mesh axes named by a rule but absent from ``mesh.axis_names`` are
    pruned (a podless mesh collapses ``("pod", "model")`` to ``"model"``);
    a rule whose axes are all pruned — or mapped to ``()`` — replicates.
    """
    names = set(mesh.axis_names)
    entries = []
    for ax in axes:
        if ax is None or ax == "null":
            entries.append(None)
            continue
        mapped = getattr(rules, ax)
        mapped = (mapped,) if isinstance(mapped, str) else tuple(mapped)
        present = tuple(a for a in mapped if a in names)
        if not present:
            entries.append(None)
        elif len(present) == 1:
            entries.append(present[0])
        else:
            entries.append(present)
    return P(*entries)


def valid_spec(shape, spec: P, mesh) -> P:
    """Drop spec entries whose dimension does not divide the shard count.

    Each dimension sharded over mesh axes with total size ``n`` must be a
    multiple of ``n``; otherwise that dimension replicates.  Trailing
    replicated entries are trimmed so fully-replicated tails compare
    equal to shorter specs.
    """
    sizes = dict(mesh.shape)
    entries = []
    for i, dim in enumerate(shape):
        entry = spec[i] if i < len(spec) else None
        if entry is None:
            entries.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        shards = 1
        for a in axes:
            shards *= sizes[a]
        entries.append(entry if dim % shards == 0 else None)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


# ----------------------------------------------------------------------
# Ambient sharding context
# ----------------------------------------------------------------------

# (mesh, rules) of the innermost active sharding_context, or None.  A
# ContextVar (not a bare module global) so nested/threaded callers each
# see their own context; :func:`shard_constraint` reads it.
_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_sharding_context", default=None)


@contextlib.contextmanager
def sharding_context(mesh, rules: ShardingRules):
    """Make ``(mesh, rules)`` ambient for :func:`shard_constraint`."""
    token = _CTX.set((mesh, rules))
    try:
        yield
    finally:
        _CTX.reset(token)


# Valid logical names for annotations.  Checked even outside a context
# so a typo'd annotation fails in single-device unit tests, not at the
# first sharded run.
_LOGICAL_AXES = frozenset(f.name for f in dataclasses.fields(ShardingRules))


def shard_constraint(x, logical_axes):
    """Pin ``x`` to its logical sharding — no-op outside a context.

    Inside a :func:`sharding_context` this lowers to
    ``jax.lax.with_sharding_constraint`` with the resolved (and
    divisibility-guarded) spec; outside one it returns ``x`` unchanged,
    which is what keeps single-device unit tests free of mesh plumbing.
    """
    for ax in logical_axes:
        if ax is not None and ax != "null" and ax not in _LOGICAL_AXES:
            raise ValueError(f"unknown logical axis {ax!r}; want one of "
                             f"{sorted(_LOGICAL_AXES)}")
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = valid_spec(x.shape, logical_to_spec(logical_axes, rules, mesh),
                      mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
