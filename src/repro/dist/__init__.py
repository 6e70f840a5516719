"""Distribution layer: logical-axis sharding rules + custom collectives.

The CT reconstruction annotates tensors with *logical* axis names
(``vol`` for volume z-planes, ``proj`` for projection subsets);
:mod:`repro.dist.sharding` maps those to mesh axes, pruning whatever the
current mesh does not have (DESIGN.md §5).  :mod:`repro.dist.collectives`
holds the hand-scheduled all-reduce variants (bucketed exact, int8
error-feedback).
"""

from .collectives import bucketed_psum, compress_psum  # noqa: F401
from .sharding import (ShardingRules, logical_to_spec,  # noqa: F401
                       shard_constraint, sharding_context, valid_spec)

__all__ = [
    "ShardingRules",
    "logical_to_spec",
    "valid_spec",
    "sharding_context",
    "shard_constraint",
    "bucketed_psum",
    "compress_psum",
]
