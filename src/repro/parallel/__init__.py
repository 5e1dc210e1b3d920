"""Distribution substrate: mesh conventions, logical-axis sharding rules,
collective helpers and optional pipeline parallelism."""

from repro.parallel.sharding import (
    DEFAULT_RULES,
    axis_rules,
    current_rules,
    logical_sharding,
    shard,
    shard_params,
)

__all__ = [
    "DEFAULT_RULES",
    "axis_rules",
    "current_rules",
    "logical_sharding",
    "shard",
    "shard_params",
]
