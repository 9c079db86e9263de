"""Distributed tier: sharding rules, ring collectives, sharded DAGM.

`shard_map` is jax's own (`jax.shard_map`); import it from there.
"""
from __future__ import annotations

from .sharding import (ShardingRules, make_rules, use_rules, shard,
                       current_rules, tree_param_sharding)
