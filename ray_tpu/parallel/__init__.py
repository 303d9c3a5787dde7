"""Parallelism primitives: mesh management, sharding rules, ring attention,
sequence parallelism, pipeline parallelism, expert parallelism.

This package supplies natively what the reference delegates to user
libraries (SURVEY.md §2.4: TP "not implemented in Ray itself", PP "not
implemented", SP/CP "absent", EP "absent") — the idiomatic TPU route: one
jax.Mesh over the pod slice, GSPMD sharding annotations for DP/FSDP/TP,
shard_map + ppermute ring attention for context parallelism, all-to-all
resharding (Ulysses) as the alternative SP mode, lax.scan pipelining for
PP, and capacity-based top-k routing for EP.
"""

from ray_tpu.parallel.mesh import (
    MeshConfig,
    build_mesh,
    logical_shardings,
    logical_to_physical,
    shard_params,
    with_sharding_constraint,
)
from ray_tpu.parallel.ring_attention import ring_attention
from ray_tpu.parallel.ulysses import ulysses_attention
from ray_tpu.parallel.pipeline import pipeline_stages
from ray_tpu.parallel.moe import grouped_matmul, moe_block

__all__ = [
    "MeshConfig",
    "build_mesh",
    "logical_shardings",
    "logical_to_physical",
    "shard_params",
    "with_sharding_constraint",
    "ring_attention",
    "ulysses_attention",
    "pipeline_stages",
    "grouped_matmul",
    "moe_block",
]
