"""Ulysses-style sequence parallelism: all-to-all head/sequence reshard.

Alternative SP mode to ring attention (SURVEY.md §5 "long-context /
sequence parallelism ... an Ulysses-style all-to-all head/sequence reshard
as an alternative mode"): activations arrive sequence-sharded; an
all-to-all converts them to head-sharded with full sequence, plain (flash)
attention runs locally, and a second all-to-all converts back.

Technique: Jacobs et al., "DeepSpeed Ulysses" (arXiv:2309.14509),
re-implemented with jax all_to_all over a mesh axis. Best when
heads >= sp_size; ring attention wins when sequence far exceeds what
all-to-all bandwidth tolerates.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = True,
    attn_fn=None,
    query_spec: P = None,
):
    """Attention with seq sharded over `axis_name` via all-to-all reshard.

    q, k, v: [batch, seq(sharded), heads, head_dim]. heads must be
    divisible by the axis size.
    """
    axis_size = mesh.shape[axis_name]
    if query_spec is None:
        query_spec = P(None, axis_name, None, None)
    if attn_fn is None:
        from ray_tpu.parallel.ring_attention import reference_attention

        attn_fn = reference_attention

    def local_fn(q_blk, k_blk, v_blk):
        # [B, L/n, H, D] -> all-to-all -> [B, L, H/n, D]
        def scatter_heads(x):
            return jax.lax.all_to_all(
                x, axis_name, split_axis=2, concat_axis=1, tiled=True
            )

        def gather_heads(x):
            return jax.lax.all_to_all(
                x, axis_name, split_axis=1, concat_axis=2, tiled=True
            )

        qh, kh, vh = scatter_heads(q_blk), scatter_heads(k_blk), scatter_heads(v_blk)
        out = attn_fn(qh, kh, vh, causal=causal)  # [B, L, H/n, D]
        return gather_heads(out)  # [B, L/n, H, D]

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(query_spec, query_spec, query_spec),
        out_specs=query_spec,
        check_vma=False,
    )(q, k, v)
