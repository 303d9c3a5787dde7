"""Ring attention: context parallelism over a mesh axis.

Long-context attention where the sequence is sharded over the "sp" mesh
axis. Each device holds a query block; key/value blocks rotate around the
ring via `jax.lax.ppermute` (XLA lowers this to ICI neighbor transfers that
overlap with the attention compute), and softmax is accumulated online
(flash-attention style running max/denominator) so the result is exact.

The reference has no analog (SURVEY.md §2.4: SP/CP/ring attention
"Absent"); this is new TPU-native capability. Technique: Liu et al., "Ring
Attention with Blockwise Transformers" (arXiv:2310.01889), re-implemented
from the paper for shard_map.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

NEG_INF = -1e30


def _block_attn(q, k, v, bias):
    """Scores for one (q-block, kv-block) pair. q:[B,Lq,H,D] k,v:[B,Lk,H,D]"""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    return s


def _online_update(carry, s, v):
    """Online-softmax accumulate one kv block (flash attention recurrence)."""
    o, m, l = carry  # o:[B,H,Lq,D] m,l:[B,H,Lq]
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])  # [B,H,Lq,Lk]
    correction = jnp.exp(m - m_new)
    l_new = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bhqd", p, v)
    o_new = o * correction[..., None] + pv
    return o_new, m_new, l_new


def _causal_bias(q_idx, k_idx, block_q, block_k, dtype):
    """Bias for a q-block at ring position q_idx vs kv-block at k_idx.

    Global positions: q in [q_idx*block_q, ...), k in [k_idx*block_k, ...).
    """
    q_pos = q_idx * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_idx * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos >= k_pos, 0.0, NEG_INF).astype(dtype)[None, None]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = True,
    query_spec: P = None,
):
    """Exact attention with the sequence sharded over `axis_name`.

    Args:
      q, k, v: [batch, seq, heads, head_dim], seq sharded over `axis_name`.
      mesh: the device mesh containing `axis_name`.
      causal: apply causal masking using *global* positions.

    Returns [batch, seq, heads, head_dim] with the same sharding as q.
    """
    axis_size = mesh.shape[axis_name]
    if query_spec is None:
        query_spec = P(None, axis_name, None, None)

    def local_fn(q_blk, k_blk, v_blk):
        # q_blk: [B, Lq_local, H, D] — this device's query block.
        my_idx = jax.lax.axis_index(axis_name)
        block_q = q_blk.shape[1]
        block_k = k_blk.shape[1]
        b, _, h, d = q_blk.shape

        o = jnp.zeros((b, h, block_q, d), dtype=jnp.float32)
        m = jnp.full((b, h, block_q), NEG_INF, dtype=jnp.float32)
        l = jnp.zeros((b, h, block_q), dtype=jnp.float32)

        def step(i, carry):
            o, m, l, k_cur, v_cur = carry
            # kv block currently held arrived from ring position my_idx - i.
            k_idx = (my_idx - i) % axis_size

            def attend(carry):
                o, m, l = carry
                if causal:
                    bias = _causal_bias(
                        my_idx, k_idx, block_q, block_k, jnp.float32
                    )
                else:
                    bias = None
                s = _block_attn(
                    q_blk.astype(jnp.float32),
                    k_cur.astype(jnp.float32),
                    v_cur.astype(jnp.float32),
                    bias,
                )
                return _online_update((o, m, l), s, v_cur.astype(jnp.float32))

            if causal:
                # Blocks entirely in the future (k_idx > my_idx) are fully
                # masked: skip their matmuls outright — on a causal ring
                # each device computes only ~half the steps instead of
                # materializing -inf scores for the rest.
                o, m, l = jax.lax.cond(
                    k_idx <= my_idx, attend, lambda c: c, (o, m, l)
                )
            else:
                o, m, l = attend((o, m, l))
            # Rotate kv to the right neighbor; overlapped with next step's
            # compute by XLA latency hiding.
            perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
            k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            return o, m, l, k_nxt, v_nxt

        o, m, l, _, _ = jax.lax.fori_loop(
            0, axis_size, step, (o, m, l, k_blk, v_blk)
        )
        out = o / jnp.maximum(l[..., None], 1e-20)
        return out.transpose(0, 2, 1, 3).astype(q_blk.dtype)  # [B,Lq,H,D]

    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(query_spec, query_spec, query_spec),
        out_specs=query_spec,
        check_vma=False,
    )(q, k, v)


def reference_attention(q, k, v, causal: bool = True):
    """Unsharded reference for testing parity."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1)
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)
