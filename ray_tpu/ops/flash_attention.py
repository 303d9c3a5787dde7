"""Fused (flash) attention, forward and backward.

Pallas TPU kernels: the forward streams K/V blocks with an online-softmax
accumulator so the [Lq, Lk] score matrix never materializes in HBM, and
additionally writes the per-row logsumexp. The backward follows
flash-attention-2: probabilities are recomputed per block from the saved
logsumexp (p = exp(s - lse)) instead of being stored — one kernel computes
dq (grid over q-blocks, inner loop over kv), a second computes dk/dv (grid
over kv-blocks, inner loop over q). delta = rowsum(do * o) is precomputed
outside the kernels.

Sequence lengths that are not multiples of the block sizes are zero-padded
up to the block grid outside the kernels, and the kernels mask scores at
positions beyond the true lengths (s -> -inf), so padded keys contribute
nothing and padded query rows are sliced off on return.

On non-TPU backends an equivalent jnp implementation runs (same math,
XLA-fused, differentiable by tracing).

Kernel structure follows the standard flash-attention-on-TPU shape
(blockwise q outer, kv inner loop, f32 accumulators, MXU-sized tiles) per
/opt/skills/guides/pallas_guide.md. The reference has no analog — it
delegates attention to torch inside user train loops (SURVEY.md §2.4).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.per_shard import per_shard

NEG_INF = -1e30


def _pad_to(x, length, axis):
    pad = length - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _score_mask(s, q_off, k_off, block_q, block_k, causal, lq, lk, lq_pad,
                lk_pad):
    """Mask scores outside the causal triangle or beyond the true lengths."""
    if not (causal or lq != lq_pad or lk != lk_pad):
        return s
    q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    valid = (q_pos < lq) & (k_pos < lk)
    if causal:
        valid &= q_pos >= k_pos
    return jnp.where(valid, s, NEG_INF)


# ---------------------------------------------------------------------------
# Pallas TPU kernels
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                      causal: bool, sm_scale: float, lq: int, lk: int,
                      lq_pad: int):
    """One (batch*head, q-block) program: loop over kv blocks.

    q_ref: [block_q, d]; k_ref/v_ref: [Lk_pad, d]; o_ref: [block_q, d];
    lse_ref: [block_q, 1] (f32 logsumexp of each row's scores — the
    trailing singleton keeps the row stats 2D, which Mosaic's
    last-two-dims tiling rule requires of every block).
    """
    from jax.experimental import pallas as pl

    q_idx = pl.program_id(1)
    block_q, d = q_ref.shape
    lk_pad = k_ref.shape[0]
    num_kv = pl.cdiv(lk_pad, block_k)

    q = q_ref[:].astype(jnp.float32) * sm_scale

    o = jnp.zeros((block_q, d), dtype=jnp.float32)
    m = jnp.full((block_q, 1), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((block_q, 1), dtype=jnp.float32)

    def body(kv_idx, carry):
        o, m, l = carry
        k_blk = k_ref[pl.ds(kv_idx * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(kv_idx * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        s = _score_mask(s, q_idx * block_q, kv_idx * block_k, block_q,
                        block_k, causal, lq, lk, lq_pad, lk_pad)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1, keepdims=True)
        o_new = o * corr + jnp.dot(
            p, v_blk, preferred_element_type=jnp.float32
        )
        return o_new, m_new, l_new

    if causal:
        # Only kv blocks up to and including the diagonal contribute.
        last = jax.lax.div(
            (q_idx + 1) * block_q + block_k - 1, jnp.int32(block_k)
        )
        num_iter = jnp.minimum(last, num_kv)
    else:
        num_iter = num_kv
    o, m, l = jax.lax.fori_loop(0, num_iter, body, (o, m, l))
    l_safe = jnp.maximum(l, 1e-20)
    o_ref[:] = (o / l_safe).astype(o_ref.dtype)
    lse_ref[:] = m + jnp.log(l_safe)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, causal: bool,
                         sm_scale: float, lq: int, lk: int, lq_pad: int):
    """dq for one (batch*head, q-block): loop over kv blocks.

    ds = p * (do @ v^T - delta);  dq = sm_scale * ds @ k.
    """
    from jax.experimental import pallas as pl

    q_idx = pl.program_id(1)
    block_q, d = q_ref.shape
    lk_pad = k_ref.shape[0]
    num_kv = pl.cdiv(lk_pad, block_k)

    q = q_ref[:].astype(jnp.float32) * sm_scale
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:]        # [block_q, 1]
    delta = delta_ref[:]    # [block_q, 1]

    def body(kv_idx, dq):
        k_blk = k_ref[pl.ds(kv_idx * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(kv_idx * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        s = _score_mask(s, q_idx * block_q, kv_idx * block_k, block_q,
                        block_k, causal, lq, lk, lq_pad, lk_pad)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    if causal:
        last = jax.lax.div(
            (q_idx + 1) * block_q + block_k - 1, jnp.int32(block_k)
        )
        num_iter = jnp.minimum(last, num_kv)
    else:
        num_iter = num_kv
    dq = jax.lax.fori_loop(
        0, num_iter, body, jnp.zeros((block_q, d), jnp.float32)
    )
    dq_ref[:] = (sm_scale * dq).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, causal: bool,
                          sm_scale: float, lq: int, lk: int, lk_pad: int):
    """dk/dv for one (batch*head, kv-block): loop over q blocks.

    dv = p^T @ do;  dk = sm_scale * ds^T @ q.
    """
    from jax.experimental import pallas as pl

    kv_idx = pl.program_id(1)
    block_k, d = k_ref.shape
    lq_pad = q_ref.shape[0]
    num_q = pl.cdiv(lq_pad, block_q)

    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)

    def body(q_i, carry):
        dk, dv = carry
        q_blk = q_ref[pl.ds(q_i * block_q, block_q), :].astype(jnp.float32)
        do_blk = do_ref[pl.ds(q_i * block_q, block_q), :].astype(jnp.float32)
        lse_blk = lse_ref[pl.ds(q_i * block_q, block_q), :]      # [block_q, 1]
        delta_blk = delta_ref[pl.ds(q_i * block_q, block_q), :]  # [block_q, 1]
        s = sm_scale * jnp.dot(
            q_blk, k.T, preferred_element_type=jnp.float32
        )
        s = _score_mask(s, q_i * block_q, kv_idx * block_k, block_q, block_k,
                        causal, lq, lk, lq_pad, lk_pad)
        p = jnp.exp(s - lse_blk)
        dv = dv + jnp.dot(p.T, do_blk, preferred_element_type=jnp.float32)
        dp = jnp.dot(do_blk, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk)
        dk = dk + jnp.dot(ds.T, q_blk, preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        # First q block that reaches this kv block's first column.
        start = jax.lax.div(kv_idx * block_k, jnp.int32(block_q))
    else:
        start = 0
    dk, dv = jax.lax.fori_loop(
        start,
        num_q,
        body,
        (
            jnp.zeros((block_k, d), jnp.float32),
            jnp.zeros((block_k, d), jnp.float32),
        ),
    )
    dk_ref[:] = (sm_scale * dk).astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _fwd_pallas(qt, kt, vt, causal, block_q, block_k, interpret):
    """qt/kt/vt: [b*h, L, d]. Returns (out [b*h, Lq, d], lse [b*h, Lq] f32)."""
    from jax.experimental import pallas as pl

    bh, lq, d = qt.shape
    lk = kt.shape[1]
    sm_scale = d ** -0.5
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    lq_pad = -(-lq // block_q) * block_q
    lk_pad = -(-lk // block_k) * block_k
    qp = _pad_to(qt, lq_pad, 1)
    kp = _pad_to(kt, lk_pad, 1)
    vp = _pad_to(vt, lk_pad, 1)

    kernel = functools.partial(
        _flash_fwd_kernel, block_k=block_k, causal=causal, sm_scale=sm_scale,
        lq=lq, lk=lk, lq_pad=lq_pad,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, lq_pad // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, lk_pad, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, lk_pad, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq_pad, d), qt.dtype),
            jax.ShapeDtypeStruct((bh, lq_pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :lq], lse[:, :lq, 0]


def _bwd_pallas(qt, kt, vt, out, lse, g, causal, block_q, block_k, interpret):
    from jax.experimental import pallas as pl

    bh, lq, d = qt.shape
    lk = kt.shape[1]
    sm_scale = d ** -0.5
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    lq_pad = -(-lq // block_q) * block_q
    lk_pad = -(-lk // block_k) * block_k

    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [bh, lq]

    qp = _pad_to(qt, lq_pad, 1)
    kp = _pad_to(kt, lk_pad, 1)
    vp = _pad_to(vt, lk_pad, 1)
    gp = _pad_to(g, lq_pad, 1)
    # Padded rows carry lse=0, delta=0 so masked scores give p=exp(-1e30)=0.
    # Trailing singleton keeps row stats 2D in-kernel (Mosaic tiling rule).
    lsep = _pad_to(lse, lq_pad, 1)[..., None]
    deltap = _pad_to(delta, lq_pad, 1)[..., None]

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel,
            block_k=block_k,
            causal=causal,
            sm_scale=sm_scale,
            lq=lq,
            lk=lk,
            lq_pad=lq_pad,
        ),
        grid=(bh, lq_pad // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, lk_pad, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, lk_pad, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq_pad, d), qt.dtype),
        interpret=interpret,
    )(qp, kp, vp, gp, lsep, deltap)

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel,
            block_q=block_q,
            causal=causal,
            sm_scale=sm_scale,
            lq=lq,
            lk=lk,
            lk_pad=lk_pad,
        ),
        grid=(bh, lk_pad // block_k),
        in_specs=[
            pl.BlockSpec((None, lq_pad, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, lq_pad, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, lq_pad, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, lq_pad, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lk_pad, d), kt.dtype),
            jax.ShapeDtypeStruct((bh, lk_pad, d), vt.dtype),
        ],
        interpret=interpret,
    )(qp, kp, vp, gp, lsep, deltap)
    return dq[:, :lq], dk[:, :lk], dv[:, :lk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_pallas_core(qt, kt, vt, causal, block_q, block_k,
                                 interpret):
    """Differentiable Pallas flash attention on [b*h, L, d] tensors."""
    out, _ = _fwd_pallas(qt, kt, vt, causal, block_q, block_k, interpret)
    return out


def _core_fwd(qt, kt, vt, causal, block_q, block_k, interpret):
    out, lse = _fwd_pallas(qt, kt, vt, causal, block_q, block_k, interpret)
    return out, (qt, kt, vt, out, lse)


def _core_bwd(causal, block_q, block_k, interpret, res, g):
    qt, kt, vt, out, lse = res
    return _bwd_pallas(
        qt, kt, vt, out, lse, g, causal, block_q, block_k, interpret
    )


_flash_attention_pallas_core.defvjp(_core_fwd, _core_bwd)


def _flash_attention_pallas(q, k, v, causal: bool, block_q: int, block_k: int,
                            interpret: bool = False):
    b, lq, h, d = q.shape
    lk = k.shape[1]
    # [b, h, l, d] layout for blocking.
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    out = _flash_attention_pallas_core(
        qt, kt, vt, causal, block_q, block_k, interpret
    )
    return out.reshape(b, h, lq, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# XLA fallback (identical math)
# ---------------------------------------------------------------------------


def _flash_attention_xla(q, k, v, causal: bool):
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1)
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 256,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    mesh=None,
    spec: P = P(),
):
    """Fused attention. q,k,v: [batch, seq, heads, head_dim].

    GQA/MQA: if k/v have fewer heads than q, they are broadcast per group
    (the repeat happens outside the kernel, so its VJP sums the per-group
    gradients back onto the shared kv heads).

    mesh, spec: under a sharded jit, the mesh and the PartitionSpec q, k
    and v share (batch and heads may be sharded, sequence and head_dim
    not); the kernels then run on each device's block.
    """
    if k.shape[2] != q.shape[2]:
        group = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not (use_pallas or interpret):
        return _flash_attention_xla(q, k, v, causal)
    kernel = functools.partial(
        _flash_attention_pallas, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interpret,
    )
    return per_shard(kernel, mesh, (spec, spec, spec), spec)(q, k, v)
