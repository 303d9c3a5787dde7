"""RMSNorm with a fused Pallas TPU kernel and jnp fallback.

The jnp path carries a custom VJP that recomputes the normalizer in the
backward pass instead of saving activations (a rematerialization the
XLA fuser sometimes misses across the scale multiply).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.per_shard import is_per_device, per_shard


def _rmsnorm_ref(x, w, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    return (x.astype(jnp.float32) * inv * w.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_xla(x, w, eps):
    return _rmsnorm_ref(x, w, eps)


def _fwd(x, w, eps):
    return _rmsnorm_ref(x, w, eps), (x, w)


def _bwd(eps, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = xf * inv
    dw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1))).astype(w.dtype)
    gw = gf * wf
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dw


_rmsnorm_xla.defvjp(_fwd, _bwd)


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    o_ref[:] = (x * inv * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_bwd_kernel(x_ref, w_ref, g_ref, dx_ref, dw_ref, *, eps: float):
    """Row-local dx plus dw accumulated across the sequential TPU grid.

    The normalizer is recomputed from x (rematerialized, as the fwd kernel
    saves nothing), so the backward reads the same inputs as the forward.
    dw_ref is one (8, d) block every grid step revisits: row 0 accumulates,
    rows 1-7 pad the block up to the fp32 sublane tile.
    """
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    xhat = x * inv
    gw = g * w
    dx = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dx_ref[:] = dx.astype(dx_ref.dtype)
    part = jnp.pad(jnp.sum(g * xhat, axis=0, keepdims=True), ((0, 7), (0, 0)))

    @pl.when(i == 0)
    def _init():
        dw_ref[:] = part

    @pl.when(i != 0)
    def _acc():
        dw_ref[:] = dw_ref[:] + part


def _rmsnorm_pallas_fwd2(x2, w, eps, block_rows, interpret):
    from jax.experimental import pallas as pl

    rows, d = x2.shape
    block_rows = min(block_rows, rows)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(pl.cdiv(rows, block_rows),),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
        interpret=interpret,
    )(x2, w.reshape(1, d))


def _rmsnorm_pallas_bwd2(x2, w, g2, eps, block_rows, interpret):
    from jax.experimental import pallas as pl

    rows, d = x2.shape
    block_rows = min(block_rows, rows)
    nblocks = -(-rows // block_rows)
    # Zero-pad a partial tail block: padded rows give g*xhat = 0, so the
    # dw accumulator adds defined zeros instead of out-of-bounds garbage
    # (real-TPU OOB block contents are undefined).
    rows_pad = nblocks * block_rows
    if rows_pad != rows:
        x2 = jnp.pad(x2, ((0, rows_pad - rows), (0, 0)))
        g2 = jnp.pad(g2, ((0, rows_pad - rows), (0, 0)))
    dx, dw_acc = pl.pallas_call(
        functools.partial(_rmsnorm_bwd_kernel, eps=eps),
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((8, d), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows_pad, d), x2.dtype),
            jax.ShapeDtypeStruct((8, d), jnp.float32),
        ],
        interpret=interpret,
    )(x2, w.reshape(1, d), g2)
    return dx[:rows], dw_acc.sum(axis=0).astype(w.dtype)


# A TPU kernel's blocks and temporaries live in scoped VMEM, 16 MiB by
# default on v5e ("Scoped allocation ... limit 16.00M" is the compiler's
# refusal). Half of it is budgeted for what _block_rows_for counts; the
# rest is headroom for what Mosaic adds.
_VMEM_BUDGET = 8 << 20
_MAX_BLOCK_ROWS = 256


def _block_rows_for(d: int, dtype) -> int:
    """Rows per block such that the backward kernel, the larger of the
    two, fits the budget: x, g and dx blocks, double-buffered by the
    pipeline, plus three float32 temporaries of the block's shape. The
    forward uses the same block so that one number is tested."""
    itemsize = jnp.dtype(dtype).itemsize
    sublane = 8 * (4 // itemsize)  # packed rows per (8, 128) tile
    per_row = d * (3 * 2 * itemsize + 3 * 4)
    rows = _VMEM_BUDGET // per_row // sublane * sublane
    return max(sublane, min(_MAX_BLOCK_ROWS, rows))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _rmsnorm_pallas(x, w, eps, block_rows: int, interpret: bool = False,
                    mesh=None, spec: P = P()):
    """The kernels on each device's rows (every axis of x but the last),
    differentiated on whole arrays.

    The rule lies outside the per-device region so that JAX transposes no
    shard_map: `spec` may leave a mesh axis unnamed ("tp", for the
    activations), and the transpose of such a region divides the
    cotangent and all-reduces dx over that axis (ops.per_shard): two
    passes over the activation that compute the identity."""
    d = x.shape[-1]

    def forward(x, w):
        return _rmsnorm_pallas_fwd2(x.reshape(-1, d), w, eps, block_rows,
                                    interpret).reshape(x.shape)

    return per_shard(forward, mesh, (spec, P()), spec)(x, w)


def _pallas_fwd(x, w, eps, block_rows, interpret, mesh, spec):
    return _rmsnorm_pallas(x, w, eps, block_rows, interpret, mesh, spec), (x, w)


def _pallas_bwd(eps, block_rows, interpret, mesh, spec, res, g):
    x, w = res
    d = x.shape[-1]
    # The rows are split over the mesh axes `spec` names and over nothing
    # else, so the scale's gradient sums over those; where the code
    # already runs on one device's rows, whoever made that region sums.
    sum_over = () if is_per_device(mesh) else tuple(
        axis for entry in spec if entry is not None
        for axis in (entry if isinstance(entry, tuple) else (entry,)))

    def backward(x, w, g):
        dx, dw = _rmsnorm_pallas_bwd2(x.reshape(-1, d), w, g.reshape(-1, d),
                                      eps, block_rows, interpret)
        return dx.reshape(x.shape), jax.lax.psum(dw, sum_over)

    return per_shard(backward, mesh, (spec, P(), spec), (spec, P()))(x, w, g)


_rmsnorm_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-6,
            use_pallas: Optional[bool] = None, interpret: bool = False,
            mesh=None, spec: P = P()):
    """RMS normalization over the last axis, scaled by w.

    mesh, spec: under a sharded jit, the mesh and x's PartitionSpec (last
    axis unsharded); the kernel then runs on each device's rows."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not (use_pallas or interpret):
        return _rmsnorm_xla(x, w, eps)
    return _rmsnorm_pallas(x, w, eps, _block_rows_for(x.shape[-1], x.dtype),
                           interpret, mesh, spec)
