"""A delta-rule layer's one-token state update over the recurrent pool, in
one pass.

For every sequence of a pool `[layers, slots, heads, K, V]` float32 and one
layer of it, `kda.kda_step`:

    S~ = Diag(exp(g)) S;   u = S~^T k;   S' = S~ + beta k (v - u)^T
    o = S'^T q = S~^T q + beta (k . q) (v - u)

`S'` cannot be written before `u` is summed over all of `K`, and the
compiler keeps no head's tile on the chip between a reduction and the
write that waits for it: it makes two fusions of `kda_step`, one that reads
a layer's states for both sums and one that reads them again to write
them, three crossings of the memory's bus. The Pallas TPU kernel takes the
WHOLE pool and the layer's index as a scalar: a grid step fetches one
slot's row of that layer (4.19 MB at 64 heads of 128 x 128) and, a head at
a time while its `[K, V]` tile is on the chip, decays it, takes both sums
over `S~`, and writes `S'` back where the row lay; the pool comes out in
the buffer it came in (`input_output_aliases`), so inside a loop over
layers nothing is sliced out or copied and the state crosses the bus
twice a step, once each way. A slot whose `g` and `beta` are 0 gets its
row back bit for bit: `1 * S + 0`.

The arithmetic is `kda_step`'s, in its order, all float32 on the vector
unit: a row of a head's state is 128 lanes of `V`, so `v`, `u`, `delta`
and `o` lie along lanes as they come and both sums run over sublanes;
`exp(g)`, `k` and `q`, one value a row of the state, come lane-dense `[H,
K]` a slot and are turned over eight heads at a time (a sublane tile of
them padded to a 128 x 128 tile, three transposes a group), after which a
head's column is a lane of the turned tile. The heads are a loop over
groups of eight with the eight written out, not sixty-four written out:
the kernel's body is traced once for each decode program an engine warms
up, and sixty-four heads of it cost 1.7 s of an engine's warm-up where
eight cost 0.7, for 0.03 ms of a step (PERF.md section 6, PR 62). On the
chip the arithmetic is 2.4 ms of a decode step at 3 layers x
128 slots and hides whole behind the rows' DMAs, which alone take what
the kernel takes (5.03 ms, 640 GB/s): the columns by a diagonal and a
lane sum a head, both sums on the matrix unit, half or a quarter of a
slot's heads a grid step or a second pass over `S~` in VMEM moved it by
0.4% at most.

On other backends the plain form runs: the layer sliced out, `kda_step`,
the layer set back, which is what a caller that holds a bare state array
calls itself. `interpret=True` runs the kernel in Pallas's interpreter
(the CPU tests).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
# Lanes of a vector register: a row of a head's state is whole ones, and
# the lane-dense operands turn over in tiles of this many each way.
_TILE = 128
# Heads a turn of the kernel's loop: a sublane tile of the lane-dense
# operands, turned over together.
_GROUP = 8
# A slot's row in and out, two buffers each (16.8 MB at 64 heads of 128 x
# 128), beside a slot's small operands: over the 16 MiB a kernel gets
# unasked, far under the 128 MiB a v5e has.
_VMEM_LIMIT = 48 << 20
# What of that limit a slot's row may take, its four buffers together.
_ROW_BUDGET = 40 << 20


def _plain(q, k, v, g, beta, pool, layer):
    from ray_tpu.models.kda import kda_step

    o, state = kda_step(q, k, v, g, beta, pool[layer])
    return o, pool.at[layer].set(state)


def kernel_takes(pool) -> bool:
    """Whether the kernel can advance this pool: float32, a row of a
    head's state whole lanes, a head's rows whole sublanes, the heads
    whole groups, and a slot's row of a layer, in and out and two buffers
    each, inside the kernel's VMEM."""
    _, _, heads, dk, dv = pool.shape
    return (pool.dtype == F32 and dv % _TILE == 0 and dk % 8 == 0
            and heads % _GROUP == 0
            and 4 * heads * dk * dv * 4 <= _ROW_BUDGET)


def _turned(x):
    """`x [_GROUP, K]` lane-dense -> `[K, 128]`: a head's values a column
    (the first `_GROUP` lanes). Padded with zeros to whole 128 x 128
    tiles, which is what turns over."""
    heads, dk = x.shape
    if dk % _TILE:
        x = jnp.concatenate([x, jnp.zeros((heads, -dk % _TILE), F32)], axis=1)
    x = jnp.concatenate([x, jnp.zeros((_TILE - heads, x.shape[1]), F32)],
                        axis=0)
    return x.T[:dk]


def _kernel(layer_ref, beta_ref, kq_ref, decay_ref, k_ref, q_ref, v_ref,
            s_ref, new_ref, o_ref):
    """One slot a grid step, its heads a group of `_GROUP` after another.

    layer_ref [1], beta_ref and kq_ref [slots * heads] (`k . q`) are in
    SMEM. decay_ref (`exp(g)`), k_ref, q_ref [heads, K] and v_ref [heads,
    V]: the slot's, lane-dense. s_ref, new_ref [heads, K, V]: the slot's
    row of the layer as it was and as it will be. o_ref [heads, V]."""
    from jax.experimental import pallas as pl

    del layer_ref  # the index maps read it
    s = pl.program_id(0)
    heads = s_ref.shape[0]

    def group(i, carry):
        h0 = pl.multiple_of(i * _GROUP, _GROUP)
        rows = pl.ds(h0, _GROUP)
        decay, k, q = (_turned(ref[rows, :])
                       for ref in (decay_ref, k_ref, q_ref))
        v = v_ref[rows, :]
        o = []
        for j in range(_GROUP):
            at = s * heads + h0 + j
            k_col = k[:, j:j + 1]
            decayed = decay[:, j:j + 1] * s_ref[h0 + j]
            u = jnp.sum(k_col * decayed, axis=0, keepdims=True)
            from_old = jnp.sum(q[:, j:j + 1] * decayed, axis=0, keepdims=True)
            delta = beta_ref[at] * (v[j:j + 1] - u)
            new_ref[h0 + j] = decayed + k_col * delta
            o.append(from_old + kq_ref[at] * delta)
        o_ref[rows, :] = jnp.concatenate(o, axis=0)
        return carry

    jax.lax.fori_loop(0, heads // _GROUP, group, None)


def _pallas(q, k, v, g, beta, pool, layer, *, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, slots, heads, dk, dv = pool.shape

    def slot(width):
        return pl.BlockSpec((None, heads, width), lambda s, *_: (s, 0, 0))

    row = pl.BlockSpec((None, None, heads, dk, dv),
                       lambda s, layer_ref, *_: (layer_ref[0], s, 0, 0, 0))
    # The pool is the FIRST result: in a trace an operation goes by its
    # name and its first result's shape (`bench/xplane/reduce.py`), and
    # the benchmark's readers find the update by the pool's.
    pool, o = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(slots,),
            in_specs=[slot(dk), slot(dk), slot(dk), slot(dv), row],
            out_specs=[row, slot(dv)],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, F32),
                   jax.ShapeDtypeStruct((slots, heads, dv), F32)],
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="kda_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), beta.reshape(-1),
      jnp.sum(k * q, axis=-1).reshape(-1), jnp.exp(g), k, q, v, pool)
    return o, pool


def kda_update(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
               beta: jax.Array, pool: jax.Array, layer,
               interpret: bool = False):
    """`kda.kda_step` on layer `layer` (a scalar) of the recurrent pool
    `[layers, B, H, K, V]` float32, every one of its B rows: q, k, g [B, H,
    K], v [B, H, V], beta [B, H] (g and beta 0 where the row must keep its
    state), all float32. Returns o [B, H, V] and the pool, that layer's
    rows advanced where they lie (donate the pool, or carry it through a
    loop, and nothing of it is copied)."""
    if not ((jax.default_backend() == "tpu" or interpret)
            and kernel_takes(pool)):
        return _plain(q, k, v, g, beta, pool, layer)
    with jax.named_scope("kda.update"):
        return _pallas(q, k, v, g, beta, pool, layer, interpret=interpret)
