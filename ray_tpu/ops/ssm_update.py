"""A Mamba-2 layer's one-token state update over the recurrent pool, in
one pass.

For every sequence of a pool `[layers, slots, heads, d_head, d_state]`
float32 and one layer of it, `mamba2.ssd_step`:

    S' = exp(dt a) S + (dt x) B^T;   y = S' C

The Pallas TPU kernel takes the WHOLE pool and the layer's index as a
scalar: a grid step fetches one slot's row of that layer (2.1 MB at
granite-4.0-h-micro), advances it, forms `y` from the advanced row while
it is on the chip and writes the row back where it lay; the pool comes out
in the buffer it came in (`input_output_aliases`), so inside a loop over
layers nothing is sliced out or copied and the state crosses the memory's
bus twice a step, once each way. (The compiler makes two fusions of
`ssd_step`, one that writes the rows in place and one that reads them
again for `y`: three crossings.) A slot whose `dt` is 0 gets its row back
bit for bit: `exp(0) S + 0`.

The arithmetic is `ssd_step`'s, all float32. A row of the state is 128
lanes of `d_state`, so `B` and `C` lie along lanes as they come; `dt x`,
one value a row, comes lane-dense and is turned to a column a tile of 128
rows at a time, and `y`, one value a row, is turned back: the sum over
`d_state` of a tile is taken for its 128 rows at once and stored as one
lane-dense row. On the chip the arithmetic is 4.3 ms of a decode step at
36 layers x 48 slots and hides whole behind the rows' DMAs, which alone
take what the kernel takes (11.4 ms; PERF.md section 6, PR 54): a form
of `y` on the matrix unit, a lane sum a row, more slots a grid step or
the kernel's own DMAs moved it by 3% at most.

On other backends the plain form runs: the layer sliced out, `ssd_step`,
the layer set back, which is what a caller that holds a bare state array
calls itself. `interpret=True` runs the kernel in Pallas's interpreter
(the CPU tests).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
# Rows of the state (a head's `d_head` rows one after another) a tile, as
# many as a row has lanes: a tile's rows lie along the lanes of `dt x` and
# of `y`, and a tile of 128 lanes of `d_state` turns over whole.
_TILE = 128
# A slot's row in and out, two buffers each (8.4 MB at 64 heads of 64 x
# 128), beside the step's small operands held whole: over the 16 MiB a
# kernel gets unasked, far under the 128 MiB a v5e has.
_VMEM_LIMIT = 48 << 20


def _plain(x, dt, a, b, c, pool, layer):
    from ray_tpu.models.mamba2 import ssd_step

    y, state = ssd_step(x, dt, a, b, c, pool[layer])
    return y, pool.at[layer].set(state)


def kernel_takes(pool, groups: int) -> bool:
    """Whether the kernel can advance this pool: float32, a row of the
    state whole lanes, a head's rows whole sublanes that divide a tile,
    a slot's rows whole tiles, and no tile astride two groups."""
    _, _, heads, p, n = pool.shape
    if not (pool.dtype == F32 and n % _TILE == 0 and p % 8 == 0
            and _TILE % p == 0 and heads % groups == 0):
        return False
    per = _TILE // p                    # heads a tile
    return heads % per == 0 and (heads // groups) % per == 0


def _kernel(layer_ref, decay_ref, u_ref, b_ref, c_ref, s_ref, o_ref, y_ref,
            *, groups: int):
    """One slot a grid step, its tiles of 128 rows one after another.

    layer_ref [1] and decay_ref [slots * heads] (`exp(dt a)`) are in SMEM.
    u_ref [slots, tiles, 128] (`dt x`, a tile's rows along lanes), b_ref
    and c_ref [slots, groups, d_state]: whole in VMEM, fetched once.
    s_ref, o_ref [heads, d_head, d_state]: the slot's row of the layer as
    it was and as it will be. y_ref [tiles, 128]: the slot's `y`, a tile's
    rows along lanes."""
    from jax.experimental import pallas as pl

    del layer_ref  # the index maps read it
    s = pl.program_id(0)
    heads, p, _ = s_ref.shape
    per = _TILE // p                    # heads a tile
    tiles = heads // per
    diagonal = (jax.lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (_TILE, _TILE), 1))
    for t in range(tiles):
        g = t * per // (heads // groups)
        b_row = b_ref[s, pl.ds(g, 1), :]
        c_row = c_ref[s, pl.ds(g, 1), :]
        # A tile's `dt x` from lanes to sublanes: each row keeps its own
        # lane of the 128 and sums the zeros beside it.
        u = jnp.sum(jnp.where(diagonal, u_ref[s, pl.ds(t, 1), :], 0.0),
                    axis=1, keepdims=True)
        added = u * b_row
        rows = []
        for i in range(per):
            h = t * per + i
            rows.append(s_ref[h] * decay_ref[s * heads + h]
                        + added[i * p:(i + 1) * p])
            o_ref[h] = rows[-1]
        tile = rows[0] if per == 1 else jnp.concatenate(rows, axis=0)
        # `y` of the tile's 128 rows at once: the products turned over,
        # `d_state` summed along sublanes, one lane-dense row stored.
        y_ref[pl.ds(t, 1), :] = jnp.sum((tile * c_row).T, axis=0,
                                        keepdims=True)


def _pallas(x, dt, a, b, c, pool, layer, *, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, slots, heads, p, n = pool.shape
    tiles = heads * p // _TILE
    decay = jnp.exp(dt * a).reshape(-1)
    u = (dt[..., None] * x).reshape(slots, tiles, _TILE)

    def whole(shape):
        return pl.BlockSpec(shape, lambda s, *_: (0,) * len(shape))

    row = pl.BlockSpec((None, None, heads, p, n),
                       lambda s, layer_ref, _: (layer_ref[0], s, 0, 0, 0))
    # The pool is the FIRST result: in a trace an operation goes by its
    # name and its first result's shape (`bench/xplane/reduce.py`), and
    # the benchmark's readers find the update by the pool's.
    pool, y = pl.pallas_call(
        functools.partial(_kernel, groups=b.shape[1]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots,),
            in_specs=[whole(u.shape), whole(b.shape), whole(c.shape), row],
            out_specs=[row, pl.BlockSpec((None, tiles, _TILE),
                                         lambda s, *_: (s, 0, 0))],
        ),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, F32),
                   jax.ShapeDtypeStruct((slots, tiles, _TILE), F32)],
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="ssm_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), decay, u, b, c, pool)
    return y.reshape(slots, heads, p), pool


def ssm_update(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
               c: jax.Array, pool: jax.Array, layer,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """`mamba2.ssd_step` on layer `layer` (a scalar) of the recurrent pool
    `[layers, B, H, P, N]` float32, every one of its B rows: x [B, H, P],
    dt [B, H] (0 where the row must keep its state), a [H], b and c [B, G,
    N], all float32. Returns y [B, H, P] (without the skip term) and the
    pool, that layer's rows advanced where they lie (donate the pool, or
    carry it through a loop, and nothing of it is copied)."""
    if not ((jax.default_backend() == "tpu" or interpret)
            and kernel_takes(pool, b.shape[1])):
        return _plain(x, dt, a, b, c, pool, layer)
    with jax.named_scope("ssm.update"):
        return _pallas(x, dt, a, b, c, pool, layer, interpret=interpret)
