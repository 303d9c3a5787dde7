"""Decode attention over a paged KV pool, read in place.

One query row a slot against the rows that slot holds in a page pool
`[layers, pages, page_size, kv_heads * head_dim]` (a row's heads side by
side), found through a block table `[slots, pages_per_slot]`.

The Pallas TPU kernel takes the WHOLE pool, the layer's index, the table
and each slot's row count as scalars, and fetches with its own DMAs the
pages that hold a slot's live rows, and only those: a slot with no rows
fetches nothing, pages past a slot's count (the NULL page among them) are
never touched. Nothing of the pool is gathered into a dense copy and
nothing of it is cast in HBM: a block of pages lands in VMEM in the
pool's dtype, the scores accumulate in float32 on the MXU (the products
of two bf16 values are exact there), the softmax runs online in float32
as `flash_attention`'s does, and the probabilities meet V as three bf16
terms whose sum is the float32 value.

One program for every mix of lengths: the kernel walks the slots in
turn and a slot's blocks inside that, and the DMAs of the block that
follows, the next live slot's first included, are in flight while a
block is computed.

On other backends the plain form runs: the slot's whole table gathered,
cast and scored, masked by the count (`grouped_attention`, which the
prefill program uses too).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.per_shard import per_shard

NEG_INF = -1e30
_LANES = 128
# K and V blocks, two buffers each, in the pool's dtype: half of the 16
# MiB of scoped VMEM a kernel may use on a v5e; the queries, the results
# and a block's scores lie beside them. Two buffers: four and six changed
# nothing on the chip, and 512 rows a block read a full cache in two
# thirds of the time 256 did (PERF.md section 6, PR 36).
_VMEM_BUDGET = 8 << 20
_MAX_BLOCK_ROWS = 512


def grouped_attention(q, kf, vf, valid, scale):
    """q [S, Lq, H, D] vs caches [S, Lk, KVH, D]; valid [S, Lq, Lk]."""
    s_, lq, h, d = q.shape
    kvh = kf.shape[2]
    group = h // kvh
    qg = q.reshape(s_, lq, kvh, group, d).astype(jnp.float32)
    scores = jnp.einsum("sqhgd,skhd->shgqk", qg, kf) * scale
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("shgqk,skhd->sqhgd", p, vf).reshape(s_, lq, h, d)
    return out.astype(q.dtype)


def _plain(q, k_pool, v_pool, layer, block_tables, rows, scale):
    s_, _, hd = q.shape
    width = block_tables.shape[1] * k_pool.shape[2]
    k = k_pool[layer, block_tables].reshape(s_, width, -1, hd)
    v = v_pool[layer, block_tables].reshape(s_, width, -1, hd)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (s_, 1, width), 2)
    valid = k_pos < rows[:, None, None]
    return grouped_attention(q[:, None], k.astype(jnp.float32),
                             v.astype(jnp.float32), valid, scale)[:, 0]


def pages_per_block(page_size: int, row_width: int, dtype) -> int:
    """Pages a block of the kernel holds, from the page's shape: as many
    rows as fit the VMEM budget (K and V, two buffers each), a whole
    number of 128-key score tiles where a page divides one, and no more
    than `_MAX_BLOCK_ROWS` (a longer block computes more dead rows at a
    sequence's end and hides no more of a DMA's latency)."""
    rows = _VMEM_BUDGET // (4 * row_width * jnp.dtype(dtype).itemsize)
    rows = min(rows, _MAX_BLOCK_ROWS)
    if rows >= _LANES and _LANES % page_size == 0:
        rows = rows // _LANES * _LANES
    return max(1, rows // page_size)


def kernel_takes(k_pool, head_dim: int) -> bool:
    """Whether the kernel can read this pool: a page is whole tiles of
    the pool's dtype (8 sublanes of 32 bits by 128 lanes), and a head is
    128 lanes or a slice of them that divides them."""
    _, _, page_size, width = k_pool.shape
    sublanes = 8 * 4 // jnp.dtype(k_pool.dtype).itemsize
    lanes = max(head_dim, _LANES)
    return (page_size % sublanes == 0 and lanes % head_dim == 0
            and width % lanes == 0)


def _kernel(layer_ref, rows_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, m_ref, l_ref, acc_ref, *, scale: float,
            ppb: int):
    """Every slot in turn, its blocks of `ppb` pages inside that.

    layer_ref [1], rows_ref [S] (rows a slot attends to) and tables_ref
    [S * pages_per_slot] are in SMEM. q_ref, o_ref [S, G, R, W] float32 in
    VMEM: W lanes hold one head, or several narrow ones side by side, each
    with its own query rows and zeros in the others' lanes, so that one
    product over W lanes gives every head its own scores; G such groups
    make a row of the pool. k_hbm, v_hbm: the pools where they lie.
    k_buf, v_buf [2, T, G * W]: a block's rows, one buffer filled while
    the other is computed on. m_ref, l_ref [G, R, 128] and acc_ref
    [G, R, W]: the running softmax of the slot at hand."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slots, groups, _, lanes = q_ref.shape
    page_size = k_hbm.shape[2]
    per_slot = tables_ref.shape[0] // slots
    block_rows = ppb * page_size
    layer = layer_ref[0]

    o_ref[...] = jnp.zeros_like(o_ref)
    # A row no DMA has written meets a probability of exactly 0, and what
    # VMEM held before may be a NaN's bits.
    v_buf[...] = jnp.zeros_like(v_buf)

    def next_live(s):
        """The first slot from `s` on that has rows, or `slots`."""
        return jax.lax.while_loop(
            lambda t: jnp.logical_and(
                t < slots, rows_ref[jnp.minimum(t, slots - 1)] == 0),
            lambda t: t + 1, s)

    def block_copies(s, b, buf, act):
        """`act` on the DMA of every live page of block `b` of slot `s`."""
        first = b * ppb

        def page_copies(j, _):
            page = tables_ref[s * per_slot + first + j]
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            act(pltpu.make_async_copy(
                k_hbm.at[layer, page], k_buf.at[buf, dst], sems.at[0, buf]))
            act(pltpu.make_async_copy(
                v_hbm.at[layer, page], v_buf.at[buf, dst], sems.at[1, buf]))

        live_pages = pl.cdiv(rows_ref[s], page_size) - first
        jax.lax.fori_loop(0, jnp.minimum(live_pages, ppb), page_copies, None)

    # A block is as long as the DMAs want it; the scores are worked out
    # over as many whole tiles of keys as its live rows reach, each count
    # a straight-line program of its own (a loop over tiles would wait
    # for the matrix unit's result once a tile; one program over the
    # whole block would compute what lies past a sequence's end).
    tile = _LANES if block_rows % _LANES == 0 else block_rows

    def scores_over(s, b, buf, width):
        """The first `width` keys of the block into the running softmax:
        two products a group of heads, the softmax between them over
        every group at once."""
        k_pos = b * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], width), 1)
        live = k_pos < rows_ref[s]
        heads = [(buf, pl.ds(0, width), pl.ds(g * lanes, lanes))
                 for g in range(groups)]
        q = q_ref[s].astype(k_buf.dtype)
        scores = scale * jnp.stack([
            jax.lax.dot_general(q[g], k_buf[at], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for g, at in enumerate(heads)])
        scores = jnp.where(live, scores, NEG_INF)
        m_old = m_ref[:, :, :1]
        m_new = jnp.maximum(m_old, scores.max(axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(scores - m_new), 0.0)
        fade = jnp.exp(m_old - m_new)
        l_new = fade * l_ref[:, :, :1] + p.sum(axis=-1, keepdims=True)
        # p as three terms of the pool's dtype, exact in sum for bf16
        # (8 + 8 + 8 bits of a float32's 24), stacked so that V passes
        # the MXU once.
        terms, rest = [], p
        for _ in range(3 if v_buf.dtype == jnp.bfloat16 else 1):
            terms.append(rest.astype(v_buf.dtype).astype(jnp.float32))
            rest = rest - terms[-1]
        stacked = jnp.concatenate(terms, axis=1).astype(v_buf.dtype)
        out = jnp.stack([
            jnp.dot(stacked[g], v_buf[at], preferred_element_type=jnp.float32)
            for g, at in enumerate(heads)])
        acc_ref[...] = fade * acc_ref[...] + sum(
            jnp.split(out, len(terms), axis=1))
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    def attend(s, b, buf):
        """Block `b` of slot `s`, waited for, into the running softmax."""
        block_copies(s, b, buf, lambda copy: copy.wait())

        @pl.when(b == 0)
        def _():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        in_block = jnp.minimum(rows_ref[s] - b * block_rows, block_rows)
        for tiles in range(1, block_rows // tile + 1):
            pl.when(pl.cdiv(in_block, tile) == tiles)(functools.partial(
                scores_over, s, b, buf, tiles * tile))

    def blocks_of(s):
        return pl.cdiv(rows_ref[jnp.minimum(s, slots - 1)], block_rows)

    def after(s, b):
        """The block that follows block `b` of slot `s`, as (slot, block):
        the slot's next, else the next live slot's first; a slot of
        `slots` or more says there is none."""
        last = b + 1 >= blocks_of(s)
        return (jax.lax.cond(last, lambda: next_live(s + 1), lambda: s),
                jnp.where(last, 0, b + 1))

    def start_if_any(s, b, buf):
        @pl.when(s < slots)
        def _():
            block_copies(jnp.minimum(s, slots - 1), b, buf,
                         lambda copy: copy.start())

    def block(state):
        """The block at hand from its buffer, the one after it started
        into the other."""
        s, b, buf = state
        then = after(s, b)
        start_if_any(*then, 1 - buf)
        attend(s, b, buf)

        @pl.when(then[0] != s)
        def _():
            o_ref[s] = acc_ref[...] / l_ref[:, :, :1]

        return (*then, 1 - buf)

    first = (next_live(0), 0)
    start_if_any(*first, 0)
    jax.lax.while_loop(lambda state: state[0] < slots, block, (*first, 0))


def _pack_queries(q, kv_heads: int, lanes: int):
    """q [S, H, D] as the kernel reads it, [S, G, R, W] float32: group g
    holds `W // D` KV heads, the queries of its a-th head in rows
    `a * H/KVH ...` and lanes `a * D ...`, zeros elsewhere; R is a whole
    float32 tile's 8 rows or more."""
    s_, h, d = q.shape
    per = lanes // d                       # KV heads a group
    group = h // kv_heads                  # queries a KV head
    rows = -(-per * group // 8) * 8
    qg = q.astype(jnp.float32).reshape(s_, kv_heads // per, per, group, d)
    eye = jnp.eye(per, dtype=jnp.float32)
    packed = jnp.einsum("sgahd,ab->sgahbd", qg, eye).reshape(
        s_, kv_heads // per, per * group, lanes)
    return jnp.pad(packed, ((0, 0), (0, 0), (0, rows - per * group), (0, 0)))


def _unpack_outputs(out, h: int, d: int):
    """`_pack_queries`' inverse on the kernel's result: [S, H, D]."""
    s_, groups, _, lanes = out.shape
    per = lanes // d
    group = h // (groups * per)
    out = out[:, :, :per * group].reshape(s_, groups, per, group, per, d)
    return jnp.einsum("sgahbd,ab->sgahd", out,
                      jnp.eye(per, dtype=out.dtype)).reshape(s_, h, d)


def _pallas(q, k_pool, v_pool, layer, block_tables, rows, *, scale: float,
            interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_, h, d = q.shape
    _, _, page_size, width = k_pool.shape
    lanes = max(d, _LANES)
    ppb = min(pages_per_block(page_size, width, k_pool.dtype),
              block_tables.shape[1])
    packed = _pack_queries(q, width // d, lanes)
    groups, q_rows = packed.shape[1:3]
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_place = pl.BlockSpec(memory_space=pl.ANY)
    buffers = pltpu.VMEM((2, ppb * page_size, width), k_pool.dtype)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, ppb=ppb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[whole, in_place, in_place],
            out_specs=whole,
            scratch_shapes=[
                buffers, buffers, pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((groups, q_rows, _LANES), jnp.float32),
                pltpu.VMEM((groups, q_rows, _LANES), jnp.float32),
                pltpu.VMEM((groups, q_rows, lanes), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(packed.shape, jnp.float32),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32), packed, k_pool, v_pool)
    return _unpack_outputs(out, h, d).astype(q.dtype)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, layer, block_tables: jax.Array,
                           rows: jax.Array, scale: float,
                           use_pallas: Optional[bool] = None,
                           interpret: bool = False, mesh=None) -> jax.Array:
    """Every slot's one query against its own rows of one layer of a page
    pool: q [S, H, D]; k_pool, v_pool [layers, pages, page_size, KVH * D];
    `layer` a scalar; block_tables [S, pages_per_slot]; rows [S], how many
    rows from the table's start a slot attends to (0: none, and the slot's
    result is finite and means nothing). Returns [S, H, D] in q's dtype.

    mesh: under a sharded jit, the engine's mesh: heads (the pools' last
    axis, q's second) lie over "tp" and the kernel runs on each device's
    heads; table, rows and layer are whole on every device."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not ((use_pallas or interpret)
            and kernel_takes(k_pool, q.shape[-1])):
        return _plain(q, k_pool, v_pool, layer, block_tables, rows, scale)
    kernel = functools.partial(_pallas, scale=scale, interpret=interpret)
    heads, pool = P(None, "tp", None), P(None, None, None, "tp")
    return per_shard(kernel, mesh, (heads, pool, pool, P(), P(), P()),
                     heads)(q, k_pool, v_pool, layer, block_tables, rows)
