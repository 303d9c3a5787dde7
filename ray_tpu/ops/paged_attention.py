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
cast and scored, masked by the count (`grouped_attention`; a prefill
pass's queries walk the table a block at a time, `paged_kv._paged_attention`).

A RING pool (a model with layers that attend over a window: `[window
layers, 1 + slots * pages a ring, page_size, kv_heads * head_dim]`, a slot's
own run of pages written round and round) is read by the same kernel under
a name of its own, `window_decode_attention`: the table it is handed starts
at the page of the window's oldest position and follows the ring from
there, and the rows of that first page that have left the window are masked
(`skip`), so that it fetches the pages that hold the window and no others.

A LATENT pool `[layers, pages, page_size, row]` (DeepSeek-V2/V3's: a row
is a token's latent and its one rope key, nothing a head, and there is
no pool of values) has a kernel of its own, `latent_decode_attention`,
that shares this one's walk and nothing of its body: a slot's heads are
the rows of both products (128 of them where a group of queries is 8),
a block of pages lands in VMEM once and is keys in all its lanes and
values in its first `rank`, and queries and results pass a slot at a
time (all slots' would not fit VMEM). Which kernel runs is what the
caller holds: a pool of keys and one of values a head, or the one pool.
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
            ppb: int, skip_ref=None):
    """Every slot in turn, its blocks of `ppb` pages inside that.

    layer_ref [1], rows_ref [S] (rows a slot attends to) and tables_ref
    [S * pages_per_slot] are in SMEM; so is skip_ref [S], where the caller
    has one (`_window_kernel`): the rows at the table's start that a slot
    fetches with their page and does not attend to. q_ref, o_ref [S, G, R, W] float32 in
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
        if skip_ref is not None:
            live = jnp.logical_and(live, k_pos >= skip_ref[s])
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


def _window_kernel(layer_ref, rows_ref, tables_ref, skip_ref, *refs,
                   **static):
    """`_kernel` with a fourth scalar operand: a slot attends to rows
    `[skip, rows)` from its table's start."""
    _kernel(layer_ref, rows_ref, tables_ref, *refs, skip_ref=skip_ref,
            **static)


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


def _pallas(q, k_pool, v_pool, layer, block_tables, rows, skip=None, *,
            scale: float, interpret: bool = False):
    """The kernel over every slot's rows `[0, rows)` from its table's
    start, `paged_decode_attention` in a trace; with `skip [S]`, over rows
    `[skip, rows)`, and `window_decode_attention` there."""
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
    scalars = [jnp.reshape(layer, (1,)), rows, block_tables.reshape(-1)]
    if skip is not None:
        scalars.append(skip)
    out = pl.pallas_call(
        functools.partial(_kernel if skip is None else _window_kernel,
                          scale=scale, ppb=ppb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
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
        name=("paged_decode_attention" if skip is None
              else "window_decode_attention"),
    )(*(a.astype(jnp.int32) for a in scalars), packed, k_pool, v_pool)
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


def ring_tables(pool, slots: int):
    """The pages of every slot's ring in a ring pool `[layers, 1 + slots *
    pages a ring, page_size, ..]`, `[slots, pages a ring]`: each slot's own
    run, in the ring's order, behind the NULL page. Nothing allocates
    them, so nothing stores them."""
    per_ring = (pool.shape[1] - 1) // slots
    return 1 + (jnp.arange(slots, dtype=jnp.int32)[:, None] * per_ring
                + jnp.arange(per_ring, dtype=jnp.int32)[None])


def window_decode_attention(q: jax.Array, k_ring: jax.Array,
                            v_ring: jax.Array, layer, newest: jax.Array,
                            window: int, scale: float,
                            use_pallas: Optional[bool] = None,
                            interpret: bool = False) -> jax.Array:
    """Every slot's one query against the last `window` positions of its
    RING in one layer of a ring pool: q [S, H, D]; k_ring, v_ring [layers,
    1 + S * pages a ring, page_size, KVH * D], position `p` of a slot in
    row `p mod ring` of its own run of pages (`ring_tables`); `layer` a
    scalar; newest [S], the position of the newest row a slot holds, the
    query's own (-1: the slot attends to nothing, and its result is finite
    and means nothing). The slot attends to positions `max(0, newest -
    window + 1) .. newest`, which the ring must hold (`window` rows and a
    page more). Returns [S, H, D] in q's dtype.

    The kernel is `paged_decode_attention`'s under a name of its own, over
    a table that starts at the page of the window's oldest position and
    follows the ring round from there: it fetches the pages that hold the
    window and no others, and masks the rows of the first page that have
    left the window. Elsewhere the plain form: the whole ring gathered,
    each row's position worked out from `newest`, masked by the window.
    One chip: nothing here shards a ring (the engine refuses a model with
    window layers under `tp` > 1)."""
    s_, _, hd = q.shape
    page_size = k_ring.shape[2]
    tables = ring_tables(k_ring, s_)
    per_ring = tables.shape[1]
    ring = per_ring * page_size
    live = newest >= 0
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if not ((use_pallas or interpret) and kernel_takes(k_ring, hd)):
        at = jnp.arange(ring, dtype=jnp.int32)[None]
        behind = (newest[:, None] - at) % ring       # of the row's position
        valid = live[:, None] & (behind <= newest[:, None]) & (behind < window)
        k, v = (pool[layer, tables].reshape(s_, ring, -1, hd).astype(
            jnp.float32) for pool in (k_ring, v_ring))
        return grouped_attention(q[:, None], k, v, valid[:, None],
                                 scale)[:, 0]
    oldest = jnp.maximum(newest - window + 1, 0)
    turned = jnp.take_along_axis(
        tables, (oldest[:, None] // page_size
                 + jnp.arange(per_ring, dtype=jnp.int32)[None]) % per_ring, 1)
    skip = jnp.where(live, oldest % page_size, 0)
    rows = jnp.where(live, skip + newest - oldest + 1, 0)
    return _pallas(q, k_ring, v_ring, layer, turned, rows, skip, scale=scale,
                   interpret=interpret)


def latent_kernel_takes(pool, heads: int, rank: int) -> bool:
    """Whether the latent kernel can read this pool `[layers, pages, page,
    row]`: a page is whole tiles of the pool's dtype, a row whole lanes,
    the latent (a row's first `rank` values, which are the values too)
    whole lanes of it, and a slot's heads whole sublanes of its queries."""
    _, _, page_size, row = pool.shape
    sublanes = 8 * 4 // jnp.dtype(pool.dtype).itemsize
    return (page_size % sublanes == 0 and heads % sublanes == 0
            and row % _LANES == 0 and rank % _LANES == 0 and 0 < rank <= row)


# Rows a block of the latent kernel holds at most (two buffers of 1024
# rows of 640 bf16 lanes are 2.6 MB, a block's float32 scores 0.5 MB), and
# the widths its scores are worked out at, in quarters of a block. From
# the chip at the cell's sizes (PERF.md section 6, PR 53): six layers over
# 67,566 live rows took 1.80 ms in blocks of 512 rows, 1.59 in 1024 and
# 1.53 in 2048 (a block's fixed costs are paid as often as there are
# blocks, and a wider quarter computes more rows past a sequence's end);
# eight widths read what four did.
_LATENT_BLOCK_ROWS = 1024
_LATENT_TILES = 4


def _latent_kernel(layer_ref, rows_ref, tables_ref, q_ref, pool_hbm, o_ref,
                   buf, sems, held_ref, m_ref, l_ref, acc_ref, *, rank: int,
                   ppb: int):
    """One slot a grid step, its blocks of `ppb` pages inside that.

    layer_ref [1], rows_ref [S] (rows a slot attends to) and tables_ref
    [S * pages_per_slot] are in SMEM. q_ref [H, row]: the slot's absorbed
    queries, scaled, as wide as a row of the pool, in the pool's dtype;
    o_ref [H, rank]. pool_hbm: the pool where it lies. buf [2, T, row]: a
    block's rows, keys in all their lanes and values in the first `rank`,
    one buffer filled while the other is computed on, the next live
    slot's first block while this slot's last is. held_ref [1] in SMEM:
    the buffer this slot's first block is in. m_ref, l_ref [H, 128] and
    acc_ref [H, rank]: the slot's running softmax."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, slots = pl.program_id(0), pl.num_programs(0)
    page_size = pool_hbm.shape[2]
    per_slot = tables_ref.shape[0] // rows_ref.shape[0]
    block_rows = ppb * page_size
    layer = layer_ref[0]
    rows = rows_ref[s]
    # Pages started, and waited for, at a stretch: 128 rows' worth. The
    # scalar unit issues a page's DMA in about 25 ns where it has several
    # to schedule together and 45 ns one at a time, wait included, and
    # issuing is not hidden behind the products (PERF.md section 6, PR 53).
    group = _LANES // page_size if _LANES % page_size == 0 else 1

    def next_live(t):
        """The first slot from `t` on that has rows, or `slots`."""
        return jax.lax.while_loop(
            lambda t: jnp.logical_and(
                t < slots, rows_ref[jnp.minimum(t, slots - 1)] == 0),
            lambda t: t + 1, t)

    def block_copies(t, b, at, start: bool):
        """The DMAs of every live page of block `b` of slot `t` into
        buffer `at`, started or waited for: whole groups of pages first,
        a group's DMAs issued as straight-line code and waited for as the
        bytes they bring (a buffer's DMAs share one semaphore, which
        counts bytes), then the pages left, one at a time."""
        first = b * ppb

        def page_copy(j):
            page = tables_ref[t * per_slot + first + j]
            dst = pl.ds(pl.multiple_of(j * page_size, page_size), page_size)
            return pltpu.make_async_copy(
                pool_hbm.at[layer, page], buf.at[at, dst], sems.at[at])

        def a_group(i, _):
            if start:
                for k in range(group):
                    page_copy(i * group + k).start()
            else:
                span = group * page_size
                dst = pl.ds(pl.multiple_of(i * span, span), span)
                pltpu.make_async_copy(buf.at[1 - at, dst], buf.at[at, dst],
                                      sems.at[at]).wait()

        def a_page(j, _):
            if start:
                page_copy(j).start()
            else:
                page_copy(j).wait()

        live = jnp.minimum(pl.cdiv(rows_ref[t], page_size) - first, ppb)
        jax.lax.fori_loop(0, live // group, a_group, None)
        jax.lax.fori_loop(live // group * group, live, a_page, None)

    def start_if_any(t, b, at):
        @pl.when(t < slots)
        def _():
            block_copies(jnp.minimum(t, slots - 1), b, at, start=True)

    @pl.when(s == 0)
    def _():
        # A row no DMA has written meets a probability of exactly 0, and
        # what VMEM held before may be a NaN's bits.
        buf[...] = jnp.zeros_like(buf)
        held_ref[0] = 0
        start_if_any(next_live(0), 0, 0)

    @pl.when(rows == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    # As in `_kernel`: the scores over as many whole tiles of keys as a
    # block's live rows reach, each count a straight-line program; a
    # quarter of a block a tile, so that there are four whatever the
    # block.
    tile = block_rows // _LATENT_TILES
    tile = tile if tile % _LANES == 0 else block_rows

    def scores_over(b, at, width):
        """The first `width` rows of the block into the running softmax:
        the heads are the rows of both products."""
        k_pos = b * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[0], width), 1)
        live = k_pos < rows
        scores = jax.lax.dot_general(
            q_ref[...], buf[at, pl.ds(0, width), :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        scores = jnp.where(live, scores, NEG_INF)
        m_old = m_ref[:, :1]
        m_new = jnp.maximum(m_old, scores.max(axis=-1, keepdims=True))
        p = jnp.where(live, jnp.exp(scores - m_new), 0.0)
        fade = jnp.exp(m_old - m_new)
        l_new = fade * l_ref[:, :1] + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = fade * acc_ref[...] + jnp.dot(
            p.astype(buf.dtype), buf[at, pl.ds(0, width), pl.ds(0, rank)],
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(rows > 0)
    def _():
        n_blocks = pl.cdiv(rows, block_rows)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def block(b, at):
            """Block `b` from buffer `at`, the one after it (the slot's
            next, else the next live slot's first) started into the
            other."""
            last = b + 1 >= n_blocks
            start_if_any(
                jax.lax.cond(last, lambda: next_live(s + 1), lambda: s),
                jnp.where(last, 0, b + 1), 1 - at)
            block_copies(s, b, at, start=False)
            in_block = jnp.minimum(rows - b * block_rows, block_rows)
            for tiles in range(1, block_rows // tile + 1):
                pl.when(pl.cdiv(in_block, tile) == tiles)(functools.partial(
                    scores_over, b, at, tiles * tile))
            return 1 - at

        held_ref[0] = jax.lax.fori_loop(0, n_blocks, block, held_ref[0])
        o_ref[...] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def latent_pages_per_block(page_size: int, pages_per_slot: int) -> int:
    """Pages a block of the latent kernel holds: `_LATENT_BLOCK_ROWS` rows'
    worth, and no more than a slot has."""
    return max(1, min(_LATENT_BLOCK_ROWS // page_size, pages_per_slot))


def _latent_pallas(q, pool, layer, block_tables, rows, *, rank: int,
                   interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_, h, row = q.shape
    page_size = pool.shape[2]
    ppb = latent_pages_per_block(page_size, block_tables.shape[1])
    # The result as the loop's weighted sum is shaped, `[S, 1, H, rank]`:
    # in a trace an operation goes by its name and its first result's
    # shape, and the benchmark's readers find the attention by that.
    return pl.pallas_call(
        functools.partial(_latent_kernel, rank=rank, ppb=ppb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s_,),
            in_specs=[
                pl.BlockSpec((None, h, row), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, None, h, rank),
                                   lambda s, *_: (s, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, ppb * page_size, row), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((h, _LANES), jnp.float32),
                pltpu.VMEM((h, _LANES), jnp.float32),
                pltpu.VMEM((h, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((s_, 1, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="latent_decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32), q.astype(pool.dtype), pool)


def latent_decode_attention(q: jax.Array, pool: jax.Array, layer,
                            block_tables: jax.Array, rows: jax.Array,
                            rank: int, interpret: bool = False,
                            mesh=None) -> jax.Array:
    """Every slot's absorbed queries against its own rows of one layer of
    a latent pool, the kernel: q [S, H, row] (a head's query multiplied
    into the latent's space beside its rope part, scaled, zeros up to the
    pool's row); pool [layers, pages, page_size, row], a row's first
    `rank` values the latent, which is key and value both; `layer` a
    scalar; block_tables [S, pages_per_slot]; rows [S], how many rows from
    the table's start a slot attends to (0: none, and the slot's result
    is zeros). Returns [S, 1, H, rank] in q's dtype: the weighted sum of
    the latents, the values' projection left to the caller.

    For a pool `latent_kernel_takes`. mesh: under a sharded jit, the
    engine's: every operand is whole on every device."""
    kernel = functools.partial(_latent_pallas, rank=rank, interpret=interpret)
    return per_shard(kernel, mesh, (P(),) * 5, P())(
        q, pool, layer, block_tables, rows)
