"""Paged KV cache: the serving engine's memory plane and its step programs.

The host half (`PagePool`, `PrefixCache`, the page hashes) decides which
pages a request owns; the device half (`init_paged_cache`, `decode_paged`,
`prefill_chunk_paged`, `cow_copy_page`, and the layer walks, sampler and
routing counters they share) is everything the engine jits. What a layer
computes is `models.transformer`'s (`attention_layer`, `mlp_half`,
`latent_layer`), the train step's and `generate`'s too: a step program
says how a layer's queries meet its cache, the `attend(q, k, v)` it hands
the layer, and a walk how the layers are iterated and what is carried.
`ray_tpu.serve.llm`, the host scheduler, imports this module; nothing
here imports the scheduler.

A request's K/V rows live in PAGES, not in one `[max_len]` row of its
own, so a 30-token chat holds a 30-token footprint, concurrency follows
the workload and not a fixed slot count, and two requests sharing a
prompt prefix store it once: the vLLM PagedAttention idea
(arXiv:2309.06180), built for the engine's TPU discipline of static
shapes and zero steady-state host traffic:

  * One page pool `[layers, pages, page_size, kv_heads * head_dim]` (a
    row's heads side by side) and a per-slot block table `[slots,
    pages_per_slot]` resident on device. Decode attends *through* the
    block table, reading each slot's live pages where they lie
    (`ops.paged_attention`: a kernel on the chip, a gather of the whole
    table elsewhere); prefill scatters rows into the pages the table
    names and walks its slot's table a block of pages at a time with a
    running softmax, as far as the chunk's end and no further
    (`_paged_attention` over `_walk_blocks`, the walk a latent pool's
    attention shares). Program shapes depend only on the pool and table
    geometry, so compilation stays bounded.
    The pool is donated to the step and carried whole through the scan
    over layers; layer i scatters into `pool[i, pages, rows]` and reads
    `pool[i]` through the table, so a step touches the rows it writes
    and the pages it attends to, and never copies the pool.
  * A host-side free-list allocator with REFCOUNTED pages. Admission
    reserves every page a request can ever touch up front
    (ceil((prompt + max_new + 1) / page_size)); decode then never
    allocates, so the block table uploads only at admission/eviction —
    the same single-upload discipline as the sampling params, and the
    steady-state decode loop keeps doing zero host->device transfers.
  * A PREFIX CACHE: a token-hash trie over full-page runs (chain hash
    per page, so a lookup is O(pages) dict probes). A request whose
    prompt prefix is resident maps the shared pages into its block
    table (refcount bump, no copy) and skips those prefill chunks
    entirely. Pages are copy-on-write: the one case where a new
    request must write into a shared page (its first recomputed token
    lands mid-page) forks that page first. Cache entries hold their own
    page references, so a donor request finishing — or being evicted —
    never invalidates the sharers; under pool pressure the cache LRU-
    releases entries back to the free list.

A model with latent attention (DeepSeek-V2/V3's) keeps ONE pool: a row is
a token's latent and its one rope key, nothing a head, and every layer
reads it through `_latent_attention`. A decode step's one query a slot
meets the rows as cached (the absorbed form): on the chip a kernel
fetches the pages that hold a slot's live rows and no others
(`ops.paged_attention.latent_decode_attention`), elsewhere a loop over
blocks of every slot's pages with a running softmax; a prefill pass
keeps that loop, each block expanded into every head's keys and values.
The host half counts pages, not what a page holds, and is the same.

A model with layers that attend over a WINDOW (SmallThinker's three in
four) keeps those layers' rows in a second pool, a RING of pages a slot
(`init_ring_pool`): the window and one prefill chunk, position `p` at row `p
mod ring` of the slot's own run of pages. Nothing allocates a ring and
nothing reserves it by a request's length; the full layers keep the tables
and the pool above. A decode step's query reads the last `window` rows of
its slot's ring in place (`ops.window_decode_attention`), a prefill chunk's
queries walk the slot's ring as they walk a table, each row at the position
it holds once the chunk is written. `_scan_layers` walks such a model a
period of its per-layer lists at a time, both pools in its carry.

Page 0 is reserved as the NULL/scratch page: block-table entries
default to it, inactive-slot decode writes park in it, and prefill
padding rows drop into it — it is never read unmasked, so its
contents are never observable.

Row i of a slot's table is absolute position i (pages are table-
ordered) and masked lanes underflow to exact 0.0 in the f32 softmax, so
greedy decoding gives `models.generate`'s tokens, whose one-length cache
is the plain reference (tests/test_paged_kv.py, tests/test_serve_llm.py).
"""

from __future__ import annotations

import functools
import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.transformer import (
    RECURRENT_KINDS,
    TransformerConfig,
    _embed_tokens,
    at_layer,
    attention_layer,
    block_causal,
    expand_latent,
    latent_layer,
    latent_stacks,
    layer_kinds,
    mix_recurrent,
    mlp_half,
    project_logits,
    residual,
    rope_tables,
)
from ray_tpu.ops import rmsnorm
from ray_tpu.ops.paged_attention import (
    latent_decode_attention,
    latent_kernel_takes,
    paged_decode_attention,
    ring_tables,
    window_decode_attention,
)
from ray_tpu.parallel.moe import EXPERT_LEAVES

# The reserved NULL/scratch page (see module docstring).
NULL_PAGE = 0


class OutOfPages(RuntimeError):
    """The pool cannot cover an allocation. Admission-time only: the
    engine requeues the request at the front of its tenant queue and
    retries as decoding requests finish and release pages."""

    def __init__(self, needed: int, free: int, total: int):
        super().__init__(
            f"page pool exhausted: need {needed} pages, {free} free of "
            f"{total} usable"
        )
        self.needed = needed
        self.free = free
        self.total = total


class PagePool:
    """Host-side free-list allocator over the device page pool.

    Pure bookkeeping — it never touches device memory. Refcounts make
    prefix sharing safe: a page is returned to the free list only when
    its last holder (request block table or prefix-cache entry)
    releases it. Single-threaded by design: only the engine loop thread
    allocates/releases (admission and eviction both happen there)."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages < 2:
            raise ValueError("PagePool needs >= 2 pages (page 0 is reserved)")
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        # LIFO free list: recently-freed pages are re-used first (their
        # rows are about to be overwritten anyway).
        self._free: List[int] = list(range(1, self.num_pages))
        self._refs = np.zeros(self.num_pages, dtype=np.int32)

    @property
    def usable(self) -> int:
        return self.num_pages - 1  # page 0 reserved

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.usable - len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Take `n` pages off the free list at refcount 1. All-or-
        nothing: raises OutOfPages without allocating anything when the
        list is short (partial grants would leak on the error path)."""
        if n < 0:
            raise ValueError("alloc of negative page count")
        if n > len(self._free):
            raise OutOfPages(n, len(self._free), self.usable)
        pages = [self._free.pop() for _ in range(n)]
        self._refs[pages] = 1
        return pages

    def ref(self, pages: Sequence[int]) -> None:
        """Add one reference to each page (prefix sharing / cache insert)."""
        for p in pages:
            if self._refs[p] <= 0:
                raise ValueError(f"ref of unallocated page {p}")
            self._refs[p] += 1

    def release(self, pages: Sequence[int]) -> None:
        """Drop one reference from each page; pages reaching zero return
        to the free list."""
        for p in pages:
            r = int(self._refs[p]) - 1
            if r < 0:
                raise ValueError(f"release of unallocated page {p}")
            self._refs[p] = r
            if r == 0:
                self._free.append(p)

    def refcount(self, page: int) -> int:
        return int(self._refs[page])

    def reset(self) -> None:
        """Forget everything (engine failure recovery: the device cache
        was rebuilt, so every outstanding reference is void)."""
        self._free = list(range(1, self.num_pages))
        self._refs[:] = 0


class PrefixCache:
    """Token-hash trie over full-page runs, flattened to one dict.

    Each cached page is keyed by the CHAIN hash of the prompt prefix it
    completes (h_i = blake2b(h_{i-1} || tokens of page i)), so a chain
    key identifies the entire token prefix, not just one page's tokens
    — matching is `for each key: dict probe`, longest resident prefix
    wins, no tree pointers needed. The cache holds its OWN reference on
    every resident page: donors finishing (or dying) cannot invalidate
    sharers, and `evict_pages` under pool pressure releases LRU entries
    deepest-first (an OrderedDict move-to-end on match keeps recency;
    entries of one insertion land in chain order, so popping from the
    front releases stale roots last — a child page is never left
    resident without its parent chain being droppable first is NOT
    required for correctness: a match simply stops at the first missing
    link)."""

    def __init__(self, pool: PagePool):
        self._pool = pool
        # chain-hash key -> (page, depth). Ordered: LRU at the front.
        self._entries: "OrderedDict[str, Tuple[int, int]]" = OrderedDict()

    @property
    def pages_held(self) -> int:
        return len(self._entries)

    def match(self, keys: Sequence[str]) -> List[int]:
        """Longest resident prefix of `keys`, as pages. The caller
        receives ONE reference per returned page (release when the
        request's block table drops them)."""
        pages: List[int] = []
        for k in keys:
            hit = self._entries.get(k)
            if hit is None:
                break
            self._entries.move_to_end(k)
            pages.append(hit[0])
        if pages:
            self._pool.ref(pages)
        return pages

    def insert(self, keys: Sequence[str], pages: Sequence[int]) -> int:
        """Publish a prompt's full pages under their chain keys (called
        at prefill completion, so concurrent requests share as early as
        possible). The cache takes its own reference on each newly
        inserted page; keys already resident just refresh recency.
        Returns the number of pages newly inserted."""
        added = 0
        for depth, (k, p) in enumerate(zip(keys, pages)):
            if k in self._entries:
                self._entries.move_to_end(k)
                continue
            self._pool.ref([p])
            self._entries[k] = (int(p), depth)
            added += 1
        return added

    def evict_pages(self, n: int) -> int:
        """Release up to `n` LRU entries back toward the pool (allocation
        pressure). Returns how many entries were dropped — the caller
        retries its alloc; freed-page count can be lower when a sharer
        still holds a reference."""
        dropped = 0
        while dropped < n and self._entries:
            _, (page, _) = self._entries.popitem(last=False)
            self._pool.release([page])
            dropped += 1
        return dropped

    def flush(self) -> int:
        """Drop every entry (chaos hook / tests). Returns entries dropped."""
        return self.evict_pages(len(self._entries))

    def reset(self) -> None:
        """Forget entries WITHOUT releasing (engine failure recovery:
        the pool was reset, the references no longer exist)."""
        self._entries.clear()

    def roots(self, limit: int = 64) -> List[str]:
        """Most-recently-used depth-0 chain keys — the replica's
        advertised prefix set for affinity routing. Depth 0 only: a
        router match on the FIRST page is what predicts the rest of the
        chain being resident, and it keeps the advertisement bounded."""
        out = [k for k, (_, d) in self._entries.items() if d == 0]
        return out[-limit:]


def page_hashes(tokens, page_size: int) -> List[str]:
    """Chain hashes of every FULL page of `tokens` (partial tail pages
    are never cached — their rows would change as the request decodes).
    Key i commits to tokens[0 : (i+1)*page_size]."""
    arr = np.asarray(tokens, dtype=np.int32).reshape(-1)
    out: List[str] = []
    parent = b""
    for i in range(len(arr) // page_size):
        h = hashlib.blake2b(
            parent + arr[i * page_size:(i + 1) * page_size].tobytes(),
            digest_size=16,
        )
        parent = h.digest()
        out.append(h.hexdigest())
    return out


def prefix_route_key(tokens, page_size: int) -> Optional[str]:
    """The depth-0 chain key of a prompt (None when the prompt does not
    fill one page) — what the handle matches against replicas'
    advertised `roots` for prefix-affinity routing."""
    arr = np.asarray(tokens, dtype=np.int32).reshape(-1)
    if page_size < 1 or len(arr) < page_size:
        return None
    return hashlib.blake2b(
        arr[:page_size].tobytes(), digest_size=16
    ).hexdigest()


def ring_pages(cfg: TransformerConfig, page_size: int, pages_per_slot: int,
               prefill_chunk: int) -> int:
    """Pages of a slot's ring in a window layer: the window and, beyond
    it, the longer of one prefill chunk and a page (a chunk's rows are all
    written before its first query reads, and the window's oldest page is
    in it only in part), in whole pages; no more than a slot's table has,
    where nothing wraps."""
    rows = cfg.sliding_window_size + max(prefill_chunk, page_size)
    return min(-(-rows // page_size), pages_per_slot)


def init_ring_pool(cfg: TransformerConfig, slots: int, page_size: int,
                   pages: int) -> Dict:
    """The second pool of a model with window layers: keys and values `[window
    layers, 1 + slots * pages, page_size, kv_heads * head_dim]`, a RING of
    `pages` pages a slot and window layer behind the NULL page, position
    `p` in row `p mod (pages * page_size)` of the slot's own run
    (`ops.paged_attention.ring_tables`). A slot owns its ring: nothing is
    reserved by a request's length and nothing grows. It rides in the layer
    walk's carry beside the full layers' pages and is donated with them."""
    shape = (cfg.window_layers, 1 + slots * pages, page_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype=cfg.dtype),
            "v": jnp.zeros(shape, dtype=cfg.dtype)}


def init_paged_cache(cfg: TransformerConfig, slots: int, num_pages: int,
                     page_size: int, pages_per_slot: int,
                     mesh=None, prefill_chunk: int = 0) -> Dict:
    """Device state of the paged cache: the page pool, per-slot lengths,
    and the block table (all entries NULL_PAGE). KV heads shard over
    "tp"; everything else is replicated. A hybrid's pool has its
    attention layers only, indexed by their own count, and the cache
    gains `rec`, the recurrent pool (`init_recurrent_pool`). A model with
    latent attention has ONE pool, under `k`: a row is a token's latent and
    its rope key side by side and zeros up to whole lanes
    (`latent_row_width`), nothing a head, and `v` is None, an argument
    without a buffer (the engine refuses such a pool under `tp` > 1). A
    model with window layers has its full layers alone in the pool, indexed
    by their own count, and the cache gains `ring`, the window layers'
    (`init_ring_pool`, sized for chunks of `prefill_chunk`)."""
    kv_layers = (cfg.attention_layers if cfg.layer_pattern
                 else cfg.n_layers - cfg.window_layers)
    # A row's heads side by side, one layout for every program that
    # touches the pool: a page is then whole tiles whatever a head's
    # width, which is how the decode kernel fetches it, and a head is a
    # slice of the row's lanes. (Under `[.., kv_heads, head_dim]` at
    # head_dim 64 the compiler turned the whole pool into another tiling
    # between a layer's scatter and its gather.)
    latent = bool(cfg.kv_lora_rank)
    shape = (kv_layers, num_pages, page_size,
             latent_row_width(cfg) if latent
             else cfg.n_kv_heads * cfg.head_dim)
    cache = {
        "k": jnp.zeros(shape, dtype=cfg.dtype),
        "v": None if latent else jnp.zeros(shape, dtype=cfg.dtype),
        "lengths": jnp.zeros((slots,), dtype=jnp.int32),
        "block_tables": jnp.zeros((slots, pages_per_slot),
                                  dtype=jnp.int32),
    }
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        # No trailing None: the spec a step's output comes back with, so
        # that the first call and every later one are one jit cache entry.
        kv_sharding = NamedSharding(mesh, P(None, None, None, "tp"))
        rep = NamedSharding(mesh, P())
        cache = {
            "k": jax.device_put(cache["k"], kv_sharding),
            "v": (None if latent
                  else jax.device_put(cache["v"], kv_sharding)),
            "lengths": jax.device_put(cache["lengths"], rep),
            "block_tables": jax.device_put(cache["block_tables"], rep),
        }
    if cfg.layer_pattern:
        cache["rec"] = init_recurrent_pool(cfg, slots)
    if cfg.window_layout:
        cache["ring"] = init_ring_pool(cfg, slots, page_size, ring_pages(
            cfg, page_size, pages_per_slot, prefill_chunk))
    return cache


def init_recurrent_pool(cfg: TransformerConfig, slots: int) -> Dict:
    """The second pool of a model with recurrent layers: a row of fixed
    size a slot and a recurrent layer. Mamba layers: `state [ssm layers,
    slots, heads, d_head, d_state]` float32 and the convolution's last
    inputs `conv [ssm layers, slots, d_conv - 1, conv_dim]` in the weights'
    dtype. Gated short convolutions: their last gated inputs `conv [conv
    layers, slots, conv_L_cache - 1, d_model]` in the weights' dtype and
    nothing else. Delta-rule layers: a matrix a head, `state [kda layers,
    slots, heads, head_dim, head_dim]` float32, and the last inputs of q's,
    k's and v's convolutions `conv [kda layers, slots, kernel - 1, 3 *
    heads * head_dim]`. It rides in the layer walk's carry beside the pages
    and is donated with them."""
    return RECURRENT_KINDS[cfg.recurrent_kind][1].init_state(
        cfg, cfg.recurrent_layers, slots)


def _rows(new, pool):
    """Rows `new [R, kv_heads, head_dim]` as `pool` holds a row."""
    return new.astype(pool.dtype).reshape(new.shape[:1] + pool.shape[3:])


def latent_row_width(cfg) -> int:
    """Values a latent pool keeps of a token in a layer: the latent and the
    rope key side by side, and zeros up to whole lanes of 128."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def _latent_rows(latent, k_r, pool):
    """Rows `[R, row width]` as `pool` holds them, of latents `[.., rank]`
    and rope keys `[.., dr]`."""
    rows = jnp.concatenate([latent, k_r], -1)
    rows = rows.reshape(-1, rows.shape[-1]).astype(pool.dtype)
    return jnp.pad(rows, ((0, 0), (0, pool.shape[-1] - rows.shape[-1])))


# Cached rows one step of `_latent_attention`'s walk gathers of each batch
# row, at most (the size the chip's readings were taken at).
LATENT_BLOCK_ROWS = 512


def latent_block_pages(page_size: int, pages_per_slot: int) -> int:
    """Pages of a slot's table that one step of `_latent_attention`'s walk
    gathers: an eighth of the table, so that the loop overshoots the longest
    live slot by an eighth of a slot's width at most, and no more than
    `LATENT_BLOCK_ROWS` rows' worth, which bounds a block's scores."""
    return max(1, min(pages_per_slot // 8, LATENT_BLOCK_ROWS // page_size))


# Cached rows one step of `_paged_attention`'s walk gathers of each batch
# row. By the chip's readings at the serving cells' shapes
# (`tools/walk_sweep.py`; PERF.md section 6, PR 71): a trip costs 0.03-0.04
# ms whatever it holds, a table of 16,384 or 2,560 rows reads fastest in
# blocks of 512 at the lengths the cells' prompts have (128 takes 1.7 times
# as long; 1,024 gains 8% behind the longest prompts, loses 40% behind the
# shortest and spills a block's scores at 64 heads), and a table of 1,024
# in one block costs what the plain form did at every length where blocks
# of 128 cost up to 3.5 times that.
WALK_BLOCK_ROWS = 512


def walk_block_pages(page_size: int, pages_per_slot: int) -> int:
    """Pages of a slot's table (or ring) that one step of
    `_paged_attention`'s walk gathers: `WALK_BLOCK_ROWS` rows' worth; a
    table of up to two such blocks is one block, read in one step with no
    loop (the trips would cost more than stopping early saves)."""
    if pages_per_slot * page_size <= 2 * WALK_BLOCK_ROWS:
        return pages_per_slot
    return max(1, WALK_BLOCK_ROWS // page_size)


def rows_walked(reach: int, page_size: int, pages_per_slot: int) -> int:
    """Rows of the blocks `_paged_attention`'s walk visits of a table
    `pages_per_slot` pages wide when the longest row of the batch sees
    `reach` rows: the host's count of what a pass's walk gathers (the
    engine's `stats()["attention"]`), by the walk's own arithmetic."""
    pages = walk_block_pages(page_size, pages_per_slot)
    n_max = -(-pages_per_slot // pages)
    n_blocks = 1 if n_max == 1 else min(-(-reach // (pages * page_size)),
                                        n_max)
    return n_blocks * pages * page_size


def _walk_blocks(tables, pages: int, page_size: int, live, stat, width: int,
                 block_of):
    """The running softmax over blocks of `pages` pages of `page_size` rows
    of each batch row's table `tables [B, pages a slot]`: the one walk of
    cached rows where they lie, a latent pool's (`_latent_attention`) and
    that of a pool of keys and one of values (`_paged_attention`). As many
    steps as the longest row's `live [B]` rows need and no more: the trip
    count is data, so neither the rows read nor the scores held grow with
    the table's width, and a table one block wide takes its one step with
    no loop.

    `block_of(j, at)`, of a block's index and its pages `at [B, pages]` (the
    NULL page past the table's end), gives the block's float32 `scores
    [*stat, rows]`, which of them are `seen` (a shape that broadcasts to
    theirs) and `weigh(weights [*stat, rows] float32) -> [*stat, width]`,
    their float32 product with the block's values. Maximum, sum and
    weighted sum are carried in float32. Returns the weighted mean `[*stat,
    width]` float32; a row that sees nothing gives zeros."""
    f32 = jnp.float32
    mp = tables.shape[1]
    n_max = -(-mp // pages)
    low = jnp.asarray(-1e30, f32)
    padded = jnp.pad(tables, ((0, 0), (0, n_max * pages - mp)))  # NULL_PAGE

    def step(j, carry):
        top, total, acc = carry                 # stat, stat, stat + [width]
        at = jax.lax.dynamic_slice_in_dim(padded, j * pages, pages, axis=1)
        scores, seen, weigh = block_of(j, at)
        scores = jnp.where(seen, scores, low)
        new_top = jnp.maximum(top, scores.max(-1))
        weights = jnp.where(seen, jnp.exp(scores - new_top[..., None]), 0.0)
        keep = jnp.exp(top - new_top)
        added = weigh(weights)
        return (new_top, total * keep + weights.sum(-1),
                acc * keep[..., None] + added)

    def start():
        return (jnp.full(stat, low), jnp.zeros(stat, f32),
                jnp.zeros(stat + (width,), f32))

    if n_max == 1:
        _, total, acc = step(0, start())
    else:
        n_blocks = jnp.minimum(-(-jnp.max(live) // (pages * page_size)),
                               n_max)
        _, total, acc = jax.lax.fori_loop(0, n_blocks, step, start())
    return acc / jnp.maximum(total, 1e-30)[..., None]


def absorbed_queries(q_n, q_r, lp, cfg, row: int):
    """The queries of latent attention's absorbed form, `[B, Q, H, row]`:
    each head's `q_n [B, Q, H, dn]` multiplied into the latent's space
    (`q_n W_uk^T`, `[rank]` a head), its rope part `q_r [B, Q, H, dr]`
    beside it, scaled, and zeros up to `row`, a cached row's width: one
    product with a cached row is the query's score against it."""
    h, dn = q_n.shape[2:]
    w_uk = lp["w_uk"].reshape(cfg.kv_lora_rank, h, dn)
    q = jnp.concatenate([jnp.einsum("bqhn,rhn->bqhr", q_n, w_uk), q_r], -1)
    q = q * jnp.asarray(cfg.attention_scale, q_n.dtype)
    return jnp.pad(q, ((0, 0),) * 3 + ((0, row - q.shape[-1]),))


def _latent_attention(q_n, q_r, lp, pool, layer, tables, q_pos, end, cfg,
                      absorbed: bool, mesh=None):
    """Latent attention of `Q` queries a batch row against the rows its
    table names in `pool[layer]`, where they lie: a loop over blocks of
    cached rows (`latent_block_pages`) with a running softmax, as many steps
    as the longest row of the batch needs and no more, so that neither the
    rows read nor the scores held grow with the pool's width.

    `q_n [B, Q, H, dn]`, `q_r [B, Q, H, dr]` (rotated); `tables [B, pages a
    slot]`; query `q` of row `b` sees cached row `k` where `k <= q_pos[b,
    q]` and `k < end[b]`; `q_pos` None: every row below `end[b]`, a decode
    step's one query a row, and on the chip the absorbed form of that is a
    kernel that fetches the pages under `end[b]` and no others
    (`ops.paged_attention.latent_decode_attention`, where the shapes allow:
    `latent_kernel_takes`). `absorbed`: the queries are first multiplied into
    the latent's space (`q_n W_uk^T`, `[H, rank]` a query), set beside
    their rope part as one vector as wide as a cached row, and scores and
    the weighted sum are taken against the rows as cached, the values'
    projection coming last; else each block's latents are first expanded
    into every head's keys (the rope key set beside each) and values. The
    same numbers either way. One product gives a block's scores in either
    form, in the order its operands give them (`[B, Q, H, K]` absorbed,
    where the heads are the queries' own axis; `[B, H, Q, K]` expanded,
    where they are the keys' too), so that no score is transposed. A row
    that sees nothing (an idle slot, an inert row) gives zeros. Returns
    `[B, Q, H * dv]` in the queries' dtype."""
    b, n_q, h, _ = q_n.shape
    ps, row = pool.shape[2], pool.shape[3]
    rank, dv = cfg.kv_lora_rank, cfg.v_head_dim
    dtype, f32 = q_n.dtype, jnp.float32
    w_uv = lp["w_uv"].reshape(rank, h, dv)
    if absorbed:
        q = absorbed_queries(q_n, q_r, lp, cfg, row)
        stat, width = (b, n_q, h), rank
    else:
        q = jnp.concatenate([q_n, q_r], -1) * jnp.asarray(
            cfg.attention_scale, dtype)
        stat, width = (b, h, n_q), dv

    def block_of(j, at):
        block = at.shape[1] * ps
        rows = pool[layer, at].reshape(b, block, row)
        k_pos = j * block + jnp.arange(block, dtype=jnp.int32)
        if q_pos is None:
            seen = k_pos < end[:, None, None]                # [B, 1, K]
        else:
            seen = ((k_pos <= q_pos[:, :, None])
                    & (k_pos < end[:, None, None]))          # [B, Q, K]
        if absorbed:
            values, seen = rows[..., :rank], seen[:, :, None]
            scores = jnp.einsum("bqhr,bkr->bqhk", q, rows,
                                preferred_element_type=f32)
        else:
            k_n, values = expand_latent(rows[..., :rank], lp, cfg)
            k_r = rows[:, :, None, rank:rank + q_r.shape[-1]]
            keys = jnp.concatenate(
                [k_n, jnp.broadcast_to(k_r, k_n.shape[:3] + k_r.shape[3:])],
                -1)
            seen = seen[:, None]
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, keys,
                                preferred_element_type=f32)
        return scores, seen, lambda weights: jnp.einsum(
            "bqhk,bkr->bqhr" if absorbed else "bhqk,bkhr->bhqr",
            weights.astype(dtype), values, preferred_element_type=f32)

    in_place = (absorbed and q_pos is None and n_q == 1
                and jax.default_backend() == "tpu"
                and latent_kernel_takes(pool, h, rank))
    with jax.named_scope("mla.attend"):
        if in_place:
            out = latent_decode_attention(q[:, 0], pool, layer, tables, end,
                                          rank, mesh=mesh)
        else:
            out = _walk_blocks(
                tables, latent_block_pages(ps, tables.shape[1]), ps, end,
                stat, width, block_of).astype(dtype)
        if absorbed:
            out = jnp.einsum("bqhr,rhv->bqhv", out, w_uv)
        else:
            out = out.transpose(0, 2, 1, 3)
        return out.reshape(b, n_q, h * dv)


def _paged_attention(q, k_pool, v_pool, layer, tables, live, seen, scale):
    """Attention of `C` queries a batch row, `q [P, C, H, D]`, against the
    rows its table `tables [P, pages]` names in `k_pool[layer]` and
    `v_pool[layer]`, where they lie (`_walk_blocks`): a slot's table or its
    ring.

    `live [P]`: how many of the table's rows, from its first, a batch row
    may see at all, the table's width at most; what bounds the walk (0: an
    inert row, which lengthens no walk and gives zeros). `seen(rows [K]) ->
    [P, C, K]`: which of the table's rows `rows` each query sees among
    those, the caller's mask made a block at a time. Keys and values are
    cast to float32 a block at a time and scores, weights and accumulators
    are float32, the operands `grouped_attention` multiplies, so the two
    differ by the order of a sum.
    Returns `[P, C, H, D]` in the queries' dtype."""
    p_, c, h, d = q.shape
    ps, mp = k_pool.shape[2], tables.shape[1]
    kvh = k_pool.shape[3] // d
    f32 = jnp.float32
    qg = q.reshape(p_, c, kvh, h // kvh, d).astype(f32)
    live = jnp.minimum(live, mp * ps)  # the pad past the table is not rows

    def block_of(j, at):
        block = at.shape[1] * ps
        keys = k_pool[layer, at].reshape(p_, block, kvh, d).astype(f32)
        values = v_pool[layer, at].reshape(p_, block, kvh, d).astype(f32)
        rows = j * block + jnp.arange(block, dtype=jnp.int32)
        scores = jnp.einsum("sqhgd,skhd->shgqk", qg, keys) * scale
        visible = seen(rows) & (rows < live[:, None, None])
        return scores, visible[:, None, None], lambda weights: jnp.einsum(
            "shgqk,skhd->shgqd", weights, values)

    out = _walk_blocks(tables, walk_block_pages(ps, mp), ps, live,
                       (p_, kvh, h // kvh, c), d, block_of)
    return out.transpose(0, 3, 1, 2, 4).reshape(p_, c, h, d).astype(q.dtype)


def _latent_through_pool(pages_w, rows_w, tables, q_pos, end, cfg,
                         absorbed: bool, mesh=None):
    """`_walk_latent`'s `attend` of a step program: every row's latent and
    rope key scattered into layer `i` of the one pool at `(pages_w,
    rows_w)`, then the queries against the rows `tables` names
    (`_latent_attention`)."""
    def attend(i, pool, lp, q_n, q_r, latent, k_r):
        pool = pool.at[i, pages_w, rows_w].set(_latent_rows(latent, k_r, pool))
        return _latent_attention(q_n, q_r, lp, pool, i, tables, q_pos, end,
                                 cfg, absorbed, mesh), pool

    return attend


def _walk_latent(params, x, pool, attend, cfg, cos, sin, positions,
                 mesh=None):
    """A latent-attention decoder's layers in turn, `_scan_layers` for a
    stack a kind: the dense layers' scan, then the expert layers', the one
    pool `[layers, ...]` riding in both carries. `attend(i, pool, lp, q_n,
    q_r, c, k_r) -> (attention [B, L, H * dv], pool)`, `latent_layer`'s
    `attend(q_n, q_r, c, k_r)` with the layer's index, the pool and the
    layer's leaves in front, writes and reads the pool at layer `i`. The
    expert stacks stay out of the scan and are read whole at the layer's index within its kind (`moe_block`). Returns x,
    the pool and the assignments each expert layer's experts received
    `[expert layers, E]` (None without experts)."""
    counts = None
    for kind, stack, first in latent_stacks(params):
        experts = ({n: stack[n] for n in EXPERT_LEAVES}
                   if kind == "moe" else {})
        scanned = {n: w for n, w in stack.items() if n not in experts}

        def layer(carry, inputs, experts=experts, first=first):
            x, pool = carry
            lp, j = inputs
            lp = {**lp, **experts}
            x, routing, pool = latent_layer(
                x, lp, cfg, cos, sin, positions,
                functools.partial(attend, first + j, pool, lp), mesh,
                j if experts else None)
            return (x, pool), (None if routing is None
                               else routing["counts"])

        depth = scanned["attn_norm"].shape[0]
        (x, pool), got = jax.lax.scan(
            layer, (x, pool), (scanned, jnp.arange(depth, dtype=jnp.int32)))
        counts = got if got is not None else counts
    return x, pool, counts


def _walk_hybrid(params, x, k_cache, v_cache, rec, attend, rec_io,
                 n_valid, cfg, cos, sin, positions, mesh=None):
    """A hybrid's layers in turn, `_scan_layers` for layers of two kinds
    (`attend` is that walk's, an attention layer `attention_layer`, a
    recurrent layer its mixer and `mlp_half`): the KV pool (attention layers
    only, indexed by their own count) and the recurrent pool `rec` both ride
    in the carry and are updated in place. ONE scan over the attention
    layers; each of its steps first walks the run of recurrent layers that stands before its attention
    layer (a `fori_loop` whose bounds are the scan's inputs: 5, 9, 9, 9 for
    granite-4.0-h-micro), and the recurrent layers after the last
    attention layer follow in a loop of their own. So a recurrent layer is
    compiled twice and an attention layer once, whatever the depth and
    whatever the pattern, and every buffer is a loop carry from the first
    layer to the last. (One scan over all layers with a `lax.cond` on the
    kind would hand each pool through the branch that does not touch it,
    and a conditional's result that is its own argument is copied: 3.7 GB
    four times a step here.) Every layer reads its weights from its kind's
    stack at its own index.

    A model with experts is walked as two such spans, split where the MLP
    changes kind and not branched there: the leading layers, whose MLP is
    dense (`layers["mlp"]`; a model may have none), then the expert layers,
    each reading its router, its choice bias and its shared expert from
    `layers["moe"]` and the expert stacks whole at its own index among them
    (`moe_block`'s `layer`, as `_scan_layers` does): all of a layer's
    experts or the held share (`cfg.experts_held`). The assignments each
    expert layer's experts received, held here or not, ride in the carry
    too, `[expert layers, E]` (`_count_routing` counts the held ones' hits,
    as for `_walk_latent`).

    `rec_io = (read, write)`: `read(rec, j) -> {name: rows [B, ...]}` of
    the rows this call advances in recurrent layer `j`, by the pool's own
    names, and `write(rec, j, rows) -> rec`; `n_valid [B]` real rows of
    each (`transformer.mix_recurrent`). `rec_io` None: the call
    advances every slot's row by one token where it lies in the pool, and
    a kind whose module names rows `IN_POOL` (Mamba-2's and the delta
    rule's `state`) has its mixer take those whole and `j`
    (`ops.ssm_update`, `ops.kda_update`: layer `j` is passed over once each
    way; every other row is sliced out at `j` and set back, which the
    compiler does in place). Returns x, the caches, `rec` and the
    assignments (None without experts)."""
    layers = params["layers"]
    stack_name, recurrent = RECURRENT_KINDS[cfg.recurrent_kind]
    stack = layers[stack_name]
    is_recurrent, within = layer_kinds(cfg)
    n_attn, n_dense = cfg.attention_layers, cfg.n_layers - cfg.expert_layers
    experts, routers, counts = {}, None, None
    if cfg.expert_layers:
        experts = {n: layers["moe"][n] for n in EXPERT_LEAVES}
        routers = {n: w for n, w in layers["moe"].items() if n not in experts}
        counts = jnp.zeros((cfg.expert_layers, cfg.num_experts), jnp.int32)

    def mlp_at(i, dense):
        """Layer `i`'s MLP leaves, and `moe_block`'s `layer` for them."""
        if dense:
            return at_layer(layers["mlp"], i), None
        return {**at_layer(routers, i - n_dense), **experts}, i - n_dense

    def mix(h, lp, rec, j):
        if rec_io is not None:
            out, rows = mix_recurrent(h, lp, cfg, rec_io[0](rec, j), n_valid)
            return out, rec_io[1](rec, j, rows)
        whole = recurrent.IN_POOL
        out, rows = mix_recurrent(
            h, lp, cfg, {name: pool if name in whole else pool[j]
                         for name, pool in rec.items()},
            n_valid, layer=j if whole else None)
        return out, {name: new if name in whole else rec[name].at[j].set(new)
                     for name, new in rows.items()}

    def recurrent_run(x, rec, counts, first, rec_first, count, dense):
        """`count` recurrent layers from layer `first`, the `rec_first`-th
        of their kind."""
        def one(t, carry):
            x, rec, counts = carry
            lp = at_layer(stack, rec_first + t)
            h = rmsnorm(x, lp["norm"], cfg.norm_eps, mesh=mesh)
            out, rec = mix(h, lp, rec, rec_first + t)
            x = residual(x, out, cfg)
            mlp, j = mlp_at(first + t, dense)
            x, routing = mlp_half(x, mlp, cfg, mesh, layer=j)
            if routing is not None:
                counts = counts.at[j].set(routing["counts"])
            return x, rec, counts

        return jax.lax.fori_loop(0, count, one, (x, rec, counts))

    def span(carry, lo, hi, dense):
        """Layers `[lo, hi)`, whose MLPs are all `dense` or all routed."""
        attn_at = lo + np.flatnonzero(~is_recurrent[lo:hi])
        tail = lo
        if len(attn_at):
            run_from = np.concatenate([[lo], attn_at[:-1] + 1])
            a0 = int(within[attn_at[0]])
            attn = layers["attn"]
            if len(attn_at) < n_attn:
                attn = jax.tree.map(lambda w: w[a0:a0 + len(attn_at)], attn)

            def period(carry, inputs):
                x, kc, vc, rec, counts = carry
                attn, a, at, first = inputs
                x, rec, counts = recurrent_run(
                    x, rec, counts, first, first - a, at - first, dense)
                mlp, j = mlp_at(at, dense)
                x, routing, (kc, vc) = attention_layer(
                    x, {**attn, **mlp}, cfg, cos, sin, positions,
                    functools.partial(attend, a, kc, vc), mesh, layer=j)
                if routing is not None:
                    counts = counts.at[j].set(routing["counts"])
                return (x, kc, vc, rec, counts), None

            carry, _ = jax.lax.scan(
                period, carry,
                (attn, a0 + jnp.arange(len(attn_at), dtype=jnp.int32),
                 jnp.asarray(attn_at, jnp.int32),
                 jnp.asarray(run_from, jnp.int32)))
            tail = int(attn_at[-1]) + 1
        if tail < hi:
            x, kc, vc, rec, counts = carry
            x, rec, counts = recurrent_run(
                x, rec, counts, tail, int(within[tail]), hi - tail, dense)
            carry = (x, kc, vc, rec, counts)
        return carry

    carry = (x, k_cache, v_cache, rec, counts)
    if n_dense:
        carry = span(carry, 0, n_dense, True)
    if cfg.expert_layers:
        carry = span(carry, n_dense, cfg.n_layers, False)
    return carry


def init_ssm_counters() -> Dict:
    """The device-resident accumulator of a model with recurrent layers
    (`init_routing_counters`' pattern: last argument and result of the
    step programs, not donated, fetched by `engine.stats()["ssm"]` alone):
    slot rows a decode step computed and those of them that belonged to a
    live sequence (the others' state is computed and left as it was),
    a prefill chunk's tokens computed and those that were real, and the
    calls of a step program."""
    return {name: jnp.zeros((), jnp.int32) for name in (
        "decode_rows_live", "decode_rows_computed", "prefill_tokens_valid",
        "prefill_tokens_computed", "calls")}


def _with_recurrent(out, rec, count, **added):
    """A step program's results with the recurrent pool and, where the
    caller passed one, the accumulator advanced by `added` appended; a
    model without recurrent layers gets `out` as it is."""
    if rec is None:
        return out
    if count is None:
        return (*out, rec)
    return (*out, rec, {
        name: total + added.get(name, 0) + (name == "calls")
        for name, total in count.items()})


def _scan_layers(params, x, k_cache, v_cache, attend, cfg, cos, sin,
                 positions, mesh=None, ring=None, attend_ring=None):
    """Every layer in turn, the whole KV cache `[layers, ...]` riding in
    the scan's carry: the one way a step threads its cache through the
    layers. `attend(i, kc, vc, q, k, v) -> (attention [B, L, H, D], (kc,
    vc))` is `attention_layer`'s `attend(q, k, v)` with the layer's index
    and the caches in front; it writes and reads the whole cache at `[i,
    ...]`.

    A carry is one buffer from the first layer to the last, so with the
    caches donated each layer's rows are scattered into the caller's own
    buffer. Scanned over as `xs` and stacked back as `ys` a cache is two
    buffers: every layer is sliced out of one and written into the
    other, and the result copied back over the donated argument.

    The expert stacks of a model that has them stay out of the scan for
    the same reason: every layer reads them whole at its own index
    (`moe_block`), where a layer sliced out for a grouped matmul would be
    copied first.

    The scan's body is a PERIOD of the model's per-layer lists
    (`cfg.layer_period`: 1 where the layers are all alike, 4 for full,
    window, window, window), the period's layers written out in it, each
    reading its weights where they lie in the stacks (`at_layer`, which
    compiles to what a scan's own slice of its inputs does), so that each
    layer's kind is known where it is traced and no pool passes through
    a branch that does not touch it: a layer without rope gets no
    `cos`; a window layer gets `ring = {"k", "v"}`, the window layers'
    pool, for its cache and `attend_ring` for its `attend`. Either pool is
    indexed by the layer's count among its own kind. Every buffer is still
    one loop carry from the first layer to the last.

    Returns x, the caches, `ring` (None as it came) and the assignments
    each layer's experts received in this call `[layers, E]`, None for a
    dense model."""
    layers, experts = params["layers"], {}
    if cfg.num_experts:
        experts = {n: layers[n] for n in EXPERT_LEAVES}
        layers = {n: w for n, w in layers.items() if n not in experts}
    period = cfg.layer_period
    window = (cfg.window_layout or (False,) * period)[:period]
    rotates = cfg.rope_layers[:period]
    # A layer's place among its own kind within a period, and how many of
    # each kind a period has.
    before = [sum(w == window[t] for w in window[:t]) for t in range(period)]
    of_kind = {w: sum(v == w for v in window) for w in (False, True)}

    def run(carry, j):
        x, kc, vc, ring = carry
        counts = []
        for t in range(period):
            at = j * of_kind[window[t]] + before[t]
            i = j * period + t
            through = (functools.partial(attend_ring, at, ring["k"], ring["v"])
                       if window[t] else functools.partial(attend, at, kc, vc))
            x, routing, pools = attention_layer(
                x, {**at_layer(layers, i), **experts}, cfg,
                cos if rotates[t] else None, sin, positions, through, mesh,
                layer=i if experts else None)
            if window[t]:
                ring = dict(zip(("k", "v"), pools))
            else:
                kc, vc = pools
            counts.append(None if routing is None else routing["counts"])
        return (x, kc, vc, ring), (jnp.stack(counts) if experts else None)

    runs = cfg.n_layers // period
    (x, k_cache, v_cache, ring), counts = jax.lax.scan(
        run, (x, k_cache, v_cache, ring), jnp.arange(runs, dtype=jnp.int32))
    if counts is not None:
        counts = counts.reshape((cfg.n_layers,) + counts.shape[2:])
    return x, k_cache, v_cache, ring, counts


def init_routing_counters(cfg: TransformerConfig) -> Dict:
    """The device-resident accumulator of a model with experts: what its
    step programs add to at every call and `engine.stats()["moe"]` fetches,
    so that nothing about routing leaves the device inside the loop."""
    per_layer = jnp.zeros((cfg.expert_layers,), jnp.int32)
    return {
        "assignments": jnp.zeros((cfg.expert_layers, cfg.num_experts),
                                 jnp.int32),
        "calls": jnp.zeros((), jnp.int32),
        "experts_hit_sum": per_layer,
        "max_load_sum": per_layer,
    }


def held_experts(cfg: TransformerConfig) -> slice:
    """The run of a layer's routed experts whose weights are here."""
    return slice(cfg.expert_share * cfg.held,
                 (cfg.expert_share + 1) * cfg.held)


def _count_routing(out, moe, counts, cfg):
    """A step program's results with the routing accumulator `moe`
    advanced by this call's `counts [expert layers, E]` appended; a caller
    that passed no accumulator gets `out` as it is. Assignments are counted
    for every expert the router can choose; experts hit and the largest
    load are of the experts held here, whose weights the call read."""
    if moe is None:
        return out
    here = counts[:, held_experts(cfg)]
    return (*out, {
        "assignments": moe["assignments"] + counts,
        "calls": moe["calls"] + 1,
        "experts_hit_sum": moe["experts_hit_sum"] + (here > 0).sum(-1),
        "max_load_sum": moe["max_load_sum"] + here.max(-1),
    })


MAX_TOP_K = 64  # per-slot top-k cap: what `submit()` checks


def _bit_pattern(dtype):
    """(the unsigned type of a float type's width, its sign bit, all of
    its bits)."""
    bits = jnp.finfo(dtype).bits
    uint = jnp.dtype(f"uint{bits}").type
    return uint, uint(1 << (bits - 1)), uint((1 << bits) - 1)


def _ordered_bits(x):
    """A float -> the unsigned integer of its own width (uint32 of a
    float32, uint16 of a bfloat16) whose order is the floats' order: the
    sign bit set on a positive, every bit flipped on a negative.
    `_from_ordered_bits` is its inverse. -0.0 is folded into +0.0, so
    that equal floats have equal images."""
    uint, sign, ones = _bit_pattern(x.dtype)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(x == 0, jnp.zeros_like(x), x), uint)
    return bits ^ jnp.where(bits >= sign, ones, sign)


def _from_ordered_bits(u, dtype):
    _, sign, ones = _bit_pattern(dtype)
    return jax.lax.bitcast_convert_type(
        u ^ jnp.where(u >= sign, sign, ones), dtype)


def _bisection_passes(dtype, wanted=None):
    """The trips of a bisection among the values of `dtype`: as many as
    the type has bits, or, under a traced flag `wanted`, none where it is
    false."""
    bits = jnp.finfo(dtype).bits
    return bits if wanted is None else jnp.where(wanted, bits, 0)


def _smallest_passing(logits, weigh, budget, floor, wanted=None):
    """For every row of `logits [S, V]`, the smallest value `c [S, 1]` of
    the logits' own type, not under `floor [S, 1]`, such that the weights
    `weigh(logits)` (`[S, V]` float32, or a scalar) summed over the
    entries strictly above `c` are under `budget [S, 1]`. With the
    softmax's weights and `top_p` of their sum that is top-p's cut; with
    ones and `k`, the row's k-th largest value.

    No sort: the sum above `c` only falls as `c` rises, so the cut is
    found by bisection over the ordered integer image of the logits' type
    (`_ordered_bits`), from `floor` to the row's maximum. Each pass is one
    masked sum over the row, and as many passes as the type has bits (16
    for the bfloat16 a head's product is, 32 for float32: read off the
    input, nothing to set) end on one representable value. That is a
    value of the row (or `floor`): between two neighbouring values of the
    row the sum above does not change, so the smallest passing cut sits
    on one. Values are compared as integers, so nothing depends on how
    the device treats denormals.

    A pass works the image and the weights out of the logits again: the
    logits are then all it reads, 2 bytes an entry from wherever they
    lie, where an image and float32 weights held across the loop are 6,
    and on the v5e the recomputation hides behind that read (at 192 rows
    of 151,936 a pass takes the 0.07 ms its 58 MB take).

    `wanted`, a traced flag, makes the passes none where it is false: the
    loop's bound is then data, the result the row's maximum and the
    caller's to drop."""
    dtype = logits.dtype

    def halve(_, bounds):
        lo, hi = bounds  # the cut is in [lo, hi]; `hi` passes
        mid = lo + (hi - lo) // 2
        above = jnp.sum(
            jnp.where(_ordered_bits(logits) > mid, weigh(logits), 0.0),
            axis=-1, keepdims=True)
        passes = above < budget
        return jnp.where(passes, lo, mid + 1), jnp.where(passes, mid, hi)

    top = jnp.max(logits, axis=-1, keepdims=True)
    bounds = _ordered_bits(floor), _ordered_bits(top)
    _, cut = jax.lax.fori_loop(0, _bisection_passes(dtype, wanted), halve,
                               bounds)
    return _from_ordered_bits(cut, dtype)


def _top_k_floor(logits, top_ks):
    """Top-k's cut for every row of `logits [S, V]`: `floor [S, 1]` of
    the logits' type, a row's `top_ks`-th largest value (the smallest
    with fewer than `k` entries strictly above it, so ties at the k-th
    stay and a `k` past the vocabulary keeps the row), `-inf` where a row
    asks for none (`top_ks` 0). No pass over the vocabulary is made
    unless some row asks."""
    none = jnp.full((logits.shape[0], 1), -jnp.inf, logits.dtype)
    kth = _smallest_passing(
        logits, lambda x: 1.0, top_ks[:, None].astype(jnp.float32), none,
        wanted=jnp.any(top_ks > 0))
    return jnp.where(top_ks[:, None] > 0, kth, none)


def _top_p_threshold(logits, top_ps, scale=lambda x: x, floor=None):
    """Top-p's cut for every row of `scale(logits) [S, V]` float32 (the
    logits over the temperature; `scale` must not decrease): `thr [S, 1]`,
    the smallest of the row's values `v` at or above `scale(floor)` (what
    top-k left; the whole row without one) such that the softmax mass of
    the entries strictly above `v` is under `top_ps [S]` of the mass at or
    above the floor. Keeping `scaled >= thr` keeps the token that crosses
    `top_p` and every tie at the cut, which is what masking the row under
    the floor, sorting it, a `cumsum` over the sorted softmax and
    `min(where(cum - probs < top_p, sorted, inf))` give.

    The cut is bisected among the values of the logits' own type
    (`_smallest_passing`) and carried into the row's space by `scale`, the
    expression that made the row, so it is a value of the row bit for bit:
    a row of bfloat16 logits holds at most 65,536 distinct values whatever
    its temperature, and 16 passes tell them apart. Agrees with the sort
    but where a partial sum lands within float32 rounding of `top_p`,
    which the sort's `cumsum` decides by its summation order too."""
    if floor is None:
        floor = jnp.full((logits.shape[0], 1), -jnp.inf, logits.dtype)
    scaled = scale(logits)
    top = jnp.max(scaled, axis=-1, keepdims=True)

    def weigh(x):
        return jnp.exp(scale(x) - top)

    budget = top_ps[:, None] * jnp.sum(
        jnp.where(scaled >= scale(floor), weigh(logits), 0.0), axis=-1,
        keepdims=True)
    return scale(_smallest_passing(logits, weigh, budget, floor))


def _pick_tokens(logits, temps, top_ks, top_ps, key):
    """Per-slot next-token selection on device: greedy where temp == 0,
    else temperature-scaled sampling with optional per-slot top-k
    (0 = off, capped at MAX_TOP_K) and top-p (1.0 = off) filtering —
    generate.py's sampling semantics, vectorized over slots so mixed
    greedy/sampled requests share one decode batch. Top-k masks first,
    top-p cuts the softmax of what is left; both cuts are found without
    sorting the vocabulary and equal the sort's (`_smallest_passing`), in
    as many passes over the logits as their type has bits, and top-k's
    only in a step where some slot asks for one."""
    greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)
    over = jnp.maximum(temps, 1e-6)[:, None]

    def scale(x):  # strictly increasing within a row
        return x.astype(jnp.float32) / over

    thr = _top_p_threshold(logits, top_ps, scale,
                           _top_k_floor(logits, top_ks))
    scaled = scale(logits)
    scaled = jnp.where(scaled < thr, -jnp.inf, scaled)
    sampled = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def decode_paged(params, tokens, k_pages, v_pages, lengths, active,
                 block_tables, temps, top_ks, top_ps, key,
                 cfg: TransformerConfig, max_len: int, mesh=None, moe=None,
                 rec=None, rec_count=None, ring=None):
    """One decode step for every slot at once, K/V read through the
    block table.

    tokens [S] int32 (last emitted per slot; 0 for inactive), lengths
    [S] (current valid cache rows per slot), active [S] bool. Returns
    (next_tokens [S], k_pages, v_pages, new_lengths), the pool updated
    in place. With `moe`, a model with experts' routing accumulator, the
    advanced accumulator comes back as a fifth result (`_count_routing`).
    With `rec`, a hybrid's recurrent pool (donate it), the pool and then
    the advanced `rec_count` (`init_ssm_counters`) come back last: a slot
    that is not active keeps its state and its convolution inputs. With
    `ring`, a model with window layers' second pool (donate it), the pool
    comes back last: `k_pages` and `v_pages` are then the full layers'
    alone, and a window layer writes the slot's row at `lengths mod ring`
    of the slot's ring and attends to the window's last rows there
    (`ops.window_decode_attention`).

    Each active slot writes its new K/V row into page
    `block_tables[slot, lengths[slot] // page_size]` at row
    `lengths[slot] % page_size`; inactive slots park the write in the
    NULL page and keep their length. An active slot then attends to its
    `lengths + 1` rows where they lie in the pool, the row just written
    among them (`ops.paged_decode_attention`): on the chip a kernel
    fetches the pages that hold them and no others, elsewhere the slot's
    whole table is gathered and masked by that count. A slot that is not
    active attends to nothing; its result is finite and unused.

    The next token is computed ON DEVICE so the engine can feed it
    straight into the next dispatched step without a host round trip.
    temps=None compiles the greedy-only program: no top-k/sort/softmax
    work on the all-greedy path."""
    s_ = tokens.shape[0]
    ps = k_pages.shape[2]
    mp = block_tables.shape[1]
    x = _embed_tokens(params, tokens[:, None], cfg)  # [S, 1, d]
    cos, sin = rope_tables(cfg, max_len)
    positions = lengths[:, None]
    pos_w = jnp.where(active, jnp.minimum(lengths, max_len - 1), 0)
    page_of = jnp.minimum(pos_w // ps, mp - 1)
    rows_w = pos_w % ps
    slot_idx = jnp.arange(s_)
    pages_w = jnp.where(active, block_tables[slot_idx, page_of], NULL_PAGE)
    rows_att = jnp.where(active, pos_w + 1, 0)

    def attend(i, kc, vc, q, k, v):
        # kc is the whole pool [layers, pages, ps, kvh * hd]: scatter one
        # row per slot into layer i, then read layer i through the table,
        # both through the layer index (slicing kc[i] out first would
        # copy the layer). Inactive slots all target (NULL_PAGE, 0);
        # whichever lands is never read.
        kc = kc.at[i, pages_w, rows_w].set(_rows(k[:, 0], kc))
        vc = vc.at[i, pages_w, rows_w].set(_rows(v[:, 0], vc))
        attn = paged_decode_attention(
            q[:, 0], kc, vc, i, block_tables, rows_att, cfg.attention_scale,
            mesh=mesh)
        return attn[:, None], (kc, vc)

    attend_ring = None
    if ring is not None:
        ring_bt = ring_tables(ring["k"], s_)
        ring_pages_w = jnp.where(
            active, ring_bt[slot_idx, pos_w // ps % ring_bt.shape[1]],
            NULL_PAGE)
        newest = jnp.where(active, pos_w, -1)

        def attend_ring(i, kc, vc, q, k, v):
            kc = kc.at[i, ring_pages_w, rows_w].set(_rows(k[:, 0], kc))
            vc = vc.at[i, ring_pages_w, rows_w].set(_rows(v[:, 0], vc))
            attn = window_decode_attention(
                q[:, 0], kc, vc, i, newest, cfg.sliding_window_size,
                cfg.attention_scale)
            return attn[:, None], (kc, vc)

    if cfg.kv_lora_rank:
        # One row a slot into the one pool, then the absorbed form against
        # the rows the slot holds, the one just written the last of them.
        x, k_new, counts = _walk_latent(
            params, x, k_pages, _latent_through_pool(
                pages_w, rows_w, block_tables, None, rows_att, cfg,
                absorbed=True, mesh=mesh), cfg, cos, sin, positions, mesh)
        v_new = None
    elif rec is None:
        x, k_new, v_new, ring, counts = _scan_layers(
            params, x, k_pages, v_pages, attend, cfg, cos, sin, positions,
            mesh, ring, attend_ring,
        )
    else:
        # Every slot's row of a layer at once, advanced where it lies in
        # the pool: an idle slot's row passes through unchanged.
        x, k_new, v_new, rec, counts = _walk_hybrid(
            params, x, k_pages, v_pages, rec, attend, None,
            active.astype(jnp.int32), cfg, cos, sin, positions, mesh)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, mesh=mesh)
    logits = project_logits(x[:, -1], params, cfg)
    new_lengths = jnp.where(active, lengths + 1, lengths)
    if temps is None:
        next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        next_tokens = _pick_tokens(logits, temps, top_ks, top_ps, key)
    out = _count_routing((next_tokens, k_new, v_new, new_lengths), moe,
                         counts, cfg)
    if ring is not None:
        return (*out, ring)
    return _with_recurrent(out, rec, rec_count,
                           decode_rows_live=active.sum(dtype=jnp.int32),
                           decode_rows_computed=s_)


def prefill_chunk_paged(params, tokens, n_valid, slot, offset, k_pages,
                        v_pages, lengths, block_tables,
                        cfg: TransformerConfig, max_len: int, mesh=None,
                        moe=None, rec=None, rec_count=None, ring=None):
    """CHUNKED prefill, one pass of it: `P` rows of one fixed-size chunk
    each, row `r` being `n_valid[r]` tokens of a prompt into slot
    `slot[r]` at row `offset[r]`. The rows share one read of the weights,
    and a long prompt's prefill interleaves with other slots' decode
    steps instead of stalling them.

    tokens [P, C] int32 (a row's first n_valid real); n_valid, slot and
    offset [P] int32, or scalars for a pass of one row. Every row's keys
    and values scatter into the pages its slot's block-table row names
    (padding and anything past `max_len` drop into the NULL page) before
    any row reads; then each row's queries attend causally, by their own
    positions, against the rows of their own slot's table under the
    chunk's end, read a block of pages at a time (`_paged_attention`: the
    walk is as long as the longest real row's end needs), earlier chunks
    included. So two consecutive chunks of ONE prompt may be two rows of a
    pass (the earlier chunk the earlier row): the later one finds the
    earlier one's keys in the pages. Sets lengths[slot] = offset + n_valid
    for every row, a later row over an earlier one, and returns the
    logits of each row's last REAL position [P, vocab] (meaningful on a
    prompt's final chunk), the pool and the lengths (and the advanced
    `moe`).

    A row with `n_valid` 0 is INERT: it writes no page, no length and no
    recurrent state, whatever its slot and offset; a pass that owes three
    rows runs the four-row program with one such row.

    Prefix-cache resumption needs nothing special here: the engine
    starts `offset` at the shared-prefix boundary and the gathered
    pages already hold the donor's K/V rows below it.

    With `rec`, a hybrid's recurrent pool (donate it): a row starts from
    its slot's row of every Mamba layer, or from zeros where its `offset`
    is 0 (a slot's first chunk, whatever the last tenant left), and
    leaves there the state and the convolution inputs after its last REAL
    token; padding advances nothing. A chunk starts from the state the
    chunk before it left, so the real rows of a pass are of different
    slots. A prompt cannot resume below a shared prefix without the state
    at that boundary, which no one keeps. The pool and the advanced
    `rec_count` come back last.

    With `ring`, a model with window layers' second pool (donate it; it
    comes back last): a window layer writes a row's keys and values at
    their positions `mod ring` of the slot's ring, then the row's queries
    walk the slot's ring (as far as the rows it holds yet), each ring row
    at the position it holds once the chunk is written, under the window:
    the one form, whatever the prompt's length. The ring holds the window
    and a chunk beyond it, so the rows of a pass are of different slots, as
    with `rec`."""
    p_, c = tokens.shape
    n_valid, slot, offset = (
        jnp.reshape(a, (-1,)).astype(jnp.int32)
        for a in (n_valid, slot, offset))
    ps = k_pages.shape[2]
    mp = block_tables.shape[1]
    width = mp * ps
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    x = _embed_tokens(params, tokens, cfg)
    cos, sin = rope_tables(cfg, max_len)
    positions = offset[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    end = (offset + n_valid)[:, None]                           # [P, 1]
    if cfg.block_length:
        # A block-diffusion model's prompt: a position sees its own block
        # whole. The engine hands it whole blocks (`n_valid` and `offset`
        # multiples of the block), so no block is cut by `end`.
        def seen(k_pos):
            return block_causal(positions, k_pos, cfg.block_length)
    else:
        def seen(k_pos):
            return k_pos <= positions[:, :, None]
    bt_rows = block_tables[slot]                                # [P, mp]
    in_range = (positions < end) & (positions < max_len)
    page_of = jnp.minimum(positions // ps, mp - 1)
    pages_w = jnp.where(in_range, jnp.take_along_axis(bt_rows, page_of, 1),
                        NULL_PAGE).reshape(-1)
    rows_w = (positions % ps).reshape(-1)
    real = n_valid > 0
    # Rows of its slot's table a pass's row may see: those under its chunk's
    # end, an inert row none.
    live = jnp.where(real, end[:, 0], 0)

    def attend(i, kc, vc, q, k, v):
        kc = kc.at[i, pages_w, rows_w].set(_rows(k.reshape(p_ * c, kvh, hd), kc))
        vc = vc.at[i, pages_w, rows_w].set(_rows(v.reshape(p_ * c, kvh, hd), vc))
        return _paged_attention(q, kc, vc, i, bt_rows, live, seen,
                                cfg.attention_scale), (kc, vc)

    attend_ring = None
    if ring is not None:
        ring_bt = ring_tables(ring["k"], lengths.shape[0])[slot]  # [P, pages]
        n_ring = ring_bt.shape[1] * ps
        if n_ring < min(cfg.sliding_window_size + c, width):
            raise ValueError(
                f"a ring of {n_ring} rows does not hold the window "
                f"({cfg.sliding_window_size}) and a chunk of {c}")
        ring_pages_w = jnp.where(
            in_range, jnp.take_along_axis(
                ring_bt, positions // ps % ring_bt.shape[1], 1),
            NULL_PAGE).reshape(-1)
        def ring_seen(r):
            # The position ring row `r` holds once the chunk is written:
            # the last one under `end` that is `r mod ring` (none where `r`
            # is not under `end`, which `live` says).
            held = end - 1 - (end - 1 - r[None]) % n_ring         # [P, K]
            behind = positions[:, :, None] - held[:, None, :]
            return (behind >= 0) & (behind < cfg.sliding_window_size)

        def attend_ring(i, kc, vc, q, k, v):
            kc = kc.at[i, ring_pages_w, rows_w].set(
                _rows(k.reshape(p_ * c, kvh, hd), kc))
            vc = vc.at[i, ring_pages_w, rows_w].set(
                _rows(v.reshape(p_ * c, kvh, hd), vc))
            return _paged_attention(q, kc, vc, i, ring_bt, live, ring_seen,
                                    cfg.attention_scale), (kc, vc)

    if cfg.kv_lora_rank:
        # The expanded form: a chunk's scores and weighted sum are as wide
        # as a head, not as a cached row, and that outweighs expanding each
        # block (on the chip a pass took 38.9 ms against 49.0 absorbed).
        x, k_new, counts = _walk_latent(
            params, x, k_pages, _latent_through_pool(
                pages_w, rows_w, bt_rows, positions, end[:, 0], cfg,
                absorbed=False),
            cfg, cos, sin, positions, mesh)
        v_new = None
    elif rec is None:
        x, k_new, v_new, ring, counts = _scan_layers(
            params, x, k_pages, v_pages, attend, cfg, cos, sin, positions,
            mesh, ring, attend_ring,
        )
    else:
        carried = offset > 0

        # A row's slot at a time: a slice of the pool at `[j, slot]` read,
        # and written in place where it lies, as a pass of one row does.
        def read_rec(rec, j):
            def rows(pool):
                got = jnp.stack([pool[j, slot[r]] for r in range(p_)])
                return jnp.where(
                    carried.reshape((-1,) + (1,) * (got.ndim - 1)), got, 0)

            return {name: rows(pool) for name, pool in rec.items()}

        def write_rec(rec, j, rows):
            out = dict(rec)
            for name, new in rows.items():
                for r in range(p_):  # an inert row puts back what it found
                    row = jnp.where(real[r], new[r], out[name][j, slot[r]])
                    out[name] = out[name].at[j, slot[r]].set(row)
            return out

        x, k_new, v_new, rec, counts = _walk_hybrid(
            params, x, k_pages, v_pages, rec, attend, (read_rec, write_rec),
            n_valid, cfg, cos, sin, positions, mesh)
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps, mesh=mesh)
    last = jnp.take_along_axis(
        x, jnp.maximum(n_valid - 1, 0)[:, None, None], axis=1)
    logits = project_logits(last[:, 0], params, cfg)
    new_lengths = lengths
    for r in range(p_):  # in the rows' order: a prompt's later chunk last
        new_lengths = jnp.where(
            real[r], new_lengths.at[slot[r]].set(offset[r] + n_valid[r]),
            new_lengths)
    out = _count_routing((logits, k_new, v_new, new_lengths), moe, counts,
                         cfg)
    if ring is not None:
        return (*out, ring)
    return _with_recurrent(out, rec, rec_count,
                           prefill_tokens_valid=n_valid.sum(),
                           prefill_tokens_computed=p_ * c)


def init_block_state(cfg: TransformerConfig, slots: int) -> Dict:
    """What a block-diffusion engine keeps a slot on the device beside its
    pages: the current block's `tokens [slots, B]` and which of its
    positions are still `masked [slots, B]` (a bit of its own: a prompt may
    hold the mask token's id). A slot's phase is read off the bits: a block
    with none masked has its commit pass next."""
    b = cfg.block_length
    return {
        "tokens": jnp.full((slots, b), cfg.mask_token_id, jnp.int32),
        "masked": jnp.ones((slots, b), bool),
    }


def start_blocks(state, lengths, start, tokens, masked, committed):
    """The slots `start [S] bool` begin their first block: `tokens [S, B]`
    (a prompt's remainder, clean) with `masked [S, B]` elsewhere, behind
    `committed [S]` rows of cache. Other slots keep what they have."""
    row = start[:, None]
    return {
        "tokens": jnp.where(row, tokens, state["tokens"]),
        "masked": jnp.where(row, masked, state["masked"]),
    }, jnp.where(start, committed, lengths)


def _fold_block(q, kv_heads: int):
    """A block's queries `[S, B, H, D]` as `paged_decode_attention` takes a
    slot's heads, `[S, KVH * (B * H/KVH), D]`: all B positions of a block
    see the same rows, so they are one group of queries B times as large on
    each key-value head."""
    s_, b, h, d = q.shape
    return q.reshape(s_, b, kv_heads, h // kv_heads, d).transpose(
        0, 2, 1, 3, 4).reshape(s_, b * h, d)


def _unfold_block(out, b: int, kv_heads: int):
    """`_fold_block`'s inverse on the attention's result: `[S, B, H, D]`."""
    s_, bh, d = out.shape
    return out.reshape(s_, kv_heads, b, bh // (b * kv_heads), d).transpose(
        0, 2, 1, 3, 4).reshape(s_, b, bh // b, d)


def _block_hidden(params, state, k_pages, v_pages, lengths, active,
                  block_tables, cfg, max_len, mesh):
    """One pass of every slot's current block through the layers: the
    block's B rows (its tokens where filled, the mask token where not) are
    written into the slot's pages at positions `lengths ..` and attend to
    the `lengths + B` rows the slot then holds, the block itself whole.
    Returns the final-norm hidden states `[S, B, d]`, the pools and the
    routing counts."""
    s_, b = state["tokens"].shape
    ps, mp = k_pages.shape[2], block_tables.shape[1]
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    with jax.named_scope("diffusion.embed"):
        fed = jnp.where(state["masked"], cfg.mask_token_id, state["tokens"])
        x = _embed_tokens(params, fed, cfg)                     # [S, B, d]
    cos, sin = rope_tables(cfg, max_len)
    positions = lengths[:, None] + jnp.arange(b, dtype=jnp.int32)[None]
    page_of = positions // ps
    # An idle slot's rows, and rows past what a slot may hold, park in the
    # NULL page (a table's entries past a slot's pages name it already).
    kept = active[:, None] & (positions < max_len) & (page_of < mp)
    pages_w = jnp.where(
        kept, jnp.take_along_axis(block_tables, jnp.minimum(page_of, mp - 1),
                                  1), NULL_PAGE).reshape(-1)
    rows_w = (positions % ps).reshape(-1)
    rows_att = jnp.where(active, jnp.minimum(lengths + b, max_len), 0)

    def attend(i, kc, vc, q, k, v):
        with jax.named_scope("diffusion.attend"):
            kc = kc.at[i, pages_w, rows_w].set(
                _rows(k.reshape(s_ * b, kvh, hd), kc))
            vc = vc.at[i, pages_w, rows_w].set(
                _rows(v.reshape(s_ * b, kvh, hd), vc))
            attn = paged_decode_attention(
                _fold_block(q, kvh), kc, vc, i, block_tables, rows_att,
                cfg.attention_scale, mesh=mesh)
            return _unfold_block(attn, b, kvh), (kc, vc)

    x, k_new, v_new, _, counts = _scan_layers(
        params, x, k_pages, v_pages, attend, cfg, cos, sin, positions, mesh)
    return (rmsnorm(x, params["final_norm"], cfg.norm_eps, mesh=mesh),
            k_new, v_new, counts)


def block_logits(params, state, k_pages, v_pages, lengths, active,
                 block_tables, cfg: TransformerConfig, max_len: int,
                 mesh=None):
    """The logits `[S, B, vocab]` float32 of one pass of every slot's
    block, and nothing advanced but the pools' rows the pass wrote: what
    `block_pass_paged` draws a pass's tokens from (`engine.prefill_logits`
    and the tests read it)."""
    x, k_new, v_new, _ = _block_hidden(
        params, state, k_pages, v_pages, lengths, active, block_tables, cfg,
        max_len, mesh)
    return project_logits(x, params, cfg).astype(jnp.float32), k_new, v_new


def block_pass_paged(params, state, k_pages, v_pages, lengths, active,
                     block_tables, temps, top_ks, top_ps, key,
                     cfg: TransformerConfig, max_len: int, mesh=None,
                     moe=None):
    """One PASS of a block-diffusion model for every slot at once: what
    `decode_paged` is to a next-token model. A slot's phase is data:

      * a block with masked positions has a DENOISING pass: its B rows
        run against the slot's committed rows and themselves, and the
        first `n = B / denoise_steps` masked positions from the left (the
        released code's "sequential" strategy) are filled with tokens
        drawn from the logits AT those positions (greedy, or under the
        slot's temperature, top-k and top-p; `temps` None compiles the
        greedy-only program);
      * a block with none has its COMMIT pass: the B clean tokens run,
        the keys and values they write are the block's, `lengths` grows
        by B and the next block starts all masked.

    Every pass writes the block's B rows into the pages at the block's
    positions, a later pass over an earlier one's, so the two kinds are
    one program. The head and the sampler run on the `n` rows a slot a
    pass may fill and no others (which positions is known before the
    logits); a committing or idle slot's rows ride along and their draws
    are dropped.

    Returns `(block [S, B]` the block's tokens after this pass's fills, a
    committing slot's the tokens it committed`, state, k_pages, v_pages,
    lengths)` and the advanced `moe`. An idle slot keeps its state and its
    length."""
    s_, b = state["tokens"].shape
    n = b // cfg.denoise_steps
    x, k_new, v_new, counts = _block_hidden(
        params, state, k_pages, v_pages, lengths, active, block_tables, cfg,
        max_len, mesh)
    tokens, masked = state["tokens"], state["masked"]
    with jax.named_scope("diffusion.unmask"):
        fill = masked & (jnp.cumsum(masked, axis=1) <= n) & active[:, None]
        # The n positions to fill first; a slot with fewer brings others
        # along, whose draws `fill` drops.
        at = jnp.argsort(~fill, axis=1, stable=True)[:, :n]
        logits = project_logits(
            jnp.take_along_axis(x, at[:, :, None], axis=1).reshape(s_ * n, -1),
            params, cfg)
        if temps is None:
            picked = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            picked = _pick_tokens(logits, *(jnp.repeat(a, n) for a in
                                            (temps, top_ks, top_ps)), key)
        drawn = jnp.zeros_like(tokens).at[
            jnp.arange(s_)[:, None], at].set(picked.reshape(s_, n))
        block = jnp.where(fill, drawn, tokens)
    commit = active & ~masked.any(axis=1)
    done = commit[:, None]
    new_state = {
        "tokens": jnp.where(done, cfg.mask_token_id, block),
        "masked": jnp.where(done, True, masked & ~fill),
    }
    new_lengths = jnp.where(commit, lengths + b, lengths)
    return _count_routing(
        (block, new_state, k_new, v_new, new_lengths), moe, counts, cfg)


def cow_copy_page(k_pages, v_pages, src, dst):
    """Copy one page's rows across all layers (the copy-on-write fork).
    Jitted by the engine with donated buffers so it runs in place."""
    k_pages = k_pages.at[:, dst].set(k_pages[:, src])
    if v_pages is not None:  # a latent pool is one
        v_pages = v_pages.at[:, dst].set(v_pages[:, src])
    return k_pages, v_pages
