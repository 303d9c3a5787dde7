"""Paged KV engine: block-table allocator, prefix cache, affinity
routing pieces, and the ServeSignals-driven autoscaler.

Covers: paged decode token for token against `generate()` on a concurrent
mixed-length batch, the one-way import (this module never loads the
scheduler), zero page leak over 1k admit/evict cycles,
prefix-share correctness when the donor's cache entries are evicted
mid-share, typed prompt rejection (+ proxy 413 mapping), chaos KV
hooks, autoscaler hysteresis with a fake clock, and the schema-v2
signals surface (old readers keep working).
"""

import time
from dataclasses import replace

import numpy as np
import pytest

from ray_tpu.serve import paged_kv
from ray_tpu.serve.paged_kv import (
    NULL_PAGE,
    OutOfPages,
    PagePool,
    PrefixCache,
    page_hashes,
    prefix_route_key,
)


def _tiny_model():
    import jax

    from ray_tpu.models import configs, init_params

    cfg = replace(configs.tiny, dtype=np.float32)
    return init_params(jax.random.PRNGKey(0), cfg), cfg


# -- page pool ------------------------------------------------------------
def test_page_pool_alloc_release_refcount():
    pool = PagePool(9, 16)
    assert pool.usable == 8 and pool.free_pages == 8 and pool.in_use == 0
    pages = pool.alloc(3)
    assert len(pages) == 3 and NULL_PAGE not in pages
    assert pool.in_use == 3 and pool.free_pages == 5
    # A second reference keeps the page allocated past one release.
    pool.ref(pages[:1])
    assert pool.refcount(pages[0]) == 2
    pool.release(pages[:1])
    assert pool.in_use == 3
    pool.release(pages)
    assert pool.in_use == 0 and pool.free_pages == 8
    # Releasing an unallocated page is a bug, not a no-op.
    with pytest.raises(ValueError):
        pool.release(pages[:1])


def test_page_pool_alloc_is_all_or_nothing():
    pool = PagePool(5, 4)  # 4 usable
    pool.alloc(3)
    with pytest.raises(OutOfPages) as ei:
        pool.alloc(2)
    assert ei.value.needed == 2 and ei.value.free == 1
    # The failed alloc must not leak its partial grab.
    assert pool.free_pages == 1


# -- prefix trie ----------------------------------------------------------
def test_page_hashes_chain_and_route_key():
    a = page_hashes(list(range(8)), 4)
    b = page_hashes([0, 1, 2, 3, 9, 9, 9, 9], 4)
    assert len(a) == 2
    assert a[0] == b[0] and a[1] != b[1]  # chain hash: shared first page
    # Only FULL pages hash; the partial tail never enters the trie.
    assert len(page_hashes(list(range(5)), 4)) == 1
    assert prefix_route_key(list(range(5)), 4) == a[0]
    assert prefix_route_key([1, 2], 4) is None


def test_prefix_cache_match_insert_evict():
    pool = PagePool(17, 4)
    cache = PrefixCache(pool)
    keys = page_hashes(list(range(12)), 4)
    pages = pool.alloc(3)
    cache.insert(keys, pages)
    pool.release(pages)  # cache holds its own refs
    assert pool.in_use == 3 and cache.pages_held == 3
    got = cache.match(keys)
    assert got == pages  # one ref per matched page handed to the caller
    pool.release(got)
    part = cache.match(keys[:2] + ["not-a-real-key"])
    assert part == pages[:2]
    pool.release(part)
    assert cache.match(page_hashes(list(range(100, 112)), 4)) == []
    assert keys[0] in cache.roots()
    # LRU eviction and flush both hand pages back to the pool.
    assert cache.evict_pages(1) >= 1
    cache.flush()
    assert cache.pages_held == 0 and pool.in_use == 0


# -- engine: token parity with the plain reference ------------------------
def test_paged_engine_matches_generate_on_mixed_lengths():
    """The paged engine, decoding a concurrent mixed-length batch greedily,
    gives token for token what `generate()` gives each prompt alone over
    its simple one-length cache (the plain reference). The longest prompt
    crosses a page boundary while it decodes."""
    import jax.numpy as jnp

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    prompts = [[1, 2, 3], [5, 6, 7, 8, 9, 10, 11], [4],
               [9, 9, 2, 1, 3, 3, 7, 7, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10]]
    refs = [
        np.asarray(generate(params, jnp.asarray([p], dtype=jnp.int32), cfg,
                            max_new_tokens=8))[0].tolist()
        for p in prompts
    ]
    eng = ContinuousBatchingEngine(
        params, cfg, num_slots=4, max_len=64, page_size=16,
    )
    try:
        handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
        outs = [h.result(timeout=180) for h in handles]
    finally:
        eng.shutdown()
    assert outs == refs


# -- one layer: trained, generated and served by one definition -----------
@pytest.mark.parametrize("field, value", [
    ("residual_multiplier", 0.5), ("attention_multiplier", 0.5),
    ("attn_output_gate", True)])
def test_a_stack_of_like_layers_trains_and_generates_as_it_is_served(
        field, value):
    """A dense stack of like attention layers (no `layer_pattern`) with one
    of the fields a hybrid's attention layers may carry: `forward`'s logits
    at the prompt's last position, `generate`'s prefill logits and the
    engine's `prefill_logits` are one layer's arithmetic
    (`transformer.attention_layer`), so they agree, and the field is in all
    three (the logits are not those of the stack without it)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import configs, forward, init_params
    from ray_tpu.models.generate import init_kv_cache, prefill
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    plain = replace(configs.tiny_gqa, dtype=jnp.float32)
    cfg = replace(plain, **{field: value})
    params = init_params(jax.random.PRNGKey(0), cfg)
    if cfg.attn_output_gate:  # a hybrid's leaf, drawn here for a plain stack
        params["layers"]["wg"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(1),
            (cfg.n_layers, cfg.d_model, cfg.n_heads * cfg.head_dim))
    prompt = jnp.asarray(_prompt(21, seed=5))[None]

    def last_logits(params, cfg):
        return np.asarray(forward(params, prompt, cfg)[0][0, -1])

    trained = last_logits(params, cfg)
    generated = np.asarray(prefill(
        params, prompt, init_kv_cache(cfg, 1, 32), cfg)[0][0])
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=64,
                                   page_size=16, prefill_chunk=8)
    try:
        served = eng.prefill_logits(np.asarray(prompt[0]))
    finally:
        eng.shutdown()
    scale = np.abs(trained).max()
    assert np.abs(generated - trained).max() < 1e-4 * scale
    assert np.abs(served - trained).max() < 1e-4 * scale
    without = last_logits({**params, "layers": {
        n: w for n, w in params["layers"].items() if n != "wg"}}, plain)
    assert np.abs(without - trained).max() > 1e-2 * scale


# -- one arrow: the scheduler imports the programs, never the reverse -----
_TRACE_WITHOUT_THE_SCHEDULER = """
import sys
import jax, jax.numpy as jnp
from ray_tpu.models import configs, init_params
from ray_tpu.serve import paged_kv

cfg = configs.tiny_qwen
slots, ps, mp, max_len, chunk = 2, 4, 4, 16, 8
params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
cache = jax.eval_shape(
    lambda: paged_kv.init_paged_cache(cfg, slots, slots * mp + 1, ps, mp))
k, v, ln, bt = (cache[n] for n in ("k", "v", "lengths", "block_tables"))
i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
out = jax.eval_shape(
    lambda p, t, k, v, ln, a, bt, tp, tk, tpp, key: paged_kv.decode_paged(
        p, t, k, v, ln, a, bt, tp, tk, tpp, key, cfg, max_len),
    params, i32(slots), k, v, ln, jax.ShapeDtypeStruct((slots,), jnp.bool_),
    bt, f32(slots), i32(slots), f32(slots),
    jax.ShapeDtypeStruct((2,), jnp.uint32))
assert out[0].shape == (slots,) and out[1].shape == k.shape
out = jax.eval_shape(
    lambda p, t, n, s, o, k, v, ln, bt: paged_kv.prefill_chunk_paged(
        p, t, n, s, o, k, v, ln, bt, cfg, max_len),
    params, i32(1, chunk), i32(), i32(), i32(), k, v, ln, bt)
assert out[0].shape == (1, cfg.vocab_size) and out[1].shape == k.shape
loaded = sorted(m for m in sys.modules if m.startswith("ray_tpu.serve.llm"))
assert not loaded, loaded
print("TRACED-WITHOUT-LLM")
"""


def test_step_programs_trace_without_importing_the_scheduler():
    """In a fresh interpreter `ray_tpu.serve.paged_kv` traces both step
    programs (sampled decode, a prefill chunk) at `configs.tiny_qwen`
    and `ray_tpu.serve.llm` is never loaded: the device code has one home
    and the host scheduler imports it, not the other way round."""
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-c", _TRACE_WITHOUT_THE_SCHEDULER], env=env,
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert done.returncode == 0, done.stderr[-3000:]
    assert "TRACED-WITHOUT-LLM" in done.stdout


def test_the_deleted_kv_options_are_gone():
    """One KV cache: the engine, the replica and the deployment name no
    `kv_mode` and no `prefill_buckets` and swallow no `**kwargs`, so a
    caller that passes one gets Python's TypeError, not an accepted-and-
    ignored keyword; the config has no `serve_kv` to choose with."""
    import dataclasses
    import inspect

    from ray_tpu._private.config import Config
    from ray_tpu.serve.llm import (
        ContinuousBatchingEngine,
        LLMReplica,
        llm_deployment,
    )

    for fn in (ContinuousBatchingEngine.__init__, LLMReplica.__init__,
               llm_deployment):
        names = inspect.signature(fn).parameters
        assert "kv_mode" not in names and "prefill_buckets" not in names
        assert not any(p.kind is p.VAR_KEYWORD for p in names.values())
    assert "serve_kv" not in {f.name for f in dataclasses.fields(Config)}


def _layerwise(params, cfg, x, k_pool, v_pool, write_kv, positions, valid,
               max_len):
    """The plain reference for a step's layers: a loop that hands
    `attention_layer` one layer's pages `pool[i]` at a time and restacks the
    pool; `write_kv` scatters into and gathers from that layer, with no
    layer index anywhere. The loop is a `lax.scan` over the pools: the
    same loop unrolled in Python compiles to other fusions, whose float32
    results differ from a scan's in the last bit (1e-6 on the logits)."""
    import jax

    import jax.numpy as jnp

    from ray_tpu.models.transformer import attention_layer
    from ray_tpu.ops import rmsnorm, rope_frequencies
    from ray_tpu.ops.paged_attention import grouped_attention

    cos, sin = rope_frequencies(cfg.head_dim, max_len, cfg.rope_theta)

    def layer(x, inputs):
        lp, k_layer, v_layer = inputs

        def attend(q, k, v):
            kc, vc, k_att, v_att = write_kv(k_layer, v_layer, k, v)
            return grouped_attention(
                q, k_att.astype(jnp.float32), v_att.astype(jnp.float32),
                valid, cfg.attention_scale), (kc, vc)

        x, _, pools = attention_layer(x, lp, cfg, cos, sin, positions, attend)
        return x, pools

    x, (k_pool, v_pool) = jax.lax.scan(
        layer, x, (params["layers"], k_pool, v_pool))
    return rmsnorm(x, params["final_norm"], cfg.norm_eps), k_pool, v_pool


@pytest.mark.parametrize("extent", ["head", "projection", None])
def test_held_back_products_change_no_head_and_no_gradient(extent):
    """`project_qkv` holds its three products back from the QK-norm
    (`optimization_barrier`: fused into a product, a per-head sum of
    squares has the chip's compiler copy `wq` and `wk` out of the stack
    every layer). The hold is no arithmetic: the heads `attention_layer`
    hands to `attend`, and the gradients a train step takes through
    `project_qkv`, equal the same lines written without it bit for bit,
    at every extent of the norm, in bfloat16 as both run. That is the
    CPU's word: on the chip the norm now reads the product as rounded to
    bfloat16, where fused it read the f32 accumulator."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import configs, init_params
    from ray_tpu.models.transformer import (
        at_layer,
        attention_layer,
        project_qkv,
    )
    from ray_tpu.ops import rmsnorm

    cfg = replace(configs.tiny_qwen, dtype=jnp.bfloat16,
                  qk_norm=extent is not None, qk_norm_extent=extent or "head")
    lp = at_layer(init_params(jax.random.PRNGKey(0), cfg)["layers"], 1)
    if extent:  # a scale of ones would hide a norm at the wrong extent
        lp = {**lp, **{n: jax.random.normal(jax.random.PRNGKey(i),
                                            lp[n].shape, cfg.dtype)
                       for i, n in enumerate(("q_norm", "k_norm"))}}
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 5, cfg.d_model),
                          cfg.dtype)

    def through_the_layer(x, lp):
        def attend(q, k, v):
            return jnp.zeros_like(q), (q, k, v)

        # No position embedding: `attend` is handed the heads as projected.
        return attention_layer(x, lp, cfg, None, None, None, attend)[2]

    def plain(x, lp):
        h = rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
        q, k, v = h @ lp["wq"], h @ lp["wk"], h @ lp["wv"]

        def norm(q, k):
            return (rmsnorm(q, lp["q_norm"], cfg.norm_eps),
                    rmsnorm(k, lp["k_norm"], cfg.norm_eps))

        if extent == "projection":
            q, k = norm(q, k)
        q = q.reshape(*x.shape[:2], cfg.n_heads, cfg.head_dim)
        k = k.reshape(*x.shape[:2], cfg.n_kv_heads, cfg.head_dim)
        v = v.reshape(*x.shape[:2], cfg.n_kv_heads, cfg.head_dim)
        return (*(norm(q, k) if extent == "head" else (q, k)), v)

    def held(x, lp):
        return project_qkv(rmsnorm(x, lp["attn_norm"], cfg.norm_eps), lp, cfg)

    def same(ours, theirs):
        for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs),
                        strict=True):
            assert a.dtype == b.dtype == cfg.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))

    same(jax.jit(through_the_layer)(x, lp), jax.jit(plain)(x, lp))

    def grads(project):
        def loss(x, lp):
            return sum(jnp.sum(jnp.sin(t.astype(jnp.float32)))
                       for t in project(x, lp))
        return jax.jit(jax.grad(loss, argnums=(0, 1)))(x, lp)

    same(grads(held), grads(plain))
    assert "optimization_barrier" in str(jax.make_jaxpr(held)(x, lp))
    assert "optimization_barrier" not in str(jax.make_jaxpr(plain)(x, lp))


@pytest.mark.parametrize("program", ["decode", "prefill_final_chunk"])
def test_carried_pool_matches_a_layer_by_layer_reference(program):
    """`decode_paged` and `prefill_chunk_paged` carry the whole pool
    through the layer scan and index it by layer; the arithmetic is that
    of a loop over per-layer pools `[pages, page_size, kv_heads,
    head_dim]`, each gathered whole through the table and masked (a decode
    step's to the bit, a pass's to the order of a sum). Slots 0
    and 1 share their first page (a prefix-cache hit), slot 2 is inactive
    (it attends to nothing), and the prefill chunk is a final one: 5 real
    rows and 3 of padding, starting mid-page. Each side is one jitted
    program, as the engine runs it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import configs, init_params
    from ray_tpu.models.transformer import _embed_tokens, project_logits

    cfg = configs.tiny_qwen
    params = init_params(jax.random.PRNGKey(0), cfg)
    slots, ps, mp, max_len = 3, 4, 4, 16
    width, kvh, hd = mp * ps, cfg.n_kv_heads, cfg.head_dim
    shape = (cfg.n_layers, 1 + slots * mp, ps, kvh, hd)
    kk, kv = jax.random.split(jax.random.PRNGKey(1))
    k_pool = jax.random.normal(kk, shape, cfg.dtype)
    v_pool = jax.random.normal(kv, shape, cfg.dtype)
    tables = jnp.asarray([[1, 2, 3, 4], [1, 5, 6, 7], [0, 0, 0, 0]], jnp.int32)
    lengths = jnp.asarray([6, 9, 0], jnp.int32)
    k_pos = jnp.arange(width)[None, None, :]

    if program == "decode":
        tokens = jnp.asarray([7, 11, 0], jnp.int32)
        active = jnp.asarray([True, True, False])

        def step(k, v):
            return paged_kv.decode_paged(
                params, tokens, k, v, lengths, active, tables,
                None, None, None, None, cfg, max_len)

        def reference(k, v):
            pages_w = jnp.where(
                active, tables[jnp.arange(slots), lengths // ps], NULL_PAGE)
            rows_w = jnp.where(active, lengths % ps, 0)

            def write_kv(kc, vc, k_new, v_new):
                kc = kc.at[pages_w, rows_w].set(k_new[:, 0])
                vc = vc.at[pages_w, rows_w].set(v_new[:, 0])
                return (kc, vc, kc[tables].reshape(slots, width, kvh, hd),
                        vc[tables].reshape(slots, width, kvh, hd))

            positions = lengths[:, None]
            x, k, v = _layerwise(
                params, cfg, _embed_tokens(params, tokens[:, None], cfg),
                k, v, write_kv, positions,
                (k_pos <= positions[:, :, None]) & active[:, None, None],
                max_len)
            logits = project_logits(x[:, -1], params, cfg)
            return (jnp.argmax(logits, axis=-1).astype(jnp.int32), k, v,
                    jnp.where(active, lengths + 1, lengths))
    else:
        slot, offset, n_valid, c = 1, 9, 5, 8
        tokens = jnp.asarray([[3, 1, 4, 1, 5, 0, 0, 0]], jnp.int32)

        def step(k, v):
            return paged_kv.prefill_chunk_paged(
                params, tokens, jnp.int32(n_valid), jnp.int32(slot),
                jnp.int32(offset), k, v, lengths, tables, cfg, max_len)

        def reference(k, v):
            pos = offset + jnp.arange(c)
            real = (pos < offset + n_valid) & (pos < max_len)
            bt_row = tables[slot]
            pages_w = jnp.where(
                real, bt_row[jnp.minimum(pos // ps, mp - 1)], NULL_PAGE)
            rows_w = pos % ps

            def write_kv(kc, vc, k_new, v_new):
                kc = kc.at[pages_w, rows_w].set(k_new[0])
                vc = vc.at[pages_w, rows_w].set(v_new[0])
                return (kc, vc, kc[bt_row].reshape(1, width, kvh, hd),
                        vc[bt_row].reshape(1, width, kvh, hd))

            valid = (k_pos <= pos[None, :, None]) & (k_pos < offset + n_valid)
            x, k, v = _layerwise(
                params, cfg, _embed_tokens(params, tokens, cfg), k, v,
                write_kv, pos[None, :], valid, max_len)
            return (project_logits(x[:, n_valid - 1], params, cfg), k, v,
                    lengths.at[slot].set(offset + n_valid))

    flat = shape[:3] + (kvh * hd,)  # as the programs hold a row
    got = jax.jit(step)(k_pool.reshape(flat), v_pool.reshape(flat))
    want = jax.jit(reference)(k_pool, v_pool)
    for name, g, w in zip(("out", "k", "v", "lengths"), got, want):
        g, w = np.asarray(g), np.asarray(w).reshape(g.shape)
        if program == "decode" or name == "lengths":
            np.testing.assert_array_equal(g, w, err_msg=name)
        else:
            # A pass walks the table a block at a time with a running
            # softmax (`_paged_attention`): the reference's numbers in
            # another order of summation, so float32's last bits on the
            # logits and, rarely, a bfloat16 row's last bit in the pool.
            np.testing.assert_allclose(
                g.astype(np.float32), w.astype(np.float32), rtol=1e-2,
                atol=1e-5, err_msg=name)
            if name == "out":
                np.testing.assert_allclose(g, w, rtol=1e-4, atol=5e-6)
    # Both wrote: the comparison above is not of two untouched pools.
    assert not np.array_equal(np.asarray(got[1]),
                              np.asarray(k_pool).reshape(flat))


# Toy models whose page row is whole 128-lane tiles, so that the decode
# kernel takes their pool: two KV heads of 64 with two queries each (a
# head is half a tile), four of 32 with one each, and the hybrid's two.
KERNEL_MODELS = {"dense": ("tiny_qwen", 64), "expert": ("tiny_olmoe", 32),
                 "hybrid": ("tiny_granite_h", 64)}


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("model", KERNEL_MODELS)
def test_decode_through_the_kernel_gives_the_plain_forms_tokens(
        model, sampled, monkeypatch):
    """`decode_paged` with attention through the Pallas kernel (interpret
    mode) against the same program through the plain form, which is what
    every other test here runs: after a prompt's prefill, eight steps of
    three slots (one inactive, two sharing their first page) give the same
    tokens, greedy and sampled, and leave the same pages."""
    import dataclasses
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import configs, init_params
    from ray_tpu.ops import paged_attention

    name, head_dim = KERNEL_MODELS[model]
    cfg = dataclasses.replace(configs.get_config(name), dtype=jnp.float32,
                              custom_head_dim=head_dim)
    params = init_params(jax.random.PRNGKey(0), cfg)
    slots, ps, mp, max_len = 3, 8, 4, 32
    cache = paged_kv.init_paged_cache(cfg, slots, 1 + slots * mp, ps, mp)
    assert paged_attention.kernel_takes(cache["k"], cfg.head_dim)
    tables = jnp.asarray([[1, 2, 3, 4], [1, 5, 6, 7], [0, 0, 0, 0]], jnp.int32)
    active = jnp.asarray([True, True, False])
    tail = {}
    if cfg.num_experts:
        tail["moe"] = paged_kv.init_routing_counters(cfg)
    if cfg.layer_pattern:
        tail["rec"], tail["rec_count"] = (cache["rec"],
                                          paged_kv.init_ssm_counters())
    # Slot 0's prompt fills the shared page and two rows of its own.
    prompt = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 0, 0]], jnp.int32)
    _, k, v, lengths, *out = paged_kv.prefill_chunk_paged(
        params, prompt, jnp.int32(10), jnp.int32(0), jnp.int32(0),
        cache["k"], cache["v"], cache["lengths"], tables, cfg, max_len,
        **tail)
    tail = dict(zip(tail, out))
    lengths = lengths.at[1].set(ps)  # slot 1 holds the shared page alone
    sampling = ((jnp.asarray([0.8, 0.0, 0.7]), jnp.asarray([0, 0, 5]),
                 jnp.asarray([0.9, 1.0, 1.0])) if sampled else (None,) * 3)

    def run(attention):
        monkeypatch.setattr(paged_kv, "paged_decode_attention", attention)
        step = jax.jit(lambda t, k, v, ln, key, tail: paged_kv.decode_paged(
            params, t, k, v, ln, active, tables, *sampling,
            key if sampled else None, cfg, max_len, **tail))
        tokens, state = [], (jnp.asarray([7, 11, 0], jnp.int32), k, v, lengths)
        carried = tail
        for i in range(8):
            t, kk, vv, ln, *out = step(*state, jax.random.PRNGKey(i), carried)
            carried = dict(zip(carried, out))
            state = (t, kk, vv, ln)
            tokens.append(np.asarray(t)[:2])
        return np.stack(tokens), state[1], state[2]

    want, k_want, v_want = run(functools.partial(
        paged_attention.paged_decode_attention, use_pallas=False))
    got, k_got, v_got = run(functools.partial(
        paged_attention.paged_decode_attention, interpret=True))
    np.testing.assert_array_equal(got, want)
    # But for the NULL page, where the inactive slot's row is parked: its
    # attention is over nothing, which the two forms need not agree on.
    for a, b in ((k_got, k_want), (v_got, v_want)):
        np.testing.assert_allclose(np.asarray(a)[:, 1:], np.asarray(b)[:, 1:],
                                   rtol=1e-4, atol=1e-4)


# -- a prefill pass is one program over rows ------------------------------
PASS_MODELS = {"dense": "tiny_qwen", "expert": "tiny_olmoe",
               "hybrid": "tiny_granite_h", "latent": "tiny_dots"}
PASS_SLOTS, PASS_PAGE, PASS_PAGES, PASS_LEN, PASS_CHUNK = 3, 4, 8, 32, 8


class _PassBench:
    """A model of one kind, a cache of three slots with a table of their
    own pages, and `paged_kv.prefill_chunk_paged` jitted as the engine
    jits it; `run(cache, rows)` dispatches `rows`, each a `(slot, offset,
    tokens)`, as ONE pass (padded to `width` rows with inert ones), and
    `one_at_a_time` as a call a row with scalars."""

    def __init__(self, model):
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import configs, init_params

        self.cfg = cfg = replace(configs.get_config(PASS_MODELS[model]),
                                 dtype=jnp.float32)
        self.params = init_params(jax.random.PRNGKey(0), cfg)
        self.program = jax.jit(
            lambda t, n, s, o, k, v, ln, bt, **tail:
            paged_kv.prefill_chunk_paged(
                self.params, t, n, s, o, k, v, ln, bt, cfg, PASS_LEN,
                **tail))

    def cache(self, poison=0.0):
        import jax
        import jax.numpy as jnp

        cache = paged_kv.init_paged_cache(
            self.cfg, PASS_SLOTS, PASS_SLOTS * PASS_PAGES + 1, PASS_PAGE,
            PASS_PAGES)
        cache["block_tables"] = jnp.asarray(
            1 + np.arange(PASS_SLOTS * PASS_PAGES, dtype=np.int32).reshape(
                PASS_SLOTS, PASS_PAGES))
        if self.cfg.num_experts:
            cache["moe"] = paged_kv.init_routing_counters(self.cfg)
        if self.cfg.layer_pattern:
            cache["rec"] = jax.tree.map(
                lambda a: a + jnp.asarray(poison, a.dtype), cache["rec"])
            cache["rec_count"] = paged_kv.init_ssm_counters()
        return cache

    def _call(self, cache, tokens, n_valid, slot, offset):
        tail = {n: cache[n] for n in ("moe", "rec", "rec_count") if n in cache}
        logits, k, v, lengths, *out = self.program(
            tokens, n_valid, slot, offset, cache["k"], cache["v"],
            cache["lengths"], cache["block_tables"], **tail)
        return logits, dict(cache, k=k, v=v, lengths=lengths,
                            **dict(zip(tail, out)))

    def run(self, cache, rows, width):
        tokens = np.zeros((width, PASS_CHUNK), np.int32)
        n_valid, slots, offsets = np.zeros((3, width), np.int32)
        slots[len(rows):] = 1      # an inert row may name any slot
        offsets[len(rows):] = 5
        for r, (slot, offset, chunk) in enumerate(rows):
            tokens[r, :len(chunk)] = chunk
            n_valid[r], slots[r], offsets[r] = len(chunk), slot, offset
        logits, cache = self._call(cache, tokens, n_valid, slots, offsets)
        return np.asarray(logits)[:len(rows)], cache

    def one_at_a_time(self, cache, rows):
        logits = []
        for slot, offset, chunk in rows:
            tokens = np.zeros((1, PASS_CHUNK), np.int32)
            tokens[0, :len(chunk)] = chunk
            out, cache = self._call(cache, tokens, np.int32(len(chunk)),
                                    np.int32(slot), np.int32(offset))
            assert out.shape == (1, self.cfg.vocab_size)
            logits.append(np.asarray(out)[0])
        return np.stack(logits), cache


@pytest.fixture(scope="module")
def pass_benches():
    return {}


@pytest.fixture(params=list(PASS_MODELS))
def pass_bench(request, pass_benches):
    if request.param not in pass_benches:
        pass_benches[request.param] = _PassBench(request.param)
    return pass_benches[request.param]


# The models whose cache is pages of keys and values alone, and those
# whose cache is pages alone (a latent pool is one pool of rows).
paged_alone = pytest.mark.parametrize("pass_bench", ["dense", "expert"],
                                      indirect=True)
pages_alone = pytest.mark.parametrize(
    "pass_bench", ["dense", "expert", "latent"], indirect=True)


def _prompt(n, seed):
    return ((np.arange(n) * (7 + 2 * seed) + seed) % 250 + 1).tolist()


def _same_cache(got, want, exact=False):
    """The pool but for the NULL page (padding and inert rows park their
    writes there), the lengths and, for a hybrid, the recurrent pool."""
    close = (np.testing.assert_array_equal if exact else
             lambda a, b, err_msg: np.testing.assert_allclose(
                 a, b, rtol=2e-4, atol=2e-5, err_msg=err_msg))
    for name in ("k", "v"):
        if want[name] is None:  # a latent pool is one
            assert got[name] is None
            continue
        close(np.asarray(got[name])[:, 1:], np.asarray(want[name])[:, 1:],
              err_msg=name)
    np.testing.assert_array_equal(np.asarray(got["lengths"]),
                                  np.asarray(want["lengths"]))
    for name, leaf in got.get("rec", {}).items():
        close(np.asarray(leaf), np.asarray(want["rec"][name]), err_msg=name)


def test_a_pass_of_rows_is_the_same_chunks_one_call_at_a_time(pass_bench):
    """Three slots' chunks as three rows of one four-row pass (a first
    chunk, a chunk behind an earlier one, a short one; the fourth row
    inert) leave the pages, the lengths, the recurrent state and each
    row's logits as three calls of the one-row program do, whatever the
    order of the slots among the rows."""
    bench = pass_bench
    earlier = [(2, 0, _prompt(PASS_CHUNK, 1))]
    _, start = bench.one_at_a_time(bench.cache(poison=2.0), earlier)
    rows = [(2, PASS_CHUNK, _prompt(5, 2)), (0, 0, _prompt(PASS_CHUNK, 3)),
            (1, 0, _prompt(3, 4))]
    want_logits, want = bench.one_at_a_time(start, rows)
    got_logits, got = bench.run(start, rows, 4)
    np.testing.assert_allclose(got_logits, want_logits, rtol=2e-4, atol=2e-4)
    _same_cache(got, want)
    assert np.asarray(got["lengths"]).tolist() == [8, 3, 13]
    if "moe" in got:
        # Programs are counted, and every row they computed is routed.
        assert int(got["moe"]["calls"]) == 2 and int(want["moe"]["calls"]) == 4
        per_row = (PASS_CHUNK * bench.cfg.experts_per_token
                   * bench.cfg.expert_layers)
        assert int(got["moe"]["assignments"].sum()) == (1 + 4) * per_row
    if "rec_count" in got:
        counted = {n: int(c) for n, c in got["rec_count"].items()}
        assert counted["calls"] == 2
        assert counted["prefill_tokens_valid"] == PASS_CHUNK + 5 + 8 + 3
        assert counted["prefill_tokens_computed"] == (1 + 4) * PASS_CHUNK


@pages_alone
def test_two_rows_of_a_pass_are_two_chunks_of_one_prompt(pass_bench):
    """A model whose cache is pages alone: two consecutive chunks of one
    prompt as two rows of one pass against two calls. Every layer
    scatters both rows' keys before either row reads, so the later row
    finds the earlier one's. (A model with recurrent layers cannot: its
    second chunk starts from the state its first one leaves.)"""
    bench = pass_bench
    prompt = _prompt(PASS_CHUNK + 6, 5)
    rows = [(1, 0, prompt[:PASS_CHUNK]), (1, PASS_CHUNK, prompt[PASS_CHUNK:])]
    want_logits, want = bench.one_at_a_time(bench.cache(), rows)
    got_logits, got = bench.run(bench.cache(), rows, 2)
    np.testing.assert_allclose(got_logits, want_logits, rtol=2e-4, atol=2e-4)
    _same_cache(got, want)
    assert np.asarray(got["lengths"]).tolist() == [0, PASS_CHUNK + 6, 0]


@pytest.mark.parametrize("width", [1, 2, 4])
def test_a_row_without_tokens_changes_nothing(pass_bench, width):
    """A pass of inert rows alone (`n_valid` 0, whatever slot and offset
    they name) writes no page, no length and no recurrent state, over a
    cache that holds a prompt and a poisoned recurrent pool."""
    bench = pass_bench
    _, start = bench.one_at_a_time(bench.cache(poison=3.0),
                                   [(1, 0, _prompt(6, 6))])
    _, after = bench.run(start, [], width)
    _same_cache(after, start, exact=True)


@paged_alone
def test_the_probes_call_is_a_pass_of_one_row(pass_bench):
    """`bench/probes/paged_kv.py` calls the program with `tokens [1, C]`
    and scalars for the count, the slot and the offset, and reads
    `logits[0]`: four results, the logits `[1, vocab]`, and what a
    one-row pass with vectors gives. (The hybrid is probed through
    `engine.prefill_logits`, tests/test_granite_hybrid.py.)"""
    import jax.numpy as jnp

    bench = pass_bench
    cache = bench.cache()
    tokens = np.zeros((1, PASS_CHUNK), np.int32)
    tokens[0, :5] = _prompt(5, 7)
    out = paged_kv.prefill_chunk_paged(
        bench.params, jnp.asarray(tokens), jnp.int32(5), jnp.int32(0),
        jnp.int32(0), cache["k"], cache["v"], cache["lengths"],
        cache["block_tables"], bench.cfg, PASS_LEN, None)
    assert len(out) == 4
    logits, k, v, lengths = out
    assert logits.shape == (1, bench.cfg.vocab_size)
    assert k.shape == cache["k"].shape and v.shape == cache["v"].shape
    assert np.asarray(lengths).tolist() == [5, 0, 0]
    rows, _ = bench.run(cache, [(0, 0, _prompt(5, 7))], 1)
    np.testing.assert_allclose(np.asarray(logits[0]), rows[0], rtol=1e-5,
                               atol=1e-5)


# -- engine: page accounting ----------------------------------------------
def test_zero_page_leak_over_1k_admit_evict_cycles():
    """1000 admissions/evictions leave the pool exactly empty. Prompts
    are shorter than a page, so nothing enters the prefix cache — every
    page cycles through alloc -> release."""
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(
        params, cfg, num_slots=8, max_len=16, page_size=4,
    )
    try:
        done = 0
        while done < 1000:
            wave = [
                eng.submit([1 + (done + i) % 50, 7], max_new_tokens=1)
                for i in range(50)
            ]
            for h in wave:
                assert len(h.result(timeout=180)) == 1
            done += len(wave)
        deadline = time.monotonic() + 30
        while eng.stats()["kv"]["pages_in_use"] != 0:
            assert time.monotonic() < deadline, (
                f"page leak after {done} cycles: "
                f"{eng.stats()['kv']}"
            )
            time.sleep(0.02)
        kv = eng.stats()["kv"]
        assert kv["prefix_cache_pages"] == 0
    finally:
        eng.shutdown()


@pytest.mark.parametrize("prompt_len, full_pages, skipped", [
    (20, 2, 16),   # two full pages and a tail: the tail is recomputed
    (24, 3, 23),   # all full pages: everything but the last token, whose
                   # row lands in a shared page (forked copy-on-write)
])
def test_prefix_cache_skips_prefill_for_shared_prompt(prompt_len, full_pages,
                                                      skipped):
    """A repeat prompt hits the prefix cache, skips exactly its resident
    full pages' tokens (at most all but the last; the skipped-token
    counter says how many) and still decodes the same greedy tokens."""
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(
        params, cfg, num_slots=2, max_len=64, page_size=8,
    )
    try:
        prompt = [(3 * i + 1) % 50 for i in range(prompt_len)]
        cold = eng.submit(prompt, max_new_tokens=6).result(timeout=180)
        # Wait for completion-side bookkeeping (insert happens at
        # prefill end; release at eviction).
        deadline = time.monotonic() + 10
        while eng.stats()["kv"]["prefix_cache_pages"] < full_pages:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        warm = eng.submit(prompt, max_new_tokens=6).result(timeout=180)
        assert warm == cold
        kv = eng.stats()["kv"]
        assert kv["prefix_hits"] == 1  # one hit event per request
        assert kv["prefill_tokens_skipped"] == skipped
        assert kv["prefix_hit_rate"] > 0
    finally:
        eng.shutdown()


def test_prefix_share_survives_donor_eviction_mid_share():
    """Flush the prefix cache (chaos hook) while a sharer is actively
    decoding off shared pages: the sharer's own page references keep the
    pages alive, output stays correct, and the pool drains to zero
    afterwards (no double release, no leak)."""
    from ray_tpu._private import chaos
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(
        params, cfg, num_slots=2, max_len=96, page_size=8,
    )
    chaos.enable()
    try:
        prompt = [(7 * i + 3) % 50 for i in range(24)]  # 3 full pages
        ref = eng.submit(prompt, max_new_tokens=30).result(timeout=180)
        sharer = eng.submit(prompt, max_new_tokens=30)
        # Let the sharer get mid-decode, then yank the donor pages' cache
        # references out from under it.
        deadline = time.monotonic() + 60
        while eng.stats()["kv"]["prefix_hits"] < 1:
            assert time.monotonic() < deadline, "sharer never hit the cache"
            time.sleep(0.005)
        chaos.flush_prefix_cache()
        out = sharer.result(timeout=180)
        assert out == ref
        deadline = time.monotonic() + 30
        while True:
            kv = eng.stats()["kv"]
            if kv["pages_in_use"] == 0 and kv["prefix_cache_pages"] == 0:
                break
            assert time.monotonic() < deadline, f"pages leaked: {kv}"
            time.sleep(0.02)
    finally:
        chaos.disable()
        eng.shutdown()


def test_chaos_exhaust_kv_pages_blocks_then_releases_admission():
    from ray_tpu._private import chaos
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(
        params, cfg, num_slots=2, max_len=32, page_size=8,
    )
    chaos.enable()
    try:
        chaos.exhaust_kv_pages(1.0)
        h = eng.submit([1, 2, 3], max_new_tokens=2)
        deadline = time.monotonic() + 30
        while eng.stats()["kv"]["chaos_held_pages"] == 0:
            assert time.monotonic() < deadline, "chaos never grabbed pages"
            time.sleep(0.02)
        # The request cannot be admitted while chaos holds the pool.
        time.sleep(0.3)
        st = eng.stats()
        assert st["active"] == 0 and st["waiting"] == 1
        chaos.exhaust_kv_pages(0.0)
        assert len(h.result(timeout=180)) == 2
    finally:
        chaos.disable()
        eng.shutdown()


# -- typed prompt rejection ----------------------------------------------
def test_prompt_too_long_is_typed_and_bounded_by_pool():
    from ray_tpu.exceptions import PromptTooLongError
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    # Pool smaller than max_len: 3 usable pages x 8 = 24 positions.
    eng = ContinuousBatchingEngine(
        params, cfg, num_slots=1, max_len=64, page_size=8,
        kv_pages=4,
    )
    try:
        with pytest.raises(PromptTooLongError) as ei:
            eng.submit(list(range(1, 40)), max_new_tokens=2)
        err = ei.value
        assert isinstance(err, ValueError)  # historical contract
        assert err.prompt_len == 39 and err.max_prompt_len == 22
        assert "39" in str(err) and "22" in str(err) and "page pool" in str(err)
        # An in-bound prompt still serves.
        assert len(
            eng.submit([5, 6, 7], max_new_tokens=2).result(timeout=180)
        ) == 2
    finally:
        eng.shutdown()


def test_proxy_maps_prompt_too_long_to_413():
    from ray_tpu.exceptions import PromptTooLongError, TaskError
    from ray_tpu.serve.proxy import _classify_error

    err = PromptTooLongError("too long", prompt_len=99, max_prompt_len=10)
    wrapped = TaskError("PromptTooLongError", "traceback...", cause=err)
    assert _classify_error(wrapped) == (413, None, "prompt_too_long")
    # Unpickleable cause: classification falls back to the class name.
    nameonly = TaskError("PromptTooLongError", "traceback...", cause=None)
    assert _classify_error(nameonly)[0] == 413


# -- autoscaler -----------------------------------------------------------
def _sig(ongoing_per_rep, n_reps, waiting=0, ttft_p99_s=None, burn=None):
    sig = {
        "replicas": [{"actor_id": f"r{i}", "ongoing": ongoing_per_rep}
                     for i in range(n_reps)],
        "waiting": waiting,
        "ttft_s": {"p99": ttft_p99_s, "p50": ttft_p99_s, "n": 10},
    }
    if burn is not None:
        sig["tenants"] = {
            "t": {"slo_windows": {"60": {"ttft": {"burn": burn}}}}
        }
    return sig


def test_autoscaler_hysteresis_with_fake_clock():
    from ray_tpu.serve.autoscale import AutoscalerState, decide
    from ray_tpu.serve.deployment import AutoscalingConfig

    acfg = AutoscalingConfig(
        min_replicas=1, max_replicas=3, target_ongoing_requests=2.0,
        upscale_delay_s=5.0, downscale_delay_s=20.0,
    )
    st = AutoscalerState()
    now, target = 1000.0, 1

    # Pressure must HOLD for upscale_delay_s before the target moves.
    assert decide(_sig(5, 1), acfg, st, now, target, 1) == 1
    assert decide(_sig(5, 1), acfg, st, now + 4.9, target, 1) == 1
    target = decide(_sig(5, 1), acfg, st, now + 5.1, target, 1)
    assert target == 2 and "ongoing" in st.last_reason

    # One replica per move: immediately after, the cooldown blocks.
    assert decide(_sig(5, 2), acfg, st, now + 5.2, target, 2) == 2
    target = decide(_sig(5, 2), acfg, st, now + 11.0, target, 2)
    assert target == 3
    # Clamped at max_replicas no matter the pressure.
    assert decide(_sig(50, 3), acfg, st, now + 60.0, target, 3) == 3

    # A blip below the hold threshold resets the timer (no flapping).
    st2 = AutoscalerState()
    decide(_sig(5, 1), acfg, st2, 0.0, 1, 1)
    decide(_sig(1, 1, waiting=0), acfg, st2, 3.0, 1, 1)  # pressure gone
    assert decide(_sig(5, 1), acfg, st2, 6.0, 1, 1) == 1  # hold restarted

    # Downscale needs a LONG quiet period and zero queue.
    now2, target = now + 100.0, 3
    assert decide(_sig(0, 3), acfg, st, now2, target, 3) == 3
    assert decide(_sig(0, 3), acfg, st, now2 + 19.0, target, 3) == 3
    target = decide(_sig(0, 3), acfg, st, now2 + 21.0, target, 3)
    assert target == 2 and st.last_reason.startswith("down")
    # Queued work vetoes downscale even with zero ongoing.
    st3 = AutoscalerState()
    assert decide(_sig(0, 2, waiting=5), acfg, st3, 0.0, 2, 2) == 2
    assert st3.low_since is None

    # Clamped at min_replicas.
    st4 = AutoscalerState()
    decide(_sig(0, 1), acfg, st4, 0.0, 1, 1)
    assert decide(_sig(0, 1), acfg, st4, 100.0, 1, 1) == 1


def test_autoscaler_optin_latency_and_burn_signals():
    from ray_tpu.serve.autoscale import AutoscalerState, decide
    from ray_tpu.serve.deployment import AutoscalingConfig

    acfg = AutoscalingConfig(
        target_ongoing_requests=10.0, upscale_delay_s=1.0,
        downscale_delay_s=1.0, max_replicas=4,
        ttft_p99_high_ms=100.0, burn_rate_high=2.0,
    )
    st = AutoscalerState()
    # TTFT p99 past the bound is upscale pressure on its own.
    sig = _sig(1, 2, ttft_p99_s=0.5)
    decide(sig, acfg, st, 0.0, 2, 2)
    assert decide(sig, acfg, st, 2.0, 2, 2) == 3
    assert "ttft" in st.last_reason
    # Elevated burn blocks downscale even when traffic looks idle.
    st2 = AutoscalerState()
    hot = _sig(0, 2, burn=5.0)
    decide(hot, acfg, st2, 0.0, 2, 2)
    assert decide(hot, acfg, st2, 50.0, 2, 2) == 3  # upscale, not down
    # Defaults (None) disable both signals entirely.
    acfg_off = AutoscalingConfig(target_ongoing_requests=10.0,
                                 upscale_delay_s=1.0)
    st3 = AutoscalerState()
    calm = _sig(1, 2, ttft_p99_s=9.9, burn=99.0)
    decide(calm, acfg_off, st3, 0.0, 2, 2)
    assert decide(calm, acfg_off, st3, 2.0, 2, 2) == 2


# -- signals schema v2 ----------------------------------------------------
def test_signals_schema_v2_and_old_reader_tolerance():
    from ray_tpu.scripts.scripts import _render_serve
    from ray_tpu.serve import observatory
    from ray_tpu.serve.autoscale import extract_load

    assert observatory.SIGNALS_SCHEMA_VERSION == 2

    # A v1-shaped doc (no kv / target_replicas / kv_util) still renders.
    old_doc = {
        "schema": 1, "seq": 3, "ts": time.time(),
        "apps": {"a": {
            "replicas": [{"actor_id": "ab" * 8, "ongoing": 1,
                          "total_served": 5}],
            "qps": 1.0, "waiting": 0,
            "ttft_s": {"p50": 0.01, "p99": 0.02, "n": 4},
            "tpot_s": {"p50": 0.001, "p99": 0.002, "n": 4},
        }},
    }
    out = _render_serve(old_doc)
    assert "app a" in out and "kv:" not in out

    # A v2 doc renders the new kv / replica-target columns.
    new_doc = {
        "schema": 2, "seq": 4, "ts": time.time(),
        "apps": {"a": {
            "replicas": [{"actor_id": "cd" * 8, "ongoing": 2,
                          "total_served": 9, "kv_util": 0.25}],
            "qps": 2.0, "waiting": 1,
            "target_replicas": 2, "running_replicas": 1,
            "kv": {"page_size": 16, "pages_total": 40, "pages_in_use": 10,
                   "util": 0.25, "prefix_hit_rate": 0.5,
                   "prefill_tokens_skipped": 128},
        }},
    }
    out = _render_serve(new_doc)
    assert "replicas=1/2" in out
    assert "kv: pages 10/40 (25%)" in out
    assert "prefix_hit=50%" in out and "kv=25%" in out

    # The decision-side reader tolerates both shapes too.
    assert extract_load(old_doc["apps"]["a"])["ongoing_mean"] == 1.0
    assert extract_load({})["replicas"] == 0


def test_engine_stats_expose_kv_plane_for_signals():
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(
        params, cfg, num_slots=2, max_len=32, page_size=8,
    )
    try:
        eng.submit([(11 * i + 1) % 40 for i in range(16)],
                   max_new_tokens=2).result(timeout=180)
        kv = eng.stats()["kv"]
        assert kv["mode"] == "paged" and kv["page_size"] == 8
        assert kv["pages_total"] == 2 * 4  # max_len rows for every slot
        assert kv["prefix_cache_pages"] == 2  # the prompt's full pages
        assert kv["roots"]  # advertised for affinity routing
        assert 0.0 <= kv["util"] <= 1.0
    finally:
        eng.shutdown()


def test_handle_affinity_prefers_covering_replica():
    """_pick_replica with a route_key must choose the replica whose
    advertised prefix set covers it, not the P2C winner."""
    from ray_tpu.serve.handle import DeploymentHandle

    class _Aid:
        def __init__(self, b):
            self._b = b

        def binary(self):
            return self._b

        def hex(self):
            return self._b.hex()

    class _Rep:
        def __init__(self, b):
            self._actor_id = _Aid(b)

    r1, r2 = _Rep(b"\x01" * 8), _Rep(b"\x02" * 8)
    h = DeploymentHandle("app")
    key = prefix_route_key(list(range(16)), 16)
    s = h._shared
    with s["lock"]:
        s["replicas"] = [r1, r2]
        s["version"] = 1
        s["last_refresh"] = time.monotonic()
        s["prefix"] = {r2._actor_id.hex(): {key}}
        s["page_size"] = 16
        # Bias load AGAINST the covering replica: affinity must still win.
        s["inflight"] = {r2._actor_id.binary(): 5}
    assert h._route_key((list(range(16)),)) == key
    for _ in range(8):
        assert h._pick_replica(route_key=key) is r2
    # No coverage -> falls back to the load-based pick.
    assert h._pick_replica(route_key="unknown") in (r1, r2)
    # Short prompts / no advertised prefixes produce no route key.
    assert h._route_key(([1, 2],)) is None
    with s["lock"]:
        s["prefix"] = {}
    assert h._route_key((list(range(16)),)) is None


def test_a_latent_pools_page_forks_without_a_second_pool():
    """Copy-on-write over ONE pool: the page's rows in every layer, and
    None where a second pool would be."""
    import jax.numpy as jnp

    from ray_tpu.models import configs

    cfg = configs.get_config("tiny_dots")
    cache = paged_kv.init_paged_cache(cfg, 2, 5, 4, 2)
    width = paged_kv.latent_row_width(cfg)
    pool = jnp.arange(cfg.n_layers * 5 * 4 * width, dtype=jnp.float32
                      ).reshape(cache["k"].shape)
    forked, none = paged_kv.cow_copy_page(pool, cache["v"], 3, 1)
    assert none is None
    np.testing.assert_array_equal(np.asarray(forked[:, 1]),
                                  np.asarray(pool[:, 3]))
    np.testing.assert_array_equal(np.asarray(forked[:, 2:]),
                                  np.asarray(pool[:, 2:]))


# A pass's attention (`paged_kv._paged_attention`) against the plain form it
# replaced: (pages a slot, rows' `offset`, rows' `n_valid`, the mask). Page
# size 4, chunks of 8 queries, and `WALK_BLOCK_ROWS` set to 8 (`small_blocks`):
# a table of up to 16 rows is one block, a wider one walks blocks of 8.
WALKS = {
    "one_block": (4, [5], [8], "causal"),
    "several_blocks": (16, [40], [8], "causal"),
    "block_does_not_divide": (19, [68], [8], "causal"),
    "rows_of_different_ends_and_an_inert_row":
        (16, [8, 33, 50, 0], [8, 7, 0, 5], "causal"),
    "block_causal": (16, [24, 4], [8, 8], "block"),
    "ring_before_its_wrap": (12, [16, 5], [8, 3], "ring"),
    "ring_across_its_wrap": (12, [44, 90], [8, 6], "ring"),
}
_WINDOW = 40  # with a chunk of 8, what a ring of 12 pages of 4 holds


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setattr(paged_kv, "WALK_BLOCK_ROWS", 8)


def _walk_case(pages_per_slot, offset, n_valid, mask, poison=False):
    """`(walked, plain, real)` of one case of `WALKS`: the walk's result,
    `grouped_attention`'s over the gathered table under the dense mask, and
    which rows of the pass are real. `poison`: every page past the blocks
    the walk should visit holds NaN."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import block_causal
    from ray_tpu.ops.paged_attention import grouped_attention

    ps, c, h, kvh, d, layer = 4, 8, 4, 2, 16, 1
    p_, mp = len(offset), pages_per_slot
    width = mp * ps
    offset, n_valid = (np.asarray(a, np.int32) for a in (offset, n_valid))
    end = offset + n_valid
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    shape = (2, 1 + p_ * mp, ps, kvh * d)
    kc = np.array(jax.random.normal(keys[0], shape, jnp.float32))
    vc = np.array(jax.random.normal(keys[1], shape, jnp.float32))
    q = jax.random.normal(keys[2], (p_, c, h, d), jnp.float32)
    tables = 1 + np.random.default_rng(0).permutation(p_ * mp).reshape(p_, mp)
    positions = offset[:, None] + np.arange(c)[None]
    rows = np.arange(width)
    if mask == "ring":
        # By brute force: ring row r holds the last position under `end`
        # that is r mod the ring.
        held = np.full((p_, width), -1)
        for r_, e in enumerate(end):
            for pos in range(e):
                held[r_, pos % width] = pos
        behind = positions[:, :, None] - held[:, None]
        valid = (held[:, None] >= 0) & (behind >= 0) & (behind < _WINDOW)
        live = np.minimum(end, width)

        def seen(r):
            at = end[:, None] - 1 - (end[:, None] - 1 - r[None]) % width
            gap = positions[:, :, None] - at[:, None]
            return (gap >= 0) & (gap < _WINDOW)
    else:
        live = end
        if mask == "block":
            def seen(k):
                return block_causal(jnp.asarray(positions), k, 4)
        else:
            def seen(k):
                return k <= positions[:, :, None]
        valid = np.asarray(seen(jnp.asarray(rows))) & (rows < end[:, None, None])
    real = n_valid > 0
    live = np.where(real, live, 0)
    if poison:
        walked = paged_kv.rows_walked(int(live.max()), ps, mp)
        for pool in (kc, vc):
            pool[:, tables[:, walked // ps:].reshape(-1)] = np.nan
    kc, vc, tables = jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(tables)
    got = jax.jit(lambda *a: paged_kv._paged_attention(
        *a, layer, tables, jnp.asarray(live), seen, d ** -0.5))(q, kc, vc)
    plain = grouped_attention(
        q, kc[layer, tables].reshape(p_, width, kvh, d),
        vc[layer, tables].reshape(p_, width, kvh, d), jnp.asarray(valid),
        d ** -0.5)
    return np.asarray(got), np.asarray(plain), real


@pytest.mark.parametrize("case", WALKS)
def test_a_pass_walks_its_table_to_the_plain_forms_numbers(case, small_blocks):
    """`_paged_attention` (blocks of a row's table, a running softmax, as
    many steps as the longest real row's end needs) gives what
    `grouped_attention` gives over the whole gathered table under the same
    mask, to float32's rounding: a table of two blocks' rows, which is one
    block (one step and no loop), several, a width the block does not divide, four rows of
    different ends one of them inert at an arbitrary offset (it sees
    nothing and gives zeros), a block-diffusion model's mask, and a ring's
    held positions before and across its wrap."""
    import jax

    got, plain, real = _walk_case(*WALKS[case])
    np.testing.assert_allclose(got[real], plain[real], rtol=2e-5, atol=2e-6)
    assert np.abs(plain[real]).max() > 0.1
    np.testing.assert_array_equal(got[~real], 0.0)
    if case == "one_block":
        pages, offset, n_valid, _ = WALKS[case]
        def traced(pages):
            return str(jax.make_jaxpr(
                lambda q, kc, vc: paged_kv._paged_attention(
                    q, kc, vc, 0, np.ones((1, pages), np.int32),
                    np.asarray([3], np.int32), lambda k: k[None, None] >= 0,
                    1.0))(np.zeros((1, 8, 4, 16), np.float32),
                          *(np.zeros((1, 2, 4, 32), np.float32),) * 2))

        assert "while" not in traced(pages) and "while" in traced(pages + 1)


@pytest.mark.parametrize("case", [
    "several_blocks", "rows_of_different_ends_and_an_inert_row",
    "ring_before_its_wrap"])
def test_a_pass_reads_no_page_past_the_block_that_holds_its_end(
        case, small_blocks):
    """Every page past the blocks the walk visits (`rows_walked` of the
    longest real row's end) filled with NaN: the walk's result is finite
    and the clean one's to the bit, so those rows are not read. The plain
    form multiplies them by a weight of zero and gives NaN: proof the test
    can fail."""
    clean, _, real = _walk_case(*WALKS[case])
    got, plain, _ = _walk_case(*WALKS[case], poison=True)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)
    assert np.isnan(plain[real]).any()


@pytest.mark.parametrize("rows, block", [
    (1024, 1024), (2560, 512), (4608, 512), (16384, 512), (512, 512)])
def test_a_walks_block_is_worked_out_from_the_tables_width(rows, block):
    """`walk_block_pages`, the one rule: blocks of 512 rows, and a table of
    up to 1,024 rows whole (the serving cells' widths, at pages of 16)."""
    assert paged_kv.walk_block_pages(16, rows // 16) * 16 == block
    assert paged_kv.rows_walked(1, 16, rows // 16) == block
    assert paged_kv.rows_walked(rows, 16, rows // 16) == rows
