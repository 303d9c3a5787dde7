"""Kernel tests: pallas kernels run in interpret mode on CPU; fallbacks
checked against straightforward references."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (
    apply_rope,
    flash_attention,
    rmsnorm,
    rope_frequencies,
    softmax_cross_entropy,
)
from ray_tpu.parallel.ring_attention import reference_attention

pytestmark = pytest.mark.slow  # jax-compile-heavy compute-path tier


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_xla_fallback(causal):
    key = jax.random.PRNGKey(0)
    q, k, v = (
        jax.random.normal(kk, (2, 64, 4, 16)) for kk in jax.random.split(key, 3)
    )
    got = flash_attention(q, k, v, causal=causal, use_pallas=False)
    expected = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_pallas_interpret(causal):
    key = jax.random.PRNGKey(1)
    q, k, v = (
        jax.random.normal(kk, (1, 128, 2, 32)) for kk in jax.random.split(key, 3)
    )
    got = flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32, interpret=True,
        use_pallas=True,
    )
    expected = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_pallas_grads(causal):
    """The round-1 bench died on a missing Pallas VJP — this pins grad
    parity of the Pallas backward (interpret mode) against the XLA path so
    the TPU training path can never silently lose its backward again."""
    key = jax.random.PRNGKey(12)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, 96, 2, 32))
    k = jax.random.normal(ks[1], (2, 96, 2, 32))
    v = jax.random.normal(ks[2], (2, 96, 2, 32))

    def loss_pallas(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                              interpret=True, use_pallas=True)
        return (out ** 2).sum()

    def loss_xla(q, k, v):
        return (flash_attention(q, k, v, causal=causal, use_pallas=False) ** 2).sum()

    lp, gp = jax.value_and_grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    lx, gx = jax.value_and_grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(lp), float(lx), rtol=2e-4)
    for a, b, name in zip(gp, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-3, err_msg=f"d{name}")


@pytest.mark.parametrize("causal,lq,lk", [(True, 80, 80), (False, 80, 112),
                                          (False, 96, 80)])
def test_flash_attention_pallas_nondivisible_blocks(causal, lq, lk):
    """Sequence lengths not divisible by the block sizes: the kernels pad
    to the block grid and mask beyond the true lengths (review finding:
    interior pl.ds clamping double-counted edge rows)."""
    ks = jax.random.split(jax.random.PRNGKey(20), 3)
    q = jax.random.normal(ks[0], (1, lq, 2, 32))
    k = jax.random.normal(ks[1], (1, lk, 2, 32))
    v = jax.random.normal(ks[2], (1, lk, 2, 32))

    def loss_pallas(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                              interpret=True, use_pallas=True)
        return (out ** 2).sum()

    def loss_xla(q, k, v):
        return (flash_attention(q, k, v, causal=causal, use_pallas=False) ** 2).sum()

    lp, gp = jax.value_and_grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    lx, gx = jax.value_and_grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(lp), float(lx), rtol=2e-4)
    for a, b, name in zip(gp, gx, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-3, err_msg=f"d{name}")


def test_flash_attention_pallas_grads_uneven_kv():
    """Cross-attention shape (Lk != Lq) through the Pallas backward."""
    q = jax.random.normal(jax.random.PRNGKey(13), (1, 64, 2, 16))
    k = jax.random.normal(jax.random.PRNGKey(14), (1, 128, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(15), (1, 128, 2, 16))

    def loss(impl):
        def f(q, k, v):
            out = flash_attention(q, k, v, causal=False, block_q=32,
                                  block_k=32, **impl)
            return (out ** 2).sum()
        return f

    gp = jax.grad(loss({"interpret": True, "use_pallas": True}),
                  argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss({"use_pallas": False}), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-3)


def test_flash_attention_gqa():
    key = jax.random.PRNGKey(2)
    q = jax.random.normal(key, (1, 32, 8, 16))
    k = jax.random.normal(jax.random.PRNGKey(3), (1, 32, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(4), (1, 32, 2, 16))
    got = flash_attention(q, k, v, use_pallas=False)
    assert got.shape == (1, 32, 8, 16)


def test_rmsnorm_matches_reference_and_grads():
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 32, 64))
    w = jax.random.normal(jax.random.PRNGKey(6), (64,)) * 0.1 + 1.0

    got = rmsnorm(x, w, use_pallas=False)
    var = (x.astype(jnp.float32) ** 2).mean(-1, keepdims=True)
    expected = x / jnp.sqrt(var + 1e-6) * w
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)

    # Grad parity with autodiff of the reference.
    def loss_custom(x, w):
        return (rmsnorm(x, w, use_pallas=False) ** 2).sum()

    def loss_ref(x, w):
        var = (x.astype(jnp.float32) ** 2).mean(-1, keepdims=True)
        return ((x / jnp.sqrt(var + 1e-6) * w) ** 2).sum()

    gx1, gw1 = jax.grad(loss_custom, argnums=(0, 1))(x, w)
    gx2, gw2 = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw1), np.asarray(gw2), rtol=1e-4, atol=1e-4)


def test_rmsnorm_pallas_interpret():
    x = jax.random.normal(jax.random.PRNGKey(7), (8, 64))
    w = jnp.ones((64,))
    got = rmsnorm(x, w, interpret=True, use_pallas=True)
    expected = rmsnorm(x, w, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows", [132, 128])
def test_rmsnorm_pallas_grads(rows):
    """Grad parity of the Pallas rmsnorm backward kernel vs the XLA path
    (the flagship model now uses the Pallas path on TPU). rows=132 with
    block_rows=64 leaves a partial tail block — dw must not sum padding."""
    from ray_tpu.ops.rmsnorm import _rmsnorm_pallas

    x = jax.random.normal(jax.random.PRNGKey(16), (4, rows // 4, 64))
    w = jax.random.normal(jax.random.PRNGKey(17), (64,)) * 0.1 + 1.0

    def loss_pallas(x, w):
        return (_rmsnorm_pallas(x, w, 1e-6, block_rows=64,
                                interpret=True) ** 2).sum()

    def loss_xla(x, w):
        return (rmsnorm(x, w, use_pallas=False) ** 2).sum()

    gp = jax.grad(loss_pallas, argnums=(0, 1))(x, w)
    gx = jax.grad(loss_xla, argnums=(0, 1))(x, w)
    for a, b, name in zip(gp, gx, ["dx", "dw"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-3, err_msg=name)


def test_model_grads_through_pallas_interpret():
    """End-to-end: the flagship forward+backward with the Pallas kernels
    forced on (interpret mode) — the path the train cells take on the
    chip."""
    from dataclasses import replace
    from unittest import mock

    from ray_tpu.models import configs, init_params, loss_fn
    import ray_tpu.models.transformer as tf_mod

    cfg = replace(configs.tiny, d_model=32, d_ff=64, vocab_size=64,
                  n_layers=2, n_heads=2, n_kv_heads=2, max_seq=64,
                  remat=True)
    params = init_params(jax.random.PRNGKey(18), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(19), (2, 33), 0,
                                cfg.vocab_size)

    def fa_forced(q, k, v, **kw):
        kw.update(interpret=True, use_pallas=True)
        return flash_attention(q, k, v, **kw)

    def rn_forced(x, w, eps=1e-6, **kw):
        return rmsnorm(x, w, eps, interpret=True, use_pallas=True)

    with mock.patch.object(tf_mod, "flash_attention", fa_forced), \
         mock.patch.object(tf_mod, "rmsnorm", rn_forced):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    assert jnp.isfinite(loss)
    assert all(jnp.all(jnp.isfinite(g)) for g in jax.tree.leaves(grads))


def test_rope_rotation_preserves_norm():
    cos, sin = rope_frequencies(32, 128)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, 4, 32))
    out = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )


def test_rope_with_positions():
    cos, sin = rope_frequencies(16, 64)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 8, 2, 16))
    pos = jnp.arange(8)[None, :] + 10
    out_shifted = apply_rope(x, cos, sin, positions=pos)
    assert out_shifted.shape == x.shape
    # Shifted positions differ from default positions.
    out_default = apply_rope(x, cos, sin)
    assert not np.allclose(np.asarray(out_shifted), np.asarray(out_default))


def test_cross_entropy_matches_reference():
    logits = jax.random.normal(jax.random.PRNGKey(10), (4, 100))
    labels = jnp.array([3, 50, 99, 0])
    got = softmax_cross_entropy(logits, labels)
    expected = -jax.nn.log_softmax(logits)[jnp.arange(4), labels]
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-6)


def test_cross_entropy_grad():
    logits = jax.random.normal(jax.random.PRNGKey(11), (4, 50))
    labels = jnp.array([1, 2, 3, 4])

    g1 = jax.grad(lambda x: softmax_cross_entropy(x, labels).sum())(logits)
    g2 = jax.grad(
        lambda x: (-jax.nn.log_softmax(x)[jnp.arange(4), labels]).sum()
    )(logits)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4,
                               atol=1e-5)


def test_chunked_lm_head_ce_parity():
    """Chunked lm_head+CE (never materializes full logits) matches the
    fused full-logits loss in value AND gradient."""
    from dataclasses import replace

    import jax
    import numpy as np

    from ray_tpu.models import configs, init_params, loss_fn

    cfg = replace(configs.tiny, max_seq=64, remat=False, dtype=jax.numpy.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                                cfg.vocab_size)
    l_full, g_full = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    cfg_c = replace(cfg, ce_chunk=8)
    l_chunk, g_chunk = jax.value_and_grad(loss_fn)(params, tokens, cfg_c)
    np.testing.assert_allclose(float(l_full), float(l_chunk), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_chunk)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )


# The three served pools' rows: (KV heads, head width, queries a KV head,
# scale). The last is the hybrid's: a head is half of a tile's 128 lanes,
# no rope, scores scaled by 1/64.
PAGED_POOLS = {"qwen3": (8, 128, 4, 128 ** -0.5),
               "olmoe": (16, 128, 1, 128 ** -0.5),
               "granite-h": (8, 64, 4, 1 / 64)}


def _paged_case(kv_heads, head_dim, group, page=16, per_slot=8, layers=3):
    """A pool, a table and lengths that meet every edge at once: slot 0
    is inactive (length 0, parked on the NULL page), then 1 row, a page's
    last row, a page's first row, max_len - 1, and two slots that share
    their first two pages (a copy-on-write prefix). Every table is out of
    order. Whole pages no live row lies in hold NaN, the NULL page too:
    a kernel that touched one would show it."""
    max_len = page * per_slot
    rows = np.asarray([0, 1, page, page + 1, max_len - 1, 40, 37], np.int32)
    slots = len(rows)
    pages = slots * per_slot + 1
    rng = np.random.default_rng(3)
    tables = rng.permutation(np.arange(1, pages))[:slots * per_slot].reshape(
        slots, per_slot).astype(np.int32)
    tables[0] = 0
    tables[6, :2] = tables[5, :2]
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    shape = (layers, pages, page, kv_heads * head_dim)
    k_pool = jax.random.normal(ks[0], shape, jnp.float32)
    v_pool = jax.random.normal(ks[1], shape, jnp.float32)
    q = jax.random.normal(ks[2], (slots, kv_heads * group, head_dim))
    live = np.zeros(pages, bool)
    for s in range(slots):
        live[tables[s, :-(-rows[s] // page)]] = True
    poison = jnp.where(jnp.asarray(live)[None, :, None, None], 0.0, jnp.nan)
    return q, k_pool, v_pool, poison, jnp.asarray(tables), jnp.asarray(rows)


@pytest.mark.parametrize("block_rows", [32, 512], ids=["4blocks", "1block"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("pool", PAGED_POOLS)
def test_paged_decode_attention_kernel_matches_the_plain_form(
        pool, dtype, block_rows, monkeypatch):
    """The decode-attention kernel (interpret mode) against the plain
    form (the whole table gathered, cast and masked), at every layer of
    the pool, in blocks shorter than a sequence and longer than any."""
    from ray_tpu.ops import paged_attention

    kv_heads, head_dim, group, scale = PAGED_POOLS[pool]
    monkeypatch.setattr(paged_attention, "_MAX_BLOCK_ROWS", block_rows)
    q, k_pool, v_pool, poison, tables, rows = _paged_case(
        kv_heads, head_dim, group)
    q, k_pool, v_pool = (a.astype(dtype) for a in (q, k_pool, v_pool))
    assert paged_attention.kernel_takes(k_pool, head_dim)
    live = np.asarray(rows) > 0
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for layer in range(k_pool.shape[0]):
        want = paged_attention.paged_decode_attention(
            q, k_pool, v_pool, jnp.int32(layer), tables, rows, scale,
            use_pallas=False)
        got = paged_attention.paged_decode_attention(
            q, k_pool + poison.astype(dtype), v_pool + poison.astype(dtype),
            jnp.int32(layer), tables, rows, scale, interpret=True)
        assert got.shape == q.shape and got.dtype == q.dtype
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.isfinite(got).all()  # the inactive slot's row too
        np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)


def test_paged_decode_attention_takes_the_plain_form_for_other_pools():
    """A page that is not whole tiles, or a head that does not divide a
    tile's lanes, is read by the plain form whatever the backend: the
    pool's shape chooses, nothing else does."""
    from ray_tpu.ops.paged_attention import kernel_takes, pages_per_block

    pool = lambda page, width, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        (2, 9, page, width), dtype)
    assert kernel_takes(pool(16, 1024), 128)
    assert kernel_takes(pool(16, 512), 64)
    assert kernel_takes(pool(8, 256, jnp.float32), 256)
    assert not kernel_takes(pool(8, 1024), 128)    # half a bf16 tile a page
    assert not kernel_takes(pool(16, 64), 32)      # a row of half a tile
    assert not kernel_takes(pool(16, 768), 96)     # 96 divides no 128
    # Blocks from the page's shape: 8 MB for K and V twice, 512 rows at
    # most: the three served rows (1, 2 and 4 KB) get 512, a row of 16 KB
    # 128, and a page that is not a divisor of 128 rows whole pages.
    for width in (512, 1024, 2048):
        assert pages_per_block(16, width, jnp.bfloat16) == 32
    assert pages_per_block(16, 8192, jnp.bfloat16) == 8
    assert pages_per_block(48, 4096, jnp.bfloat16) == 5


def test_paged_decode_attention_per_shard_matches_whole():
    """Under the engine's mesh the kernel runs on each device's KV heads
    (the pools' last axis and the queries' heads over "tp"), table, rows
    and layer whole on every device."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.paged_attention import paged_decode_attention
    from ray_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(tp=2), jax.devices()[:2])
    q, k_pool, v_pool, _, tables, rows = _paged_case(4, 64, 2)
    whole = paged_decode_attention(q, k_pool, v_pool, jnp.int32(1), tables,
                                   rows, 0.125, interpret=True)
    put = lambda a, *spec: jax.device_put(a, NamedSharding(mesh, P(*spec)))  # noqa: E731
    sharded = jax.jit(lambda q, k, v, t, r: paged_decode_attention(
        q, k, v, jnp.int32(1), t, r, 0.125, interpret=True, mesh=mesh))(
        put(q, None, "tp"), put(k_pool, None, None, None, "tp"),
        put(v_pool, None, None, None, "tp"), put(tables), put(rows))
    live = np.asarray(rows) > 0
    np.testing.assert_allclose(np.asarray(sharded)[live],
                               np.asarray(whole)[live], rtol=1e-5, atol=1e-5)


def _latent_case(page, per_slot=40, layers=2):
    """A small latent pool (16 heads, a 128-wide latent and a 64-wide rope
    key in rows of 256), tables and lengths at every edge: no rows (twice:
    the first slot and one between live ones), one row, a page's last row,
    two lengths that end mid-page (one under a tile of 128 rows, one over
    it), a full table. A table's entries past its slot's rows are the NULL
    page; every page no live row lies in holds NaN, the NULL page too."""
    from dataclasses import replace

    from ray_tpu.models import configs

    cfg = replace(configs.get_config("tiny_dots"), n_heads=16,
                  kv_lora_rank=128, qk_rope_head_dim=64, qk_nope_head_dim=32,
                  v_head_dim=32, dtype=jnp.float32)
    max_len = page * per_slot
    end = np.asarray([0, 1, page, 2 * page + 5, 0, 128 + page + 3, max_len],
                     np.int32)
    slots, pages = len(end), len(end) * per_slot + 1
    tables = np.random.default_rng(3).permutation(np.arange(1, pages))[
        :slots * per_slot].reshape(slots, per_slot).astype(np.int32)
    live = np.zeros(pages, bool)
    for s in range(slots):
        tables[s, -(-end[s] // page):] = 0
        live[tables[s, :-(-end[s] // page)]] = True
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    rank, h = cfg.kv_lora_rank, cfg.n_heads
    lp = {"w_uk": jax.random.normal(
              ks[0], (rank, h * cfg.qk_nope_head_dim)) * rank ** -0.5,
          "w_uv": jax.random.normal(
              ks[1], (rank, h * cfg.v_head_dim)) * rank ** -0.5}
    q_n = jax.random.normal(ks[2], (slots, 1, h, cfg.qk_nope_head_dim))
    q_r = jax.random.normal(ks[3], (slots, 1, h, cfg.qk_rope_head_dim))
    pool = jax.random.normal(ks[4], (layers, pages, page, 256))
    poison = jnp.where(jnp.asarray(live)[None, :, None, None], 0.0, jnp.nan)
    return cfg, lp, q_n, q_r, pool, poison, jnp.asarray(tables), jnp.asarray(end)


@pytest.mark.parametrize("block_rows", [128, 512], ids=["1tile", "4tiles"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("page", [16, 32])
def test_latent_decode_attention_kernel_matches_the_loop(
        page, dtype, block_rows, monkeypatch):
    """The latent pool's decode kernel (interpret mode) against the loop
    that runs off the chip (`_latent_attention`, absorbed), at every layer
    of the pool: the same queries (`absorbed_queries`), the values'
    projection after. A slot of 640 rows (1280 at pages of 32) is five
    (ten) blocks of one tile, or a block of four tiles and one of one
    (two and a half blocks); a block's pages come as whole groups of a
    tile's pages and the pages left."""
    from ray_tpu.ops import paged_attention
    from ray_tpu.serve import paged_kv

    monkeypatch.setattr(paged_attention, "_LATENT_BLOCK_ROWS", block_rows)
    cfg, lp, q_n, q_r, pool, poison, tables, end = _latent_case(page)
    lp, q_n, q_r, pool = jax.tree.map(
        lambda a: a.astype(dtype), (lp, q_n, q_r, pool))
    rank, h = cfg.kv_lora_rank, cfg.n_heads
    assert paged_attention.latent_kernel_takes(pool, h, rank)
    live = np.asarray(end) > 0
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    q = paged_kv.absorbed_queries(q_n, q_r, lp, cfg, pool.shape[-1])
    for layer in range(pool.shape[0]):
        want = paged_kv._latent_attention(
            q_n, q_r, lp, pool, layer, tables, None, end, cfg, absorbed=True)
        got = paged_attention.latent_decode_attention(
            q[:, 0], pool + poison.astype(dtype), jnp.int32(layer), tables,
            end, rank, interpret=True)
        assert got.shape == (len(live), 1, h, rank) and got.dtype == dtype
        assert not np.asarray(got, np.float32)[~live].any()
        got = jnp.einsum("bqhr,rhv->bqhv", got,
                         lp["w_uv"].reshape(rank, h, -1)).reshape(want.shape)
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.abs(want[live]).max() > 0.1
        np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)


def test_latent_decode_attention_takes_the_loop_for_other_pools():
    """A page that is not whole tiles, a row or a latent that is not whole
    lanes, or heads that are not whole sublanes: the loop, whatever the
    backend."""
    from ray_tpu.ops.paged_attention import latent_kernel_takes

    pool = lambda page, row, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        (2, 9, page, row), dtype)
    assert latent_kernel_takes(pool(16, 640), 128, 512)
    assert latent_kernel_takes(pool(8, 256, jnp.float32), 8, 128)
    assert not latent_kernel_takes(pool(8, 640), 128, 512)   # half a tile
    assert not latent_kernel_takes(pool(16, 576), 128, 512)  # 4.5 lanes' tiles
    assert not latent_kernel_takes(pool(16, 128), 4, 32)     # tiny_dots' rows
    assert not latent_kernel_takes(pool(16, 640), 8, 512)    # 8 bf16 heads


def test_kernels_per_shard_match_whole():
    """Under a mesh the ops run each kernel on a device's block
    (ops.per_shard). Values and gradients equal the unsharded call,
    including the replicated scale's, which sums over batch shards."""
    from jax.sharding import NamedSharding

    from ray_tpu.models.transformer import _ACT_SPEC, _HEADS_SPEC
    from ray_tpu.parallel import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(fsdp=2, tp=2), jax.devices()[:4])
    ks = jax.random.split(jax.random.PRNGKey(20), 5)
    x = jax.random.normal(ks[0], (4, 16, 64))
    w = jax.random.normal(ks[1], (64,)) * 0.1 + 1.0
    q = jax.random.normal(ks[2], (4, 16, 4, 32))
    k = jax.random.normal(ks[3], (4, 16, 2, 32))
    v = jax.random.normal(ks[4], (4, 16, 2, 32))

    def loss(x, w, q, k, v, mesh=None):
        h = rmsnorm(x, w, interpret=True, mesh=mesh, spec=_ACT_SPEC)
        a = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True,
                            mesh=mesh, spec=_HEADS_SPEC)
        return (h ** 2).sum() + (a ** 2).sum()

    whole = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(x, w, q, k, v)
    put = lambda a, spec: jax.device_put(a, NamedSharding(mesh, spec))  # noqa: E731
    args = (put(x, _ACT_SPEC), put(w, jax.sharding.PartitionSpec()),
            put(q, _HEADS_SPEC), put(k, _HEADS_SPEC), put(v, _HEADS_SPEC))
    sharded = jax.jit(jax.value_and_grad(
        lambda *a: loss(*a, mesh=mesh), argnums=(0, 1, 2, 3, 4)))(*args)
    for a, b in zip(jax.tree.leaves(sharded), jax.tree.leaves(whole)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
