"""Parallelism primitive tests on the virtual 8-device CPU mesh.

This is the test strategy SURVEY.md §4.2 calls for: sharding/collective
code paths execute on xla_force_host_platform_device_count=8 CPU devices,
no TPU required.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel import (
    MeshConfig,
    build_mesh,
    logical_to_physical,
    moe_block,
    pipeline_stages,
    ring_attention,
    shard_params,
    ulysses_attention,
)
from ray_tpu.parallel.ring_attention import reference_attention

pytestmark = pytest.mark.slow  # jax-compile-heavy compute-path tier


def test_mesh_config_factorization():
    cfg = MeshConfig.for_devices(8, tp=2)
    assert cfg.tp == 2 and cfg.fsdp == 4 and cfg.num_devices == 8
    with pytest.raises(ValueError):
        MeshConfig.for_devices(8, tp=3)


def test_build_mesh():
    mesh = build_mesh(MeshConfig(fsdp=4, tp=2))
    assert mesh.shape["fsdp"] == 4
    assert mesh.shape["tp"] == 2
    assert mesh.shape["dp"] == 1


def test_logical_to_physical():
    spec = logical_to_physical(("batch", "seq", "act_heads"))
    assert spec == jax.sharding.PartitionSpec(("dp", "fsdp"), "sp", "tp")


def test_shard_params_places_on_mesh():
    mesh = build_mesh(MeshConfig(fsdp=4, tp=2))
    params = {"w": jnp.ones((8, 16)), "b": jnp.ones((16,))}
    axes = {"w": ("embed", "mlp"), "b": None}
    sharded = shard_params(params, axes, mesh)
    # w: embed->fsdp, mlp->tp
    shard_shape = sharded["w"].sharding.shard_shape(sharded["w"].shape)
    assert shard_shape == (2, 8)  # 8/4, 16/2


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = build_mesh(MeshConfig(sp=8))
    key = jax.random.PRNGKey(0)
    b, l, h, d = 2, 64, 4, 16
    q, k, v = (
        jax.random.normal(kk, (b, l, h, d), dtype=jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    expected = reference_attention(q, k, v, causal=causal)
    got = ring_attention(q, k, v, mesh, axis_name="sp", causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_jit_grad():
    mesh = build_mesh(MeshConfig(sp=8))
    b, l, h, d = 1, 32, 2, 8
    q = jax.random.normal(jax.random.PRNGKey(1), (b, l, h, d))

    @jax.jit
    def loss(q):
        out = ring_attention(q, q, q, mesh, axis_name="sp")
        return (out ** 2).sum()

    g = jax.grad(loss)(q)
    assert g.shape == q.shape
    assert bool(jnp.isfinite(g).all())


def test_ulysses_matches_reference():
    mesh = build_mesh(MeshConfig(sp=8))
    key = jax.random.PRNGKey(2)
    b, l, h, d = 2, 64, 8, 16  # heads divisible by sp
    q, k, v = (
        jax.random.normal(kk, (b, l, h, d), dtype=jnp.float32)
        for kk in jax.random.split(key, 3)
    )
    expected = reference_attention(q, k, v, causal=True)
    got = ulysses_attention(q, k, v, mesh, axis_name="sp", causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_pipeline_matches_sequential():
    mesh = build_mesh(MeshConfig(pp=4))
    S, M, mb, dim = 4, 8, 4, 16
    key = jax.random.PRNGKey(3)
    ws = jax.random.normal(key, (S, dim, dim)) * 0.1

    def stage_fn(w, x):
        # w is the device-local stack shard: [layers_per_stage=1, dim, dim].
        return jnp.tanh(x @ w[0])

    xs = jax.random.normal(jax.random.PRNGKey(4), (M, mb, dim))
    got = pipeline_stages(stage_fn, ws, xs, mesh, axis_name="pp")
    # Sequential reference
    expected = xs
    for s in range(S):
        expected = jax.vmap(lambda x: stage_fn(ws[s:s + 1], x))(expected)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def _moe_setup(tokens=32, d=16, ff=16, experts=4):
    from ray_tpu.models.transformer import TransformerConfig

    cfg = TransformerConfig(d_model=d, d_ff=ff, num_experts=experts,
                            experts_per_token=2, dtype=jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(keys[0], (tokens, d))
    lp = {
        "router": jax.random.normal(keys[1], (d, experts)) * 0.1,
        "w_gate": jax.random.normal(keys[2], (experts, d, ff)) * 0.1,
        "w_up": jax.random.normal(keys[3], (experts, d, ff)) * 0.1,
        "w_down": jax.random.normal(keys[4], (experts, ff, d)) * 0.1,
    }
    return cfg, x, lp


def test_moe_layer_runs_and_balances():
    cfg, x, lp = _moe_setup()
    out, stats = moe_block(x, lp, cfg)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all())
    # Dropless: every token's two choices were computed.
    assert int(stats["counts"].sum()) == x.shape[0] * 2


def test_moe_layer_sharded_over_ep():
    mesh = build_mesh(MeshConfig(ep=4))
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg, x, lp = _moe_setup()
    want, _ = moe_block(x, lp, cfg)
    sharded = {name: jax.device_put(w, NamedSharding(mesh, P("ep")))
               if name != "router" else w for name, w in lp.items()}

    @jax.jit
    def run(x, lp):
        return moe_block(x, lp, cfg)

    out, stats = run(x, sharded)
    assert out.shape == x.shape
    # The partitioner places the block; the result is the unsharded one.
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert int(stats["counts"].sum()) == x.shape[0] * 2

@pytest.mark.slow
def test_pipeline_transformer_trains_and_matches_single_device():
    """The REAL model under pp: loss AND grads must match a single-device
    run (VERDICT r1 weak #4 — pp must be a training capability, not a toy)."""
    import functools
    from dataclasses import replace

    from ray_tpu.models import (
        configs, init_params, loss_fn, param_logical_axes,
    )

    cfg = replace(
        configs.tiny,
        n_layers=4,
        d_model=32,
        d_ff=64,
        vocab_size=128,
        dtype=jnp.float32,
        remat=False,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                cfg.vocab_size)

    ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params, tokens, cfg)

    mesh = build_mesh(MeshConfig(pp=4))
    sharded = shard_params(params, param_logical_axes(cfg), mesh)
    pp_step = jax.jit(
        jax.value_and_grad(functools.partial(loss_fn, cfg=cfg, mesh=mesh))
    )
    pp_loss, pp_grads = pp_step(sharded, tokens)

    np.testing.assert_allclose(float(pp_loss), float(ref_loss), rtol=1e-5)
    for path, ref_leaf in jax.tree_util.tree_leaves_with_path(ref_grads):
        pp_leaf = jax.tree_util.tree_leaves_with_path(pp_grads)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(
                dict(jax.tree_util.tree_leaves_with_path(pp_grads))[path]
            )),
            np.asarray(ref_leaf),
            rtol=5e-4, atol=1e-5,
            err_msg=f"grad mismatch at {jax.tree_util.keystr(path)}",
        )

def test_pipeline_composes_with_dp():
    """pp x dp: microbatch batch dims split over dp inside the pipeline;
    loss and grads still match a single-device run."""
    import functools
    from dataclasses import replace

    from ray_tpu.models import (
        configs, init_params, loss_fn, param_logical_axes,
    )

    cfg = replace(
        configs.tiny,
        n_layers=2,
        d_model=32,
        d_ff=64,
        vocab_size=128,
        dtype=jnp.float32,
        remat=False,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                cfg.vocab_size)
    ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params, tokens, cfg)

    mesh = build_mesh(MeshConfig(dp=2, pp=2))
    sharded = shard_params(params, param_logical_axes(cfg), mesh)
    pp_loss, pp_grads = jax.jit(
        jax.value_and_grad(functools.partial(loss_fn, cfg=cfg, mesh=mesh))
    )(sharded, tokens)

    np.testing.assert_allclose(float(pp_loss), float(ref_loss), rtol=1e-5)
    ref_leaves = jax.tree_util.tree_leaves(ref_grads)
    pp_leaves = jax.tree_util.tree_leaves(jax.device_get(pp_grads))
    for r, p in zip(ref_leaves, pp_leaves):
        np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                   rtol=5e-4, atol=1e-5)


@pytest.mark.slow
def test_pipeline_composes_with_tp():
    """pp x tp: tensor-parallel weight shards inside each pipeline stage;
    loss and grads still match a single-device run."""
    import functools
    from dataclasses import replace

    from ray_tpu.models import (
        configs, init_params, loss_fn, param_logical_axes,
    )

    cfg = replace(
        configs.tiny,
        n_layers=2,
        d_model=32,
        d_ff=64,
        vocab_size=128,
        dtype=jnp.float32,
        remat=False,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                cfg.vocab_size)
    ref_loss, ref_grads = jax.value_and_grad(loss_fn)(params, tokens, cfg)

    mesh = build_mesh(MeshConfig(tp=2, pp=2))
    sharded = shard_params(params, param_logical_axes(cfg), mesh)
    pp_loss, pp_grads = jax.jit(
        jax.value_and_grad(functools.partial(loss_fn, cfg=cfg, mesh=mesh))
    )(sharded, tokens)

    np.testing.assert_allclose(float(pp_loss), float(ref_loss), rtol=1e-5)
    ref_leaves = jax.tree_util.tree_leaves(ref_grads)
    pp_leaves = jax.tree_util.tree_leaves(jax.device_get(pp_grads))
    for r, p in zip(ref_leaves, pp_leaves):
        np.testing.assert_allclose(np.asarray(p), np.asarray(r),
                                   rtol=5e-4, atol=1e-5)


def test_tp_sharded_decode_matches_single_device():
    """KV-cache prefill+decode under tensor parallelism produces the
    SAME tokens as the unsharded model (GSPMD shards heads/hidden; the
    cache follows by propagation) — the serving-on-pods layout."""
    from dataclasses import replace

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import configs, init_params, param_logical_axes
    from ray_tpu.models.generate import decode_step, init_kv_cache, prefill
    from ray_tpu.parallel import MeshConfig, build_mesh, shard_params

    devices = jax.devices()[:8]
    cfg = replace(configs.tiny, d_model=64, d_ff=128, vocab_size=128,
                  n_layers=2, n_heads=8, n_kv_heads=8, max_seq=64,
                  remat=False)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                cfg.vocab_size)

    def run(p):
        cache = init_kv_cache(cfg, 2, 48)
        logits, cache = jax.jit(
            lambda pp, t, c: prefill(pp, t, c, cfg)
        )(p, prompt, cache)
        toks = []
        step = jax.jit(lambda pp, t, c: decode_step(pp, t, c, cfg))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for _ in range(6):
            toks.append(np.asarray(tok))
            logits, cache = step(p, tok, cache)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return np.stack(toks)

    base = run(params)
    mesh = build_mesh(MeshConfig(tp=8), devices)
    sharded = shard_params(params, param_logical_axes(cfg), mesh)
    tp = run(sharded)
    np.testing.assert_array_equal(base, tp)
