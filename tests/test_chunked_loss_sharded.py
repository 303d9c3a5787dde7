"""The chunked loss under a mesh, on four of the tier-1 virtual devices:
`loss_fn` hands `chunked_lm_head_ce` the head's table whole over "fsdp"
(the table's own logical axes with "embed" left whole), which changes
how the table lies during the loss and nothing of what is computed."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, init_params, loss_fn, param_logical_axes
from ray_tpu.models.transformer import _table_split_on_model_axis
from ray_tpu.ops import softmax_cross_entropy
from ray_tpu.ops.cross_entropy import chunked_lm_head_ce
from ray_tpu.parallel import MeshConfig, build_mesh, shard_params

HEADS = {"tied": True, "untied": False}


@pytest.mark.parametrize("head", sorted(HEADS))
def test_loss_and_gradients_under_a_mesh_match_unsharded(head):
    """`tiny_qwen` with a loss chunk of 8 under fsdp=2 x tp=2: the loss
    and every gradient leaf equal those of the same step with no mesh,
    for a tied table (`embed.T`, laid `("vocab", "embed")`) and for a
    head of its own (`lm_head`, laid `("embed", "vocab")`)."""
    cfg = replace(configs.get_config("tiny_qwen"), ce_chunk=8,
                  tie_embeddings=HEADS[head])
    mesh = build_mesh(MeshConfig(fsdp=2, tp=2), jax.devices()[:4])
    params = init_params(jax.random.PRNGKey(58), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0,
                                cfg.vocab_size)
    want = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    step = jax.jit(jax.value_and_grad(
        lambda p, t: loss_fn(p, t, cfg, mesh)))
    got = step(shard_params(params, param_logical_axes(cfg), mesh), tokens)
    assert _table_split_on_model_axis(cfg, mesh)  # the rule engaged
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4, err_msg=str(path))


@pytest.mark.parametrize("axes", ["fsdp1", "no-mesh"])
def test_nothing_to_gather_leaves_the_step_as_it_was(axes):
    """A mesh whose "fsdp" is 1, and no mesh at all: the table is whole
    on its model axis already, the rule stays out, and the loss and the
    gradients are the unchunked loss's."""
    cfg = replace(configs.get_config("tiny_qwen"), ce_chunk=8)
    mesh = (build_mesh(MeshConfig(tp=2), jax.devices()[:2])
            if axes == "fsdp1" else None)
    assert not _table_split_on_model_axis(cfg, mesh)
    params = init_params(jax.random.PRNGKey(58), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0,
                                cfg.vocab_size)
    want = jax.value_and_grad(loss_fn)(params, tokens, replace(cfg, ce_chunk=0))
    if mesh is not None:
        params = shard_params(params, param_logical_axes(cfg), mesh)
    got = jax.jit(jax.value_and_grad(
        lambda p, t: loss_fn(p, t, cfg, mesh)))(params, tokens)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=1e-4)


@pytest.mark.parametrize("softcap", [0.0, 30.0])
def test_chunked_loss_matches_the_unchunked_on_the_same_inputs(softcap):
    """`chunked_lm_head_ce` against `softmax_cross_entropy` over the whole
    product: the mean loss and the gradients of the hidden states and of
    the table."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    hidden = jax.random.normal(ks[0], (4, 32, 64))
    table = jax.random.normal(ks[1], (64, 256)) * 0.2
    labels = jax.random.randint(ks[2], (4, 32), 0, 256)

    def whole(hidden, table):
        logits = hidden @ table
        if softcap:
            logits = softcap * jnp.tanh(logits / softcap)
        return softmax_cross_entropy(logits, labels).mean()

    def chunked(hidden, table):
        return chunked_lm_head_ce(hidden, table, labels, 8, softcap=softcap)

    want = jax.value_and_grad(whole, argnums=(0, 1))(hidden, table)
    got = jax.jit(jax.value_and_grad(chunked, argnums=(0, 1)))(hidden, table)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-6)
