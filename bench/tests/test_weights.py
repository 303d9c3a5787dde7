"""bench/weights.py knows a tree, a seed and `path -> rule`, and no leaf by
name: stacks of unlike length under `layers` at any nesting, a rule that is
neither a normal draw nor a one, and, for the two architectures the
benchmark has, the parent's weights bit for bit."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spec
import weights
from reference.draws import normal, ones

F32 = jnp.float32


# Mamba-2's published initialisation (arXiv:2405.21060 and its modelling
# code), as a throwaway rule set: A uniform in [1, 16] and kept as its log,
# dt log-uniform in [0.001, 0.1] and kept as its inverse softplus, the
# depthwise convolution uniform in +-1/sqrt(kernel).
def log_of_uniform(key, shape, lo, hi):
    return jnp.log(jax.random.uniform(key, shape, F32, lo, hi))


def inverse_softplus_of_log_uniform(key, shape, lo, hi):
    dt = jnp.exp(jax.random.uniform(key, shape, F32, np.log(lo), np.log(hi)))
    return dt + jnp.log(-jnp.expm1(-dt))


def uniform(key, shape, bound):
    return jax.random.uniform(key, shape, F32, -bound, bound)


D, INNER, HEADS, KERNEL = 16, 32, 4, 4
RULES = {
    ("layers", "ssm", "w_in"): (normal, D ** -0.5),
    ("layers", "ssm", "a_log"): (log_of_uniform, 1.0, 16.0),
    ("layers", "ssm", "dt_bias"): (inverse_softplus_of_log_uniform,
                                   0.001, 0.1),
    ("layers", "ssm", "conv_w"): (uniform, KERNEL ** -0.5),
    ("layers", "ssm", "wo"): (normal, D ** -0.5),
    ("layers", "attn", "wq"): (normal, D ** -0.5),
    ("layers", "attn", "wo"): (normal, D ** -0.5),
    ("embed",): (normal, 1.0),
    ("final_norm",): (ones,),
}


def two_stacks(dtype):
    def leaf(*shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    return {
        "embed": leaf(64, D),
        "final_norm": leaf(D),
        "layers": {
            "ssm": {"w_in": leaf(3, D, INNER), "a_log": leaf(3, HEADS),
                    "dt_bias": leaf(3, HEADS),
                    "conv_w": leaf(3, INNER, KERNEL), "wo": leaf(3, D, D)},
            "attn": {"wq": leaf(1, D, INNER), "wo": leaf(1, D, D)},
        },
    }


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_two_stacks_of_unlike_length_under_a_published_state_space_rule_set(
        dtype):
    shapes = two_stacks(dtype)
    params = weights.fill_tree(shapes, 2**31 + 77, RULES.__getitem__)
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    for want, got in zip(jax.tree.leaves(shapes), jax.tree.leaves(params)):
        assert (got.shape, got.dtype) == (want.shape, want.dtype)
    ssm, attn = params["layers"]["ssm"], params["layers"]["attn"]
    # The draws come through their functions (bf16 rounds a value by up
    # to 2**-8 of itself).
    slack = 1.0 if dtype == jnp.float32 else 1.01
    a = np.exp(np.asarray(ssm["a_log"], np.float64))
    assert (a >= 1.0 / slack).all() and (a <= 16.0 * slack).all()
    assert a.max() / a.min() > 2.0  # a draw, not a constant
    dt = np.log1p(np.exp(np.asarray(ssm["dt_bias"], np.float64)))
    assert (dt >= 0.001 / slack).all() and (dt <= 0.1 * slack).all()
    conv = np.asarray(ssm["conv_w"], np.float32)
    assert np.abs(conv).max() <= 0.5 and conv.std() > 0.2
    assert (np.asarray(params["final_norm"], np.float32) == 1.0).all()
    # A stack's layers differ from each other, in every leaf of it.
    for leaf in ssm.values():
        layers = np.asarray(leaf, np.float32)
        assert not (layers[0] == layers[1]).all()
        assert not (layers[1] == layers[2]).all()
    # Two leaves of one name, one shape and one rule under two kinds of
    # layer do not draw alike: the key folds in the path, not the name.
    assert ssm["wo"].shape[1:] == attn["wo"].shape[1:]
    assert not (np.asarray(ssm["wo"][0], np.float32)
                == np.asarray(attn["wo"][0], np.float32)).all()
    # The same seed gives the same weights, another seed others.
    again = weights.fill_tree(shapes, 2**31 + 77, RULES.__getitem__)
    other = weights.fill_tree(shapes, 2**31 + 78, RULES.__getitem__)
    for x, y, z in zip(*map(jax.tree.leaves, (params, again, other))):
        assert (np.asarray(x, np.float32) == np.asarray(y, np.float32)).all()
    assert not (np.asarray(params["embed"], np.float32)
                == np.asarray(other["embed"], np.float32)).all()


def programs_compiled(monkeypatch, make) -> int:
    """How many fill programs `make()` hands to `jax.jit`; none runs."""
    made = []

    def jit(fn, **kw):
        made.append(fn)
        return lambda key: None

    with monkeypatch.context() as m:
        m.setattr(jax, "jit", jit)
        make()
    return len(made)


def test_equal_rules_over_equal_shapes_share_one_program(monkeypatch):
    shapes = two_stacks(jnp.float32)
    shapes["layers"]["ssm"]["w_gate"] = shapes["layers"]["ssm"]["w_in"]
    rules = dict(RULES)
    rules["layers", "ssm", "w_gate"] = (normal, D ** -0.5)
    n = programs_compiled(monkeypatch, lambda: weights.fill_tree(
        shapes, 7, rules.__getitem__))
    assert n == len(RULES)  # the added leaf rides on w_in's program


def leaf_rules_of(model: str, reference: str, **changed):
    from dataclasses import replace

    from ray_tpu.models import configs

    spec.named_module({"reference": reference}, "reference")  # imported now
    cfg = replace(configs.get_config(model), **changed)
    return cfg, spec.leaf_rules(cfg, {"reference": reference})


# A cold deploy compiles one fill program a distinct (shape, type, rule,
# stacked, sharding), and `setup_s` pays each: as many as the parent's
# bench/weights.py made (counted on commit 650af8f, the same way).
@pytest.mark.parametrize("model, reference, parents", [
    ("qwen3-4b", "qwen3", 9), ("olmoe-1b-7b", "olmoe", 9)])
def test_a_deploy_compiles_as_many_fill_programs_as_the_parent(
        monkeypatch, model, reference, parents):
    cfg, rules = leaf_rules_of(model, reference)
    assert programs_compiled(monkeypatch, lambda: weights.make_params(
        cfg, 7, rules)) == parents


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update("/".join(p.key for p in path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 over every leaf's path, type, shape and bytes of
# `weights.make_params(cfg, seed)` as commit 650af8f (PR 32, this PR's
# parent) made them on the CPU, before `weights.py` lost its table of names:
# the routing, the margins and every reading of `correct` hang on them.
# The last is Qwen3-4B's case, which `tiny_qwen` is not: a tied table,
# served in bfloat16.
TIED_BF16 = (("tie_embeddings", True), ("dtype", jnp.bfloat16))
PARENTS = {
    ("tiny_qwen", "qwen3", 7, ()):
        "cd6813d2dd2b34d7bf26eb650777b2df09e6b528908d6e733ddf5121a3b8003e",
    ("tiny_qwen", "qwen3", 2**31 + 12345, ()):
        "c2f24660d7557daa9db11beea751c56595c49c4f52701790c94dd03acb9f740b",
    ("tiny_olmoe", "olmoe", 7, ()):
        "1f42faf4c4901d22996fdb873f021788730bd7d1789fc68f97703871fbbc8ff0",
    ("tiny_olmoe", "olmoe", 2**31 + 12345, ()):
        "92d8b3a4b537e053de166d97886dbe7143f980da60b81ad6da07a7ba4fdd2a3c",
    ("tiny_qwen", "qwen3", 2**31 + 12345, TIED_BF16):
        "affeed49c7a0b77cfd0da599a36a93197b783f9ea785a9c5bce758b7fb74b226",
}


@pytest.mark.parametrize("model, reference, seed, changed", PARENTS)
def test_weights_are_the_parents_bit_for_bit(model, reference, seed, changed):
    cfg, rules = leaf_rules_of(model, reference, **dict(changed))
    assert digest(weights.make_params(cfg, seed, rules)) == PARENTS[
        model, reference, seed, changed]
