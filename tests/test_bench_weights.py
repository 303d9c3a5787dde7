"""`bench/weights.py` and the references' `leaf_init`, held by the tests
the driver runs (bench/tests/ has the harness's own, which tier-1 does not
run): a seed's weights for the architectures the benchmark has are pinned
bit for bit, a published size may be a sequence, and the hybrid's leaves
come through Mamba-2's published rule."""

import dataclasses
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import spec  # noqa: E402
import weights  # noqa: E402


def leaf_rules_of(model, reference, **changed):
    from ray_tpu.models import configs

    cfg = dataclasses.replace(configs.get_config(model), **changed)
    doc = {"reference": reference,
           "published_extra": {"mamba_d_conv": "mamba_d_conv"}}
    return cfg, spec.leaf_rules(cfg, doc)


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update("/".join(p.key for p in path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


# sha256 over every leaf's path, type, shape and bytes of
# `weights.make_params(cfg, seed, rules)` on the CPU. The first five are
# what commit 650af8f (PR 32) made, before `weights.py` lost its table of
# names (bench/tests/test_weights.py has the same five): the routing, the
# margins and every reading of `correct` in the accepted cells hang on them.
# The last two pin the hybrid's as PR 35 first made them.
TIED_BF16 = (("tie_embeddings", True), ("dtype", jnp.bfloat16))
PINNED = {
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
    ("tiny_granite_h", "granite_hybrid", 7, ()): 
        "ad15e3acf5778f44417cbc1ba45f280605bc2beed07c597534d872c8809297cb",
    ("tiny_granite_h", "granite_hybrid", 2**31 + 12345,
     (("dtype", jnp.bfloat16),)): 
        "21f45835d20d53ec37e9767f964fc9b478becb6a547badafc54a7dbb5d074fa0",
}


@pytest.mark.parametrize("model, reference, seed, changed", PINNED)
def test_a_seeds_weights_are_pinned_bit_for_bit(model, reference, seed,
                                                changed):
    cfg, rules = leaf_rules_of(model, reference, **dict(changed))
    assert digest(weights.make_params(cfg, seed, rules)) == PINNED[
        model, reference, seed, changed]


def test_the_hybrids_leaves_come_through_the_published_rule():
    """Three stacks of unlike length under `layers`, `A_log`, `dt_bias`
    and the convolution drawn by Mamba-2's rule and not as ones (all ones:
    a state gone in two tokens, so that `correct` could not see a dropped
    state), and the same name under two kinds of layer drawn apart."""
    cfg, rules = leaf_rules_of("tiny_granite_h", "granite_hybrid")
    params = weights.make_params(cfg, 2**31 + 77, rules)
    ssm, attn, mlp = (params["layers"][k] for k in ("ssm", "attn", "mlp"))
    assert (ssm["w_in"].shape[0], attn["wq"].shape[0],
            mlp["w_gate"].shape[0]) == (3, 1, 4)
    a = np.exp(np.asarray(ssm["a_log"], np.float64))
    assert (a >= 1.0).all() and (a <= 16.0).all() and a.max() / a.min() > 2
    dt = np.log1p(np.exp(np.asarray(ssm["dt_bias"], np.float64)))
    assert (dt >= 0.001 * 0.999).all() and (dt <= 0.1 * 1.001).all()
    assert dt.max() / dt.min() > 2
    for name in ("conv_w", "conv_b"):
        conv = np.asarray(ssm[name], np.float32)
        assert np.abs(conv).max() <= 0.5 and conv.std() > 0.2
    for name in ("d_skip", "gate_norm", "norm"):
        assert (np.asarray(ssm[name]) == 1.0).all()
    for name in ("w_in", "w_dt", "w_out"):
        w = np.asarray(ssm[name], np.float32)
        assert abs(w.std() / cfg.d_model ** -0.5 - 1.0) < 0.1, name
        assert not (w[0] == w[1]).all()
    assert abs(np.asarray(params["embed"]).std() / cfg.d_model ** -0.5
               - 1.0) < 0.1                                   # tied


def test_a_published_size_may_be_a_sequence():
    """A layer pattern is a list in a configuration file and a tuple in
    the program's frozen config: equal when their items are, refused when
    not, and handed on in `dims` as a value a static argument can be."""
    doc = {"model": "tiny_granite_h", "num_hidden_layers": 4,
           "layer_types": ["mamba", "mamba", "attention", "mamba"],
           "published_extra": {"layer_types": "layer_pattern"}}
    from ray_tpu.models import configs

    cfg = configs.get_config("tiny_granite_h")
    for key, field in spec.PUBLISHED_KEYS.items():
        doc.setdefault(key, getattr(cfg, field))
    dims = spec.dims_of(spec.program_config(doc, "tpu"), doc)
    assert dims["layer_pattern"] == ("mamba", "mamba", "attention", "mamba")
    hash(tuple(sorted(dims.items())))  # as the references' jits take it
    with pytest.raises(SystemExit, match=(
            r"layer_types: file \['mamba', 'attention', 'mamba', 'mamba'\], "
            r"program \('mamba', 'mamba', 'attention', 'mamba'\)")):
        spec.program_config(dict(doc, layer_types=[
            "mamba", "attention", "mamba", "mamba"]), "tpu")
    with pytest.raises(SystemExit, match="layer_types: file"):
        spec.program_config(dict(doc, layer_types=["mamba", "attention"]),
                            "tpu")
