"""Seeded weights made where they live: on the device, in the type they are
served in, one small program per distinct leaf shape.

`jit(init_params)` unrolls every layer's random draws into one program (36
layers of Qwen3-4B: most of a cold deploy, PERF.md section 5). Here the tree
comes from `jax.eval_shape(init_params)`, so it is the program's own, and
each stacked leaf `[L, ...]` is filled by a `lax.map` over L keys: one
layer's draw is compiled once and its float32 temporaries are one layer's.

Scales are the model's published initialisation as `init_params` has it:
d**-0.5 for the input projections, d**-0.5 * (2L)**-0.5 for the two
projections that write the residual stream, ones for the norm scales, and
the tied embedding table at the head's scale (PERF.md finding 7).
"""

from __future__ import annotations

import zlib
from typing import Dict

import jax
import jax.numpy as jnp

# Leaf name -> which scale it takes. Anything else is a norm scale (ones).
_PROJECTIONS = ("wq", "wk", "wv", "w_gate", "w_up", "router", "lm_head")
_RESIDUAL_WRITERS = ("wo", "w_down")


def seed_key(seed: int):
    """A key from any whole number: `--seed` may pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _scale(name: str, cfg) -> float:
    base = cfg.d_model ** -0.5
    if name in _PROJECTIONS:
        return base
    if name in _RESIDUAL_WRITERS:
        return base * (2 * cfg.n_layers) ** -0.5
    if name == "embed":
        return base if cfg.tie_embeddings else 1.0
    return 0.0  # a norm scale: ones


def make_params(cfg, seed: int, shardings=None) -> Dict:
    """The parameter tree of `init_params(key, cfg)` with seeded values,
    each leaf made on the device (laid out by `shardings`, a tree of the
    same shape, when given)."""
    from ray_tpu.models import init_params

    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    key = seed_key(seed)
    programs = {}

    def make(path, leaf, sharding):
        name = path[-1].key
        stacked = len(path) > 1  # under "layers": leading axis is depth
        scale = _scale(name, cfg)
        sig = (leaf.shape, str(leaf.dtype), scale, stacked, sharding)
        if sig not in programs:
            def fill(k, shape=leaf.shape, dtype=leaf.dtype):
                if scale == 0.0:
                    return jnp.ones(shape, dtype)
                if not stacked:
                    return (jax.random.normal(k, shape, jnp.float32)
                            * scale).astype(dtype)
                return jax.lax.map(
                    lambda kk: (jax.random.normal(kk, shape[1:], jnp.float32)
                                * scale).astype(dtype),
                    jax.random.split(k, shape[0]))
            programs[sig] = jax.jit(fill, out_shardings=sharding)
        leaf_key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        return programs[sig](leaf_key)

    if shardings is None:
        shardings = jax.tree.map(lambda _: None, shapes)
    return jax.tree_util.tree_map_with_path(make, shapes, shardings)
