"""Seeded weights made where they live: on the device, in the type they are
served in, one small program per distinct leaf shape and rule.

`jit(init_params)` unrolls every layer's random draws into one program (36
layers of Qwen3-4B: most of a cold deploy, PERF.md section 5). Here the tree
comes from `jax.eval_shape(init_params)`, so it is the program's own, and
every leaf under `layers`, at whatever depth of nesting, carries a leading
stack axis of whatever length (a stack a kind of layer) and is filled by a
`lax.map` over that many keys: one layer's draw is compiled once and its
float32 temporaries are one layer's.

What is drawn is not known here. `leaf_rule(path)` gives the rule of the
leaf at `path`, the tuple of keys from the root of the tree: a hashable
tuple `(draw, *args)` whose `draw(key, shape, *args)` makes one layer's
float32 array (`spec.leaf_rules`: the `leaf_init` of the reference module
the configuration names, bench/reference/draws.py). A leaf's key folds in
its path below `layers` joined by `/`, so that two kinds' leaves of one
name do not draw alike.
"""

from __future__ import annotations

import zlib
from typing import Callable, Dict, Tuple

import jax


def seed_key(seed: int):
    """A key from any whole number: `--seed` may pass 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def fill_tree(shapes, seed: int, leaf_rule: Callable[[Tuple[str, ...]], Tuple],
              shardings=None) -> Dict:
    """`shapes` (a tree of dicts of `jax.ShapeDtypeStruct`) with seeded
    values, each leaf made on the device (laid out by `shardings`, a tree
    of the same shape, when given) by the rule `leaf_rule` gives its path."""
    key = seed_key(seed)
    programs = {}

    def make(path, leaf, sharding):
        path = tuple(p.key for p in path)
        stacked = path[0] == "layers"  # a leading stack axis
        rule = leaf_rule(path)
        sig = (leaf.shape, str(leaf.dtype), rule, stacked, sharding)
        if sig not in programs:
            draw, *args = rule

            def fill(k, shape=leaf.shape, dtype=leaf.dtype):
                if not stacked:
                    return draw(k, shape, *args).astype(dtype)
                return jax.lax.map(
                    lambda kk: draw(kk, shape[1:], *args).astype(dtype),
                    jax.random.split(k, shape[0]))
            programs[sig] = jax.jit(fill, out_shardings=sharding)
        name = "/".join(path[1:] if stacked else path)
        leaf_key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        return programs[sig](leaf_key)

    if shardings is None:
        shardings = jax.tree.map(lambda _: None, shapes)
    return jax.tree_util.tree_map_with_path(make, shapes, shardings)


def make_params(cfg, seed: int, leaf_rule, shardings=None) -> Dict:
    """The parameter tree of `init_params(key, cfg)` with seeded values."""
    from ray_tpu.models import init_params

    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    return fill_tree(shapes, seed, leaf_rule, shardings)
