"""The draws the leaf tables of the references here are made of.

A reference's `leaf_init(path, dims)` returns a rule for one leaf of the
program's parameter tree: a tuple `(draw, *args)` of a module-level
function `draw(key, shape, *args) -> float32 [shape]`, which makes ONE
layer's array (the harness maps it over a stack and casts it), and the
plain numbers it takes. A tuple of a function and numbers hashes by value,
so that equal rules over equal shapes share one compiled program. A
reference whose initialisation is not one of these two (a uniform or a
log-uniform draw passed through a function) defines that draw beside its
`leaf_init`, in its own file.
"""

import jax
import jax.numpy as jnp


def normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def ones(key, shape):
    return jnp.ones(shape, jnp.float32)
