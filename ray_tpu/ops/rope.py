"""Rotary position embeddings (RoPE).

Pure jnp — XLA fuses the elementwise rotation into adjacent matmuls, so a
Pallas kernel buys nothing here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def yarn_mscale(factor: float, coefficient: float) -> float:
    """YaRN's attention temperature: 0.1 * coefficient * ln(factor) + 1
    (1 for a factor of 1 or less, or a coefficient of 0)."""
    if factor <= 1.0 or not coefficient:
        return 1.0
    return 0.1 * coefficient * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's blend of frequencies `[dim // 2]` (arXiv:2309.00071, as
    DeepSeek-V3's modelling code has it): pair `i` of plain rope turns
    `f_i = theta**(-2i/dim)` a position. Pairs that turn more than
    `beta_fast` times over the `original_max` positions keep `f_i`, pairs
    that turn fewer than `beta_slow` times take `f_i / factor`, and those
    between blend linearly in `i`."""
    half = dim // 2
    f = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def pair_of(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 10000.0,
                     dtype=jnp.float32, inv_freq=None, magnitude: float = 1.0):
    """Precompute cos/sin tables: [max_seq, head_dim//2]. `inv_freq`
    replaces plain rope's frequencies (`yarn_inv_freq`), and `magnitude`
    multiplies both tables."""
    if inv_freq is None:
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
        )
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    if magnitude != 1.0:
        return ((jnp.cos(freqs) * magnitude).astype(dtype),
                (jnp.sin(freqs) * magnitude).astype(dtype))
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: jax.Array = None):
    """Rotate pairs of features. x: [batch, seq, heads, head_dim].

    positions: optional [batch, seq] global positions (for sequence-sharded
    blocks pass the block's global offsets); defaults to arange(seq).
    """
    b, l, h, d = x.shape
    if positions is None:
        cos_p = cos[:l][None, :, None, :]
        sin_p = sin[:l][None, :, None, :]
    else:
        cos_p = cos[positions][:, :, None, :]
        sin_p = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    rot1 = x1 * cos_p - x2 * sin_p
    rot2 = x2 * cos_p + x1 * sin_p
    return jnp.concatenate([rot1, rot2], axis=-1).astype(x.dtype)
