"""Top-p's threshold found by bisection (`paged_kv._top_p_threshold`)
against the sort it replaced.

The reference below is the sampler as it stood with the sort: the row
sorted, a softmax and a `cumsum` over the sorted copy, the smallest kept
value as the cut. The engine's sampler must keep the same set and, under
one key, draw the same tokens. The two may part only where a partial sum
lands on `top_p` to within float32's rounding, which the sort's `cumsum`
decides by its summation order as well; such an entry is recognised by its
preceding mass, recomputed here in float64.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.serve.paged_kv import (
    MAX_TOP_K,
    _from_ordered_bits,
    _ordered_bits,
    _pick_tokens,
    _top_p_threshold,
)

TOP_PS = (0.1, 0.5, 0.9, 0.95, 1.0)
# Qwen3's and OLMoE's vocabularies, one between, one under MAX_TOP_K.
VOCABS = (8, 1000, 50304, 151936)


def _pick_tokens_sorted(logits, temps, top_ks, top_ps, key):
    """The sampler with the vocabulary-wide sort. Returns the tokens, the
    top-k-masked scaled logits and the cut."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    k = min(MAX_TOP_K, logits.shape[-1])
    topv = jax.lax.top_k(scaled, k)[0]
    idx = jnp.clip(top_ks - 1, 0, k - 1)
    kth = jnp.take_along_axis(topv, idx[:, None], axis=-1)
    scaled = jnp.where((top_ks > 0)[:, None] & (scaled < kth),
                       -jnp.inf, scaled)
    sorted_l = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_l, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_ps[:, None]
    thr = jnp.min(jnp.where(keep, sorted_l, jnp.inf), axis=-1,
                  keepdims=True)
    cut = jnp.where(scaled < thr, -jnp.inf, scaled)
    sampled = jax.random.categorical(key, cut, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy), scaled, thr


def _rows(kind, vocab, n, seed):
    """`n` rows of logits. flat: standard normal, what the benchmark's
    seeded weights give (unit-rms hidden rows against head rows at
    d**-0.5). peaked: one token holds 0.99 of the softmax, as a trained
    model's confident step does. ties: quarter-integer values, so that
    every value is shared by many entries and the cut falls inside a
    group of equals."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, vocab)).astype(np.float32)
    if kind == "ties":
        x = np.round(x * 4) / 4
    if kind == "peaked":
        rest = np.log(np.exp(x[:, 1:].astype(np.float64)).sum(-1))
        x[:, 0] = rest + np.log(0.99 / 0.01)
        x = rng.permuted(x, axis=-1)
    return x


def _mass_above(scaled_row):
    """For every entry of the row, the softmax mass of the entries
    strictly above its value, in float64."""
    row = scaled_row.astype(np.float64)
    weights = np.exp(row - row.max())
    values, group = np.unique(row, return_inverse=True)
    at_or_under = np.cumsum(np.bincount(group, weights, len(values)))
    return (1.0 - at_or_under / at_or_under[-1])[group]


@pytest.mark.parametrize("top_k", [0, 5, 40], ids=["k-off", "k5", "k40"])
@pytest.mark.parametrize("kind", ["flat", "peaked", "ties"])
@pytest.mark.parametrize("vocab", VOCABS)
def test_bisected_threshold_keeps_what_the_sort_keeps(vocab, kind, top_k):
    # Two rows a top_p, at temperatures 0.7 (the benchmark's) and 1.0,
    # and two greedy slots among them: one decode batch, mixed.
    temps = np.array([0.7, 1.0] * len(TOP_PS) + [0.0, 0.0], np.float32)
    top_ps = np.array([p for p in TOP_PS for _ in (0, 1)] + [0.9, 1.0],
                      np.float32)
    order = np.random.default_rng(vocab).permutation(len(temps))
    temps, top_ps = temps[order], top_ps[order]
    n = len(temps)
    logits = _rows(kind, vocab, n, seed=vocab + top_k)
    if kind == "peaked":
        # 0.99 of the softmax AFTER the temperature, in every sampled row.
        logits = logits * np.maximum(temps, 1.0)[:, None]
    top_ks = np.full((n,), top_k, np.int32)
    key = jax.random.PRNGKey(vocab * 7 + top_k)
    args = tuple(map(jnp.asarray, (logits, temps, top_ks, top_ps)))

    want, scaled, thr_sorted = jax.jit(_pick_tokens_sorted)(*args, key)
    got = jax.jit(_pick_tokens)(*args, key)
    thr = jax.jit(_top_p_threshold)(scaled, args[3])

    scaled = np.asarray(scaled)
    thr, thr_sorted = np.asarray(thr), np.asarray(thr_sorted)
    if 0 < top_k < vocab:
        assert np.isneginf(scaled).any()
    # The cut is one of the row's own values, or keeps the whole row.
    assert all(np.isneginf(thr[r, 0]) or (scaled[r] == thr[r, 0]).any()
               for r in range(n))
    kept, kept_sorted = scaled >= thr, scaled >= thr_sorted
    same = (kept == kept_sorted).all(-1)
    for r in np.nonzero(~same)[0]:
        mass = _mass_above(scaled[r])[kept[r] != kept_sorted[r]]
        assert np.abs(mass - top_ps[r]).max() <= 1e-6, (r, mass, top_ps[r])
    assert same[temps > 0].mean() >= 0.5  # the exception, not the rule
    np.testing.assert_array_equal(np.asarray(got)[same],
                                  np.asarray(want)[same])
    np.testing.assert_array_equal(np.asarray(got)[temps == 0],
                                  logits[temps == 0].argmax(-1))


def test_cut_inside_a_group_of_equals_keeps_the_whole_group():
    # Masses 0.4, then three at 0.15, then 0.05 x 3: top_p 0.5 is crossed
    # by the first of the three equals, and all three stay.
    probs = np.array([0.05, 0.15, 0.4, 0.15, 0.05, 0.15, 0.05], np.float64)
    scaled = jnp.asarray(np.log(probs)[None], jnp.float32)
    thr = _top_p_threshold(scaled, jnp.array([0.5], jnp.float32))
    np.testing.assert_array_equal(np.asarray(scaled >= thr)[0],
                                  probs >= 0.15)
    # Just under the first token's mass the cut is the first token alone.
    thr = _top_p_threshold(scaled, jnp.array([0.39], jnp.float32))
    np.testing.assert_array_equal(np.asarray(scaled >= thr)[0], probs == 0.4)


def test_ordered_bits_order_float32_and_invert():
    x = jnp.array([-jnp.inf, -3.4e38, -2.0, -1.0, -1.2e-38, 0.0, 1.2e-38,
                   1.0, 2.0, 3.4e38, jnp.inf], jnp.float32)
    u = np.asarray(_ordered_bits(x)).astype(np.int64)
    assert (np.diff(u) > 0).all()
    back = np.asarray(_from_ordered_bits(_ordered_bits(x)))
    np.testing.assert_array_equal(back.view(np.uint32),
                                  np.asarray(x).view(np.uint32))
    # Equal floats, equal images.
    zeros = _ordered_bits(jnp.array([-0.0, 0.0], jnp.float32))
    assert zeros[0] == zeros[1]
