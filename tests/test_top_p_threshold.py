"""Top-p's and top-k's cuts found by bisection
(`paged_kv._smallest_passing`) against the sort and `lax.top_k` they
replaced.

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
    _bisection_passes,
    _from_ordered_bits,
    _ordered_bits,
    _pick_tokens,
    _smallest_passing,
    _top_k_floor,
    _top_p_threshold,
)

TOP_PS = (0.1, 0.5, 0.9, 0.95, 1.0)
# Qwen3's and OLMoE's vocabularies, one between, one under MAX_TOP_K.
VOCABS = (8, 1000, 50304, 151936)


def _pick_tokens_sorted(logits, temps, top_ks, top_ps, key):
    """The sampler with the vocabulary-wide sort and `lax.top_k`. Returns
    the tokens, the top-k-masked scaled logits and the cut."""
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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("top_k", [0, 5, 40], ids=["k-off", "k5", "k40"])
@pytest.mark.parametrize("kind", ["flat", "peaked", "ties"])
@pytest.mark.parametrize("vocab", VOCABS)
def test_bisected_threshold_keeps_what_the_sort_keeps(vocab, kind, top_k,
                                                      dtype):
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
    # The logits as a head of `dtype` hands them over: the reference sees
    # the same rounded values, in float32.
    args = (jnp.asarray(logits).astype(dtype),
            *map(jnp.asarray, (temps, top_ks, top_ps)))
    logits = np.asarray(args[0].astype(jnp.float32))

    want, scaled, thr_sorted = jax.jit(_pick_tokens_sorted)(*args, key)
    got = jax.jit(_pick_tokens)(*args, key)
    # The cut alone, as `_pick_tokens` takes it: from the logits in their
    # own type, carried over the temperature, above top-k's floor.
    thr = jax.jit(lambda lg, t, ks, ps: _top_p_threshold(
        lg, ps, lambda x: x.astype(jnp.float32) / jnp.maximum(t, 1e-6)[:, None],
        _top_k_floor(lg, ks)))(*args)

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


def _loops(jaxpr):
    """Every loop of a jaxpr, nested ones too: a `fori_loop` of known
    bounds is a `scan` of that length and counts as its trips, one whose
    bound is data is a `while` and counts as None."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn.params["length"])
        elif eqn.primitive.name == "while":
            found.append(None)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_loops(sub))
    return found


@pytest.mark.parametrize("dtype,trips", [
    (jnp.bfloat16, 16), (jnp.float16, 16), (jnp.float32, 32)],
    ids=["bf16", "f16", "f32"])
def test_bisection_makes_as_many_trips_as_the_logits_have_bits(dtype, trips):
    n, vocab = 4, 1000
    args = (jnp.zeros((n, vocab), dtype), jnp.ones((n,), jnp.float32),
            jnp.zeros((n,), jnp.int32), jnp.ones((n,), jnp.float32),
            jax.random.PRNGKey(0))
    # Top-p's loop has the type's bits for its bound, in the program.
    assert _loops(jax.make_jaxpr(_top_p_threshold)(
        args[0], args[3]).jaxpr) == [trips]
    # Top-k's comes first and its bound is data: the same number where a
    # row asks for a top-k and none where no row does.
    assert _loops(jax.make_jaxpr(_pick_tokens)(*args).jaxpr) == [None, trips]
    assert _bisection_passes(dtype) == trips
    assert int(_bisection_passes(dtype, jnp.asarray(True))) == trips
    assert int(_bisection_passes(dtype, jnp.asarray(False))) == 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["flat", "ties"])
@pytest.mark.parametrize("vocab", VOCABS)
def test_kth_largest_is_what_top_k_gives(vocab, kind, dtype):
    ks = np.array([1, 5, 40, 64, 0], np.int32)
    logits = jnp.asarray(_rows(kind, vocab, len(ks), seed=vocab)).astype(dtype)
    floor = np.asarray(jax.jit(_top_k_floor)(logits, jnp.asarray(ks))
                       .astype(jnp.float32))[:, 0]
    sorted_rows = np.sort(np.asarray(logits.astype(jnp.float32)))[:, ::-1]
    top = np.asarray(jax.lax.top_k(logits.astype(jnp.float32),
                                   min(MAX_TOP_K, vocab))[0])
    for r, k in enumerate(ks):
        if k == 0:  # a row that asks for none keeps everything
            assert np.isneginf(floor[r])
        elif k > vocab:  # past the vocabulary: the whole row stays
            assert floor[r] <= sorted_rows[r, -1]
        else:
            assert floor[r] == top[r, k - 1] == sorted_rows[r, k - 1]
            # Ties at the k-th stay: `>= floor` keeps at least k.
            kept = (sorted_rows[r] >= floor[r]).sum()
            assert kept >= k and (sorted_rows[r] > floor[r]).sum() < k
            if kind == "ties" and vocab >= 1000 and k > 1:
                assert kept > k


def test_a_row_draws_in_a_mixed_batch_what_it_draws_alone():
    # One slot asks for a top-k, the others none: top-k's passes run for
    # the whole batch, and no row's draw may notice. A draw
    # depends on the key and the row's place, so a row "alone" is the
    # batch with every other row's top-k and top-p switched off.
    n, vocab = 6, 1000
    logits = jnp.asarray(_rows("flat", vocab, n, seed=7), jnp.bfloat16)
    temps = jnp.full((n,), 0.7, jnp.float32)
    top_ps = jnp.asarray([0.9, 0.5, 1.0, 0.95, 0.9, 0.1], jnp.float32)
    top_ks = np.zeros(n, np.int32)
    top_ks[2] = 5
    pick = jax.jit(_pick_tokens)
    different = 0
    for seed in range(8):
        key = jax.random.PRNGKey(seed)
        mixed = np.asarray(pick(logits, temps, jnp.asarray(top_ks), top_ps,
                                key))
        none = np.asarray(pick(logits, temps, jnp.zeros(n, jnp.int32),
                               top_ps, key))
        for r in range(n):
            alone_ks = np.zeros(n, np.int32)
            alone_ks[r] = top_ks[r]
            alone_ps = np.ones(n, np.float32)
            alone_ps[r] = top_ps[r]
            alone = np.asarray(pick(logits, temps, jnp.asarray(alone_ks),
                                    jnp.asarray(alone_ps), key))
            assert mixed[r] == alone[r], (seed, r)
        # The rows that ask for no top-k draw what the batch without any
        # draws (top-k's passes not made), and the one that asks is held
        # to its five largest.
        np.testing.assert_array_equal(np.delete(mixed, 2), np.delete(none, 2))
        five = np.argsort(-np.asarray(logits[2].astype(jnp.float32)))[:5]
        assert mixed[2] in five
        different += mixed[2] != none[2]
    assert different  # top-k did cut row 2's draw in some seed


def test_counting_bisection_with_a_floor_starts_at_the_floor():
    # `_smallest_passing` never returns under its floor, whatever passes.
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0]], jnp.bfloat16)
    floor = jnp.asarray([[1.0]], jnp.bfloat16)
    cut = _smallest_passing(logits, lambda x: 1.0, jnp.asarray([[4.0]]),
                            floor)
    assert cut.dtype == jnp.bfloat16 and float(cut[0, 0]) == 1.0
    cut = _smallest_passing(logits, lambda x: 1.0, jnp.asarray([[1.0]]),
                            floor)
    assert float(cut[0, 0]) == 3.0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16],
                         ids=["f32", "bf16", "f16"])
def test_ordered_bits_order_every_width_and_invert(dtype):
    info = jnp.finfo(dtype)
    x = jnp.asarray([-jnp.inf, float(info.min), -2.0, -1.0,
                     -float(info.tiny), 0.0, float(info.tiny), 1.0, 2.0,
                     float(info.max), jnp.inf], dtype)
    u = _ordered_bits(x)
    assert u.dtype.itemsize == x.dtype.itemsize
    assert (np.diff(np.asarray(u).astype(np.int64)) > 0).all()
    back = _from_ordered_bits(u, dtype)
    np.testing.assert_array_equal(
        np.asarray(jax.lax.bitcast_convert_type(back, u.dtype)),
        np.asarray(jax.lax.bitcast_convert_type(x, u.dtype)))
    zeros = _ordered_bits(jnp.asarray([-0.0, 0.0], dtype))
    assert zeros[0] == zeros[1]
