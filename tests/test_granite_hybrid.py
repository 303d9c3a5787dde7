"""The hybrid decoder (Mamba-2 layers among attention layers, Granite's
multipliers, no position embedding) at toy size, `tiny_granite_h`, on the
CPU with seeded float32 weights: `transformer.forward`, the mixer's two
forms, the engine's step programs and the engine itself against the
benchmark's plain reference `bench/reference/granite_hybrid.py`, which
walks the recurrence a token at a time.

Tolerances: both sides compute in float32 here and differ by the order of
accumulation alone (1e-7 of a logit's size was read). Each limit below is
1e-4 relative or tighter: a thousand times that, and a hundred times under
what bfloat16 anywhere on the path or a wrong term would give. The one
bfloat16 run is held to the limits `bench/serve_cell.py` holds a served
model to."""

import os
import sys
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, forward, init_params, loss_fn, mamba2
from ray_tpu.serve import paged_kv
from ray_tpu.serve.llm import ContinuousBatchingEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import spec  # noqa: E402
import weights  # noqa: E402
from reference import granite_hybrid as reference  # noqa: E402

CFG = configs.get_config("tiny_granite_h")
EXTRA = ("layer_pattern", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
         "mamba_d_conv", "mamba_n_groups", "mamba_expand", "mamba_chunk_size",
         "mamba_conv_bias", "mamba_proj_bias", "embedding_multiplier",
         "attention_multiplier", "residual_multiplier", "logits_scaling",
         "position_embedding_type")
FILE = {"reference": "granite_hybrid",
        "published_extra": {name: name for name in EXTRA}}
DIMS = spec.dims_of(CFG, FILE)
TOLERANCE = 1e-4
CHUNK = 16           # the engine's prefill chunk: two of the mixer's blocks
# Shorter than a chunk, exactly one, and several with a remainder.
PROMPT_LENS = (5, CHUNK, 2 * CHUNK + 9)


def seeded_params(cfg=CFG, seed=7):
    """The benchmark's weights: every leaf by the reference's `leaf_init`,
    the norm scales drawn too (ones would hide a norm over the wrong
    extent behind its scale's symmetry)."""
    params = weights.make_params(cfg, seed, spec.leaf_rules(cfg, FILE))
    key = jax.random.PRNGKey(seed + 1)
    for i, (stack, name) in enumerate((("ssm", "norm"), ("ssm", "gate_norm"),
                                       ("ssm", "d_skip"),
                                       ("attn", "attn_norm"),
                                       ("mlp", "mlp_norm"))):
        leaf = params["layers"][stack][name]
        params["layers"][stack][name] = jax.random.uniform(
            jax.random.fold_in(key, i), leaf.shape, jnp.float32, 0.5, 1.5
        ).astype(leaf.dtype)
    return params


@pytest.fixture(scope="module")
def params():
    return seeded_params()


def prompt_of(n, seed=0):
    return [int(t) for t in np.random.default_rng(100 * seed + n).integers(
        0, CFG.vocab_size, n)]


def reference_logits(params, tokens):
    """[T, vocab] float32 from the reference's full forward pass."""
    hidden = reference.hidden_layerwise(params, jnp.asarray(tokens, jnp.int32),
                                        DIMS)
    return np.asarray(reference.logits_rows(params, hidden, DIMS))


def rel_rms(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def margins(ref_rows, tokens):
    """How far the reference's logit of each served token lies under the
    reference's largest, in units of the row's rms."""
    return [float((row.max() - row[t]) / np.sqrt(np.mean(row ** 2)))
            for row, t in zip(ref_rows, tokens)]


def engine_for(params, cfg=CFG, **kw):
    kw = {"num_slots": 3, "max_len": 96, "prefill_chunk": CHUNK, **kw}
    return ContinuousBatchingEngine(params, cfg, **kw)


def check_served(params, prompt, served, limit=TOLERANCE):
    ref = reference_logits(params, prompt + served[:-1])
    worst = max(margins(ref[len(prompt) - 1:], served))
    assert worst < limit, (len(prompt), served, worst)


# -- the model ---------------------------------------------------------------

def test_the_named_config_is_the_published_model():
    big = configs.get_config("granite-4.0-h-micro")
    shapes = jax.eval_shape(lambda k: init_params(k, big),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 3_191_396_096                        # 3.19 B
    assert big.n_layers == len(big.layer_pattern) == 40
    assert [i for i, t in enumerate(big.layer_pattern) if t == "attention"] == [
        5, 15, 25, 35]
    stacks = shapes["layers"]
    assert stacks["ssm"]["w_in"].shape == (36, 2048, 8448)
    assert stacks["ssm"]["w_dt"].shape == (36, 2048, 64)  # in_proj: 8512
    assert stacks["attn"]["wq"].shape == (4, 2048, 2048)
    assert stacks["mlp"]["w_gate"].shape == (40, 2048, 8192)
    assert "lm_head" not in shapes                        # tied
    assert big.attention_scale == 0.015625
    assert configs.get_config("qwen3-4b").attention_scale == 128 ** -0.5
    # The toy config: both kinds, two Mamba layers in a row, and a block
    # that the test prompts span several times.
    assert CFG.layer_pattern[:2] == ("mamba", "mamba")
    assert "attention" in CFG.layer_pattern
    assert max(PROMPT_LENS) > 4 * CFG.mamba_chunk_size


@pytest.mark.parametrize("length", [5, 8, 21, 33])
def test_forward_logits_against_the_reference(params, length):
    """Whole sequences through `transformer.forward`'s chunked form, under,
    at and over the mixer's block of 8 with a remainder."""
    tokens = prompt_of(length)
    logits, _ = jax.jit(lambda p, t: forward(p, t, CFG))(
        params, jnp.asarray([tokens]))
    ref = reference_logits(params, tokens)
    assert rel_rms(np.asarray(logits[0]), ref) < TOLERANCE


def test_loss_and_gradients_against_the_reference(params):
    tokens = jnp.asarray(prompt_of(34))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, tokens[None], CFG)))(params)
    ref_loss, ref_grads = reference.loss_and_grads(params, tokens, DIMS)
    assert abs(float(loss) - float(ref_loss)) < TOLERANCE * float(ref_loss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree.leaves(ref_grads)) == 21
    for (path, got), want in zip(flat, jax.tree.leaves(ref_grads)):
        scale = float(jnp.abs(want).max())
        assert scale > 0, path
        assert float(jnp.abs(got - want).max()) < TOLERANCE * scale, path


def mixer_inputs(length, seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    heads, p, n, g = (CFG.mamba_n_heads, CFG.mamba_d_head, CFG.mamba_d_state,
                      CFG.mamba_n_groups)
    x = jax.random.normal(keys[0], (2, length, heads, p))
    dt = jax.nn.softplus(jax.random.normal(keys[1], (2, length, heads)))
    a = -jnp.exp(jax.random.normal(keys[2], (heads,)))
    b = jax.random.normal(keys[3], (2, length, g, n))
    c = jax.random.normal(keys[4], (2, length, g, n))
    state = jax.random.normal(keys[5], (2, heads, p, n))
    return x, dt, a, b, c, state


@pytest.mark.parametrize("length", [3, 8, 19, 32])
def test_the_chunked_form_is_the_one_token_form(length):
    """`ssd_chunked` over a sequence from a given state against `ssd_step`
    a token at a time, outputs and final state, and against itself over
    the sequence cut in two with the state handed on."""
    x, dt, a, b, c, state = mixer_inputs(length)
    y, final = mamba2.ssd_chunked(x, dt, a, b, c, state,
                                  CFG.mamba_chunk_size)
    s, rows = state, []
    for t in range(length):
        y_t, s = mamba2.ssd_step(x[:, t], dt[:, t], a, b[:, t], c[:, t], s)
        rows.append(y_t)
    stepped = jnp.stack(rows, axis=1)
    scale = float(jnp.abs(stepped).max())
    assert float(jnp.abs(y - stepped).max()) < TOLERANCE * scale
    assert float(jnp.abs(final - s).max()) < TOLERANCE * float(
        jnp.abs(s).max())
    cut = length // 3 + 1
    y1, mid = mamba2.ssd_chunked(x[:, :cut], dt[:, :cut], a, b[:, :cut],
                                 c[:, :cut], state, CFG.mamba_chunk_size)
    if cut < length:
        y2, end = mamba2.ssd_chunked(x[:, cut:], dt[:, cut:], a, b[:, cut:],
                                     c[:, cut:], mid, CFG.mamba_chunk_size)
        assert float(jnp.abs(jnp.concatenate([y1, y2], 1) - y).max()
                     ) < TOLERANCE * scale
        assert float(jnp.abs(end - final).max()) < TOLERANCE * float(
            jnp.abs(final).max())


# -- the step programs -------------------------------------------------------

SLOTS, MAX_LEN, PAGE = 3, 64, 8
PAGES_PER_SLOT = MAX_LEN // PAGE


def fresh_cache(poison=0.0):
    cache = paged_kv.init_paged_cache(CFG, SLOTS, SLOTS * PAGES_PER_SLOT + 1,
                                      PAGE, PAGES_PER_SLOT)
    table = np.zeros((SLOTS, PAGES_PER_SLOT), np.int32)
    for s in range(SLOTS):
        table[s] = 1 + s * PAGES_PER_SLOT + np.arange(PAGES_PER_SLOT)
    cache["block_tables"] = jnp.asarray(table)
    cache["rec"] = jax.tree.map(lambda a: a + jnp.asarray(poison, a.dtype),
                                cache["rec"])
    return cache


@pytest.fixture(scope="module")
def programs():
    prefill = jax.jit(
        lambda p, t, n, s, o, k, v, ln, bt, rec, count:
        paged_kv.prefill_chunk_paged(p, t, n, s, o, k, v, ln, bt, CFG,
                                     MAX_LEN, None, None, rec, count))
    decode = jax.jit(
        lambda p, t, k, v, ln, a, bt, rec, count: paged_kv.decode_paged(
            p, t, k, v, ln, a, bt, None, None, None, None, CFG, MAX_LEN,
            None, None, rec, count))
    return prefill, decode


SLOT_AXIS = {"state": 1, "conv": 1}


def rows(cache, name, slot):
    """A slot's rows of every Mamba layer in the recurrent pool."""
    return jnp.take(cache["rec"][name], slot, axis=SLOT_AXIS[name])


def prefill_prompt(prefill, params, cache, slot, prompt, filler=0):
    """`prompt` into `slot` chunk by chunk; padding rows hold `filler`."""
    count = paged_kv.init_ssm_counters()
    k, v, lengths, rec = (cache[n] for n in ("k", "v", "lengths", "rec"))
    for off in range(0, len(prompt), CHUNK):
        chunk = prompt[off:off + CHUNK]
        padded = np.full((1, CHUNK), filler, np.int32)
        padded[0, :len(chunk)] = chunk
        logits, k, v, lengths, rec, count = prefill(
            params, padded, np.int32(len(chunk)), np.int32(slot),
            np.int32(off), k, v, lengths, cache["block_tables"], rec, count)
    return logits, dict(cache, k=k, v=v, lengths=lengths, rec=rec), count


def test_the_pools_are_the_models_own(params):
    cache = fresh_cache()
    # Pages for the attention layer alone, a row's heads side by side (a
    # head of 16 does not fill a tile's lanes); a state row a Mamba layer.
    assert cache["k"].shape == (1, SLOTS * PAGES_PER_SLOT + 1, PAGE,
                                CFG.n_kv_heads * CFG.head_dim)
    assert cache["rec"]["state"].shape == (3, SLOTS, 8, 16, 16)
    assert cache["rec"]["state"].dtype == jnp.float32
    assert cache["rec"]["conv"].shape == (3, SLOTS, 3, 128 + 2 * 16)
    # A model without recurrent layers has no second pool.
    dense = paged_kv.init_paged_cache(configs.get_config("tiny_qwen"), 2, 9,
                                      8, 4)
    assert sorted(dense) == ["block_tables", "k", "lengths", "v"]


@pytest.mark.parametrize("length", PROMPT_LENS)
def test_a_chunks_padding_advances_nothing(params, programs, length):
    """A first chunk starts from zeros whatever the slot's row held, a
    prompt of several chunks carries state and convolution inputs across
    them, and the padding rows of the last chunk neither advance the
    state nor enter the saved convolution inputs: the state the chunked
    prefill leaves is the one-token form's over the real tokens, whatever
    the padding holds and whatever the last tenant left."""
    prefill, decode = programs
    prompt = prompt_of(length)
    logits, cache, count = prefill_prompt(prefill, params, fresh_cache(),
                                          1, prompt)
    ref = reference_logits(params, prompt)
    assert rel_rms(np.asarray(logits[0]), ref[-1]) < TOLERANCE
    other, dirty, _ = prefill_prompt(prefill, params, fresh_cache(poison=3.0),
                                     1, prompt, filler=201)
    assert rel_rms(np.asarray(other[0]), ref[-1]) < TOLERANCE
    for name in ("state", "conv"):
        got, again = rows(cache, name, 1), rows(dirty, name, 1)
        assert float(jnp.abs(got - again).max()) <= 1e-6 * float(
            jnp.abs(got).max()), name
        # The other slots' rows were not touched.
        assert (np.asarray(rows(dirty, name, 0)) == 3.0).all()
    counted = jax.device_get(count)
    chunks = -(-length // CHUNK)
    assert counted["prefill_tokens_valid"] == length
    assert counted["prefill_tokens_computed"] == chunks * CHUNK
    assert counted["calls"] == chunks
    # The same state by the one-token form: decode the prompt's tokens one
    # after another into another slot (its first token through a chunk).
    _, stepped, _ = prefill_prompt(prefill, params, fresh_cache(), 2,
                                   prompt[:1])
    k, v, lengths, rec = (stepped[n] for n in ("k", "v", "lengths", "rec"))
    active = jnp.asarray([False, False, True])
    count = paged_kv.init_ssm_counters()
    for token in prompt[1:]:
        tokens = jnp.zeros((SLOTS,), jnp.int32).at[2].set(token)
        _, k, v, lengths, rec, count = decode(
            params, tokens, k, v, lengths, active, stepped["block_tables"],
            rec, count)
    for name in ("state", "conv"):
        want, got = rows({"rec": rec}, name, 2), rows(cache, name, 1)
        assert float(jnp.abs(got - want).max()) < TOLERANCE * float(
            jnp.abs(want).max()), name


def test_decode_leaves_idle_and_prefilling_slots_as_they_were(params,
                                                              programs):
    """The decode program runs over every slot: one that is idle, or whose
    prompt is half way through its chunks, keeps its state and its
    convolution inputs bit for bit, and the half-way prompt then finishes
    as if no step had run."""
    prefill, decode = programs
    long_prompt, short = prompt_of(2 * CHUNK + 9), prompt_of(7)
    _, cache, _ = prefill_prompt(prefill, params, fresh_cache(poison=2.0), 0,
                                 short)
    # Slot 1: the first chunk of the long prompt only.
    _, cache, _ = prefill_prompt(prefill, params, cache, 1,
                                 long_prompt[:CHUNK])
    before = jax.tree.map(np.asarray, cache["rec"])
    k, v, lengths, rec = (cache[n] for n in ("k", "v", "lengths", "rec"))
    active = jnp.asarray([True, False, False])
    count = paged_kv.init_ssm_counters()
    tokens = jnp.asarray([short[-1], 9, 9], jnp.int32)
    for _ in range(3):
        tokens, k, v, lengths, rec, count = decode(
            params, tokens, k, v, lengths, active, cache["block_tables"],
            rec, count)
    for name in ("state", "conv"):
        now, was = (np.moveaxis(np.asarray(a), SLOT_AXIS[name], 1)
                    for a in (rec[name], before[name]))
        assert (now[:, 1:] == was[:, 1:]).all(), name
        assert not (now[:, 0] == was[:, 0]).all(), name
    assert list(np.asarray(lengths)) == [len(short) + 3, CHUNK, 0]
    counted = jax.device_get(count)
    assert counted["decode_rows_live"] == 3
    assert counted["decode_rows_computed"] == 3 * SLOTS
    # The rest of the long prompt, from where its first chunk stopped.
    cache = dict(cache, k=k, v=v, lengths=lengths, rec=rec)
    rest, c = long_prompt[CHUNK:], paged_kv.init_ssm_counters()
    for off in range(0, len(rest), CHUNK):
        chunk = rest[off:off + CHUNK]
        padded = np.zeros((1, CHUNK), np.int32)
        padded[0, :len(chunk)] = chunk
        logits, k, v, lengths, rec, c = prefill(
            params, padded, np.int32(len(chunk)), np.int32(1),
            np.int32(CHUNK + off), k, v, lengths, cache["block_tables"], rec,
            c)
    ref = reference_logits(params, long_prompt)
    assert rel_rms(np.asarray(logits[0]), ref[-1]) < TOLERANCE


@pytest.mark.parametrize("width", [2, 4])
def test_a_pass_advances_each_rows_slot_from_its_own_state(params, programs,
                                                           width):
    """Two slots' chunks as rows of one pass, slot 2's first chunk (from
    zeros, whatever its row held) and slot 0's second (from what its first
    left), the rest of the pass inert rows that name slot 1: each slot's
    state, convolution inputs and logits are what a call a chunk leaves,
    slot 1's poisoned row is not touched, and the counters sum over rows."""
    prefill, _ = programs
    long_prompt, short = prompt_of(CHUNK + 7, 4), prompt_of(9, 4)
    _, start, _ = prefill_prompt(prefill, params, fresh_cache(poison=3.0), 0,
                                 long_prompt[:CHUNK])
    # One call a chunk.
    want_short, cache, _ = prefill_prompt(prefill, params, start, 2, short)
    k, v, lengths, rec = (cache[n] for n in ("k", "v", "lengths", "rec"))
    padded = np.zeros((1, CHUNK), np.int32)
    padded[0, :7] = long_prompt[CHUNK:]
    want_long, k, v, lengths, rec, _ = prefill(
        params, padded, np.int32(7), np.int32(0), np.int32(CHUNK), k, v,
        lengths, cache["block_tables"], rec, paged_kv.init_ssm_counters())
    want = dict(cache, k=k, v=v, lengths=lengths, rec=rec)
    # The same two chunks as rows of one pass.
    tokens = np.zeros((width, CHUNK), np.int32)
    tokens[0, :9], tokens[1, :7] = short, long_prompt[CHUNK:]
    n_valid, slot, offset = np.zeros((3, width), np.int32)
    n_valid[:2], slot[:2], offset[:2] = (9, 7), (2, 0), (0, CHUNK)
    slot[2:], offset[2:] = 1, 5
    logits, k, v, lengths, rec, count = prefill(
        params, tokens, n_valid, slot, offset, start["k"], start["v"],
        start["lengths"], start["block_tables"], start["rec"],
        paged_kv.init_ssm_counters())
    assert logits.shape == (width, CFG.vocab_size)
    assert rel_rms(np.asarray(logits[0]), np.asarray(want_short[0])) < TOLERANCE
    assert rel_rms(np.asarray(logits[1]), np.asarray(want_long[0])) < TOLERANCE
    assert list(np.asarray(lengths)) == list(np.asarray(want["lengths"]))
    for name in ("state", "conv"):
        for s in (0, 2):
            got, ref = rows({"rec": rec}, name, s), rows(want, name, s)
            assert float(jnp.abs(got - ref).max()) < TOLERANCE * float(
                jnp.abs(ref).max()), (name, s)
        assert (np.asarray(rows({"rec": rec}, name, 1)) == 3.0).all(), name
    counted = jax.device_get(count)
    assert counted["calls"] == 1
    assert counted["prefill_tokens_valid"] == 9 + 7
    assert counted["prefill_tokens_computed"] == width * CHUNK


# -- the engine --------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(params):
    eng = engine_for(params)
    yield eng
    eng.shutdown()


@pytest.mark.parametrize("length", PROMPT_LENS)
def test_engine_prefill_then_decode_against_the_reference(params, engine,
                                                          length):
    """Prefill (under a chunk, exactly one, several and a remainder) into
    pages and state rows, then eight greedy decode steps, against the
    reference's one full forward pass over prompt + tokens: the prefill's
    logits outright, every served token by its margin."""
    prompt = prompt_of(length, seed=1)
    first = engine.prefill_logits(prompt)
    served = engine.submit(prompt, max_new_tokens=8).result(timeout=180)
    assert len(served) == 8
    ref = reference_logits(params, prompt + served[:-1])
    assert rel_rms(first, ref[len(prompt) - 1]) < TOLERANCE
    assert max(margins(ref[len(prompt) - 1:], served)) < TOLERANCE


def test_two_decode_in_neighbouring_slots_while_a_third_prefills(params,
                                                                 engine):
    """Requests of unlike length share the decode program with each
    other's rows and with a prompt that is chunk by chunk on its way in."""
    a, b, c = prompt_of(7, 2), prompt_of(23, 2), prompt_of(2 * CHUNK + 11, 2)
    steps = engine.stats()["steps"]
    ha = engine.submit(a, max_new_tokens=24)
    hb = engine.submit(b, max_new_tokens=24)
    deadline = time.monotonic() + 120
    while engine.stats()["steps"] < steps + 2:   # both decode by now
        assert time.monotonic() < deadline
        time.sleep(0.005)
    hc = engine.submit(c, max_new_tokens=8)
    for prompt, handle in ((a, ha), (b, hb), (c, hc)):
        check_served(params, prompt, handle.result(timeout=180))
    # The third came in while the others were decoding.
    assert hc.admitted_at_step < max(ha.admitted_at_step,
                                     hb.admitted_at_step) + 24


def test_stats_know_the_second_pool(params, engine):
    before = engine.stats()
    engine.submit(prompt_of(CHUNK + 3, 3), max_new_tokens=4).result(timeout=180)
    after = engine.stats()
    ssm, kv = after["ssm"], after["kv"]
    state_row = 3 * 8 * 16 * 16 * 4 + 3 * 3 * 160 * 4      # float32 model
    assert ssm["bytes_per_slot"] == state_row
    assert ssm["pool_bytes"] == 3 * state_row
    delta = {k: ssm[k] - before["ssm"][k] for k in ssm}
    assert delta["state_resets"] == 1
    assert delta["prefix_reuse_skipped"] == 1      # the prompt fills a page
    assert delta["prefill_tokens_valid"] == CHUNK + 3
    assert delta["prefill_tokens_computed"] == 2 * CHUNK
    assert 3 <= delta["decode_rows_live"] <= 4
    assert delta["decode_rows_computed"] % 3 == 0
    assert delta["decode_rows_live"] <= delta["decode_rows_computed"]
    # No prefix cache, by what the model is: its keys stay and read nothing.
    assert kv["roots"] == [] and kv["prefix_cache_pages"] == 0
    assert kv["prefix_hits"] == kv["prefix_misses"] == 0
    assert kv["prefix_hit_rate"] is None and kv["prefill_tokens_skipped"] == 0
    assert "moe" not in after


def test_a_reused_slot_and_a_prompt_sent_twice(params):
    """One slot: a long tenant, then a short prompt in its place, then the
    same short prompt again. Without snapshots of the state no prefix is
    reused: each time the answer is a fresh engine's."""
    long_prompt, prompt = prompt_of(2 * CHUNK + 9, 4), prompt_of(CHUNK + 5, 4)
    eng = engine_for(params, num_slots=1)
    try:
        check_served(params, long_prompt,
                     eng.submit(long_prompt, max_new_tokens=8).result(180))
        first = eng.submit(prompt, max_new_tokens=8).result(timeout=180)
        second = eng.submit(prompt, max_new_tokens=8).result(timeout=180)
        stats = eng.stats()
    finally:
        eng.shutdown()
    fresh = engine_for(params, num_slots=1)
    try:
        alone = fresh.submit(prompt, max_new_tokens=8).result(timeout=180)
    finally:
        fresh.shutdown()
    assert first == second == alone
    check_served(params, prompt, second)
    assert stats["ssm"]["state_resets"] == 3
    assert stats["ssm"]["prefix_reuse_skipped"] == 3
    assert stats["kv"]["prefill_tokens_skipped"] == 0


def test_the_probe_builds_one_slot_and_no_second_pool(params, engine,
                                                      monkeypatch):
    """`prefill_logits` runs on a scratch cache of one slot of whatever the
    engine's cache is made of, and compiles its shapes once."""
    made = []
    build = paged_kv.init_paged_cache

    def recording(cfg, slots, num_pages, *rest, **kw):
        made.append((slots, num_pages))
        return build(cfg, slots, num_pages, *rest, **kw)

    monkeypatch.setattr(paged_kv, "init_paged_cache", recording)
    prompt = prompt_of(CHUNK + 2, 5)
    first = engine.prefill_logits(prompt)
    compiles = engine.stats()["compiles"]
    again = engine.prefill_logits(prompt)
    assert engine.stats()["compiles"] == compiles
    assert (first == again).all()
    assert made == [(1, 96 // engine.page_size + 1)] * 2


def test_bfloat16_is_held_to_the_serving_cells_limits():
    """The model served as the cell serves it, bfloat16 weights and
    activations (the state stays float32), against the float32 reference
    on the same weights, at `bench/serve_cell.py`'s tolerances."""
    import serve_cell

    cfg = replace(CFG, dtype=jnp.bfloat16)
    params = seeded_params(cfg)
    assert params["layers"]["ssm"]["w_in"].dtype == jnp.bfloat16
    eng = engine_for(params, cfg)
    try:
        assert eng._tail["rec"]["state"].dtype == jnp.float32
        assert eng._tail["rec"]["conv"].dtype == jnp.bfloat16
        for length in PROMPT_LENS:
            prompt = prompt_of(length, 6)
            first = eng.prefill_logits(prompt)
            served = eng.submit(prompt, max_new_tokens=8).result(timeout=180)
            ref = reference_logits(params, prompt + served[:-1])
            assert rel_rms(first, ref[len(prompt) - 1]
                           ) <= serve_cell.LOGITS_TOLERANCE
            assert max(margins(ref[len(prompt) - 1:], served)
                       ) <= serve_cell.MARGIN_TOLERANCE
    finally:
        eng.shutdown()


def test_recurrent_layers_refuse_tensor_parallel_serving(params):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="recurrent pool .* is not sharded "
                                         "over tp=2"):
        ContinuousBatchingEngine(params, CFG, num_slots=2, max_len=64,
                                 mesh=mesh)


def test_generate_refuses_a_model_with_recurrent_layers(params):
    """`models.generate` keeps keys and values for every layer and nothing
    else; it says so in one line and does not decode a hybrid wrongly."""
    from ray_tpu.models.generate import generate

    with pytest.raises(ValueError, match="recurrent layers decodes through "
                                         "ContinuousBatchingEngine"):
        generate(params, jnp.asarray([prompt_of(5)]), CFG, max_new_tokens=2)


def test_the_pipeline_refuses_a_hybrid(params):
    from jax.sharding import Mesh

    from ray_tpu.models import forward_pipelined

    mesh = Mesh(np.array(jax.devices()[:2]), ("pp",))
    with pytest.raises(ValueError, match="stages of like layers"):
        forward_pipelined(params, jnp.asarray([prompt_of(8)] * 2), CFG, mesh)
