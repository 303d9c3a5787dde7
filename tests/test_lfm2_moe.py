"""The conv-and-attention hybrid with routed experts (LFM2-24B-A2B's
block: gated short convolutions among attention layers with a QK-norm by
head and a rope, a dense MLP in the leading layers and sigmoid-and-bias
routed experts in the rest) at toy size, `tiny_lfm2_moe`, on the CPU with
seeded float32 weights: `transformer.forward`, the mixer's three forms,
the engine's step programs and the engine itself against the benchmark's
plain reference `bench/reference/lfm2_moe.py`, which has no cache, no
chunks and no grouped product.

Tolerances: both sides compute in float32 here and differ by the order of
accumulation alone (5e-7 of a logit's size was read). Each limit below is
1e-4 relative or tighter: two hundred times that, and a hundred times under
what bfloat16 anywhere on the path or a wrong term would give (the bfloat16
run below reads 1e-2). The one bfloat16 run is held to the limits
`bench/serve_cell.py` holds a served model to."""

import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, forward, init_params, loss_fn, shortconv
from ray_tpu.parallel.moe import route
from ray_tpu.serve import paged_kv
from ray_tpu.serve.llm import ContinuousBatchingEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import spec  # noqa: E402
import weights  # noqa: E402
from reference import lfm2_moe as reference  # noqa: E402

CFG = configs.get_config("tiny_lfm2_moe")
EXTRA = ("layer_pattern", "conv_L_cache", "first_k_dense_replace",
         "num_experts", "experts_per_token", "moe_intermediate_size",
         "norm_topk_prob", "use_expert_bias", "routed_scaling_factor")
FILE = {"reference": "lfm2_moe",
        "published_extra": {name: name for name in EXTRA}}
DIMS = spec.dims_of(CFG, FILE)
TOLERANCE = 1e-4
CHUNK = 16           # the engine's prefill chunk
# Shorter than a chunk, exactly one, and several with a remainder.
PROMPT_LENS = (5, CHUNK, 2 * CHUNK + 9)
N_CONV, N_MOE = 3, 4


def seeded_params(cfg=CFG, seed=7):
    """The benchmark's weights: every leaf by the reference's `leaf_init`
    (a non-zero `router_bias` among them), the norm scales drawn too (ones
    would hide a norm over the wrong extent behind its scale's symmetry)."""
    params = weights.make_params(cfg, seed, spec.leaf_rules(cfg, FILE))
    key = jax.random.PRNGKey(seed + 1)
    for i, (stack, name) in enumerate((
            ("conv", "norm"), ("attn", "attn_norm"), ("attn", "q_norm"),
            ("attn", "k_norm"), ("mlp", "mlp_norm"), ("moe", "mlp_norm"))):
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


# -- the model ---------------------------------------------------------------

def test_the_named_config_is_the_published_model():
    big = configs.get_config("lfm2-24b-a2b")
    shapes = jax.eval_shape(lambda k: init_params(k, big),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert count == 23_977_879_168          # 23.98 B (tied: 23.84 B)
    assert count - 65536 * 2048 == 23_843_661_440
    assert big.n_layers == len(big.layer_pattern) == 40
    assert [i for i, t in enumerate(big.layer_pattern)
            if t == "full_attention"] == list(range(2, 40, 4))
    assert set(big.layer_pattern) == {"conv", "full_attention"}
    assert big.first_k_dense_replace == 2 and big.expert_layers == 38
    stacks = shapes["layers"]
    assert stacks["conv"]["w_in"].shape == (30, 2048, 6144)
    assert stacks["conv"]["conv_w"].shape == (30, 2048, 3)
    assert stacks["conv"]["w_out"].shape == (30, 2048, 2048)
    assert stacks["attn"]["wq"].shape == (10, 2048, 2048)
    assert stacks["attn"]["wk"].shape == (10, 2048, 512)
    assert stacks["attn"]["q_norm"].shape == (10, 64)     # a norm by head
    assert stacks["mlp"]["w_gate"].shape == (2, 2048, 11776)
    assert stacks["moe"]["router"].shape == (38, 2048, 64)
    assert stacks["moe"]["router_bias"].shape == (38, 64)
    assert stacks["moe"]["router_bias"].dtype == jnp.float32
    assert stacks["moe"]["w_gate"].shape == (38, 64, 2048, 1536)
    assert stacks["moe"]["w_down"].shape == (38, 64, 1536, 2048)
    assert shapes["lm_head"].shape == (2048, 65536)       # held apart
    # The cut the benchmark serves: the first ten layers as published.
    cut = configs.get_config("lfm2-24b-a2b-l10")
    assert cut.layer_pattern == big.layer_pattern[:10]
    assert (cut.recurrent_layers, cut.attention_layers,
            cut.expert_layers) == (8, 2, 8)
    assert replace(cut, n_layers=40, layer_pattern=big.layer_pattern) == big
    # The toy config: both kinds of mixer, two conv layers in a row, one
    # leading dense layer, a choice bias, prompts over several chunks.
    assert CFG.layer_pattern[:2] == ("conv", "conv")
    assert "full_attention" in CFG.layer_pattern
    assert (CFG.first_k_dense_replace, CFG.expert_layers) == (1, N_MOE)
    assert CFG.use_expert_bias and CFG.qk_norm


@pytest.mark.parametrize("length", [1, 2, 5, 21, 33])
def test_forward_logits_against_the_reference(params, length):
    """Whole sequences through `transformer.forward`, shorter than the
    convolution's kernel, as long, and longer."""
    tokens = prompt_of(length)
    logits, _ = jax.jit(lambda p, t: forward(p, t, CFG))(
        params, jnp.asarray([tokens]))
    ref = reference_logits(params, tokens)
    assert rel_rms(np.asarray(logits[0]), ref) < TOLERANCE


def test_loss_and_gradients_against_the_reference(params):
    """The next-token term alone (`aux_weight` 0: the reference has no
    balance term), and every leaf's gradient, the choice bias's exact
    zero among them (it chooses and never weighs)."""
    tokens = jnp.asarray(prompt_of(34))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, tokens[None], CFG, aux_weight=0.0)))(params)
    ref_loss, ref_grads = reference.loss_and_grads(params, tokens, DIMS)
    assert abs(float(loss) - float(ref_loss)) < TOLERANCE * float(ref_loss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree.leaves(ref_grads)) == 24
    for (path, got), want in zip(flat, jax.tree.leaves(ref_grads)):
        scale = float(jnp.abs(want).max())
        if path[-1].key == "router_bias":
            assert scale == 0.0 and float(jnp.abs(got).max()) == 0.0
            continue
        assert scale > 0, path
        assert float(jnp.abs(got - want).max()) < TOLERANCE * scale, path


def test_the_balance_term_reads_the_expert_layers_alone(params):
    """`forward`'s second result is the load-balancing term over the four
    expert layers' routing (the leading dense layer has none)."""
    _, aux = forward(params, jnp.asarray([prompt_of(20)]), CFG)
    assert np.isfinite(float(aux)) and float(aux) > 0


def mixer_leaves(seed=3):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    d = CFG.d_model
    return {"w_in": jax.random.normal(keys[0], (d, 3 * d)) * d ** -0.5,
            "conv_w": jax.random.uniform(keys[1], (d, 3), jnp.float32, -1, 1),
            "w_out": jax.random.normal(keys[2], (d, d)) * d ** -0.5}


@pytest.mark.parametrize("length", [1, 2, 3, 8, 19])
def test_the_chunked_form_is_the_one_token_form(length):
    """`shortconv.mixer` over a sequence behind given inputs against itself
    a token at a time, outputs and the inputs kept, against itself over the
    sequence cut in two with the kept rows handed on, and against the
    reference's mixer from zeros."""
    lp = mixer_leaves()
    h = jax.random.normal(jax.random.PRNGKey(length), (2, length, CFG.d_model))
    behind = jax.random.normal(jax.random.PRNGKey(99), (2, 2, CFG.d_model))
    whole = jnp.full((2,), length, jnp.int32)
    y, kept = shortconv.mixer(h, lp, CFG, behind, whole)
    c, rows = behind, []
    for t in range(length):
        y_t, c = shortconv.mixer(h[:, t:t + 1], lp, CFG, c,
                                 jnp.ones((2,), jnp.int32))
        rows.append(y_t)
    stepped = jnp.concatenate(rows, axis=1)
    scale = float(jnp.abs(stepped).max())
    assert float(jnp.abs(y - stepped).max()) < TOLERANCE * scale
    assert float(jnp.abs(kept - c).max()) < 1e-6
    cut = length // 3 + 1
    if cut < length:
        y1, mid = shortconv.mixer(h[:, :cut], lp, CFG, behind,
                                  jnp.full((2,), cut, jnp.int32))
        y2, end = shortconv.mixer(h[:, cut:], lp, CFG, mid,
                                  jnp.full((2,), length - cut, jnp.int32))
        assert float(jnp.abs(jnp.concatenate([y1, y2], 1) - y).max()
                     ) < TOLERANCE * scale
        assert float(jnp.abs(end - kept).max()) < 1e-6
    fresh, _ = shortconv.mixer(h[:1], lp, CFG, jnp.zeros((1, 2, CFG.d_model)),
                               whole[:1])
    with jax.default_matmul_precision("highest"):
        want = reference.conv_mixer(h[0], lp, DIMS)
    assert float(jnp.abs(fresh[0] - want).max()) < TOLERANCE * float(
        jnp.abs(want).max())


def test_the_mixers_padding_advances_nothing():
    """Rows past `n_valid` stay out of the kept inputs, and a row count of
    0 (a decode step's idle slot) keeps what was there bit for bit."""
    lp = mixer_leaves()
    h = jax.random.normal(jax.random.PRNGKey(1), (3, 8, CFG.d_model))
    behind = jax.random.normal(jax.random.PRNGKey(2), (3, 2, CFG.d_model))
    n_valid = jnp.asarray([5, 0, 1], jnp.int32)
    y, kept = shortconv.mixer(h, lp, CFG, behind, n_valid)
    noisy = h.at[:, 5:].set(7.0)
    y2, kept2 = shortconv.mixer(noisy.at[1].set(h[1]).at[2].set(h[2]), lp,
                                CFG, behind, n_valid)
    assert (np.asarray(kept) == np.asarray(kept2)).all()
    assert (np.asarray(y[0, :5]) == np.asarray(y2[0, :5])).all()
    assert (np.asarray(kept[1]) == np.asarray(behind[1])).all()
    # One real row: the newest kept row is its gated input, the older one
    # the newest of those behind.
    assert (np.asarray(kept[2, 0]) == np.asarray(behind[2, 1])).all()
    _, five = shortconv.mixer(h[:1, :5], lp, CFG, behind[:1],
                              jnp.asarray([5], jnp.int32))
    assert float(jnp.abs(five - kept[:1]).max()) < 1e-6


def test_the_bias_changes_the_choice_and_not_the_weights():
    """`route` with sigmoid scores: the experts are the largest of score +
    bias, their weights the scores alone over their sum + 1e-6."""
    cfg = CFG
    logits = jnp.asarray([[2.0, 1.0, 0.5, 0.0, -1.0, -1.0, -2.0, -3.0]])
    scores = jax.nn.sigmoid(logits)
    _, plain_w, plain = route(logits, cfg, jnp.zeros((8,)))
    assert sorted(np.asarray(plain[0])) == [0, 1]
    bias = jnp.zeros((8,)).at[7].set(2.0)
    got_scores, w, chosen = route(logits, cfg, bias)
    assert sorted(np.asarray(chosen[0])) == [0, 7]
    assert (np.asarray(got_scores) == np.asarray(scores)).all()
    picked = scores[0, chosen[0]]
    want = picked / (picked.sum() + 1e-6)
    assert float(jnp.abs(w[0] - want).max()) < 1e-7
    assert float(w.sum()) < 1.0       # the sum + 1e-6 divides
    # Expert 7's weight is its small score's share, not its biased one's.
    assert float(w[0][list(np.asarray(chosen[0])).index(7)]) < 0.1
    # And the reference routes the same way.
    ref_chosen, gates = reference.route(logits, jnp.eye(8), bias, DIMS)
    assert sorted(np.asarray(ref_chosen[0])) == [0, 7]
    assert float(jnp.abs(gates[0, chosen[0]] - w[0]).max()) < 1e-6


def test_the_seeded_bias_moves_choices(params):
    """With the reference's draw of `router_bias` some token-layers choose
    other experts than the scores alone would: the choice on score + bias
    is exercised by every comparison in this file."""
    tokens = jnp.asarray(prompt_of(40, 9), jnp.int32)
    with_bias = np.asarray(reference.routing_layerwise(params, tokens, DIMS))
    assert with_bias.shape == (N_MOE, 40, CFG.experts_per_token)
    bare = jax.tree.map(lambda a: a, params)
    bare["layers"] = dict(params["layers"], moe=dict(
        params["layers"]["moe"],
        router_bias=jnp.zeros_like(params["layers"]["moe"]["router_bias"])))
    without = np.asarray(reference.routing_layerwise(bare, tokens, DIMS))
    moved = (with_bias != without).any(-1).mean()
    assert 0.02 < moved < 0.9, moved


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


def counters():
    return paged_kv.init_routing_counters(CFG), paged_kv.init_ssm_counters()


@pytest.fixture(scope="module")
def programs():
    prefill = jax.jit(
        lambda p, t, n, s, o, k, v, ln, bt, moe, rec, count:
        paged_kv.prefill_chunk_paged(p, t, n, s, o, k, v, ln, bt, CFG,
                                     MAX_LEN, None, moe, rec, count))
    decode = jax.jit(
        lambda p, t, k, v, ln, a, bt, moe, rec, count: paged_kv.decode_paged(
            p, t, k, v, ln, a, bt, None, None, None, None, CFG, MAX_LEN,
            None, moe, rec, count))
    return prefill, decode


def rows(cache, slot):
    """A slot's rows of every conv layer in the recurrent pool."""
    return cache["rec"]["conv"][:, slot]


def prefill_prompt(prefill, params, cache, slot, prompt, filler=0):
    """`prompt` into `slot` chunk by chunk; padding rows hold `filler`."""
    moe, count = counters()
    k, v, lengths, rec = (cache[n] for n in ("k", "v", "lengths", "rec"))
    for off in range(0, len(prompt), CHUNK):
        chunk = prompt[off:off + CHUNK]
        padded = np.full((1, CHUNK), filler, np.int32)
        padded[0, :len(chunk)] = chunk
        logits, k, v, lengths, moe, rec, count = prefill(
            params, padded, np.int32(len(chunk)), np.int32(slot),
            np.int32(off), k, v, lengths, cache["block_tables"], moe, rec,
            count)
    return (logits, dict(cache, k=k, v=v, lengths=lengths, rec=rec),
            (moe, count))


def test_the_pools_are_the_models_own(params):
    cache = fresh_cache()
    # Pages for the two attention layers alone; the conv pool is the last
    # two gated inputs a slot and conv layer, and nothing else.
    assert cache["k"].shape == (2, SLOTS * PAGES_PER_SLOT + 1, PAGE,
                                CFG.n_kv_heads * CFG.head_dim)
    assert sorted(cache["rec"]) == ["conv"]
    assert cache["rec"]["conv"].shape == (N_CONV, SLOTS, 2, CFG.d_model)
    assert cache["rec"]["conv"].dtype == CFG.dtype
    big = jax.eval_shape(lambda: paged_kv.init_recurrent_pool(
        configs.get_config("lfm2-24b-a2b-l10"), 96))
    assert big["conv"].shape == (8, 96, 2, 2048)
    assert big["conv"].dtype == jnp.bfloat16
    # The Mamba hybrid's pool is what it was.
    granite = jax.eval_shape(lambda: paged_kv.init_recurrent_pool(
        configs.get_config("granite-4.0-h-micro"), 48))
    assert granite["state"].shape == (36, 48, 64, 64, 128)
    assert granite["state"].dtype == jnp.float32
    assert granite["conv"].shape == (36, 48, 3, 4352)
    assert granite["conv"].dtype == jnp.bfloat16
    assert paged_kv.init_routing_counters(CFG)["assignments"].shape == (
        N_MOE, CFG.num_experts)


@pytest.mark.parametrize("length", PROMPT_LENS)
def test_a_chunks_padding_advances_nothing(params, programs, length):
    """A first chunk starts from zeros whatever the slot's row held, a
    prompt of several chunks carries its gated inputs across them, and the
    padding rows of the last chunk stay out of the kept inputs: what the
    chunked prefill leaves is the one-token form's over the real tokens,
    whatever the padding holds and whatever the last tenant left."""
    prefill, decode = programs
    prompt = prompt_of(length)
    logits, cache, (moe, count) = prefill_prompt(
        prefill, params, fresh_cache(), 1, prompt)
    ref = reference_logits(params, prompt)
    assert rel_rms(np.asarray(logits[0]), ref[-1]) < TOLERANCE
    other, dirty, _ = prefill_prompt(prefill, params, fresh_cache(poison=3.0),
                                     1, prompt, filler=201)
    assert rel_rms(np.asarray(other[0]), ref[-1]) < TOLERANCE
    got, again = rows(cache, 1), rows(dirty, 1)
    assert float(jnp.abs(got - again).max()) <= 1e-6 * float(
        jnp.abs(got).max())
    # The other slots' rows were not touched.
    assert (np.asarray(rows(dirty, 0)) == 3.0).all()
    counted, routed = jax.device_get(count), jax.device_get(moe)
    chunks = -(-length // CHUNK)
    assert counted["prefill_tokens_valid"] == length
    assert counted["prefill_tokens_computed"] == chunks * CHUNK
    assert counted["calls"] == routed["calls"] == chunks
    # Every row a chunk computes is routed, padding too, in each of the
    # four expert layers.
    assert routed["assignments"].shape == (N_MOE, CFG.num_experts)
    assert (routed["assignments"].sum(-1)
            == chunks * CHUNK * CFG.experts_per_token).all()
    # The same inputs by the one-token form: decode the prompt's tokens one
    # after another into another slot (its first token through a chunk).
    _, stepped, _ = prefill_prompt(prefill, params, fresh_cache(), 2,
                                   prompt[:1])
    k, v, lengths, rec = (stepped[n] for n in ("k", "v", "lengths", "rec"))
    active = jnp.asarray([False, False, True])
    moe, count = counters()
    for token in prompt[1:]:
        tokens = jnp.zeros((SLOTS,), jnp.int32).at[2].set(token)
        _, k, v, lengths, moe, rec, count = decode(
            params, tokens, k, v, lengths, active, stepped["block_tables"],
            moe, rec, count)
    want, got = rows({"rec": rec}, 2), rows(cache, 1)
    assert float(jnp.abs(got - want).max()) < TOLERANCE * float(
        jnp.abs(want).max())


def test_decode_leaves_idle_and_prefilling_slots_as_they_were(params,
                                                              programs):
    """The decode program runs over every slot: one that is idle, or whose
    prompt is half way through its chunks, keeps its gated inputs bit for
    bit, and the half-way prompt then finishes as if no step had run."""
    prefill, decode = programs
    long_prompt, short = prompt_of(2 * CHUNK + 9), prompt_of(7)
    _, cache, _ = prefill_prompt(prefill, params, fresh_cache(poison=2.0), 0,
                                 short)
    # Slot 1: the first chunk of the long prompt only.
    _, cache, _ = prefill_prompt(prefill, params, cache, 1,
                                 long_prompt[:CHUNK])
    before = np.asarray(cache["rec"]["conv"])
    k, v, lengths, rec = (cache[n] for n in ("k", "v", "lengths", "rec"))
    active = jnp.asarray([True, False, False])
    moe, count = counters()
    tokens = jnp.asarray([short[-1], 9, 9], jnp.int32)
    for _ in range(3):
        tokens, k, v, lengths, moe, rec, count = decode(
            params, tokens, k, v, lengths, active, cache["block_tables"],
            moe, rec, count)
    now = np.asarray(rec["conv"])
    assert (now[:, 1:] == before[:, 1:]).all()
    assert not (now[:, 0] == before[:, 0]).all()
    assert list(np.asarray(lengths)) == [len(short) + 3, CHUNK, 0]
    counted, routed = jax.device_get(count), jax.device_get(moe)
    assert counted["decode_rows_live"] == 3
    assert counted["decode_rows_computed"] == 3 * SLOTS
    assert routed["calls"] == 3
    assert routed["assignments"].sum() == (
        3 * SLOTS * CFG.experts_per_token * N_MOE)
    assert (routed["experts_hit_sum"] <= 3 * SLOTS * CFG.experts_per_token
            ).all() and (routed["experts_hit_sum"] > 0).all()
    # The rest of the long prompt, from where its first chunk stopped.
    cache = dict(cache, k=k, v=v, lengths=lengths, rec=rec)
    rest = long_prompt[CHUNK:]
    moe, c = counters()
    for off in range(0, len(rest), CHUNK):
        chunk = rest[off:off + CHUNK]
        padded = np.zeros((1, CHUNK), np.int32)
        padded[0, :len(chunk)] = chunk
        logits, k, v, lengths, moe, rec, c = prefill(
            params, padded, np.int32(len(chunk)), np.int32(1),
            np.int32(CHUNK + off), k, v, lengths, cache["block_tables"], moe,
            rec, c)
    ref = reference_logits(params, long_prompt)
    assert rel_rms(np.asarray(logits[0]), ref[-1]) < TOLERANCE


def test_a_pass_advances_each_rows_slot_from_its_own_inputs(params, programs):
    """Two slots' chunks as rows of one pass, slot 2's first chunk (from
    zeros, whatever its row held) and slot 0's second (from what its first
    left): each slot's kept inputs and logits are what a call a chunk
    leaves, and slot 1's poisoned row is not touched."""
    prefill, _ = programs
    long_prompt, short = prompt_of(CHUNK + 7, 4), prompt_of(9, 4)
    _, start, _ = prefill_prompt(prefill, params, fresh_cache(poison=3.0), 0,
                                 long_prompt[:CHUNK])
    want_short, cache, _ = prefill_prompt(prefill, params, start, 2, short)
    k, v, lengths, rec = (cache[n] for n in ("k", "v", "lengths", "rec"))
    padded = np.zeros((1, CHUNK), np.int32)
    padded[0, :7] = long_prompt[CHUNK:]
    moe, count = counters()
    want_long, k, v, lengths, _, rec, _ = prefill(
        params, padded, np.int32(7), np.int32(0), np.int32(CHUNK), k, v,
        lengths, cache["block_tables"], moe, rec, count)
    tokens = np.zeros((2, CHUNK), np.int32)
    tokens[0, :9], tokens[1, :7] = short, long_prompt[CHUNK:]
    logits, _, _, got_lengths, moe, got, count = prefill(
        params, tokens, np.asarray([9, 7], np.int32),
        np.asarray([2, 0], np.int32), np.asarray([0, CHUNK], np.int32),
        start["k"], start["v"], start["lengths"], start["block_tables"],
        *counters()[:1], start["rec"], counters()[1])
    assert rel_rms(np.asarray(logits[0]), np.asarray(want_short[0])) < TOLERANCE
    assert rel_rms(np.asarray(logits[1]), np.asarray(want_long[0])) < TOLERANCE
    assert list(np.asarray(got_lengths)) == list(np.asarray(lengths))
    for s in (0, 2):
        ref = rec["conv"][:, s]
        assert float(jnp.abs(got["conv"][:, s] - ref).max()
                     ) < TOLERANCE * float(jnp.abs(ref).max()), s
    assert (np.asarray(got["conv"][:, 1]) == 3.0).all()
    assert jax.device_get(count)["prefill_tokens_valid"] == 16
    assert jax.device_get(moe)["calls"] == 1


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
    pages and conv rows, then eight greedy decode steps, against the
    reference's one full forward pass over prompt + tokens: the prefill's
    logits outright, every served token by its margin."""
    prompt = prompt_of(length, seed=1)
    first = engine.prefill_logits(prompt)
    served = engine.submit(prompt, max_new_tokens=8).result(timeout=180)
    assert len(served) == 8
    ref = reference_logits(params, prompt + served[:-1])
    assert rel_rms(first, ref[len(prompt) - 1]) < TOLERANCE
    assert max(margins(ref[len(prompt) - 1:], served)) < TOLERANCE


def test_requests_of_unlike_length_share_the_step_programs(params, engine):
    prompts = [prompt_of(7, 2), prompt_of(23, 2), prompt_of(2 * CHUNK + 11, 2)]
    handles = [engine.submit(p, max_new_tokens=12) for p in prompts]
    for prompt, handle in zip(prompts, handles):
        served = handle.result(timeout=180)
        ref = reference_logits(params, prompt + served[:-1])
        assert max(margins(ref[len(prompt) - 1:], served)) < TOLERANCE


def test_stats_know_the_routing_and_the_recurrent_counters(params, engine):
    before = engine.stats()
    engine.submit(prompt_of(CHUNK + 3, 3), max_new_tokens=4).result(timeout=180)
    after = engine.stats()
    ssm, moe, kv = after["ssm"], after["moe"], after["kv"]
    row = N_CONV * 2 * CFG.d_model * 4                     # float32 model
    assert ssm["bytes_per_slot"] == row and ssm["pool_bytes"] == 3 * row
    delta = {k: ssm[k] - before["ssm"][k] for k in ssm}
    assert delta["state_resets"] == 1
    assert delta["prefix_reuse_skipped"] == 1      # the prompt fills a page
    assert delta["prefill_tokens_valid"] == CHUNK + 3
    assert delta["prefill_tokens_computed"] == 2 * CHUNK
    assert 3 <= delta["decode_rows_live"] <= 4
    assert delta["decode_rows_live"] <= delta["decode_rows_computed"]
    # The routing counters are over the four expert layers, every expert
    # held here, and advance with the recurrent ones call for call.
    assert (moe["expert_layers"], moe["num_experts"],
            moe["experts_held"]) == (N_MOE, 8, 8)
    calls = moe["calls"] - before["moe"]["calls"]
    assert calls == delta["calls"] > 0
    routed = moe["assignments"] - before["moe"]["assignments"]
    rows_computed = (delta["prefill_tokens_computed"]
                     + delta["decode_rows_computed"])
    assert routed == rows_computed * CFG.experts_per_token * N_MOE
    assert moe["held_assignments"] == moe["assignments"]
    assert len(moe["per_expert"]) == 8
    hit = moe["experts_hit_sum"] - before["moe"]["experts_hit_sum"]
    assert N_MOE * calls <= hit <= N_MOE * calls * 8
    # No prefix cache, by what the model is: its keys stay and read nothing.
    assert kv["roots"] == [] and kv["prefix_cache_pages"] == 0
    assert kv["prefix_hit_rate"] is None and kv["prefill_tokens_skipped"] == 0


def test_an_attention_layer_among_the_leading_dense_layers():
    """Three leading dense layers, an attention layer the third: the walk
    is split where the MLP changes kind, so each span scans its own part of
    the attention stack (`_walk_hybrid`'s `span`), and the whole-sequence
    form scans the dense MLPs and the routed ones apart."""
    cfg = replace(CFG, first_k_dense_replace=3)
    dims = spec.dims_of(cfg, FILE)
    params = seeded_params(cfg)
    assert params["layers"]["mlp"]["w_gate"].shape[0] == 3
    assert params["layers"]["moe"]["router"].shape[0] == 2
    prompt = prompt_of(2 * CHUNK + 5, 8)
    hidden = reference.hidden_layerwise(params, jnp.asarray(prompt, jnp.int32),
                                        dims)
    ref = np.asarray(reference.logits_rows(params, hidden, dims))
    logits, _ = forward(params, jnp.asarray([prompt]), cfg)
    assert rel_rms(np.asarray(logits[0]), ref) < TOLERANCE
    eng = engine_for(params, cfg)
    try:
        first = eng.prefill_logits(prompt)
        served = eng.submit(prompt, max_new_tokens=4).result(timeout=180)
        routed = eng.stats()["moe"]
    finally:
        eng.shutdown()
    assert rel_rms(first, ref[-1]) < TOLERANCE
    seq = jnp.asarray(prompt + served[:-1], jnp.int32)
    after = np.asarray(reference.logits_rows(
        params, reference.hidden_layerwise(params, seq, dims), dims))
    assert max(margins(after[len(prompt) - 1:], served)) < TOLERANCE
    assert routed["expert_layers"] == 2


def test_a_reused_slot_and_a_prompt_sent_twice(params):
    """One slot: a long tenant, then a short prompt in its place, then the
    same short prompt again. Without snapshots of the conv rows no prefix
    is reused: each time the answer is the reference's."""
    long_prompt, prompt = prompt_of(2 * CHUNK + 9, 4), prompt_of(CHUNK + 5, 4)
    eng = engine_for(params, num_slots=1)
    try:
        eng.submit(long_prompt, max_new_tokens=8).result(180)
        first = eng.submit(prompt, max_new_tokens=8).result(timeout=180)
        second = eng.submit(prompt, max_new_tokens=8).result(timeout=180)
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert first == second
    ref = reference_logits(params, prompt + second[:-1])
    assert max(margins(ref[len(prompt) - 1:], second)) < TOLERANCE
    assert stats["ssm"]["state_resets"] == 3
    assert stats["ssm"]["prefix_reuse_skipped"] == 3
    assert stats["kv"]["prefill_tokens_skipped"] == 0


def test_bfloat16_is_held_to_the_serving_cells_limits():
    """The model served as the cell serves it, bfloat16 weights,
    activations and conv rows (the choice bias stays float32), against the
    float32 reference on the same weights, at `bench/serve_cell.py`'s
    tolerances; and bfloat16 is what the float32 tolerance above refuses.

    A router input rounded to bfloat16 now and then swaps a token's last
    chosen expert for the next one (3% of token-layers at these toy widths,
    2 of 8 experts), and at 64 channels one swapped expert moves that
    position's logits by more than either limit. So a flip is told from a
    fault: the program's own choices (its whole-sequence form, bfloat16)
    are set against `reference.routing_layerwise`, every compared position
    whose choices agree in all four expert layers is held to both limits,
    and those are most positions."""
    import serve_cell

    from ray_tpu.models import transformer

    cfg = replace(CFG, dtype=jnp.bfloat16)
    params = seeded_params(cfg)
    assert params["layers"]["conv"]["w_in"].dtype == jnp.bfloat16
    assert params["layers"]["moe"]["router_bias"].dtype == jnp.float32
    chosen = jax.jit(lambda p, t: transformer._hybrid_layers(
        p, transformer._embed_tokens(p, t, cfg), cfg, None, None)[1]["experts"])
    eng = engine_for(params, cfg)
    worst, held, flipped, token_layers = 0.0, 0, 0, 0
    try:
        assert eng._tail["rec"]["conv"].dtype == jnp.bfloat16
        for seed in (6, 8):
            for length in PROMPT_LENS:
                prompt = prompt_of(length, seed)
                first = eng.prefill_logits(prompt)
                served = eng.submit(prompt, max_new_tokens=8).result(
                    timeout=180)
                seq = jnp.asarray(prompt + served[:-1], jnp.int32)
                ref = reference_logits(params, seq)[length - 1:]
                want = np.asarray(reference.routing_layerwise(params, seq,
                                                              DIMS))
                got = np.sort(np.asarray(chosen(params, seq[None])), -1)
                flips = (got.reshape(want.shape) != want).any(-1)  # [N_MOE, T]
                flipped, token_layers = (flipped + int(flips.sum()),
                                         token_layers + flips.size)
                same = ~flips[:, length - 1:].any(0)               # [8]
                if same[0]:
                    rel = rel_rms(first, ref[0])
                    worst = max(worst, rel)
                    assert rel <= serve_cell.LOGITS_TOLERANCE, (seed, length)
                for ok, margin in zip(same, margins(ref, served)):
                    held += int(ok)
                    assert not ok or margin <= serve_cell.MARGIN_TOLERANCE, (
                        seed, length, margin)
    finally:
        eng.shutdown()
    assert held >= 40, held                 # of 48 compared positions
    assert 0 < flipped < 0.1 * token_layers
    assert worst > 10 * TOLERANCE


# -- what is refused ---------------------------------------------------------

def test_recurrent_layers_refuse_tensor_parallel_serving(params):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="recurrent pool .* is not sharded "
                                         "over tp=2"):
        ContinuousBatchingEngine(params, CFG, num_slots=2, max_len=64,
                                 mesh=mesh)


def test_generate_refuses_a_model_with_recurrent_layers(params):
    from ray_tpu.models.generate import generate

    with pytest.raises(ValueError, match="recurrent layers decodes through "
                                         "ContinuousBatchingEngine"):
        generate(params, jnp.asarray([prompt_of(5)]), CFG, max_new_tokens=2)


def test_the_pipeline_refuses_a_hybrid(params):
    from jax.sharding import Mesh

    from ray_tpu.models import forward_pipelined

    mesh = Mesh(np.array(jax.devices()[:5]), ("pp",))      # 5 layers
    with pytest.raises(ValueError, match="dense layers only|like layers"):
        forward_pipelined(params, jnp.asarray([prompt_of(8)] * 2), CFG, mesh)


REFUSED = {
    "an unknown kind": (dict(layer_pattern=("conv", "rwkv", "full_attention",
                                            "conv", "full_attention")),
                        "unknown layer kinds"),
    "Mamba and conv layers together": (
        dict(layer_pattern=("conv", "mamba", "full_attention", "conv",
                            "attention")),
        r"\['conv', 'mamba'\] in one model"),
    "one kind alone": (dict(layer_pattern=("conv",) * 5), "both kinds"),
    "a pattern of another length": (dict(n_layers=4), "names 5 layers"),
    "latent attention": (dict(kv_lora_rank=16), "latent attention beside"),
    # A held share of the experts and shared experts beside recurrent
    # layers are served since PR 61 (tests/test_solar_open2.py); a share
    # that is none of the experts' is refused as it is under latent
    # attention.
    "a held share that does not divide": (dict(experts_held=3),
                                          "must divide num_experts"),
    "a share past the last": (dict(experts_held=4, expert_share=2),
                              "name one of the shares"),
    "no expert layer": (dict(first_k_dense_replace=5),
                        "first_k_dense_replace"),
}


@pytest.mark.parametrize("case", REFUSED)
def test_a_hybrid_that_is_not_written_is_refused_by_name(case):
    change, says = REFUSED[case]
    with pytest.raises(ValueError, match=says):
        init_params(jax.random.PRNGKey(0), replace(CFG, **change))
