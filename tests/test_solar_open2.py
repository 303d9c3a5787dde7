"""The delta-rule-and-attention hybrid with a held share of routed experts
(Solar-Open2-250B's block: channel-gated delta-rule layers among gated NoPE
attention layers, every layer's MLP routed experts of which this chip holds
a share, beside a shared expert) at toy size, `tiny_solar_open2`, on the
CPU with seeded float32 weights: the delta rule's three forms against each
other, `transformer.forward`, the engine's step programs and the engine
itself against the benchmark's plain reference
`bench/reference/solar_open2.py`, which has no cache, no chunks, no blocks
and no grouped product; and the shares of a layer's experts against the
uncut layer.

Tolerances: both sides compute in float32 here and differ by the order of
accumulation alone (2e-7 of a logit's size was read, 1e-6 of a state's
between the chunked form and the scan). Each limit below is 1e-4 relative
or tighter: a hundred times that, and a hundred times under what bfloat16
anywhere on the path or a wrong term would give (a state kept in bfloat16
reads 3e-4 of a logit at these widths, `beta` without its 2 reads 3e-2).
The one bfloat16 run is held to the limits `bench/serve_cell.py` holds a
served model to."""

import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, forward, init_params, kda
from ray_tpu.ops import kda_update as kda_update_op
from ray_tpu.parallel.moe import moe_block
from ray_tpu.serve import paged_kv
from ray_tpu.serve.llm import ContinuousBatchingEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import spec  # noqa: E402
import weights  # noqa: E402
from reference import solar_open2 as reference  # noqa: E402

CFG = configs.get_config("tiny_solar_open2")
EXTRA = ("layer_pattern", "kda_num_heads", "kda_head_dim",
         "kda_short_conv_kernel_size", "kda_allow_neg_eigval",
         "attn_output_gate", "first_k_dense_replace", "num_experts",
         "experts_held", "expert_share", "experts_per_token",
         "moe_intermediate_size", "n_shared_experts", "norm_topk_prob",
         "routed_scaling_factor", "scoring_func", "use_expert_bias")
FILE = {"reference": "solar_open2",
        "published_extra": {name: name for name in EXTRA}}
DIMS = spec.dims_of(CFG, FILE)
TOLERANCE = 1e-4
CHUNK = 16           # the engine's prefill chunk
# Shorter than a chunk, exactly one, and several with a remainder.
PROMPT_LENS = (5, CHUNK, 2 * CHUNK + 9)
N_KDA, N_ATTN, N_MOE = 6, 2, 8
HEADS, DK = CFG.kda_num_heads, CFG.kda_head_dim


def seeded_params(cfg=CFG, seed=7):
    """The benchmark's weights: every leaf by the reference's `leaf_init`
    (a non-zero `router_bias` among them), the norm scales drawn too (ones
    would hide a norm over the wrong extent behind its scale's symmetry)."""
    dims = spec.dims_of(cfg, FILE)
    params = weights.make_params(
        cfg, seed, lambda path: reference.leaf_init(
            path, dict(dims, tie_embeddings=cfg.tie_embeddings)))
    key = jax.random.PRNGKey(seed + 1)
    for i, (stack, name) in enumerate((
            ("kda", "norm"), ("kda", "gate_norm"), ("attn", "attn_norm"),
            ("moe", "mlp_norm"))):
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


def reference_logits(params, tokens, dims=DIMS):
    """[T, vocab] float32 from the reference's full forward pass."""
    hidden = reference.hidden_layerwise(params, jnp.asarray(tokens, jnp.int32),
                                        dims)
    return np.asarray(reference.logits_rows(params, hidden, dims))


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
    big = configs.get_config("solar-open2-250b")
    shapes = jax.eval_shape(lambda k: init_params(k, big),
                            jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(count / 250e9 - 1) < 0.02                   # 250.3 B
    assert big.n_layers == len(big.layer_pattern) == 48
    assert [i for i, t in enumerate(big.layer_pattern)
            if t == "full_attention"] == list(range(0, 48, 4))
    assert set(big.layer_pattern) == {"kda", "full_attention"}
    assert big.first_k_dense_replace == 0 and big.expert_layers == 48
    assert big.position_embedding_type == "nope" and big.attn_output_gate
    assert big.head_dim == 128 and not big.tie_embeddings
    stacks = shapes["layers"]
    assert "mlp" not in stacks                  # every layer's MLP is routed
    assert stacks["kda"]["w_qkv"].shape == (36, 4096, 3 * 8192)
    assert stacks["kda"]["conv_w"].shape == (36, 3 * 8192, 4)
    assert stacks["kda"]["w_fa"].shape == (36, 4096, 128)
    assert stacks["kda"]["w_fb"].shape == (36, 128, 8192)
    assert stacks["kda"]["a_log"].shape == (36, 64)
    assert stacks["kda"]["dt_bias"].shape == (36, 8192)
    assert stacks["kda"]["w_beta"].shape == (36, 4096, 64)
    assert stacks["kda"]["gate_norm"].shape == (36, 128)
    assert stacks["kda"]["w_out"].shape == (36, 8192, 4096)
    assert stacks["attn"]["wq"].shape == (12, 4096, 8192)
    assert stacks["attn"]["wk"].shape == (12, 4096, 1024)
    assert stacks["attn"]["wg"].shape == (12, 4096, 8192)
    assert stacks["moe"]["router"].shape == (48, 4096, 320)
    assert stacks["moe"]["router_bias"].dtype == jnp.float32
    assert stacks["moe"]["w_gate"].shape == (48, 320, 4096, 1280)
    assert stacks["moe"]["shared_down"].shape == (48, 1280, 4096)
    assert shapes["lm_head"].shape == (4096, 196608)
    # The cut the benchmark serves: the first period, a share of eight.
    cut = configs.get_config("solar-open2-250b-ep8-l4")
    assert cut.layer_pattern == big.layer_pattern[:4] == (
        "full_attention", "kda", "kda", "kda")
    assert (cut.recurrent_layers, cut.attention_layers,
            cut.expert_layers) == (3, 1, 4)
    assert (cut.held, cut.num_experts, cut.vocab_size) == (40, 320, 24576)
    held = jax.eval_shape(lambda k: init_params(k, cut),
                          jax.random.PRNGKey(0))["layers"]["moe"]
    assert held["w_gate"].shape == (4, 40, 4096, 1280)
    assert held["router"].shape == (4, 4096, 320)
    assert replace(cut, n_layers=48, layer_pattern=big.layer_pattern,
                   vocab_size=196608, experts_held=0) == big
    # The toy config: two periods, a held half of 8 experts, a shared
    # expert, the gate, a choice bias, beta up to 2.
    assert CFG.layer_pattern == ("full_attention", "kda", "kda", "kda") * 2
    assert (CFG.held, CFG.num_experts, CFG.n_shared_experts) == (4, 8, 1)
    assert CFG.attn_output_gate and CFG.kda_allow_neg_eigval
    assert CFG.use_expert_bias and CFG.head_dim == DK == 16


@pytest.mark.parametrize("length", [1, 2, 5, 21, 70])
def test_forward_logits_against_the_reference(params, length):
    """Whole sequences through `transformer.forward`, shorter than the
    convolution's kernel, as long, longer, and longer than a block."""
    tokens = prompt_of(length)
    logits, _ = jax.jit(lambda p, t: forward(p, t, CFG))(
        params, jnp.asarray([tokens]))
    ref = reference_logits(params, tokens)
    assert rel_rms(np.asarray(logits[0]), ref) < TOLERANCE


def test_the_gate_and_the_two_are_seen(params):
    """What the comparison would let through were it loose: the attention
    layers without their gate, and `beta` without its 2, each read far over
    the tolerance against the sound reference."""
    tokens = prompt_of(40, 5)
    ref = reference_logits(params, tokens)
    ungated = jax.tree.map(lambda a: a, params)
    ungated["layers"] = dict(params["layers"], attn={
        n: w for n, w in params["layers"]["attn"].items() if n != "wg"})
    got, _ = forward(ungated, jnp.asarray([tokens]), CFG)
    assert rel_rms(np.asarray(got[0]), ref) > 100 * TOLERANCE
    halved, _ = forward(params, jnp.asarray([tokens]),
                        replace(CFG, kda_allow_neg_eigval=False))
    assert rel_rms(np.asarray(halved[0]), ref) > 100 * TOLERANCE


def test_a_layer_without_the_gate_is_traced_as_it_was():
    """`gate_attention` hands back its argument itself where a layer has no
    leaf `wg`, and only `attn_output_gate` draws one: the three writings of
    the attention layer trace for every other model what they traced
    before the gate was written (the dense control's step programs were
    the parent's jaxpr for jaxpr, PR 61)."""
    from ray_tpu.models.transformer import gate_attention

    attn = jnp.ones((1, 2, 8))
    assert gate_attention(attn, None, {"wo": None}) is attn
    for name in configs.NAMED_CONFIGS:
        cfg = configs.get_config(name)
        shapes = jax.eval_shape(lambda k, c=cfg: init_params(k, c),
                                jax.random.PRNGKey(0))
        leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
        assert any(path[-1].key == "wg" for path, _ in leaves) == (
            cfg.attn_output_gate), name


# -- the delta rule's three forms --------------------------------------------

def rule_inputs(length, batch=2, seed=0, hard=True):
    """q, k (normed as the mixer norms them), v, g, beta and a non-zero
    state; with `hard`, channel 0 decays by exp(-30) a token, so that its
    cumulative product underflows float32 within three tokens of a block."""
    ks = jax.random.split(jax.random.PRNGKey(1000 * seed + length), 6)
    shape = (batch, length, HEADS, DK)
    q, k, v = (jax.random.normal(key, shape) for key in ks[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -0.3 * jnp.exp(jax.random.normal(ks[3], shape))
    if hard:
        g = g.at[..., 0].set(-30.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    state = jax.random.normal(ks[5], (batch, HEADS, DK, DK))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("length", [2, 17, 64, 100, 150])
def test_the_chunked_form_is_the_scan(length):
    """`kda_chunked` against `kda_scan`, outputs and the final state: a
    sequence inside a block, exactly one, and several that are no multiple
    of 64, from a non-zero state, one channel decaying hard."""
    q, k, v, g, beta, state = rule_inputs(length)
    assert float(beta.max()) > 1.0                     # beta reaches above 1
    want, want_state = kda.kda_scan(q, k, v, g, beta, state)
    got, got_state = jax.jit(kda.kda_chunked)(q, k, v, g, beta, state)
    assert np.isfinite(np.asarray(got)).all()
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < TOLERANCE * scale
    assert float(jnp.abs(got_state - want_state).max()) < TOLERANCE * float(
        jnp.abs(want_state).max())
    # Cut in two, the state handed on: the same.
    cut = length // 3 + 1
    first, mid = kda.kda_chunked(q[:, :cut], k[:, :cut], v[:, :cut],
                                 g[:, :cut], beta[:, :cut], state)
    if cut + 1 < length:
        second, end = kda.kda_chunked(q[:, cut:], k[:, cut:], v[:, cut:],
                                      g[:, cut:], beta[:, cut:], mid)
        assert float(jnp.abs(jnp.concatenate([first, second], 1) - want
                             ).max()) < TOLERANCE * scale
        assert float(jnp.abs(end - want_state).max()) < TOLERANCE * float(
            jnp.abs(want_state).max())


def test_a_quotient_of_cumulative_products_would_not_do():
    """The hard channel's cumulative decay over a block is exp(-1920): a
    form that divides by it has nothing to divide by. The scan and the
    chunked form agree there because every decay is exp(G_i - G_j)."""
    q, k, v, g, beta, state = rule_inputs(64)
    assert float(jnp.exp(jnp.cumsum(g, axis=1))[..., 0].min()) == 0.0
    got, _ = kda.kda_chunked(q, k, v, g, beta, state)
    want, _ = kda.kda_scan(q, k, v, g, beta, state)
    assert float(jnp.abs(got - want).max()) < TOLERANCE * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("length", [1, 5, 70])
def test_a_run_of_the_one_token_form_is_the_scan(length):
    q, k, v, g, beta, state = rule_inputs(length, seed=1)
    want, want_state = kda.kda_scan(q, k, v, g, beta, state)
    step, outs = jax.jit(kda.kda_step), []
    for t in range(length):
        o, state = step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state)
        outs.append(o)
    got = jnp.stack(outs, axis=1)
    assert float(jnp.abs(got - want).max()) < TOLERANCE * float(
        jnp.abs(want).max())
    assert float(jnp.abs(state - want_state).max()) < TOLERANCE * float(
        jnp.abs(want_state).max())


def test_the_reference_recurrence_is_the_scan():
    """The reference's `delta_rule` (one sequence from zeros, einsums) and
    the program's `kda_scan` are the same recurrence."""
    q, k, v, g, beta, state = rule_inputs(40, batch=1, seed=2)
    want, want_state = kda.kda_scan(q, k, v, g, beta, jnp.zeros_like(state))
    with jax.default_matmul_precision("highest"):
        got, got_state = reference.delta_rule(q[0], k[0], v[0], g[0], beta[0])
    assert float(jnp.abs(got - want[0]).max()) < TOLERANCE * float(
        jnp.abs(want).max())
    assert float(jnp.abs(got_state - want_state[0]).max()) < TOLERANCE * float(
        jnp.abs(want_state).max())


def test_the_state_tells_a_bfloat16_state_from_a_float32_one():
    """The control first logits cannot fail (PR 61's chip runs: 0.00510
    against a sound 0.00507 at a limit of 0.08): the reference with its
    state rounded to bfloat16 after every token. The state after a long
    sequence sees it, an eighth bit's worth a token summed over a channel's
    memory, where the program's chunked form stays at float32's distance
    from the sound reference: the number a comparison has to read before a
    cell's `correct` says anything of the delta rule's state."""
    q, k, v, g, beta, state = rule_inputs(300, batch=1, seed=3, hard=False)
    with jax.default_matmul_precision("highest"):
        _, want = reference.delta_rule(q[0], k[0], v[0], g[0], beta[0])
        _, rounded = reference.delta_rule(q[0], k[0], v[0], g[0], beta[0],
                                          state_dtype=jnp.bfloat16)
    _, got = jax.jit(kda.kda_chunked)(q, k, v, g, beta, jnp.zeros_like(state))
    size = float(jnp.sqrt(jnp.mean(want ** 2)))
    program = float(jnp.sqrt(jnp.mean((got[0] - want) ** 2))) / size
    control = float(jnp.sqrt(jnp.mean(
        (rounded.astype(jnp.float32) - want) ** 2))) / size
    # float32 sums in another order against 8 bits of mantissa a token:
    # read 7.7e-7 and 2.4e-3 here; any limit between 1e-5 and 1e-3 parts
    # them.
    assert program < 1e-5 < 1e-3 < control


def mixer_leaves(params, layer=1):
    return jax.tree.map(lambda a: a[layer], params["layers"]["kda"])


@pytest.mark.parametrize("length", [1, 3, 19, 70])
def test_the_mixer_is_the_references(params, length):
    """`kda.mixer` over a sequence from zeros against the reference's mixer,
    and against itself a token at a time with the state and the kept
    convolution inputs handed on."""
    lp = mixer_leaves(params)
    h = jax.random.normal(jax.random.PRNGKey(length), (2, length, CFG.d_model))
    fresh = jax.tree.map(lambda a: a[0], kda.init_state(CFG, 1, 2))
    whole = jnp.full((2,), length, jnp.int32)
    y, state, kept = kda.mixer(h, lp, CFG, fresh["state"], fresh["conv"],
                               whole)
    with jax.default_matmul_precision("highest"):
        want = reference.kda_mixer(h[0], lp, DIMS)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(y[0] - want).max()) < TOLERANCE * scale
    s, c, rows = fresh["state"], fresh["conv"], []
    for t in range(length):
        y_t, s, c = kda.mixer(h[:, t:t + 1], lp, CFG, s, c,
                              jnp.ones((2,), jnp.int32))
        rows.append(y_t)
    assert float(jnp.abs(jnp.concatenate(rows, 1) - y).max()
                 ) < TOLERANCE * scale
    assert float(jnp.abs(s - state).max()) < TOLERANCE * float(
        jnp.abs(state).max())
    assert float(jnp.abs(c - kept).max()) < 1e-6


def test_the_mixers_padding_advances_nothing(params):
    """Rows past `n_valid` stay out of the state and of the kept inputs
    whatever they hold, and a row count of 0 (a decode step's idle slot, a
    pass's inert row) leaves both bit for bit, in the chunked form and in
    the one-token form."""
    lp = mixer_leaves(params)
    h = jax.random.normal(jax.random.PRNGKey(1), (3, 8, CFG.d_model))
    state = jax.random.normal(jax.random.PRNGKey(2), (3, HEADS, DK, DK))
    behind = jax.random.normal(jax.random.PRNGKey(3), (3, 3, 3 * HEADS * DK))
    n_valid = jnp.asarray([5, 0, 8], jnp.int32)
    y, s, kept = kda.mixer(h, lp, CFG, state, behind, n_valid)
    noisy = h.at[0, 5:].set(7.0)
    y2, s2, kept2 = kda.mixer(noisy, lp, CFG, state, behind, n_valid)
    assert (np.asarray(s) == np.asarray(s2)).all()
    assert (np.asarray(kept) == np.asarray(kept2)).all()
    assert (np.asarray(y[0, :5]) == np.asarray(y2[0, :5])).all()
    assert (np.asarray(s[1]) == np.asarray(state[1])).all()     # bit for bit
    assert (np.asarray(kept[1]) == np.asarray(behind[1])).all()
    assert not (np.asarray(s[0]) == np.asarray(state[0])).all()
    _, five, five_kept = kda.mixer(h[:1, :5], lp, CFG, state[:1], behind[:1],
                                   jnp.asarray([5], jnp.int32))
    assert float(jnp.abs(five - s[:1]).max()) < TOLERANCE * float(
        jnp.abs(five).max())
    assert float(jnp.abs(five_kept - kept[:1]).max()) < 1e-6
    _, idle, idle_kept = kda.mixer(h[:, :1], lp, CFG, state, behind,
                                   jnp.asarray([1, 0, 1], jnp.int32))
    assert (np.asarray(idle[1]) == np.asarray(state[1])).all()
    assert (np.asarray(idle_kept[1]) == np.asarray(behind[1])).all()
    assert not (np.asarray(idle[0]) == np.asarray(state[0])).all()


# -- the shares of a layer's experts -----------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """One layer's MLP with all 8 experts drawn, the reference uncut
    (`experts_held` 0) against the program's two shares of 4: what each
    share's held experts add, the shared expert counted once, is what the
    whole layer gives. And each share alone is the reference's same share."""
    whole_cfg = replace(CFG, experts_held=0)
    whole_dims = spec.dims_of(whole_cfg, FILE)
    whole = seeded_params(whole_cfg, seed=11)["layers"]["moe"]
    lp = jax.tree.map(lambda a: a[2], whole)               # one layer
    assert lp["w_gate"].shape[0] == 8
    h = jax.random.normal(jax.random.PRNGKey(4), (23, CFG.d_model))

    def by_reference(leaves, dims):
        with jax.default_matmul_precision("highest"):
            return reference.experts(
                h, leaves, lambda e: tuple(
                    leaves[n][e] for n in reference.EXPERT_LEAVES), dims)[0]

    def shared_alone(leaves):
        return reference._swiglu(h, leaves["shared_gate"], leaves["shared_up"],
                                 leaves["shared_down"])

    want = by_reference(lp, whole_dims)
    with jax.default_matmul_precision("highest"):
        shared = shared_alone(lp)
    parts = []
    for share in (0, 1):
        cfg = replace(CFG, expert_share=share)
        mine = dict(lp, **{n: lp[n][4 * share:4 * share + 4]
                           for n in reference.EXPERT_LEAVES})
        got, stats = moe_block(h, mine, cfg)
        assert stats["counts"].shape == (8,)               # over all experts
        assert int(stats["counts"].sum()) == 23 * CFG.experts_per_token
        same = by_reference(mine, spec.dims_of(cfg, FILE))
        assert float(jnp.abs(got - same).max()) < TOLERANCE * float(
            jnp.abs(same).max())
        parts.append(got - shared)
    total = parts[0] + parts[1] + shared
    assert float(jnp.abs(total - want).max()) < TOLERANCE * float(
        jnp.abs(want).max())
    # A share is a part and not the whole: the other's experts are missed.
    assert float(jnp.abs(parts[0] + shared - want).max()) > 0.05 * float(
        jnp.abs(want).max())


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


def fresh_cache(poison=0.0, cfg=CFG):
    cache = paged_kv.init_paged_cache(cfg, SLOTS, SLOTS * PAGES_PER_SLOT + 1,
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


def programs_for(cfg):
    prefill = jax.jit(
        lambda p, t, n, s, o, k, v, ln, bt, moe, rec, count:
        paged_kv.prefill_chunk_paged(p, t, n, s, o, k, v, ln, bt, cfg,
                                     MAX_LEN, None, moe, rec, count))
    decode = jax.jit(
        lambda p, t, k, v, ln, a, bt, moe, rec, count: paged_kv.decode_paged(
            p, t, k, v, ln, a, bt, None, None, None, None, cfg, MAX_LEN,
            None, moe, rec, count))
    return prefill, decode


@pytest.fixture(scope="module")
def programs():
    return programs_for(CFG)


# The model with delta-rule heads of a width `ops.kda_update`'s kernel takes
# (values of whole lanes, heads in whole eights; the toy's four of 16 take
# the plain form): the served-path cases that decode run on it too, the
# kernel interpreted in the walk.
WIDE = replace(CFG, kda_num_heads=8, kda_head_dim=128)


@pytest.fixture(scope="module")
def wide_params():
    return seeded_params(WIDE)


@pytest.fixture
def kernel_in_the_walk(monkeypatch):
    """`kda.mixer`'s `kda_update` run in Pallas's interpreter for the length
    of a test (off the TPU it would take the plain form); afterwards, that
    the walk did reach it."""
    calls = []

    def interpreted(*args):
        calls.append(kda_update_op.kernel_takes(args[5]))
        return kda_update_op.kda_update(*args, interpret=True)

    monkeypatch.setattr(kda, "kda_update", interpreted)
    yield
    assert calls and all(calls)


@pytest.fixture(params=["plain", "kernel"])
def served(request, params, programs):
    """What a step-program case runs on, as (cfg, params, the reference's
    dims, prefill, decode): the toy model through the plain form of the
    one-token update, and `WIDE` through the kernel."""
    if request.param == "plain":
        return CFG, params, DIMS, *programs
    request.getfixturevalue("kernel_in_the_walk")
    return (WIDE, request.getfixturevalue("wide_params"),
            spec.dims_of(WIDE, FILE), *programs_for(WIDE))


def rows(cache, slot):
    """A slot's rows of every delta-rule layer in the recurrent pool."""
    return {name: pool[:, slot] for name, pool in cache["rec"].items()}


def close(got, want):
    return all(float(jnp.abs(got[n] - want[n]).max())
               < TOLERANCE * float(jnp.abs(want[n]).max()) for n in want)


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
    # Pages for the two attention layers alone; the delta-rule pool is a
    # float32 matrix a head and the convolutions' last three inputs, a slot
    # and delta-rule layer.
    assert cache["k"].shape == (N_ATTN, SLOTS * PAGES_PER_SLOT + 1, PAGE,
                                CFG.n_kv_heads * CFG.head_dim)
    assert sorted(cache["rec"]) == ["conv", "state"]
    assert cache["rec"]["state"].shape == (N_KDA, SLOTS, HEADS, DK, DK)
    assert cache["rec"]["state"].dtype == jnp.float32
    assert cache["rec"]["conv"].shape == (N_KDA, SLOTS, 3, 3 * HEADS * DK)
    big = jax.eval_shape(lambda: paged_kv.init_recurrent_pool(
        configs.get_config("solar-open2-250b-ep8-l4"), 128))
    assert big["state"].shape == (3, 128, 64, 128, 128)
    assert big["state"].dtype == jnp.float32
    assert big["conv"].shape == (3, 128, 3, 24576)
    assert big["conv"].dtype == jnp.bfloat16
    # The other hybrids' pools are what they were.
    granite = jax.eval_shape(lambda: paged_kv.init_recurrent_pool(
        configs.get_config("granite-4.0-h-micro"), 48))
    assert granite["state"].shape == (36, 48, 64, 64, 128)
    assert granite["conv"].shape == (36, 48, 3, 4352)
    lfm2 = jax.eval_shape(lambda: paged_kv.init_recurrent_pool(
        configs.get_config("lfm2-24b-a2b-l10"), 96))
    assert sorted(lfm2) == ["conv"] and lfm2["conv"].shape == (8, 96, 2, 2048)
    assert paged_kv.init_routing_counters(CFG)["assignments"].shape == (
        N_MOE, CFG.num_experts)


@pytest.mark.parametrize("length", PROMPT_LENS)
def test_a_chunks_padding_advances_nothing(params, programs, length):
    """A first chunk starts from zeros whatever the slot's rows held, a
    prompt of several chunks carries its state and its convolution inputs
    across them, and the padding rows of the last chunk advance neither:
    what the chunked prefill leaves is the one-token form's over the real
    tokens, whatever the padding holds and whatever the last tenant left."""
    prefill, decode = programs
    prompt = prompt_of(length)
    logits, cache, (moe, count) = prefill_prompt(
        prefill, params, fresh_cache(), 1, prompt)
    ref = reference_logits(params, prompt)
    assert rel_rms(np.asarray(logits[0]), ref[-1]) < TOLERANCE
    other, dirty, _ = prefill_prompt(prefill, params, fresh_cache(poison=3.0),
                                     1, prompt, filler=201)
    assert rel_rms(np.asarray(other[0]), ref[-1]) < TOLERANCE
    assert close(rows(dirty, 1), rows(cache, 1))
    # The other slots' rows were not touched.
    assert all((np.asarray(r) == 3.0).all() for r in rows(dirty, 0).values())
    counted, routed = jax.device_get(count), jax.device_get(moe)
    chunks = -(-length // CHUNK)
    assert counted["prefill_tokens_valid"] == length
    assert counted["prefill_tokens_computed"] == chunks * CHUNK
    assert counted["calls"] == routed["calls"] == chunks
    # Every row a chunk computes is routed over ALL experts, padding too,
    # in each of the eight layers; experts hit are of the four held.
    assert routed["assignments"].shape == (N_MOE, CFG.num_experts)
    assert (routed["assignments"].sum(-1)
            == chunks * CHUNK * CFG.experts_per_token).all()
    assert (routed["experts_hit_sum"] <= chunks * CFG.held).all()
    # The same by the one-token form: decode the prompt's tokens one after
    # another into another slot (its first token through a chunk).
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
    assert close(rows(cache, 1), rows({"rec": rec}, 2))


def test_decode_leaves_idle_and_prefilling_slots_as_they_were(served):
    """The decode program runs over every slot: one that is idle, or whose
    prompt is half way through its chunks, keeps its state and its
    convolution inputs bit for bit, and the half-way prompt then finishes
    as if no step had run. Through the plain one-token form and through
    the kernel over the pool (`served`)."""
    cfg, params, dims, prefill, decode = served
    long_prompt, short = prompt_of(2 * CHUNK + 9), prompt_of(7)
    _, cache, _ = prefill_prompt(prefill, params,
                                 fresh_cache(poison=2.0, cfg=cfg), 0, short)
    # Slot 1: the first chunk of the long prompt only.
    _, cache, _ = prefill_prompt(prefill, params, cache, 1,
                                 long_prompt[:CHUNK])
    before = jax.tree.map(np.asarray, cache["rec"])
    k, v, lengths, rec = (cache[n] for n in ("k", "v", "lengths", "rec"))
    active = jnp.asarray([True, False, False])
    moe, count = counters()
    tokens = jnp.asarray([short[-1], 9, 9], jnp.int32)
    for _ in range(3):
        tokens, k, v, lengths, moe, rec, count = decode(
            params, tokens, k, v, lengths, active, cache["block_tables"],
            moe, rec, count)
    for name in ("state", "conv"):
        now = np.asarray(rec[name])
        assert (now[:, 1:] == before[name][:, 1:]).all(), name
        assert not (now[:, 0] == before[name][:, 0]).all(), name
    assert list(np.asarray(lengths)) == [len(short) + 3, CHUNK, 0]
    counted, routed = jax.device_get(count), jax.device_get(moe)
    assert counted["decode_rows_live"] == 3
    assert counted["decode_rows_computed"] == 3 * SLOTS
    assert routed["calls"] == 3
    assert routed["assignments"].sum() == (
        3 * SLOTS * CFG.experts_per_token * N_MOE)
    # The rest of the long prompt, from where its first chunk stopped.
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
    ref = reference_logits(params, long_prompt, dims)
    assert rel_rms(np.asarray(logits[0]), ref[-1]) < TOLERANCE


def test_a_pass_advances_each_rows_slot_from_its_own_state(params, programs):
    """Two slots' chunks as rows of one pass, slot 2's first chunk (from
    zeros, whatever its rows held) and slot 0's second (from what its first
    left): each slot's state, kept inputs and logits are what a call a
    chunk leaves, and slot 1's poisoned rows are not touched."""
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
        assert close(rows({"rec": got}, s), rows({"rec": rec}, s)), s
    assert all((np.asarray(r) == 3.0).all()
               for r in rows({"rec": got}, 1).values())
    assert jax.device_get(count)["prefill_tokens_valid"] == 16
    assert jax.device_get(moe)["calls"] == 1


# -- the engine --------------------------------------------------------------

@pytest.fixture(scope="module")
def engine(params):
    eng = engine_for(params)
    yield eng
    eng.shutdown()


@pytest.fixture(params=["plain", "kernel"])
def served_engine(request):
    """(engine, params, the reference's dims): the module's engine on the
    toy model, and one on `WIDE` whose decode program is traced with the
    kernel interpreted in the walk."""
    if request.param == "plain":
        yield (request.getfixturevalue("engine"),
               request.getfixturevalue("params"), DIMS)
        return
    request.getfixturevalue("kernel_in_the_walk")
    wide = request.getfixturevalue("wide_params")
    eng = engine_for(wide, WIDE)
    yield eng, wide, spec.dims_of(WIDE, FILE)
    eng.shutdown()


@pytest.mark.parametrize("length", PROMPT_LENS)
def test_engine_prefill_then_decode_against_the_reference(served_engine,
                                                          length):
    """Prefill (under a chunk, exactly one, several and a remainder) into
    pages and the delta-rule pool, then eight greedy decode steps through
    both pools, against the reference's one full forward pass over prompt +
    tokens: the prefill's logits outright, every served token by its
    margin. Through the plain one-token form and through the kernel over
    the pool (`served_engine`)."""
    engine, params, dims = served_engine
    prompt = prompt_of(length, seed=1)
    first = engine.prefill_logits(prompt)
    served = engine.submit(prompt, max_new_tokens=8).result(timeout=180)
    assert len(served) == 8
    ref = reference_logits(params, prompt + served[:-1], dims)
    assert rel_rms(first, ref[len(prompt) - 1]) < TOLERANCE
    assert max(margins(ref[len(prompt) - 1:], served)) < TOLERANCE


def test_requests_of_unlike_length_share_the_step_programs(params, engine):
    prompts = [prompt_of(7, 2), prompt_of(23, 2), prompt_of(2 * CHUNK + 11, 2)]
    handles = [engine.submit(p, max_new_tokens=12) for p in prompts]
    for prompt, handle in zip(prompts, handles):
        served = handle.result(timeout=180)
        ref = reference_logits(params, prompt + served[:-1])
        assert max(margins(ref[len(prompt) - 1:], served)) < TOLERANCE


def test_stats_know_the_held_share_and_the_second_pool(params, engine):
    before = engine.stats()
    engine.submit(prompt_of(CHUNK + 3, 3), max_new_tokens=4).result(timeout=180)
    after = engine.stats()
    ssm, moe, kv = after["ssm"], after["moe"], after["kv"]
    row = N_KDA * (HEADS * DK * DK + 3 * 3 * HEADS * DK) * 4   # float32 model
    assert ssm["bytes_per_slot"] == row and ssm["pool_bytes"] == 3 * row
    delta = {k: ssm[k] - before["ssm"][k] for k in ssm}
    assert delta["state_resets"] == 1
    assert delta["prefix_reuse_skipped"] == 1      # the prompt fills a page
    assert delta["prefill_tokens_valid"] == CHUNK + 3
    assert delta["prefill_tokens_computed"] == 2 * CHUNK
    assert 3 <= delta["decode_rows_live"] <= 4
    # The routing counters: every layer is an expert layer, the router
    # chooses among 8, 4 are held, and the held experts' assignments are a
    # part of all that were made.
    assert (moe["expert_layers"], moe["num_experts"],
            moe["experts_held"]) == (N_MOE, 8, 4)
    calls = moe["calls"] - before["moe"]["calls"]
    assert calls == delta["calls"] > 0
    routed = moe["assignments"] - before["moe"]["assignments"]
    rows_computed = (delta["prefill_tokens_computed"]
                     + delta["decode_rows_computed"])
    assert routed == rows_computed * CFG.experts_per_token * N_MOE
    held = moe["held_assignments"] - before["moe"]["held_assignments"]
    assert 0 < held < routed
    assert len(moe["per_expert"]) == 8
    hit = moe["experts_hit_sum"] - before["moe"]["experts_hit_sum"]
    assert 0 < hit <= N_MOE * calls * 4
    # No prefix cache, by what the model is.
    assert kv["roots"] == [] and kv["prefix_cache_pages"] == 0
    assert kv["prefill_tokens_skipped"] == 0


def test_a_reused_slot_and_a_prompt_sent_twice(params):
    """One slot: a long tenant, then a short prompt in its place, then the
    same short prompt again. Without snapshots of the state no prefix is
    reused: each time the answer is the reference's."""
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
    assert stats["kv"]["prefill_tokens_skipped"] == 0


def test_bfloat16_is_held_to_the_serving_cells_limits():
    """The model served as a serving cell would serve it, bfloat16 weights,
    activations and convolution rows (the state and the choice bias stay
    float32), against the float32 reference on the same weights, at
    `bench/serve_cell.py`'s tolerances; and bfloat16 is what the float32
    tolerance above refuses.

    A router input rounded to bfloat16 now and then swaps a token's last
    chosen expert for the next one, and at 64 channels one swapped expert
    moves that position's logits by more than either limit. So a flip is
    told from a fault: the program's own choices (its whole-sequence form,
    bfloat16) are set against `reference.routing_layerwise`, and every
    compared position whose choices agree in all eight layers is held to
    both limits."""
    import serve_cell

    from ray_tpu.models import transformer

    cfg = replace(CFG, dtype=jnp.bfloat16)
    params = seeded_params(cfg)
    assert params["layers"]["kda"]["w_qkv"].dtype == jnp.bfloat16
    assert params["layers"]["moe"]["router_bias"].dtype == jnp.float32
    chosen = jax.jit(lambda p, t: transformer._hybrid_layers(
        p, transformer._embed_tokens(p, t, cfg), cfg, None, None)[1]["experts"])
    eng = engine_for(params, cfg)
    worst, held = 0.0, 0
    try:
        assert eng._tail["rec"]["state"].dtype == jnp.float32
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
                # A flip at any earlier position reaches this one through
                # the attention layers and the state: hold the positions
                # of a sequence without one.
                if flips.any():
                    continue
                rel = rel_rms(first, ref[0])
                worst = max(worst, rel)
                assert rel <= serve_cell.LOGITS_TOLERANCE, (seed, length)
                for margin in margins(ref, served):
                    held += 1
                    assert margin <= serve_cell.MARGIN_TOLERANCE, (
                        seed, length, margin)
    finally:
        eng.shutdown()
    assert held >= 16, held                 # of 48 compared positions
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


KINDS = ("full_attention", "kda", "kda", "kda")
REFUSED = {
    "an unknown kind": (dict(layer_pattern=KINDS + ("full_attention", "kda",
                                                    "rwkv", "kda")),
                        "unknown layer kinds"),
    "delta-rule and Mamba layers together": (
        dict(layer_pattern=KINDS + ("full_attention", "kda", "mamba", "kda")),
        r"\['kda', 'mamba'\] in one model"),
    "delta-rule and conv layers together": (
        dict(layer_pattern=KINDS + ("full_attention", "conv", "kda", "kda")),
        r"\['conv', 'kda'\] in one model"),
    "one kind alone": (dict(layer_pattern=("kda",) * 8), "both kinds"),
    "a pattern of another length": (dict(n_layers=4), "names 8 layers"),
    "latent attention": (dict(kv_lora_rank=16), "latent attention beside"),
    "a held share that does not divide": (dict(experts_held=3),
                                          "must divide num_experts"),
    "a share past the last": (dict(expert_share=2), "name one of the shares"),
    "heads without a width": (dict(kda_head_dim=0), "kda_num_heads and"),
}


@pytest.mark.parametrize("case", REFUSED)
def test_a_hybrid_that_is_not_written_is_refused_by_name(case):
    change, says = REFUSED[case]
    with pytest.raises(ValueError, match=says):
        init_params(jax.random.PRNGKey(0), replace(CFG, **change))
