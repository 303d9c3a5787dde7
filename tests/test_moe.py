"""The expert block and the model built on it (OLMoE's layer at toy size,
`tiny_olmoe`), on the CPU with seeded float32 weights: the block against a
per-token loop, the engine and the train step against the benchmark's plain
reference `bench/reference/olmoe.py`, and the routing counters.

Tolerances: both sides compute in float32 here, so they differ by the order
of accumulation alone: 1e-6 of a logit's size, 1e-5 after two layers of
sums. Each limit below is 1e-4 or tighter: a hundred times that, and a
hundred times under what bfloat16 anywhere on the path (3e-3 a rounding,
1e-2 after two layers) or a wrong term would give."""

import os
import sys
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, forward, init_params, loss_fn
from ray_tpu.models.generate import generate
from ray_tpu.parallel.moe import load_balancing_loss, moe_block
from ray_tpu.serve.llm import ContinuousBatchingEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import olmoe as reference  # noqa: E402

CFG = configs.get_config("tiny_olmoe")
DIMS = {
    "vocab_size": CFG.vocab_size, "d_model": CFG.d_model, "d_ff": CFG.d_ff,
    "n_layers": CFG.n_layers, "n_heads": CFG.n_heads,
    "n_kv_heads": CFG.n_kv_heads, "head_dim": CFG.head_dim,
    "norm_eps": CFG.norm_eps, "rope_theta": CFG.rope_theta,
    "num_experts": CFG.num_experts,
    "experts_per_token": CFG.experts_per_token,
    "norm_topk_prob": CFG.norm_topk_prob,
}
TOLERANCE = 1e-4


def seeded_params(cfg=CFG, seed=0):
    """`init_params` with the norm scales drawn too (ones would hide a
    QK-norm taken over the wrong extent behind its scale's symmetry)."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 1)
    for i, name in enumerate(("attn_norm", "mlp_norm", "q_norm", "k_norm")):
        leaf = params["layers"][name]
        params["layers"][name] = jax.random.uniform(
            jax.random.fold_in(key, i), leaf.shape, leaf.dtype, 0.5, 1.5)
    return params


def layer_of(params, i=0):
    return jax.tree.map(lambda a: a[i], params["layers"])


def per_token_loop(h, lp, cfg):
    """sum_j w_j * expert_{e_j}(h_t), a token and a choice at a time."""
    h = np.asarray(h, np.float64)
    lp = jax.tree.map(lambda a: np.asarray(a, np.float64), lp)
    logits = h @ lp["router"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    out = np.zeros_like(h)
    counts = np.zeros(cfg.num_experts, np.int64)
    for t in range(h.shape[0]):
        top = np.argsort(-probs[t], kind="stable")[:cfg.experts_per_token]
        w = probs[t, top]
        if cfg.norm_topk_prob:
            w = w / w.sum()
        for e, w_e in zip(top, w):
            gate = h[t] @ lp["w_gate"][e]
            inner = gate / (1.0 + np.exp(-gate)) * (h[t] @ lp["w_up"][e])
            out[t] += w_e * (inner @ lp["w_down"][e])
            counts[e] += 1
    return out, counts


def routed(lp, favoured, strength=8.0):
    """`lp` with a router that sends every token whose first feature is 1
    to the `favoured` experts (and leaves the others without a token)."""
    router = np.asarray(lp["router"]).copy()
    router[0, :] = -strength
    router[0, list(favoured)] = strength
    return {**lp, "router": jnp.asarray(router)}


def block(h, lp, cfg=CFG):
    return jax.jit(lambda h, lp: moe_block(h, lp, cfg))(h, lp)


@pytest.mark.parametrize("case", ["even", "uneven", "empty experts",
                                  "renormalised"])
def test_block_equals_a_per_token_loop_over_its_experts(case):
    cfg = replace(CFG, norm_topk_prob=case == "renormalised")
    lp = layer_of(seeded_params())
    h = jax.random.normal(jax.random.PRNGKey(3), (24, cfg.d_model))
    if case != "even":
        h = h.at[:, 0].set(1.0)
    if case == "uneven":        # expert 3 in every token's choice
        lp = routed(lp, [3], strength=4.0)
    if case == "empty experts":  # experts 5, 6, 7 never chosen
        lp = routed(lp, range(5), strength=4.0)
    y, stats = block(h, lp, cfg)
    want, counts = per_token_loop(h, lp, cfg)
    assert np.array_equal(np.asarray(stats["counts"]), counts)
    assert counts.sum() == 24 * cfg.experts_per_token
    if case == "uneven":
        assert counts[3] == 24
    if case == "empty experts":
        assert (counts[5:] == 0).all() and (counts[:5] > 0).all()
    scale = np.sqrt(np.mean(want ** 2))
    assert np.abs(np.asarray(y) - want).max() / scale < TOLERANCE


def test_block_is_the_same_under_a_permutation_of_the_tokens():
    lp = layer_of(seeded_params())
    h = jax.random.normal(jax.random.PRNGKey(4), (32, CFG.d_model))
    perm = jax.random.permutation(jax.random.PRNGKey(5), 32)
    y, stats = block(h, lp)
    y_perm, stats_perm = block(h[perm], lp)
    # A token's row meets the same matrices in the same order wherever it
    # sorts to, so only the tiles' edges can differ: float32 rounding.
    np.testing.assert_allclose(np.asarray(y_perm), np.asarray(y)[perm],
                               rtol=1e-5, atol=1e-6)
    assert np.array_equal(stats["counts"], stats_perm["counts"])


def test_no_assignment_is_lost_when_two_experts_take_every_token():
    """40 tokens, all on experts 0 and 1 of 8: the capacity the old dispatch
    gave an expert was 1.25 * 40 * 2 / 8 = 12 rows, and it dropped the
    other 28 of each."""
    lp = routed(layer_of(seeded_params()), [0, 1])
    h = jax.random.normal(jax.random.PRNGKey(6), (40, CFG.d_model))
    h = h.at[:, 0].set(1.0)
    y, stats = block(h, lp)
    assert np.asarray(stats["counts"]).tolist() == [40, 40, 0, 0, 0, 0, 0, 0]
    assert set(np.asarray(stats["experts"]).reshape(-1).tolist()) == {0, 1}
    want, _ = per_token_loop(h, lp, CFG)
    scale = np.sqrt(np.mean(want ** 2))
    assert np.abs(np.asarray(y) - want).max() / scale < TOLERANCE
    assert (np.abs(np.asarray(y)).sum(-1) > 0).all()  # every token served


def test_block_reads_a_layer_in_place_from_the_whole_stack():
    params = seeded_params()
    h = jax.random.normal(jax.random.PRNGKey(7), (16, CFG.d_model))
    for i in range(CFG.n_layers):
        lp = layer_of(params, i)
        y, stats = block(h, lp)
        whole = {**params["layers"], "router": lp["router"]}
        y_at, stats_at = jax.jit(
            lambda h, lp, i: moe_block(h, lp, CFG, i))(h, whole, jnp.int32(i))
        np.testing.assert_allclose(np.asarray(y_at), np.asarray(y),
                                   rtol=1e-6, atol=1e-7)
        assert np.array_equal(stats["counts"], stats_at["counts"])


def rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def reference_logits(params, tokens):
    tokens = jnp.asarray(tokens, jnp.int32)
    hidden = reference.hidden_layerwise(params, tokens, DIMS)
    return np.asarray(reference.logits_rows(params, hidden, DIMS))


def engine_for(params, cfg, **kw):
    return ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=64,
                                    prefill_chunk=8, page_size=8, **kw)


PROMPT = [int(t) for t in np.random.default_rng(11).integers(0, 256, 21)]


def test_engine_prefill_then_decode_through_pages_against_the_reference():
    """Prefill in three chunks into pages, then eight greedy decode steps
    through the block table, against the reference's one full forward pass
    over prompt + tokens: the prefill's logits outright, and every served
    token by how far the reference's logit for it lies under the
    reference's largest (token equality would hang on float32 near-ties)."""
    params = seeded_params()
    eng = engine_for(params, CFG)
    try:
        first = eng.prefill_logits(PROMPT)
        served = eng.submit(PROMPT, max_new_tokens=8).result(timeout=120)
    finally:
        eng.shutdown()
    ref = reference_logits(params, PROMPT + served[:-1])
    assert rel_rms(first, ref[len(PROMPT) - 1]) < TOLERANCE
    for i, token in enumerate(served):
        row = ref[len(PROMPT) - 1 + i]
        margin = (row.max() - row[token]) / np.sqrt(np.mean(row ** 2))
        assert margin < TOLERANCE, (i, token, int(row.argmax()), margin)


@pytest.mark.parametrize("rows", [2, 4])
def test_a_prompt_prefilled_as_rows_of_one_pass_against_the_reference(rows):
    """The prompt's three chunks of 8 as rows of ONE call of the prefill
    program (`rows` 4: all three and an inert row; `rows` 2: two, then the
    third beside an inert row): the last real row's logits against the
    reference's forward pass over the whole prompt, every expert product
    over the rows' tokens at once, and the accumulator counting programs
    and every row they routed."""
    from ray_tpu.serve import paged_kv

    params = seeded_params()
    chunk, page, pages, max_len = 8, 8, 8, 64
    cache = paged_kv.init_paged_cache(CFG, 2, 2 * pages + 1, page, pages)
    table = jnp.asarray(1 + np.arange(2 * pages, dtype=np.int32).reshape(2, -1))
    k, v, lengths = cache["k"], cache["v"], cache["lengths"]
    moe = paged_kv.init_routing_counters(CFG)
    offsets = list(range(0, len(PROMPT), chunk))
    calls = 0
    while offsets:
        now, offsets = offsets[:rows], offsets[rows:]
        tokens = np.zeros((rows, chunk), np.int32)
        n_valid, slot, offset = np.zeros((3, rows), np.int32)
        for r, off in enumerate(now):
            piece = PROMPT[off:off + chunk]
            tokens[r, :len(piece)] = piece
            n_valid[r], slot[r], offset[r] = len(piece), 1, off
        logits, k, v, lengths, moe = paged_kv.prefill_chunk_paged(
            params, jnp.asarray(tokens), n_valid, slot, offset, k, v, lengths,
            table, CFG, max_len, None, moe)
        calls += 1
        last = len(now) - 1
    ref = reference_logits(params, PROMPT)
    assert rel_rms(np.asarray(logits[last]), ref[-1]) < TOLERANCE
    assert np.asarray(lengths).tolist() == [0, len(PROMPT)]
    assert int(moe["calls"]) == calls == -(-3 // rows)
    assert int(moe["assignments"].sum()) == (
        calls * rows * chunk * CFG.experts_per_token * CFG.n_layers)


@pytest.mark.parametrize("mistake", ["QK-norm per head",
                                     "top-k weights renormalised"])
def test_the_reference_comparison_catches_a_wrong_layer(mistake):
    """The same comparison, with the program's model wrong in one of the
    two ways OLMoE differs from the models beside it, reads a hundred
    times over the limit."""
    params = seeded_params()
    if mistake == "QK-norm per head":
        cfg = replace(CFG, qk_norm_extent="head")
        wrong = {**params, "layers": {
            **params["layers"],
            "q_norm": params["layers"]["q_norm"][:, :CFG.head_dim],
            "k_norm": params["layers"]["k_norm"][:, :CFG.head_dim]}}
    else:
        cfg, wrong = replace(CFG, norm_topk_prob=True), params
    eng = engine_for(wrong, cfg)
    try:
        first = eng.prefill_logits(PROMPT)
    finally:
        eng.shutdown()
    ref = reference_logits(params, PROMPT)
    assert rel_rms(first, ref[-1]) > 100 * TOLERANCE


def test_loss_and_gradients_against_the_reference():
    params = seeded_params()
    tokens = jax.random.randint(jax.random.PRNGKey(8), (33,), 0,
                                CFG.vocab_size)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, tokens[None], CFG)))(params)
    ref_loss, ref_grads = reference.loss_and_grads(params, tokens, DIMS)
    assert abs(float(loss) - float(ref_loss)) < TOLERANCE * float(ref_loss)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = dict(jax.tree_util.tree_flatten_with_path(ref_grads)[0])
    assert len(flat) == len(ref_flat)
    for path, g in flat:
        # 1e-3 of a leaf's own size: the gradients pass through every sum
        # of the forward pass twice, and the router's are differences of
        # nearly equal terms.
        assert rel_rms(g, ref_flat[path]) < 1e-3, jax.tree_util.keystr(path)
    assert float(jnp.abs(grads["layers"]["router"]).sum()) > 0


def test_load_balancing_term_alone_against_its_formula():
    """E * sum_e P_e * sum_j f_{j,e} over all layers' tokens together, by
    the program (from its per-layer means and counts), by the reference
    (from one-hot masks) and by hand from the program's own choices."""
    params = seeded_params()
    tokens = jax.random.randint(jax.random.PRNGKey(9), (1, 40), 0,
                                CFG.vocab_size)
    _, aux = forward(params, tokens, CFG)
    x = params["embed"][tokens[0]]
    probs, chosen = [], []
    for i in range(CFG.n_layers):
        x, p, c = reference.layer(x, layer_of(params, i), DIMS)
        probs.append(p)
        chosen.append(c)
    want = reference.load_balancing(jnp.concatenate(probs),
                                    jnp.concatenate(chosen), DIMS)
    assert abs(float(aux) - float(want)) < TOLERANCE * float(want)
    n, k, e = 40 * CFG.n_layers, CFG.experts_per_token, CFG.num_experts
    f = np.zeros((k, e))
    for row in np.asarray(jnp.concatenate(chosen)):
        for j, expert in enumerate(row):
            f[j, expert] += 1.0 / n
    by_hand = e * float((np.asarray(jnp.concatenate(probs)).mean(0)
                         * f.sum(0)).sum())
    assert abs(float(aux) - by_hand) < TOLERANCE * by_hand
    # Even routing gives k: all probabilities 1/E, all shares k/E.
    even = load_balancing_loss(jnp.full((3, e), 1.0 / e),
                               jnp.full((3, e), 16 * k // e), 16)
    assert abs(float(even) - k) < 1e-6


def test_generate_on_the_expert_model_follows_the_reference():
    params = seeded_params()
    prompt = jnp.asarray([PROMPT[:9]], jnp.int32)
    out = np.asarray(generate(params, prompt, CFG, max_new_tokens=6))[0]
    ref = reference_logits(params, PROMPT[:9] + out[:-1].tolist())
    for i, token in enumerate(out.tolist()):
        row = ref[8 + i]
        assert (row.max() - row[token]) / np.sqrt(np.mean(row ** 2)) \
            < TOLERANCE


def test_engine_counts_every_assignment_and_fetches_them_in_stats_only():
    params = seeded_params()
    eng = engine_for(params, CFG)
    try:
        assert eng.stats()["moe"]["assignments"] == 0  # warm-up not counted
        eng.submit(PROMPT, max_new_tokens=6).result(timeout=120)
        eng.submit(PROMPT[:5], max_new_tokens=3,
                   temperature=0.7).result(timeout=120)
        # The loop publishes its last turn and goes idle: `stats()` fetches
        # the device's counters before it reads a ledger that publishes at
        # a turn's end, and in between a turn may end.
        time.sleep(0.6)
        stats = eng.stats()
    finally:
        eng.shutdown()
    moe, phases = stats["moe"], stats["timing"]["phases"]
    passes, steps = stats["timing"]["prefill_chunks"], \
        phases["decode_dispatch"]["n"]
    # The first prompt's three chunks are one pass of four rows (one
    # inert), the second prompt's one chunk a pass of one.
    assert stats["timing"]["prefill_rows"] == 3 + 1
    assert passes == 1 + 1 and steps >= 5 + 2
    # Every row a step program computed: a chunk's 8 in each row of a pass
    # (padding and an inert row too), a decode step's 2 slots (idle ones
    # too), k experts each, in every layer.
    rows = (4 + 1) * 8 + steps * 2
    k, layers = CFG.experts_per_token, CFG.n_layers
    assert moe["calls"] == passes + steps
    assert moe["assignments"] == rows * k * layers
    assert sum(moe["per_expert"]) == moe["assignments"]
    assert len(moe["per_expert"]) == CFG.num_experts
    calls = moe["calls"] * layers
    assert k <= moe["experts_hit_sum"] / calls <= CFG.num_experts
    assert moe["max_load_sum"] / calls >= rows * k / moe["calls"] \
        / CFG.num_experts
    # The phase ledger's keys are what they were: no new host phase.
    assert "moe" not in " ".join(phases)
    dense = ContinuousBatchingEngine(
        init_params(jax.random.PRNGKey(0), configs.tiny), configs.tiny,
        num_slots=2, max_len=32)
    try:
        assert "moe" not in dense.stats()
    finally:
        dense.shutdown()


def _block_as_it_was(h, lp, cfg, layer=None):
    """`moe_block` as PR 51 left it (softmax scores, every expert held,
    none shared), kept here word for word as the yardstick of `unchanged`."""
    from ray_tpu.models.transformer import _act
    from ray_tpu.parallel.moe import EXPERT_LEAVES, grouped_matmul

    tokens = h.shape[0]
    k, num_experts = cfg.experts_per_token, cfg.num_experts
    logits = jnp.dot(h, lp["router"], preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, chosen = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        weights = weights / weights.sum(-1, keepdims=True)
    flat = chosen.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    counts = jnp.bincount(flat, length=num_experts).astype(jnp.int32)
    rows = h[order // k]
    stacks = [lp[name] for name in EXPERT_LEAVES]
    group_sizes = counts
    if layer is not None:
        depth = stacks[0].shape[0]
        stacks = [w.reshape((depth * num_experts,) + w.shape[2:])
                  for w in stacks]
        group_sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((depth * num_experts,), jnp.int32), counts,
            (layer * num_experts,))
    w_gate, w_up, w_down = stacks
    inner = (_act(cfg)(grouped_matmul(rows, w_gate, group_sizes))
             * grouped_matmul(rows, w_up, group_sizes))
    out = grouped_matmul(inner.astype(h.dtype), w_down, group_sizes)
    out = out[jnp.argsort(order)].reshape(tokens, k, -1)
    return jnp.einsum("tk,tkd->td", weights, out).astype(h.dtype), counts


@pytest.mark.parametrize("renormalised", [False, True])
@pytest.mark.parametrize("in_place", [False, True])
def test_the_softmax_block_of_a_whole_model_is_unchanged_bit_for_bit(
        renormalised, in_place):
    """What a held share, sigmoid scores and a shared expert added to the
    block changes no bit of a model that has none of them."""
    cfg = replace(CFG, norm_topk_prob=renormalised)
    params = seeded_params()
    h = jax.random.normal(jax.random.PRNGKey(11), (24, cfg.d_model))
    if in_place:
        lp = dict(layer_of(params, 1),
                  **{n: params["layers"][n] for n in
                     ("w_gate", "w_up", "w_down")})
        args = (h, lp, cfg, jnp.int32(1))
    else:
        args = (h, layer_of(params, 1), cfg)
    want, want_counts = jax.jit(_block_as_it_was, static_argnums=2)(*args)
    got, stats = jax.jit(moe_block, static_argnums=2)(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(stats["counts"]),
                                  np.asarray(want_counts))


# The grouped products the five sparse serving cells launch, `(m, k, n)` of
# the gate and up product and of the down product at a decode step's rows
# (`bench/configs/`: OLMoE, dots.vlm1's share, LFM2-24B-A2B's stage,
# Solar-Open2's share, SDAR-30B-A3B's stage at a block pass's rows), and the
# weight tile each was fastest or within a point of fastest with on the chip
# (`tools/gmm_sweep.py`, PR 64; SDAR's, PR 68).
CELL_PRODUCTS = {
    "olmoe gate-up": ((128, 2048, 1024), (2048, 1024)),
    "olmoe down": ((128, 1024, 2048), (1024, 2048)),
    "dots gate-up": ((512, 7168, 2048), (1024, 2048)),
    "dots down": ((512, 2048, 7168), (2048, 1024)),
    "lfm2 gate-up": ((384, 2048, 1536), (2048, 768)),
    "lfm2 down": ((384, 1536, 2048), (1536, 1024)),
    "solar gate-up": ((1024, 4096, 1280), (4096, 640)),
    "solar down": ((1024, 1280, 4096), (1280, 1024)),
    "sdar gate-up": ((3072, 2048, 768), (2048, 768)),
    "sdar down": ((3072, 768, 2048), (768, 2048)),
}
OTHER_PRODUCTS = {
    "a pass's rows": ((4096, 4096, 1280), (4096, 640)),
    "float32 operands": ((128, 2048, 1536), (2048, 512)),
    # 1,408 is 11 x 128: whole or not at all, and `k` whole does not fit it.
    "a width only itself divides": ((128, 4096, 1408), (1024, 1408)),
    # 4,736 is 37 x 128 and does not fit whole: the plain tile, remainders
    # and all, as every width had before the rule.
    "no divisor fits": ((128, 4096, 4736), (1024, 1024)),
    "no multiple of 128 divides": ((128, 5000, 2048), (1024, 1024)),
    "toy widths": ((24, 64, 96), (64, 96)),
}


def step_bytes(tiling, itemsize):
    """What one grid step of the kernel holds in VMEM: two buffers of the
    weight tile, of the rows and of the float32 result, and the float32
    accumulator."""
    tm, tk, tn = tiling
    return 2 * (tk * tn + tm * tk) * itemsize + 3 * tm * tn * 4


@pytest.mark.parametrize("product", [*CELL_PRODUCTS, *OTHER_PRODUCTS])
def test_the_tiles_of_a_grouped_product_divide_its_shape(product):
    """`gmm_tiling` from `(m, k, n)` and the item size alone: no remainder
    in `k` (the kernel would mask both operands on the last step) or in `n`
    (a step mostly past the edge) wherever a multiple of 128 of at least
    half the plain tile divides and fits the bound on a step's bytes, `k`
    in one tile where that fits (the rows' tile is then fetched once a row
    tile and not once a group), the plain tile of 1,024 where nothing
    divides."""
    from ray_tpu.parallel import moe

    itemsize = 4 if product == "float32 operands" else 2
    (m, k, n), tile = {**CELL_PRODUCTS, **OTHER_PRODUCTS}[product]
    tm, tk, tn = moe.gmm_tiling(m, k, n, itemsize)
    assert tm == min(128, -(-m // 8) * 8)
    assert (tk, tn) == tile
    assert step_bytes((tm, tk, tn), itemsize) <= moe._STEP_BYTES < 16 << 20
    if product.startswith("no "):
        assert (k % tk, n % tn) != (0, 0)
    else:
        assert (k % tk, n % tn) == (0, 0)
    if product in CELL_PRODUCTS:
        assert tk % 128 == 0 and tn % 128 == 0 and min(tk, tn) >= 512
        # `k` whole wherever it fits beside half a plain tile of `n`.
        assert (tk == k) == (step_bytes((tm, k, 512), 2) <= moe._STEP_BYTES)


def per_group_products(x, w, sizes):
    """Each group's rows times its own matrix, a `jnp.dot` a group in
    float32; rows behind the last group stay zero."""
    out = np.zeros((x.shape[0], w.shape[-1]), np.float32)
    start = 0
    for g, size in enumerate(np.asarray(sizes)):
        if size:
            out[start:start + size] = jnp.dot(
                x[start:start + size].astype(jnp.float32),
                w[g].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
        start += size
    return out


@pytest.mark.parametrize("k,n", [(512, 1280), (1280, 512), (2560, 1280),
                                 (512, 1536), (1536, 512)])
@pytest.mark.parametrize("case", ["ragged", "empty groups", "rows behind",
                                  "one layer of a stack"])
def test_grouped_matmul_at_widths_of_1280_and_1536(k, n, case):
    """The kernel in the interpreter with the rule's tiles at the widths
    that are no whole tile of 1,024, against a plain product a group: the
    order of the float32 sums over `k` is all that may differ."""
    from ray_tpu.parallel.moe import gmm_tiling, grouped_matmul

    sizes = {"ragged": [5, 1, 130, 3, 17, 40],
             "empty groups": [0, 9, 0, 0, 131, 0],
             # A held share: 37 of the 200 rows belong to groups held here.
             "rows behind": [4, 0, 6, 20, 0, 7],
             # Layer 1 of 3 in a stack of 3 x 2 groups.
             "one layer of a stack": [0, 0, 11, 150, 0, 0]}[case]
    m = 200 if case == "rows behind" else sum(sizes)
    dtype = jnp.bfloat16 if (k, n) == (512, 1280) else jnp.float32
    x = jax.random.normal(jax.random.PRNGKey(k), (m, k), dtype)
    w = jax.random.normal(jax.random.PRNGKey(n), (len(sizes), k, n), dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    tm, tk, tn = gmm_tiling(m, k, n, x.dtype.itemsize)
    assert k % tk == 0 and n % tn == 0 and 1024 < max(tk, tn) <= 2560
    live = int(sizes.sum())
    got = np.asarray(jax.jit(grouped_matmul)(x, w, sizes))[:live]
    want = per_group_products(x, w, sizes)[:live]
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_grouped_matmul_gradients_against_a_plain_product_a_group():
    """The cotangents of the rows and of the weights through the kernel's
    own backward products (which keep the plain tile whatever tile the
    forward product took), against `jax.grad` of a product a group."""
    from ray_tpu.parallel.moe import gmm_tiling, grouped_matmul

    m, k, n, sizes = 50, 2048, 1280, [7, 0, 30, 13]
    assert gmm_tiling(m, k, n, 4)[1:] not in ((k, n), (1024, 1024))
    x = jax.random.normal(jax.random.PRNGKey(0), (m, k), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (len(sizes), k, n))
    weigh = jax.random.normal(jax.random.PRNGKey(2), (m, n))
    starts = np.concatenate([[0], np.cumsum(sizes)])

    def plain(x, w):
        rows = [jnp.dot(x[a:b], w[g], precision=jax.lax.Precision.HIGHEST)
                for g, (a, b) in enumerate(zip(starts, starts[1:]))]
        return (jnp.concatenate(rows) * weigh).sum()

    def kernel(x, w):
        return (grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32))
                * weigh).sum()

    want = jax.grad(plain, argnums=(0, 1))(x, w)
    got = jax.jit(jax.grad(kernel, argnums=(0, 1)))(x, w)
    for g, wnt in zip(got, want):
        assert g.shape == wnt.shape
        assert np.abs(np.asarray(g - wnt)).max() <= 1e-5 * np.abs(wnt).max()
    assert not np.asarray(got[1][1]).any()   # a group without rows
