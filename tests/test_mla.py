"""Latent attention and the model built on it (dots.vlm1.inst's language
model at toy size, `tiny_dots`: a query bottleneck, a 32-wide latent and an
8-wide rope key a token, one leading dense layer, sigmoid scores chosen
within 2 of 4 groups of 16 experts, a shared expert, YaRN), on the CPU with
seeded float32 weights, against the benchmark's plain reference
`bench/reference/dots_vlm1.py`.

Tolerances: both sides compute in float32 here, so they differ by the order
of accumulation alone (1e-6 of a logit's size a sum, 1e-5 after three
layers). Each limit below is 1e-4: a hundred times under what bfloat16
anywhere on the path or a wrong term would give."""

import math
import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, forward, init_params
from ray_tpu.models.transformer import rope_tables
from ray_tpu.parallel.moe import moe_block
from ray_tpu.serve import paged_kv
from ray_tpu.serve.llm import ContinuousBatchingEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import dots_vlm1 as reference  # noqa: E402

CFG = configs.get_config("tiny_dots")
TOLERANCE = 1e-4
EXTRA = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size",
         "num_experts", "experts_held", "expert_share", "n_shared_experts",
         "experts_per_token", "n_group", "topk_group",
         "routed_scaling_factor", "norm_topk_prob", "scoring_func",
         "first_k_dense_replace", "rope_factor",
         "rope_original_max_position", "rope_beta_fast", "rope_beta_slow",
         "rope_mscale", "rope_mscale_all_dim")


def dims_of(cfg):
    return {name: getattr(cfg, name) for name in (
        "vocab_size", "d_model", "d_ff", "n_layers", "n_heads", "n_kv_heads",
        "head_dim", "norm_eps", "rope_theta") + EXTRA}


DIMS = dims_of(CFG)


def seeded_params(cfg=CFG, seed=0):
    """`init_params` with the norm scales and the router's bias drawn too:
    ones would hide a norm over the wrong extent, and a zero bias the
    difference between choosing by score + bias and weighing by score."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    key = jax.random.PRNGKey(seed + 1)
    for kind, stack in params["layers"].items():
        for i, name in enumerate(sorted(stack)):
            k = jax.random.fold_in(key, i + 100 * (kind == "moe"))
            if name.endswith("norm"):
                stack[name] = jax.random.uniform(
                    k, stack[name].shape, stack[name].dtype, 0.5, 1.5)
            elif name == "router_bias":
                stack[name] = 0.3 * jax.random.normal(k, stack[name].shape)
    return params


def tokens_of(n, seed=3):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, CFG.vocab_size))


def reference_logits(params, tokens, dims=DIMS):
    hidden = reference.hidden_layerwise(params, jnp.asarray(tokens), dims)
    return np.asarray(reference.logits_rows(params, hidden, dims))


def test_forward_logits_against_the_reference():
    params, tokens = seeded_params(), tokens_of(50)
    logits, _ = forward(params, jnp.asarray(tokens)[None], CFG)
    ref = reference_logits(params, tokens)
    assert np.abs(np.asarray(logits[0]) - ref).max() < TOLERANCE * np.abs(
        ref).max()


@pytest.mark.parametrize("chunk", [16, 8])
def test_prefill_then_decode_through_the_latent_pool(chunk):
    """Pages of 8 and, at 128 positions a slot, blocks of 16 rows: a
    37-token prompt crosses two (or four) chunk boundaries, four page
    boundaries and two blocks, and the 8 decoded tokens cross another page.
    Prefill in the expanded form, decode in the absorbed one."""
    assert paged_kv.latent_block_pages(8, 128 // 8) * 8 == 16
    params, prompt = seeded_params(), tokens_of(37)
    eng = ContinuousBatchingEngine(params, CFG, num_slots=4, max_len=128,
                                   prefill_chunk=chunk, page_size=8)
    try:
        ref = reference_logits(params, prompt)
        first = eng.prefill_logits(prompt)
        assert np.abs(first - ref[-1]).max() < TOLERANCE * np.abs(ref).max()
        served = eng.submit(prompt, max_new_tokens=8).result()
        seq = list(prompt)
        for token in served:  # greedy: the reference's full forward each
            assert int(reference_logits(params, np.asarray(seq))[-1].argmax()
                       ) == token
            seq.append(token)
    finally:
        eng.shutdown()


def test_the_pool_is_one_and_a_row_is_a_latent_and_a_rope_key():
    cache = paged_kv.init_paged_cache(CFG, 4, 9, 8, 4)
    assert cache["v"] is None
    # 32 + 8 values of a token, and zeros up to whole lanes of 128 (at the
    # published 512 + 64: 640; a row of 576 had the chip's compiler turn
    # the whole pool into another layout and back in every step).
    assert cache["k"].shape == (CFG.n_layers, 9, 8, 128)
    big = configs.get_config("dots-vlm1")
    assert paged_kv.latent_row_width(big) == 640


def test_absorbed_equals_expanded():
    """`_latent_attention` in its two forms over one pool of random rows:
    two batch rows of 5 queries at different lengths, tables that name
    pages out of order, three blocks of 8 rows for the longer row."""
    h, dn, dr = CFG.n_heads, CFG.qk_nope_head_dim, CFG.qk_rope_head_dim
    rank, dv = CFG.kv_lora_rank, CFG.v_head_dim
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    lp = {"w_uk": jax.random.normal(keys[0], (rank, h * dn)) * rank ** -0.5,
          "w_uv": jax.random.normal(keys[1], (rank, h * dv)) * rank ** -0.5}
    q_n = jax.random.normal(keys[2], (2, 5, h, dn))
    q_r = jax.random.normal(keys[3], (2, 5, h, dr))
    pool = jax.random.normal(
        keys[4], (2, 17, 8, paged_kv.latent_row_width(CFG)))
    tables = jnp.asarray([[3, 1, 2, 9, 0, 0, 0, 0], [16, 5, 4, 0, 0, 0, 0, 0]],
                         jnp.int32)
    assert paged_kv.latent_block_pages(8, 8) == 1
    end = jnp.asarray([23, 11], jnp.int32)
    q_pos = end[:, None] - 5 + jnp.arange(5, dtype=jnp.int32)[None]
    a, b = (paged_kv._latent_attention(q_n, q_r, lp, pool, 1, tables, q_pos,
                                       end, CFG, absorbed=form)
            for form in (False, True))
    assert a.shape == (2, 5, h * dv)
    assert np.abs(np.asarray(a)).max() > 0.1
    assert np.abs(np.asarray(a - b)).max() < TOLERANCE * np.abs(
        np.asarray(a)).max()


def test_the_yarn_table_against_the_formula():
    dr, theta = CFG.qk_rope_head_dim, CFG.rope_theta
    factor, original = CFG.rope_factor, CFG.rope_original_max_position

    def pair_of(rotations):
        return dr * math.log(original / (2 * math.pi * rotations)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(CFG.rope_beta_fast)), 0)
    high = min(math.ceil(pair_of(CFG.rope_beta_slow)), dr - 1)
    assert 0 <= low < high < dr // 2 + 2  # the blend lies inside the table
    inv = []
    for i in range(dr // 2):
        f = theta ** (-2 * i / dr)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        inv.append(f / factor * ramp + f * (1 - ramp))
    cos, sin = rope_tables(CFG, 64)
    ang = np.arange(64)[:, None] * np.asarray(inv)[None, :]
    assert np.abs(np.asarray(cos) - np.cos(ang)).max() < 1e-5
    assert np.abs(np.asarray(sin) - np.sin(ang)).max() < 1e-5
    # The attention temperature: 0.1 * mscale_all_dim * ln(factor) + 1 on q
    # and on k.
    m = 0.1 * CFG.rope_mscale_all_dim * math.log(factor) + 1
    assert CFG.attention_scale == pytest.approx(CFG.head_dim ** -0.5 * m * m)
    big = configs.get_config("dots-vlm1")
    assert big.attention_scale == pytest.approx(0.07217 * 1.87385, rel=1e-4)


def layer_of(params, kind="moe", i=0):
    return jax.tree.map(lambda a: a[i], params["layers"][kind])


def routing_transcription(h, lp, cfg):
    """Chosen by `sel`, weighted by `sc`, groups masked: a token at a time
    in numpy. Returns each token's weight for every expert [T, E]."""
    h = np.asarray(h, np.float64)
    logits = h @ np.asarray(lp["router"], np.float64)
    sc = 1.0 / (1.0 + np.exp(-logits))
    sel = sc + np.asarray(lp["router_bias"], np.float64)
    e, g = cfg.num_experts, cfg.n_group
    gates = np.zeros_like(sc)
    for t in range(h.shape[0]):
        groups = sel[t].reshape(g, e // g)
        score = np.sort(groups, axis=-1)[:, -2:].sum(-1)
        kept = np.argsort(-score, kind="stable")[:cfg.topk_group]
        masked = np.full_like(groups, -np.inf)
        masked[kept] = groups[kept]
        chosen = np.argsort(-masked.reshape(-1), kind="stable")[
            :cfg.experts_per_token]
        w = sc[t, chosen]
        gates[t, chosen] = w / (w.sum() + 1e-20) * cfg.routed_scaling_factor
    return gates


def swiglu(h, gate, up, down):
    a = h @ gate
    return (a / (1.0 + np.exp(-a)) * (h @ up)) @ down


def test_routing_with_a_bias_against_a_numpy_transcription():
    params = seeded_params()
    lp = layer_of(params)
    h = jax.random.normal(jax.random.PRNGKey(5), (24, CFG.d_model))
    gates = routing_transcription(h, lp, CFG)
    # The bias moved some choice: the test would pass without one else.
    unbiased = routing_transcription(
        h, dict(lp, router_bias=jnp.zeros_like(lp["router_bias"])), CFG)
    assert ((gates > 0) != (unbiased > 0)).any()
    h64 = np.asarray(h, np.float64)
    w = jax.tree.map(lambda a: np.asarray(a, np.float64), lp)
    want = swiglu(h64, w["shared_gate"], w["shared_up"], w["shared_down"])
    for e in range(CFG.num_experts):
        want += gates[:, e:e + 1] * swiglu(h64, w["w_gate"][e], w["w_up"][e],
                                           w["w_down"][e])
    got, stats = moe_block(h, lp, CFG)
    assert np.abs(np.asarray(got) - want).max() < TOLERANCE * np.abs(
        want).max()
    assert (np.asarray(stats["counts"]) == (gates > 0).sum(0)).all()


def share_of(lp, cfg, share, held):
    """What the chip holding the `share`-th run of `held` experts has of an
    expert layer: the same router and shared expert, its own experts."""
    cut = dict(lp)
    for name in ("w_gate", "w_up", "w_down"):
        cut[name] = lp[name][share * held:(share + 1) * held]
    return cut, replace(cfg, experts_held=held, expert_share=share)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts as 4 shares of 4: the parts the shares' own experts give,
    with the shared expert (every chip's alike) counted once, equal the
    uncut layer's result, in the program and in the reference."""
    params = seeded_params()
    lp = layer_of(params)
    h = jax.random.normal(jax.random.PRNGKey(6), (24, CFG.d_model))
    whole, whole_stats = moe_block(h, lp, CFG)
    no_shared = {n: w for n, w in lp.items() if not n.startswith("shared")}
    shared = whole - moe_block(h, no_shared, CFG)[0]
    parts, counted = [], 0
    for share in range(4):
        cut, cfg = share_of(no_shared, CFG, share, 4)
        y, stats = moe_block(h, cut, cfg)
        parts.append(y)
        # The router is the whole layer's on every share.
        assert (np.asarray(stats["counts"])
                == np.asarray(whole_stats["counts"])).all()
        counted += int(stats["counts"][share * 4:(share + 1) * 4].sum())
    assert counted == 24 * CFG.experts_per_token  # each assignment once
    assert np.abs(np.asarray(sum(parts) + shared - whole)).max() < TOLERANCE
    # The reference given the same shares, on rows it norms itself (scale
    # one): each share against the program's, and the shares' sum, with the
    # shared expert every share added counted once, against the uncut layer.
    normed = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True)
                               + CFG.norm_eps)
    ones = dict(lp, mlp_norm=jnp.ones(CFG.d_model))
    ref_parts = []
    for share in range(4):
        cut, cfg = share_of(ones, CFG, share, 4)
        with jax.default_matmul_precision("highest"):
            out, _ = reference.experts(
                h, lambda name, *a, cut=cut: cut[name],
                lambda j, cut=cut: tuple(
                    cut[n][j] for n in ("w_gate", "w_up", "w_down")),
                dims_of(cfg))
        got, _ = moe_block(normed, cut, cfg)
        assert np.abs(np.asarray(out - h - got)).max() < TOLERANCE
        ref_parts.append(out - h)
    ref_whole, _ = reference.experts(
        h, lambda name, *a: ones[name],
        lambda j: tuple(ones[n][j] for n in ("w_gate", "w_up", "w_down")),
        DIMS)
    ref_shared = reference._swiglu(normed, lp["shared_gate"], lp["shared_up"],
                                   lp["shared_down"])
    assert np.abs(np.asarray(
        sum(ref_parts) - 3 * ref_shared - (ref_whole - h))).max() < TOLERANCE


def test_a_held_share_of_the_model_against_the_reference_of_that_share():
    """The whole model with 4 of its 16 experts held (share 2): forward,
    prefill and greedy decode against the reference given the same share."""
    cfg = replace(CFG, experts_held=4, expert_share=2)
    params = seeded_params(cfg)
    assert params["layers"]["moe"]["w_gate"].shape[1] == 4
    assert params["layers"]["moe"]["router"].shape[-1] == 16
    dims, prompt = dims_of(cfg), tokens_of(29)
    ref = reference_logits(params, prompt, dims)
    logits, _ = forward(params, jnp.asarray(prompt)[None], cfg)
    assert np.abs(np.asarray(logits[0]) - ref).max() < TOLERANCE * np.abs(
        ref).max()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=64,
                                   prefill_chunk=16, page_size=8)
    try:
        served = eng.submit(prompt, max_new_tokens=4).result()
        seq = list(prompt)
        for token in served:
            assert int(reference_logits(params, np.asarray(seq), dims)[
                -1].argmax()) == token
            seq.append(token)
        moe = eng.stats()["moe"]
    finally:
        eng.shutdown()
    assert (moe["experts_held"], moe["num_experts"], moe["expert_layers"]
            ) == (4, 16, 2)
    assert 0 < moe["held_assignments"] < moe["assignments"]
    assert moe["assignments"] % (cfg.experts_per_token * 2) == 0


def test_the_engine_compiles_nothing_over_32_steps_and_counts_what_it_reads():
    params = seeded_params()
    # Drawn before the engine warms up: compilations are the process's.
    prompts = [tokens_of(n, seed=n) for n in (5, 21, 40)]
    eng = ContinuousBatchingEngine(params, CFG, num_slots=4, max_len=128,
                                   prefill_chunk=16, page_size=8)
    try:
        handles = [eng.submit(p, max_new_tokens=36) for p in prompts]
        for h in handles:
            assert len(h.result()) == 36
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert stats["steps"] >= 32
    assert stats["recompiles_post_warm"] == 0
    att = stats["attention"]
    # What decode attention is given to read: the decoding slots' rows in
    # whole pages of 8 (on the chip a kernel fetches those pages and no
    # others), at least the live rows and under a page a slot a step more,
    # never the pool.
    assert att["decode_rows_read"] % 8 == 0
    assert att["decode_rows_live"] <= att["decode_rows_read"]
    assert att["decode_rows_read"] < att["decode_rows_live"] + 8 * 3 * stats[
        "steps"]
    assert att["decode_rows_read"] < att["decode_rows_held"]


def test_unsupported_combinations_raise_at_construction():
    from jax.sharding import Mesh

    params = init_params(jax.random.PRNGKey(0), CFG)
    devices = np.array(jax.devices()[:2])
    with pytest.raises(ValueError, match="latent attention serves on one"):
        ContinuousBatchingEngine(params, CFG, mesh=Mesh(devices, ("tp",)))
    with pytest.raises(ValueError, match="do not split into pp=2 stages"):
        ContinuousBatchingEngine(
            params, CFG, mesh=Mesh(devices.reshape(1, 2), ("tp", "pp")))


def test_the_program_and_the_reference_draw_every_leaf_at_one_scale():
    """`init_params` and the reference's `leaf_init` state one
    initialisation: a leaf's standard deviation under either."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    dims = dict(DIMS, tie_embeddings=False)

    def check(path, leaf):
        path = tuple(p.key for p in path)
        draw, *args = reference.leaf_init(path, dims)
        shape = leaf.shape[1:] if path[0] == "layers" else leaf.shape
        drawn = np.asarray(draw(jax.random.PRNGKey(1), shape, *args))
        mine = np.asarray(leaf[0] if path[0] == "layers" else leaf)
        if drawn.std() == 0:
            assert (mine == drawn).all(), path
        else:
            assert mine.std() == pytest.approx(drawn.std(), rel=0.25), path

    jax.tree_util.tree_map_with_path(check, params)
