"""SDAR-30B-A3B-Chat at a small size on the CPU (`tiny_sdar_moe`: 2 layers,
hidden 64, 4 query and 2 key-value heads, 8 experts of 32, 2 a token,
blocks of 4): the program's block-causal forward, its block step program
and the engine's generation by diffusion over blocks against the
benchmark's plain reference `bench/reference/sdar_moe.py`, which has no
cache, no pages and no kernels and imports nothing from the program.

The reference replays generation from the tokens and the pass in which each
was drawn, which under the engine's "sequential" order follows from the
prompt's length."""

import os
import sys
import time
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.exceptions import RequestCancelledError
from ray_tpu.models import configs, forward, generate, init_params, loss_fn
from ray_tpu.ops.paged_attention import paged_decode_attention
from ray_tpu.serve import paged_kv
from ray_tpu.serve.llm import ContinuousBatchingEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import spec  # noqa: E402
import weights  # noqa: E402
from reference import sdar_moe as reference  # noqa: E402

CFG = configs.get_config("tiny_sdar_moe")
B = CFG.block_length
FILE = {"reference": "sdar_moe", "published_extra": {name: name for name in (
    "num_experts", "experts_per_token", "moe_intermediate_size",
    "norm_topk_prob", "block_length", "mask_token_id", "denoise_steps")}}
DIMS = spec.dims_of(CFG, FILE)
TOLERANCE = 1e-4
CHUNK, PAGE = 8, 4


@pytest.fixture(scope="module")
def params():
    """The benchmark's weights (the reference's `leaf_init`), the norm
    scales drawn too: ones would hide a norm over the wrong extent. The two
    writers of the residual stream are scaled up: at (2 x 2) ** -0.5 two
    layers add little to a masked position's embedding, and every masked
    position then draws the same token whatever it attends to."""
    params = weights.make_params(CFG, 7, spec.leaf_rules(CFG, FILE))
    for name in ("wo", "w_down"):
        params["layers"][name] = params["layers"][name] * 5.0
    key = jax.random.PRNGKey(8)
    for i, name in enumerate(("attn_norm", "q_norm", "k_norm", "mlp_norm")):
        leaf = params["layers"][name]
        params["layers"][name] = jax.random.uniform(
            jax.random.fold_in(key, i), leaf.shape, jnp.float32, 0.5, 1.5)
    return params


def prompt_of(n, seed=0):
    return [int(t) for t in np.random.default_rng(100 * seed + n).integers(
        0, CFG.vocab_size, n)]


def rel_rms(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def engine_for(params, steps=CFG.denoise_steps, **kw):
    kw = {"num_slots": 3, "max_len": 64, "prefill_chunk": CHUNK,
          "page_size": PAGE, **kw}
    return ContinuousBatchingEngine(
        params, replace(CFG, denoise_steps=steps), **kw)


def sequential_pass_of(prompt_len, total, steps):
    """The pass in which "sequential" draws each position of a sequence of
    `total` whose first `prompt_len` are a prompt (-1): a pass fills the
    next B / steps masked positions of a block from the left, the prompt's
    remainder in its first block counted as filled."""
    per_pass, out = B // steps, np.full(total, -1)
    for start in range(prompt_len - prompt_len % B, total, B):
        masked = [p for p in range(max(start, prompt_len), start + B)]
        for i, p in enumerate(masked):
            if p < total:
                out[p] = i // per_pass
    return out


def replayed_logits(params, prompt, tokens, pass_of, steps):
    """The reference's logits [len(tokens), vocab] from which each
    generated token was drawn. The sequence is padded to whole blocks with
    positions no pass ever fills (`steps`: the mask token in every
    stream)."""
    seq = list(prompt) + list(tokens)
    pad = -len(seq) % B
    rows = reference.replay(
        params, jnp.asarray(seq + [0] * pad, jnp.int32),
        dict(DIMS, denoise_steps=steps),
        np.concatenate([pass_of, np.full(pad, steps)]), steps)
    return np.asarray(reference.logits_rows(
        params, rows[len(prompt):len(seq)], DIMS))


# -- the model ---------------------------------------------------------------

def test_the_named_config_is_the_published_model():
    big = configs.get_config("sdar-30b-a3b")
    shapes = jax.eval_shape(lambda k: init_params(k, big),
                            jax.random.PRNGKey(0))
    layers = shapes["layers"]
    assert layers["wq"].shape == (48, 2048, 4096)
    assert layers["wk"].shape == (48, 2048, 512)
    assert layers["q_norm"].shape == (48, 128)        # a norm by head
    assert layers["router"].shape == (48, 2048, 128)
    assert layers["w_gate"].shape == (48, 128, 2048, 768)
    assert layers["w_down"].shape == (48, 128, 768, 2048)
    assert shapes["lm_head"].shape == (2048, 151936)
    matrices = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)
                   if a.ndim > 1 and a.shape[-1] > 128 or a.ndim > 2)
    assert round(matrices / 1e9, 2) == 30.53
    cut = configs.get_config("sdar-30b-a3b-l6")
    assert cut == replace(big, n_layers=6)
    assert (big.block_length, big.denoise_steps, big.mask_token_id) == (
        4, 2, 151669)


def test_training_and_the_one_token_loop_refuse_the_model(params):
    tokens = jnp.asarray([prompt_of(9)], jnp.int32)
    with pytest.raises(NotImplementedError, match="noise schedule"):
        loss_fn(params, tokens, CFG)
    with pytest.raises(NotImplementedError, match="block of positions"):
        generate(params, tokens, CFG, max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="noise schedule"):
        reference.loss_and_grads(params, tokens[0], DIMS)
    with pytest.raises(NotImplementedError, match="noise schedule"):
        reference.loss_layerwise(params, tokens[0], DIMS)


@pytest.mark.parametrize("length", [4, 11, 24])
def test_forward_is_the_reference_s_clean_stream(params, length):
    tokens = jnp.asarray(prompt_of(length), jnp.int32)
    got, _ = forward(params, tokens[None], CFG, return_hidden=True)
    want = np.asarray(reference.clean_hidden(params, tokens, DIMS))
    assert rel_rms(np.asarray(got[0]), want) < TOLERANCE
    # The control: under a plain causal mask (blocks of 1) the same
    # weights give other states.
    causal = np.asarray(reference.clean_hidden(
        params, tokens, dict(DIMS, block_length=1)))
    assert rel_rms(np.asarray(got[0]), causal) > 100 * TOLERANCE


def test_harness_rows_are_the_sequential_replay_shifted_by_one(params):
    tokens = jnp.asarray(prompt_of(16), jnp.int32)
    rows = np.asarray(reference.hidden_layerwise(params, tokens, DIMS))
    want = np.asarray(reference.replay(
        params, tokens, DIMS, sequential_pass_of(0, 16, CFG.denoise_steps),
        CFG.denoise_steps))
    np.testing.assert_array_equal(rows[:-1], want[1:])
    assert not rows[-1].any()


# -- the kernel's call ---------------------------------------------------------

def test_folded_block_queries_are_one_query_at_a_time():
    """A block's B queries folded into each key-value head's group
    (`paged_kv._fold_block`) through the decode-attention kernel
    (interpreter), against the kernel given one position's queries at a
    time: all B see the same rows."""
    slots, kvh, group, hd, pages, ps = 3, 2, 4, 128, 6, 8
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(slots, B, kvh * group, hd)), jnp.float32)
    pool = [jnp.asarray(rng.normal(size=(2, slots * pages + 1, ps, kvh * hd)),
                        jnp.bfloat16) for _ in range(2)]
    tables = jnp.asarray(1 + np.arange(slots * pages).reshape(slots, pages),
                         jnp.int32)
    rows = jnp.asarray([5, 0, 41], jnp.int32)

    def attend(queries):
        return paged_decode_attention(queries, *pool, 1, tables, rows,
                                      hd ** -0.5, interpret=True)

    folded = paged_kv._unfold_block(
        attend(paged_kv._fold_block(q, kvh)), B, kvh)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(folded[:, b]),
                                   np.asarray(attend(q[:, b])), atol=2e-6)


# -- the block step program ----------------------------------------------------

def _one_slot(params, prompt, steps=2):
    """A slot whose prompt's whole blocks are prefilled, and its first
    block started: (cfg, state, k, v, lengths, table)."""
    cfg = replace(CFG, denoise_steps=steps)
    cache = paged_kv.init_paged_cache(cfg, 1, 17, PAGE, 16)
    table = jnp.arange(1, 17, dtype=jnp.int32)[None]
    end = len(prompt) - len(prompt) % B
    row = jnp.zeros((1, 16), jnp.int32).at[0, :end].set(
        jnp.asarray(prompt[:end], jnp.int32))
    _, k, v, lengths = paged_kv.prefill_chunk_paged(
        params, row, end, 0, 0, cache["k"], cache["v"], cache["lengths"],
        table, cfg, 64)
    state = paged_kv.init_block_state(cfg, 1)
    rest = jnp.asarray(prompt[end:], jnp.int32)
    state["tokens"] = state["tokens"].at[0, :len(rest)].set(rest)
    state["masked"] = state["masked"].at[0, :len(rest)].set(False)
    return cfg, state, k, v, lengths, table


@pytest.mark.parametrize("commit", [True, False], ids=["commit", "no-commit"])
def test_a_later_block_sees_what_the_commit_pass_wrote(params, commit):
    """Two denoising passes fill block 0 behind an 8-token prompt; with
    the commit pass the first logits of block 1 are the reference's, and
    WITHOUT it (the last denoising pass's rows kept, the length advanced
    by hand) they are not: the rows that pass wrote saw the mask token at
    the positions it filled."""
    prompt = prompt_of(8)
    cfg, state, k, v, lengths, table = _one_slot(params, prompt)
    active = jnp.ones(1, bool)
    blocks = []
    for _ in range(2 + commit):
        block, state, k, v, lengths = paged_kv.block_pass_paged(
            params, state, k, v, lengths, active, table, None, None, None,
            None, cfg, 64)
        blocks.append(np.asarray(block[0]))
    assert int(lengths[0]) == (12 if commit else 8)
    assert bool(state["masked"].all()) == commit
    if not commit:
        state, lengths = paged_kv.init_block_state(cfg, 1), lengths + B
    logits, _, _ = paged_kv.block_logits(
        params, state, k, v, lengths, active, table, cfg, 64)
    tokens = blocks[1].tolist()
    want = replayed_logits(
        params, prompt, tokens + [0],
        sequential_pass_of(8, 13, 2), 2)[B]
    got = rel_rms(np.asarray(logits[0, 0]), want)
    assert got < TOLERANCE if commit else got > 100 * TOLERANCE


# -- the engine ----------------------------------------------------------------

@pytest.fixture(scope="module", params=[1, 2, 4], ids="steps-{}".format)
def served(request, params):
    """One engine a number of denoising passes, and what it served: prompts
    of every length mod 4 with `max_new_tokens` of every value mod 4."""
    steps = request.param
    eng = engine_for(params, steps)
    try:
        out = []
        for n, (p_len, new) in enumerate(
                [(8, 8), (9, 7), (6, 5), (3, 6), (15, 9), (2, 1)]):
            prompt = prompt_of(p_len, seed=n)
            h = eng.submit(prompt, max_new_tokens=new)
            out.append((prompt, new, h.result(timeout=120), h))
        time.sleep(0.7)  # the loop drains its last pass and goes idle
        yield steps, out, eng.stats()
    finally:
        eng.shutdown()


def test_engine_generation_is_the_reference_s_replay(params, served):
    steps, out, _ = served
    for prompt, new, tokens, _ in out:
        assert len(tokens) == new
        pass_of = sequential_pass_of(len(prompt), len(prompt) + new, steps)
        assert (pass_of[len(prompt):] >= 0).all()
        ref = replayed_logits(params, prompt, tokens, pass_of, steps)
        assert [int(row.argmax()) for row in ref] == tokens


@pytest.fixture(scope="module")
def probe(params):
    eng = engine_for(params)
    yield eng
    eng.shutdown()


@pytest.mark.parametrize("length", [12, 13, 14, 15, 3])
def test_first_logits_are_the_reference_s(params, probe, length):
    """`prefill_logits`, which the benchmark's cell reads through its
    probe: the prompt's whole blocks prefilled, its remainder clean in the
    first block, and the logits at that block's first masked position."""
    prompt = prompt_of(length, seed=9)
    got = probe.prefill_logits(prompt)
    want = replayed_logits(
        params, prompt, [0],
        sequential_pass_of(length, length + 1, CFG.denoise_steps),
        CFG.denoise_steps)[0]
    assert rel_rms(got, want) < TOLERANCE


def test_diffusion_counters_add_up(served):
    steps, out, stats = served
    d = stats["diffusion"]
    assert (d["block_length"], d["denoise_steps"]) == (B, steps)
    assert d["denoise_slot_passes"] + d["commit_slot_passes"] == d["slot_passes"]
    assert d["commit_slot_passes"] == d["blocks_committed"]
    assert d["tokens_committed"] == sum(len(t) for _, _, t, _ in out)
    assert d["slot_passes_offered"] == 3 * d["passes"] == 3 * stats["steps"]
    assert d["slot_passes"] <= d["slot_passes_offered"]
    # The head and the sampler run B / steps rows a slot a pass, idle and
    # committing slots' too; the draws kept are the positions filled: every
    # position of every block committed but a prompt's remainder, and what
    # the pass a finished request rides under the loop's lag fills.
    assert d["head_rows"] == 3 * (B // steps) * d["passes"]
    filled = B * d["blocks_committed"] - sum(len(p) % B for p, _, _, _ in out)
    assert filled <= d["head_rows_used"] <= filled + B // steps * len(out)
    # A block costs `steps` denoising passes and a commit pass, a first
    # block fewer where the prompt's remainder fills part of it, and a
    # finished request rides one more pass under the loop's lag.
    blocks = sum(-(-(len(p) % B + n) // B) for p, n, _, _ in out)
    assert d["blocks_committed"] == blocks
    assert stats["recompiles_post_warm"] == 0
    # No token comes out of prefill and nothing is fetched behind it, so
    # no pass drains the device.
    timing = stats["timing"]
    assert timing["phases"]["prefill_first_token_wait"]["n"] == 0
    assert timing["pass_drain"] == {"n": 0, "ms_total": 0.0}
    assert stats["attention"]["decode_rows_read"] > 0
    assert stats["moe"]["calls"] > d["passes"]


def test_a_prompt_may_hold_the_mask_token(params):
    """Whether a position is masked is a bit kept with the slot: the mask
    token's id in a prompt's remainder is a token like any other."""
    mask = CFG.mask_token_id
    prompt = prompt_of(5) + [mask, mask]
    eng = engine_for(params)
    try:
        tokens = eng.submit(prompt, max_new_tokens=5).result(timeout=120)
    finally:
        eng.shutdown()
    ref = replayed_logits(params, prompt, tokens,
                          sequential_pass_of(7, 12, 2), 2)
    assert [int(row.argmax()) for row in ref] == tokens


def _until(ready, limit=60.0):
    deadline = time.monotonic() + limit
    while not ready():
        assert time.monotonic() < deadline
        time.sleep(0.005)


def test_slots_in_different_phases_share_a_pass_and_one_is_cancelled(params):
    """Three requests admitted apart, so that their slots are in different
    phases of their blocks in one pass; one is cancelled mid-block and its
    slot goes to a fourth. The others' tokens are what they are alone."""
    eng = engine_for(params, num_slots=2)
    alone = engine_for(params, num_slots=1)
    try:
        prompts = [prompt_of(9, seed=20), prompt_of(6, seed=21),
                   prompt_of(4, seed=22)]
        first = eng.submit(prompts[0], max_new_tokens=12)
        _until(lambda: first.produced)  # its first block is out
        doomed = eng.submit(prompts[1], max_new_tokens=40)
        _until(lambda: doomed.produced)
        doomed.cancel()
        last = eng.submit(prompts[2], max_new_tokens=7)
        got = [first.result(timeout=120), last.result(timeout=120)]
        with pytest.raises(RequestCancelledError):
            doomed.result(timeout=10)
        want = [alone.submit(prompts[0], max_new_tokens=12).result(120),
                alone.submit(prompts[2], max_new_tokens=7).result(120)]
        time.sleep(0.7)
        d = eng.stats()["diffusion"]
    finally:
        eng.shutdown()
        alone.shutdown()
    assert got == want
    assert d["denoise_slot_passes"] + d["commit_slot_passes"] == d["slot_passes"]
    assert d["commit_slot_passes"] >= d["blocks_committed"]
    assert d["tokens_committed"] >= 19


def test_eos_ends_a_request_at_the_end_of_its_block(params):
    prompt = prompt_of(8, seed=30)
    eng = engine_for(params)
    try:
        plain = eng.submit(prompt, max_new_tokens=12).result(timeout=120)
    finally:
        eng.shutdown()
    eng = engine_for(params, eos_id=plain[5])
    try:
        cut = eng.submit(prompt, max_new_tokens=12).result(timeout=120)
    finally:
        eng.shutdown()
    assert cut == plain[:8]  # the block that holds the token, whole


def test_a_slot_is_evicted_before_a_block_would_pass_max_len(params):
    eng = engine_for(params, max_len=22)  # the last whole block ends at 20
    try:
        tokens = eng.submit(prompt_of(9), max_new_tokens=50).result(120)
        with pytest.raises(Exception, match="last whole block"):
            eng.submit(prompt_of(20), max_new_tokens=1)
    finally:
        eng.shutdown()
    assert len(tokens) == 11


def test_a_shared_prefix_is_prefilled_once(params):
    """Whole pages (4 rows = 1 block here) of a prompt another request left
    in the prefix cache are not prefilled again, and the tokens are the
    same."""
    prompt = prompt_of(13, seed=40)
    eng = engine_for(params)
    try:
        first = eng.submit(prompt, max_new_tokens=6).result(timeout=120)
        again = eng.submit(prompt, max_new_tokens=6).result(timeout=120)
        kv = eng.stats()["kv"]
    finally:
        eng.shutdown()
    assert again == first
    assert kv["prefix_hits"] == 1 and kv["prefill_tokens_skipped"] == 12


@pytest.mark.parametrize("fault", ["chunk", "page", "steps"])
def test_the_engine_refuses_what_a_block_cannot_be(params, fault):
    kw, cfg = {"num_slots": 1, "max_len": 64, "page_size": PAGE}, CFG
    if fault == "chunk":
        kw["prefill_chunk"] = 6
    elif fault == "page":
        kw["page_size"] = 6
    else:
        cfg = replace(CFG, denoise_steps=3)
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(params, cfg, **kw)


def test_a_next_token_model_is_served_as_it_was():
    """`block_length` 0 takes none of this: no `diffusion` in `stats()`, and
    greedy tokens are `models.generate`'s, whose one-length cache is the
    plain form."""
    cfg = configs.get_config("tiny_olmoe")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = prompt_of(11)
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=64,
                                   prefill_chunk=CHUNK)
    try:
        tokens = eng.submit(prompt, max_new_tokens=9).result(timeout=120)
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert "diffusion" not in stats and "moe" in stats
    want = generate(params, jnp.asarray([prompt], jnp.int32), cfg,
                    max_new_tokens=9)
    assert tokens == np.asarray(want[0]).tolist()
