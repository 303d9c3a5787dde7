"""SmallThinker-21BA3B-Instruct at a small size on the CPU
(`tiny_smallthinker`: 4 layers in the published pattern full, window,
window, window; a window of 6 over pages of 4, hidden 64, 4 query and 2
key-value heads, 8 ReGLU experts of 32, 2 a token, the router on the
layer's input): the program's forward, its step programs through the RING of
pages a slot, and the engine end to end, against the benchmark's plain
reference `bench/reference/smallthinker.py`, which has no cache, no ring
and no kernels and imports nothing from the program.

The window (6) is no multiple of the page (4) and shorter than the
sequences, and the ring (the window and a chunk of 4 in whole pages: 12
rows) is passed several times: the window's edge, a page that is in the
window only in part and the ring's wrap are in every comparison."""

import os
import sys
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import configs, forward, generate, loss_fn
from ray_tpu.ops.paged_attention import window_decode_attention
from ray_tpu.serve import paged_kv
from ray_tpu.serve.llm import ContinuousBatchingEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import spec  # noqa: E402
import weights  # noqa: E402
from reference import smallthinker as reference  # noqa: E402

CFG = configs.get_config("tiny_smallthinker")
WINDOW = CFG.sliding_window_size
FILE = {"reference": "smallthinker", "published_extra": {name: name for name in (
    "num_experts", "experts_per_token", "moe_intermediate_size",
    "norm_topk_prob", "sliding_window_size", "sliding_window_layout",
    "rope_layout")}}
DIMS = spec.dims_of(CFG, FILE)
TOLERANCE = 1e-4
CHUNK, PAGE, MAX_LEN = 4, 4, 64
RING = paged_kv.ring_pages(CFG, PAGE, MAX_LEN // PAGE, CHUNK) * PAGE
# Under the window, at its edge, and round the ring one to four times.
LENGTHS = (3, WINDOW - 1, WINDOW, WINDOW + 1, RING + 1, 2 * RING + 3,
           4 * RING + 2)
DECODED = 8


@pytest.fixture(scope="module")
def params():
    """The benchmark's weights (the reference's `leaf_init`), the norm
    scales drawn too (ones would hide a norm in the wrong place) and the two
    writers of the residual stream scaled up, so that what a layer attends
    to and which experts it takes move the logits by far more than
    rounding."""
    params = weights.make_params(CFG, 11, spec.leaf_rules(CFG, FILE))
    for name in ("wo", "w_down"):
        params["layers"][name] = params["layers"][name] * 4.0
    key = jax.random.PRNGKey(12)
    for i, name in enumerate(("attn_norm", "mlp_norm")):
        leaf = params["layers"][name]
        params["layers"][name] = jax.random.uniform(
            jax.random.fold_in(key, i), leaf.shape, jnp.float32, 0.5, 1.5)
    return params


def tokens_of(n, seed=0):
    return np.random.default_rng(100 * seed + n).integers(
        0, CFG.vocab_size, n).astype(np.int32)


def rel_rms(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / np.sqrt(np.mean(want ** 2)))


def reference_logits(params, tokens):
    """The reference's logits at every position of `tokens`."""
    rows = reference.hidden_layerwise(params, jnp.asarray(tokens), DIMS)
    return np.asarray(reference.logits_rows(params, rows, DIMS))


class StepPrograms:
    """The engine's two step programs over a cache of two slots, jitted
    once a config, with the logits a call projects handed out (the decode
    step returns tokens): a `jax.debug.callback` where the programs call
    `project_logits`."""

    def __init__(self, cfg):
        self.cfg, self.seen = cfg, []
        project = paged_kv.project_logits

        def watched(x, params, cfg):
            logits = project(x, params, cfg)
            jax.debug.callback(self.seen.append, logits, ordered=True)
            return logits

        def watching(program):
            """`program` jitted, `project_logits` watched while it is
            traced and at no other time."""
            def traced(*args):
                with pytest.MonkeyPatch.context() as patch:
                    patch.setattr(paged_kv, "project_logits", watched)
                    return program(*args)

            return jax.jit(traced)

        self.prefill = watching(
            lambda p, t, n, s, o, k, v, ln, bt, ring:
            paged_kv.prefill_chunk_paged(p, t, n, s, o, k, v, ln, bt, cfg,
                                         MAX_LEN, ring=ring))
        self.decode = watching(
            lambda p, t, k, v, ln, a, bt, ring: paged_kv.decode_paged(
                p, t, k, v, ln, a, bt, None, None, None, None, cfg, MAX_LEN,
                ring=ring))

    def logits(self, params, tokens, n_prefill, slot=1):
        """Logits at positions `n_prefill - 1 ..` of `tokens`: the first
        `n_prefill` prefilled in chunks into `slot`, the rest decoded one
        step each, every step fed the sequence's own next token."""
        per_slot = MAX_LEN // PAGE
        cache = paged_kv.init_paged_cache(
            self.cfg, 2, 2 * per_slot + 1, PAGE, per_slot,
            prefill_chunk=CHUNK)
        k, v, lengths = (cache[n] for n in ("k", "v", "lengths"))
        ring = cache.get("ring")  # a model without a window layer has none
        table = np.zeros((2, per_slot), np.int32)
        table[slot] = 1 + slot * per_slot + np.arange(per_slot)
        self.seen.clear()
        for off in range(0, n_prefill, CHUNK):
            chunk = tokens[off:min(off + CHUNK, n_prefill)]
            padded = np.zeros((1, CHUNK), np.int32)
            padded[0, :len(chunk)] = chunk
            _, k, v, lengths, *rest = self.prefill(
                params, padded, np.int32(len(chunk)), np.int32(slot),
                np.int32(off), k, v, lengths, table, ring)
            ring = rest[0] if rest else None
        jax.effects_barrier()
        out = [np.asarray(self.seen[-1])[0]]
        self.seen.clear()
        active = np.arange(2) == slot
        for t in tokens[n_prefill:]:
            fed = np.where(active, t, 0).astype(np.int32)
            _, k, v, lengths, *rest = self.decode(
                params, fed, k, v, lengths, active, table, ring)
            ring = rest[0] if rest else None
        jax.effects_barrier()
        assert int(lengths[slot]) == len(tokens)
        return np.stack(out + [np.asarray(a)[slot] for a in self.seen])


@pytest.fixture(scope="module")
def programs():
    return StepPrograms(CFG)


@pytest.mark.parametrize("n_prefill", LENGTHS)
def test_chunked_prefill_then_decode_through_the_ring_match_the_reference(
        params, programs, n_prefill):
    """Prefill in chunks of 4, then 8 decode steps through the ring, every
    logit against the reference's one full forward of the sequence."""
    tokens = tokens_of(n_prefill + DECODED)
    got = programs.logits(params, tokens, n_prefill)
    want = reference_logits(params, tokens)[n_prefill - 1:]
    assert got.shape == want.shape
    assert rel_rms(got, want) < TOLERANCE
    assert np.abs(got - want).max() < 1e-3 * np.abs(want).max()


@pytest.mark.parametrize("n", [WINDOW - 1, WINDOW + 1, 3 * RING])
def test_forward_matches_the_reference(params, n):
    tokens = tokens_of(n, seed=1)
    got, _ = forward(params, jnp.asarray(tokens)[None], CFG)
    assert rel_rms(np.asarray(got[0]), reference_logits(params, tokens)
                   ) < TOLERANCE


# What the comparison must see: each a program that computes another model.
FAULTS = {
    "no-window": dict(sliding_window_layout=(0, 0, 0, 0)),
    "rope-on-a-full-layer": dict(rope_layout=(1, 1, 1, 1)),
    "router-on-the-normed-stream": dict(router_reads="mlp_input"),
    "silu": dict(activation="silu"),
    "window-one-more": dict(sliding_window_size=WINDOW + 1),
    "window-one-fewer": dict(sliding_window_size=WINDOW - 1),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_faulty_forward_fails_the_comparison(params, fault):
    tokens = tokens_of(3 * RING, seed=2)
    got, _ = forward(params, jnp.asarray(tokens)[None],
                     replace(CFG, **FAULTS[fault]))
    assert rel_rms(np.asarray(got[0]), reference_logits(params, tokens)
                   ) > 100 * TOLERANCE


@pytest.mark.parametrize("fault", FAULTS)
def test_faulty_step_programs_fail_the_comparison(params, fault):
    n_prefill = 2 * RING + 3
    tokens = tokens_of(n_prefill + DECODED, seed=3)
    got = StepPrograms(replace(CFG, **FAULTS[fault])).logits(
        params, tokens, n_prefill)
    want = reference_logits(params, tokens)[n_prefill - 1:]
    assert rel_rms(got, want) > 100 * TOLERANCE


def engine_for(params, **kw):
    kw = {"num_slots": 2, "max_len": MAX_LEN, "prefill_chunk": CHUNK,
          "page_size": PAGE, **kw}
    return ContinuousBatchingEngine(params, CFG, **kw)


def generated(params, prompt, n):
    return [int(t) for t in np.asarray(generate(
        params, jnp.asarray(prompt)[None], CFG, max_new_tokens=n))[0]]


def test_engine_serves_two_slots_of_unlike_lengths_as_generate_does(params):
    """Two requests at once, one under the window and one well past it
    and round the ring, greedy, against `models.generate` (whose cache
    holds every position and masks by the window); then the engine's
    counts of what its window layers read."""
    prompts = [tokens_of(4, seed=4), tokens_of(2 * RING + 1, seed=4)]
    want = [generated(params, prompt, 12) for prompt in prompts]
    engine = engine_for(params)
    try:
        handles = [engine.submit(p, max_new_tokens=12) for p in prompts]
        assert [h.result() for h in handles] == want
        rows = engine.stats()["attention"]
        assert 0 < rows["window_rows_read"] < rows["window_rows_unwindowed"]
        assert rows["decode_rows_live"] <= rows["decode_rows_read"]
        assert rows["decode_rows_read"] <= rows["decode_rows_held"]
        assert engine.stats()["recompiles_post_warm"] == 0
    finally:
        engine.shutdown()


def test_a_slot_taken_again_starts_from_an_empty_ring(params):
    """One slot: a request that fills its ring twice over, then a short
    one in the same slot, which must see no row of the first."""
    engine = engine_for(params, num_slots=1)
    try:
        first = tokens_of(2 * RING + 2, seed=5)
        assert engine.submit(first, max_new_tokens=6).result() == generated(
            params, first, 6)
        for n in (2, WINDOW + 1):
            short = tokens_of(n, seed=6)
            assert engine.submit(short, max_new_tokens=10).result() == (
                generated(params, short, 10))
            want = reference_logits(params, short)[-1]
            assert rel_rms(engine.prefill_logits(short), want) < TOLERANCE
    finally:
        engine.shutdown()


def test_no_prefix_is_reused_and_the_skip_is_counted(params):
    engine = engine_for(params)
    try:
        prompt = tokens_of(3 * PAGE + 1, seed=7)
        first = engine.submit(prompt, max_new_tokens=5).result()
        assert engine.submit(prompt, max_new_tokens=5).result() == first
        kv = engine.stats()["kv"]
        assert kv["prefix_reuse_skipped"] == 2
        assert kv["prefix_hits"] == kv["prefix_cache_pages"] == 0
        assert kv["prefill_tokens_skipped"] == 0
        # A slot owns its ring: the pages reserved are the full layers'.
        assert kv["pages_in_use"] == 0
    finally:
        engine.shutdown()


def test_admission_reserves_the_full_layers_pages_and_nothing_for_the_ring(
        params):
    engine = engine_for(params)
    try:
        assert engine._k.shape[0] == CFG.n_layers - CFG.window_layers
        ring = engine._tail["ring"]["k"]
        assert ring.shape == (CFG.window_layers, 1 + 2 * RING // PAGE, PAGE,
                              CFG.n_kv_heads * CFG.head_dim)
        assert engine.stats()["kv"]["pages_total"] == 2 * MAX_LEN // PAGE
    finally:
        engine.shutdown()


def test_the_engine_refuses_a_ring_under_tp(params):
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    with pytest.raises(ValueError, match="window layers serves on one chip"):
        ContinuousBatchingEngine(params, CFG, mesh=mesh)


# The kernel in Pallas's interpreter against the plain form, at shapes it
# takes (a page whole float32 tiles, a row of the pool whole lanes): slots
# with nothing, under the window, at its edge, and round a ring of 40 rows.
K_SLOTS, K_HEADS, K_KV, K_DIM, K_PAGE, K_WINDOW, K_RING = 9, 16, 8, 16, 8, 20, 5
NEWEST = (-1, 3, 18, 19, 20, 27, 39, 40, 101)


def test_the_ring_kernel_reads_what_the_plain_form_reads():
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (K_SLOTS, K_HEADS, K_DIM), jnp.float32)
    shape = (2, 1 + K_SLOTS * K_RING, K_PAGE, K_KV * K_DIM)
    # Peaked scores: one wrong row shows.
    k_ring = 4.0 * jax.random.normal(key[1], shape, jnp.float32)
    v_ring = jax.random.normal(key[2], shape, jnp.float32)
    newest = jnp.asarray(NEWEST, jnp.int32)
    args = (q, k_ring, v_ring, jnp.int32(1), newest, K_WINDOW, K_DIM ** -0.5)
    plain = window_decode_attention(*args, use_pallas=False)
    kernel = window_decode_attention(*args, interpret=True)
    live = np.asarray(newest) >= 0
    np.testing.assert_allclose(np.asarray(kernel)[live],
                               np.asarray(plain)[live], rtol=2e-5, atol=2e-5)
    # And the plain form against the positions written out: slot s attends
    # to the last `window` positions up to newest[s], position p in row
    # p mod ring of its own pages.
    ring = K_RING * K_PAGE
    for s, n in enumerate(NEWEST):
        if n < 0:
            continue
        at = np.arange(max(0, n - K_WINDOW + 1), n + 1) % ring
        rows = lambda pool: np.asarray(pool)[  # noqa: E731
            1, 1 + s * K_RING + at // K_PAGE, at % K_PAGE].reshape(
                len(at), K_KV, K_DIM)
        qs = np.asarray(q)[s].reshape(K_KV, K_HEADS // K_KV, K_DIM)
        scores = np.einsum("hgd,khd->hgk", qs, rows(k_ring)) * K_DIM ** -0.5
        p = np.exp(scores - scores.max(-1, keepdims=True))
        want = np.einsum("hgk,khd->hgd", p / p.sum(-1, keepdims=True),
                         rows(v_ring)).reshape(K_HEADS, K_DIM)
        np.testing.assert_allclose(np.asarray(plain)[s], want, rtol=2e-5,
                                   atol=2e-5)


def test_the_train_step_runs_the_model_at_cpu_size(params):
    """The program's loss and its gradients on a short batch: finite, and
    the loss the reference's next-token loss (the program's balance term
    taken out)."""
    tokens = jnp.asarray(np.stack([tokens_of(17, seed=8),
                                   tokens_of(17, seed=9)]))
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(p, tokens, CFG, aux_weight=0.0))(params)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    want = np.mean([float(reference.loss_and_grads(params, row, DIMS)[0])
                    for row in tokens])
    assert abs(float(loss) - want) < 1e-4 * want


@pytest.mark.parametrize("name,layers,window_layers", [
    ("smallthinker-21b-a3b", 52, 39), ("smallthinker-21b-a3b-l8", 8, 6),
    ("tiny_smallthinker", 4, 3)])
def test_the_named_configs_hold_the_published_lists(name, layers,
                                                    window_layers):
    cfg = configs.get_config(name)
    assert cfg.n_layers == layers == len(cfg.rope_layout)
    assert cfg.sliding_window_layout == (0, 1, 1, 1) * (layers // 4)
    assert cfg.rope_layout == cfg.sliding_window_layout
    assert (cfg.window_layers, cfg.layer_period) == (window_layers, 4)
    assert cfg.activation == "relu" and cfg.router_reads == "layer_input"


# The committed tools of the cell: the controls `PERF.md` reports are made
# by `tools/swa_controls.py`, the parent-against-change fingerprints by
# `tools/step_programs.py`.
TOOLS = os.path.join(os.path.dirname(BENCH), "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)

import step_programs  # noqa: E402
import swa_controls  # noqa: E402


@pytest.mark.parametrize("control", [c for c in swa_controls.CONTROLS
                                     if c != "e4m3"])
def test_a_committed_control_fails_the_comparison(params, control):
    """Each computes another model, and leaves the sizes the file states
    and the reference is handed the published ones."""
    cfg = swa_controls.faulty(CFG, control)
    assert spec.dims_of(cfg, FILE) == DIMS
    tokens = tokens_of(3 * RING, seed=4)
    got, _ = forward(params, jnp.asarray(tokens)[None], cfg)
    assert rel_rms(np.asarray(got[0]), reference_logits(params, tokens)
                   ) > 100 * TOLERANCE


def test_a_made_control_tree_hands_out_the_faulty_config(tmp_path):
    import json
    import subprocess

    dest = str(tmp_path / "rope-on-full")
    swa_controls.make("rope-on-full", dest)
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, sys; sys.path.insert(0, '.');"
         "from ray_tpu.models import configs;"
         "c = configs.get_config('smallthinker-21b-a3b-l8');"
         "print(json.dumps([c.rope_layers, c.rope_layout, c.window_layers]))"],
        cwd=dest, capture_output=True, text=True, check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    rotates, published, window_layers = json.loads(
        out.stdout.strip().splitlines()[-1])
    assert rotates == [True] * 8
    assert published == [0, 1, 1, 1] * 2 and window_layers == 6
    with open(os.path.join(dest, swa_controls.CELL_TRAFFIC)) as f:
        assert json.load(f)["ramp_s"] == 2.0


def test_step_program_fingerprints_are_of_the_program_alone():
    once = step_programs.fingerprints(["tiny_smallthinker"])
    assert sorted(once) == [f"tiny_smallthinker:{p}"
                            for p in ("decode", "prefill1", "prefill2")]
    assert once == step_programs.fingerprints(["tiny_smallthinker"])
    assert step_programs.fingerprints(["no-such-model"]) == {}


def test_ops_hash_ignores_the_order_of_a_loop_s_state():
    body = ("  %a.1 = f32[8,128]{1,0} add(%x, %y)\n"
            "  ROOT %m.2 = bf16[4]{0} multiply(%p, %q)\n")
    one = body + "  %t = (s32[], f32[8,128]{1,0}) tuple(%i, %a.1)\n"
    two = ("  %t = (f32[8,128]{1,0}, s32[]) tuple(%a.9, %i)\n"
           + "\n".join(reversed(body.replace(".1", ".9").splitlines())))
    assert step_programs.ops_hash(one) == step_programs.ops_hash(two)
    assert step_programs.ops_hash(one) != step_programs.ops_hash(
        one.replace("add(", "subtract("))
