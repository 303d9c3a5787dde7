"""LLM serving showcase: the generation stack behind a Serve deployment —
batched prefill+decode, per-model multiplexing, streaming tokens.

This is the TPU serving story end to end: serve.batch coalesces
concurrent prompts into one batched generate() call (one set of MXU
passes), multiplexing keeps several checkpoints LRU-resident per replica,
and token streaming rides the generator protocol.
"""

import threading
import time
from dataclasses import replace

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu import serve


@pytest.fixture
def rt_serve():
    rt.init(num_cpus=4)
    yield
    serve.shutdown()
    rt.shutdown()


@pytest.mark.slow
def test_batched_llm_generation(rt_serve):
    @serve.deployment(max_ongoing_requests=8)
    class LLM:
        def __init__(self):
            import jax

            from ray_tpu.models import configs, init_params

            self.cfg = replace(configs.tiny, dtype=np.float32)
            self.params = init_params(jax.random.PRNGKey(0), self.cfg)

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.75)
        def generate_batch(self, prompts):
            import jax.numpy as jnp

            from ray_tpu.models import generate

            # Same-length prompts stack into ONE batched generate call.
            batch = jnp.asarray(np.stack(prompts), dtype=jnp.int32)
            out = generate(self.params, batch, self.cfg, max_new_tokens=4)
            return [np.asarray(row).tolist() for row in out]

        def __call__(self, prompt):
            return self.generate_batch(np.asarray(prompt, dtype=np.int32))

    handle = serve.run(LLM.bind(), name="llm")
    prompts = [[1 + i, 7, 42, 3] for i in range(8)]
    results = [None] * 8

    def call(i):
        results[i] = rt.get(handle.remote(prompts[i]), timeout=120)

    threads = [threading.Thread(target=call, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    assert all(r is not None and len(r) == 4 for r in results)

    # Same prompt => same greedy tokens regardless of batch composition.
    again = rt.get(handle.remote(prompts[0]), timeout=120)
    assert again == results[0]

    # Batching actually coalesced concurrent prompts.
    handle._refresh(force=True)
    replica = handle._shared["replicas"][0]
    stats = rt.get(replica.stats.remote(), timeout=30)
    assert max(stats["batch_sizes"]["generate_batch"]) > 1


@pytest.mark.slow
def test_streaming_token_generation(rt_serve):
    @serve.deployment
    class StreamLLM:
        def __init__(self):
            import jax

            from ray_tpu.models import configs, init_params

            self.cfg = replace(configs.tiny, dtype=np.float32)
            self.params = init_params(jax.random.PRNGKey(0), self.cfg)

        def __call__(self, prompt, n=5):
            import jax.numpy as jnp

            from ray_tpu.models.generate import (
                decode_step, init_kv_cache, prefill,
            )

            tokens = jnp.asarray([prompt], dtype=jnp.int32)
            cache = init_kv_cache(self.cfg, 1, tokens.shape[1] + n)
            logits, cache = prefill(self.params, tokens, cache, self.cfg)
            for _ in range(n):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                yield int(nxt[0])
                logits, cache = decode_step(self.params, nxt, cache, self.cfg)

    handle = serve.run(StreamLLM.bind(), name="sllm")
    toks = list(handle.options(stream=True).remote([5, 9, 2], n=5))
    assert len(toks) == 5 and all(isinstance(t, int) for t in toks)

    # The stream matches batch generation of the same prompt (greedy).
    from ray_tpu.models import configs, generate, init_params
    import jax
    import jax.numpy as jnp

    cfg = replace(configs.tiny, dtype=np.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ref = generate(
        params, jnp.asarray([[5, 9, 2]], dtype=jnp.int32), cfg,
        max_new_tokens=5,
    )
    assert toks == np.asarray(ref[0]).tolist()


def _tiny_model():
    import jax

    from ray_tpu.models import configs, init_params

    cfg = replace(configs.tiny, dtype=np.float32)
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def test_continuous_batching_greedy_parity():
    """Engine decode == generate() greedy decode for concurrent
    mixed-length prompts (per-slot lengths do not perturb the math)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=3, max_len=64)
    try:
        prompts = [[1, 2, 3], [5, 6, 7, 8, 9], [4], [9, 9, 2, 1]]
        refs = [
            np.asarray(
                generate(params, jnp.asarray([p], dtype=jnp.int32), cfg,
                         max_new_tokens=5)
            )[0].tolist()
            for p in prompts
        ]
        handles = [eng.submit(p, max_new_tokens=5) for p in prompts]
        outs = [h.result(timeout=180) for h in handles]
        assert outs == refs
    finally:
        eng.shutdown()


def test_continuous_batching_joins_mid_decode():
    """A request arriving while another decodes is admitted at a step
    boundary (admitted_at_step > 0) — the capability the static batcher
    lacks — and both decode correctly."""
    import jax.numpy as jnp

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=4, max_len=128)
    try:
        first = eng.submit([3, 7, 11, 2], max_new_tokens=40)
        # Wait until the first request is visibly mid-decode.
        deadline = time.monotonic() + 60
        while eng.stats()["steps"] < 3:
            assert time.monotonic() < deadline, "engine never stepped"
            time.sleep(0.01)
        second = eng.submit([8, 1], max_new_tokens=5)
        out2 = second.result(timeout=180)
        out1 = first.result(timeout=180)
        assert second.admitted_at_step >= 3, (
            "second request did not join a running decode loop"
        )
        ref1 = np.asarray(
            generate(params, jnp.asarray([[3, 7, 11, 2]], dtype=jnp.int32),
                     cfg, max_new_tokens=40)
        )[0].tolist()
        ref2 = np.asarray(
            generate(params, jnp.asarray([[8, 1]], dtype=jnp.int32), cfg,
                     max_new_tokens=5)
        )[0].tolist()
        assert out1 == ref1 and out2 == ref2
    finally:
        eng.shutdown()


@pytest.mark.slow
def test_continuous_batching_throughput_vs_static():
    """At mixed arrivals, the continuous engine must clear >=2x the
    tokens/s of one-request-at-a-time static decoding (BENCH north-star
    configs[4]: 'more than parity' vs serve/batching.py)."""
    import jax.numpy as jnp

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    n_req, n_tok = 8, 16
    prompts = [[1 + i, 5, 9] for i in range(n_req)]

    # Static batch=1 baseline: requests served back to back.
    t0 = time.perf_counter()
    for p in prompts:
        np.asarray(generate(params, jnp.asarray([p], dtype=jnp.int32), cfg,
                            max_new_tokens=n_tok))
    static_s = time.perf_counter() - t0

    eng = ContinuousBatchingEngine(params, cfg, num_slots=4, max_len=64)
    try:
        eng.submit(prompts[0], max_new_tokens=n_tok).result(timeout=180)
        t0 = time.perf_counter()
        handles = []
        for i, p in enumerate(prompts):
            handles.append(eng.submit(p, max_new_tokens=n_tok))
            time.sleep(0.002 * i)  # staggered (Poisson-ish) arrivals
        for h in handles:
            h.result(timeout=300)
        cont_s = time.perf_counter() - t0
    finally:
        eng.shutdown()
    speedup = static_s / cont_s
    assert speedup >= 2.0, (
        f"continuous batching speedup {speedup:.2f}x < 2x "
        f"(static={static_s:.2f}s continuous={cont_s:.2f}s)"
    )


def test_llm_deployment_serving(rt_serve):
    """llm_deployment end to end through serve: blocking generate and
    token streaming against the continuous-batching replica."""
    import jax.numpy as jnp

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import llm_deployment

    app = llm_deployment(_tiny_model, num_slots=4, max_len=64,
                         default_max_new_tokens=6)
    handle = serve.run(app, name="cllm")
    params, cfg = _tiny_model()
    prompt = [2, 4, 6]
    ref = np.asarray(
        generate(params, jnp.asarray([prompt], dtype=jnp.int32), cfg,
                 max_new_tokens=6)
    )[0].tolist()
    out = rt.get(handle.remote(prompt), timeout=180)
    assert out == ref
    toks = list(
        handle.options(stream=True, method_name="stream").remote(prompt)
    )
    assert toks == ref


def test_prefill_logits_are_the_first_step_of_the_served_path():
    """engine.prefill_logits: the logits the first served token is the
    argmax of, equal to the plain forward pass, over several chunks,
    without touching the serving loop's cache; its one-slot scratch cache
    compiles the prefill's shapes once, on the first call."""
    import jax.numpy as jnp

    from ray_tpu.models import forward
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    prompt = [(5 * i) % 250 + 1 for i in range(20)]  # 3 chunks @ 8
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=64,
                                   prefill_chunk=8)
    try:
        served = eng.submit(prompt, max_new_tokens=4).result(timeout=180)
        # The engine counts every compilation in the process (JAX's own
        # events): run the eager reference before the snapshot.
        reference, _ = forward(params, jnp.asarray([prompt]), cfg)
        reference = np.asarray(reference[0, -1])
        eng.prefill_logits(prompt)
        compiles = eng.stats()["compiles"]
        logits = eng.prefill_logits(prompt)
        assert eng.stats()["compiles"] == compiles
        np.testing.assert_allclose(logits, reference, rtol=1e-4, atol=1e-4)
        assert int(logits.argmax()) == served[0]
        again = eng.submit(prompt, max_new_tokens=4).result(timeout=180)
        assert again == served
        with pytest.raises(ValueError, match="prompt length"):
            eng.prefill_logits(list(range(63)))
    finally:
        eng.shutdown()


def test_continuous_batching_mixed_sampling():
    """Per-request sampling params: a sampled (temperature/top_k)
    request shares the decode batch with a greedy one WITHOUT
    perturbing the greedy request's exact output."""
    import jax.numpy as jnp

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=3, max_len=64)
    try:
        greedy = eng.submit([3, 7, 11, 2], max_new_tokens=6)
        sampled = eng.submit([5, 1], max_new_tokens=6,
                             temperature=0.9, top_k=20, top_p=0.95)
        g = greedy.result(timeout=180)
        s = sampled.result(timeout=180)
        ref = np.asarray(
            generate(params, jnp.asarray([[3, 7, 11, 2]], dtype=jnp.int32),
                     cfg, max_new_tokens=6)
        )[0].tolist()
        assert g == ref
        assert len(s) == 6
        assert all(0 <= t < cfg.vocab_size for t in s)
    finally:
        eng.shutdown()
    with pytest.raises(ValueError):
        eng.submit([1], top_k=10_000)  # beyond MAX_TOP_K


def test_continuous_batching_steady_state_zero_host_traffic():
    """PERF CONTRACT for the device-resident hot loop: once all slots
    are admitted and decoding (mixed greedy + sampled), a >=32-step
    window must see ZERO recompilations and ZERO host->device
    sampling-param uploads. Any per-step jnp.asarray of temps/top_k/
    top_p/active, or a shape/dtype flip that retraces a jitted step,
    reintroduces the per-step host round trips this engine was rebuilt
    to eliminate."""
    import time

    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=4, max_len=256)
    try:
        handles = [
            eng.submit([3, 7, 11, 2], max_new_tokens=160),
            eng.submit([5, 1, 8], max_new_tokens=160),
            eng.submit([2, 9], max_new_tokens=160,
                       temperature=0.7, top_k=16),
            eng.submit([4, 4, 6, 1, 3], max_new_tokens=160,
                       temperature=1.1, top_p=0.9),
        ]
        deadline = time.monotonic() + 180
        # Steady state: every request admitted, prefills drained.
        while time.monotonic() < deadline:
            s0 = eng.stats()
            if s0["active"] == 4 and s0["prefilling"] == 0:
                break
            time.sleep(0.01)
        else:
            pytest.fail(f"never reached steady state: {eng.stats()}")
        # Let the loop take two more steps before opening the window:
        # the LAST admission's param upload lands at the next snapshot
        # after stats() can already report active==4.
        settle = s0["steps"] + 2
        while time.monotonic() < deadline:
            s0 = eng.stats()
            if s0["steps"] >= settle:
                break
            time.sleep(0.005)
        while time.monotonic() < deadline:
            s1 = eng.stats()
            if s1["steps"] - s0["steps"] >= 32:
                break
            time.sleep(0.01)
        assert s1["steps"] - s0["steps"] >= 32, (
            f"window too short: {s1['steps'] - s0['steps']} steps"
        )
        assert s1["active"] == 4, "a request finished inside the window"
        assert s1["compiles"] == s0["compiles"], (
            f"recompiled mid-decode: {s0['compiles']} -> {s1['compiles']}"
        )
        assert s1["param_uploads"] == s0["param_uploads"], (
            "sampling params re-uploaded during steady-state decode: "
            f"{s0['param_uploads']} -> {s1['param_uploads']}"
        )
        assert s1["recompiles_post_warm"] == 0
        # Warmup is the ONLY compile site: admission of real traffic
        # (greedy AND sampled, prefill, pick) hits warmed programs.
        assert s1["compiles"] == s1["warm_compiles"]
        for h in handles:
            out = h.result(timeout=180)
            assert len(out) == 160
    finally:
        eng.shutdown()


def test_continuous_batching_step_timing_breakdown():
    """stats()['timing'] decomposes engine steps into the ledger's
    phases; totals are cumulative (probes delta two snapshots: a mean is
    a total over a phase's `n`)."""
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=64)
    try:
        eng.submit([3, 7, 11], max_new_tokens=12).result(timeout=180)
        # The last token is pushed inside the turn that drains it, and
        # the turn's times are added when it ends: give it that moment.
        deadline = time.monotonic() + 10
        def fetched():
            return eng.stats()["timing"]["phases"]["decode_fetch_wait"]["n"]

        while fetched() < 11 and time.monotonic() < deadline:
            time.sleep(0.01)
        t = eng.stats()["timing"]
        dispatch, fetch = (t["phases"][k] for k in ("decode_dispatch",
                                                    "decode_fetch_wait"))
        # Eleven steps after the pass's own token; the loop may have
        # dispatched one more before it saw the last.
        assert dispatch["n"] >= fetch["n"] >= 11
        for part in (dispatch, fetch):
            assert part["ms_total"] >= 0.0
        # A decode turn is its dispatch, its fetch and the rest.
        assert (dispatch["ms_total"] + fetch["ms_total"]
                <= t["turn_ms_total"] * (1 + 1e-9))
    finally:
        eng.shutdown()


def test_attention_counters_follow_the_decoding_slots():
    """stats()['attention']: rows in the pages decode attention is given
    to read against rows the pool holds, cumulative over dispatched decode
    steps, from the host's mirror of each decoding slot's length. Nothing
    before the first request (warm-up's steps are not counted); a step of
    one slot at length n reads ceil((n + 1) / page) pages of 4 rows and
    holds slots x max_len."""
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=64,
                                   page_size=4)
    try:
        assert eng.stats()["attention"] == {
            "decode_rows_read": 0, "decode_rows_held": 0,
            "decode_rows_live": 0, "prefill_rows_walked": 0,
            "prefill_rows_held": 0}
        eng.submit([3, 7, 11], max_new_tokens=6).result(timeout=180)
        first = eng.stats()["attention"]
        # Lengths 3..7 at the five steps that gave tokens 2..6, each with
        # the row it writes: 4, 8, 8, 8, 8 rows in whole pages; the loop
        # may have dispatched one step more before it saw the last token.
        steps, extra = divmod(first["decode_rows_held"], 2 * 64)
        assert extra == 0 and steps in (5, 6)
        assert first["decode_rows_read"] == 36 + 12 * (steps - 5)
        # The rows themselves, each step's with the one it writes.
        assert first["decode_rows_live"] == 30 + 9 * (steps - 5)
        eng.submit(list(range(1, 30)), max_new_tokens=4).result(timeout=180)
        second = eng.stats()["attention"]
        assert second["decode_rows_read"] > first["decode_rows_read"]
        assert second["decode_rows_held"] > first["decode_rows_held"]
        assert second["decode_rows_read"] <= second["decode_rows_held"]
    finally:
        eng.shutdown()


def test_prefill_walk_counters_follow_the_passes_rows(monkeypatch):
    """stats()['attention']['prefill_rows_walked'] and ['prefill_rows_held']:
    for each real row of a dispatched prefill pass, the rows of the blocks
    the pass's walk visits of the slot's table (as far as the longest real
    row's end, in whole blocks: `paged_kv.rows_walked`), summed over the
    layers, and the same rows at the table's full width; cumulative, from
    the pass's own `(offset, n_valid)`, nothing fetched, warm-up's passes
    not counted. With `WALK_BLOCK_ROWS` at 8 a table of 16 pages of 4 walks
    blocks of 8 rows (at the program's 512 it would be one block)."""
    from ray_tpu.serve import paged_kv
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    monkeypatch.setattr(paged_kv, "WALK_BLOCK_ROWS", 8)
    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=64,
                                   page_size=4, prefill_chunk=8)
    try:
        def counts():
            att = eng.stats()["attention"]
            return att["prefill_rows_walked"], att["prefill_rows_held"]

        assert counts() == (0, 0)
        layers = cfg.n_layers
        # One pass of one row, (0, 3): a block of 8 rows of the table's 64.
        eng.submit([3, 7, 11], max_new_tokens=1).result(timeout=180)
        assert counts() == (layers * 8, layers * 64)
        # One pass of three chunks of one prompt, (0, 8), (8, 8), (16, 1),
        # in the four-row program: the walk goes to row 17, three blocks,
        # for each of the three real rows; the inert row adds nothing.
        eng.submit(list(range(1, 18)), max_new_tokens=1).result(timeout=180)
        assert counts() == (layers * (8 + 3 * 24), layers * (64 + 3 * 64))
        # The host's arithmetic by hand: two real rows and an inert one
        # whose offset lengthens nothing.
        before = counts()
        with eng._lock:
            eng._count_prefill_walk_locked(np.asarray([0, 40, 8]),
                                           np.asarray([8, 0, 2]))
        assert counts() == (before[0] + layers * 2 * 16,
                            before[1] + layers * 2 * 64)
    finally:
        eng.shutdown()


def test_sampler_counters_follow_the_slots_that_ask_for_a_top_k():
    """stats()['sampler']: sampled decode steps dispatched, and those of
    them with a live slot whose `top_k > 0`, in which the step finds
    every row's k-th largest logit; cumulative, from the host's mirrors.
    Both stay 0 while every request is greedy (warm-up's steps are not
    counted, and a greedy step runs the greedy-only program)."""
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=64)
    try:
        none = {"dispatches": 0, "top_k_dispatches": 0}
        assert eng.stats()["sampler"] == none
        eng.submit([3, 7, 11], max_new_tokens=6).result(timeout=180)
        # A top_k on a greedy request asks the sampler for nothing.
        eng.submit([3, 7], max_new_tokens=4, top_k=5).result(timeout=180)
        assert eng.stats()["sampler"] == none
        eng.submit([5, 1], max_new_tokens=6, temperature=0.9,
                   top_p=0.9).result(timeout=180)
        first = eng.stats()["sampler"]
        # Five steps gave tokens 2..6; the loop may have dispatched one
        # more before it saw the last token.
        assert first["dispatches"] in (5, 6)
        assert first["top_k_dispatches"] == 0
        eng.submit([2, 9], max_new_tokens=6, temperature=0.7,
                   top_k=16).result(timeout=180)
        second = eng.stats()["sampler"]
        asked = second["dispatches"] - first["dispatches"]
        assert asked in (5, 6)
        assert second["top_k_dispatches"] == asked
        # A slot without a top-k beside one with: the step pays for both.
        a = eng.submit([4, 4, 6], max_new_tokens=6, temperature=1.1)
        b = eng.submit([2, 9], max_new_tokens=6, temperature=0.7, top_k=3)
        a.result(timeout=180), b.result(timeout=180)
        third = eng.stats()["sampler"]
        assert third["dispatches"] > second["dispatches"]
        assert second["top_k_dispatches"] < third["top_k_dispatches"] <= (
            third["dispatches"] - first["dispatches"])
    finally:
        eng.shutdown()


# -- the loop's phase ledger --------------------------------------------

_LEDGER_CHILDREN = ("admit", "prefill_dispatch", "prefill_first_token_wait",
                    "prefill_publish", "upload", "decode_dispatch",
                    "decode_fetch_wait", "distribute")
_LEDGER_CHUNK = 8
# Distinct prompts under a page in common: the prefix cache skips nothing.
_LEDGER_PROMPTS = [[(7 * i + j) % 250 + 1 for j in range(n)]
                   for i, n in enumerate((44, 3, 29, 10, 17, 8))]


@pytest.fixture(scope="module")
def ledger_run(tmp_path_factory):
    """Mixed traffic through one engine under a CPU profiler trace
    (python tracer off): a request alone, so that its prefill turns have
    no decoding slot, then greedy and sampled requests together, then an
    idle stretch and another. Returns stats() at each point and the host plane's
    `engine.*` spans per thread as (name, start, end)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=3, max_len=128,
                                   prefill_chunk=_LEDGER_CHUNK)
    turn, turn_returned = eng._turn, []
    eng._turn = lambda: turn_returned.append(turn())
    trace_dir = str(tmp_path_factory.mktemp("ledger_trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        before = eng.stats()
        eng.submit(_LEDGER_PROMPTS[0], max_new_tokens=6).result(timeout=180)
        alone = eng.stats()
        sampling = [{}, {"temperature": 0.8, "top_p": 0.9}, {},
                    {"temperature": 1.1, "top_k": 8}, {}]
        handles = [eng.submit(p, max_new_tokens=12, **kw)
                   for p, kw in zip(_LEDGER_PROMPTS[1:], sampling)]
        for h in handles:
            assert len(h.result(timeout=180)) == 12
        time.sleep(0.7)  # the loop goes idle: a wait_for_work closes
        after = eng.stats()
        time.sleep(0.6)  # ... and another, with nothing on the device
        idle = eng.stats()
    finally:
        jax.profiler.stop_trace()
        eng.shutdown()
    threads = []
    (xplane,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for ev in line.events if ev.name.startswith("engine.")]
            if spans:
                threads.append(spans)
    return {"before": before, "alone": alone, "after": after, "idle": idle,
            "threads": threads, "turn_returned": turn_returned}


def _ledger_sums_to_the_turn_total(run):
    t = run["after"]["timing"]
    assert t["turns"] > 0 and t["turn_ms_total"] > 0
    assert (t["work_ms_total"] + t["wait_ms_total"] + t["other_ms_total"]
            == pytest.approx(t["turn_ms_total"], rel=1e-9))
    assert t["other_ms_total"] >= 0
    phases = t["phases"]
    assert set(phases) == set(_LEDGER_CHILDREN) | {"wait_for_work"}
    for key in _LEDGER_CHILDREN:
        assert phases[key]["n"] > 0 and phases[key]["ms_total"] > 0, key
    children = sum(phases[k]["ms_total"] for k in _LEDGER_CHILDREN)
    assert children == pytest.approx(
        t["work_ms_total"] + t["wait_ms_total"], rel=1e-9)
    # Idle time is beside the turns: in `phases`, in no total.
    assert phases["wait_for_work"]["ms_total"] >= 500.0
    assert phases["wait_for_work"]["ms_total"] > t["turn_ms_total"] - children


def _ledger_counts_prefill_chunks_and_passes(run):
    """`prefill_chunks` counts dispatches of the prefill program, one a
    pass, and `prefill_rows` the chunks of prompts they held."""
    t0, t1 = run["before"]["timing"], run["after"]["timing"]
    rows = sum(-(-len(p) // _LEDGER_CHUNK) for p in _LEDGER_PROMPTS)
    assert t1["prefill_rows"] - t0["prefill_rows"] == rows
    assert t1["phases"]["prefill_dispatch"]["n"] == t1["prefill_chunks"]
    # No request was cancelled: every pass dispatched, at most four rows.
    assert t1["prefill_passes"] == t1["prefill_chunks"]
    assert 0 < t1["prefill_passes"] <= t1["turns"]
    assert rows / 4 <= t1["prefill_chunks"] < rows
    # Six requests finished prefill in at most six passes' drains.
    assert 0 < t1["phases"]["prefill_first_token_wait"]["n"] <= 6


def _ledger_counts_turns_without_a_decoding_slot(run):
    """A 44-token prompt alone, six chunks, prefills in two passes of a
    turn each; only the last leaves a slot decoding, and a clock over
    the turns that dispatch a decode step would not count the first."""
    t0, t1 = run["before"]["timing"], run["alone"]["timing"]
    turns = t1["turns"] - t0["turns"]
    decoding = _delta(t0, t1, "phases.decode_dispatch.n")
    assert t1["prefill_passes"] - t0["prefill_passes"] == 2
    assert t1["prefill_rows"] - t0["prefill_rows"] == 6
    assert decoding >= 5 and turns - decoding >= 1


def _loop_spans(run):
    loop = [spans for spans in run["threads"]
            if any(n == "engine.turn" for n, _s, _e in spans)]
    assert len(loop) == 1, "engine.turn spans on one thread, the loop's"
    return loop[0]


def _ledger_spans_nest_in_the_profilers_trace(run):
    """On the profiler's clock, in the loop's thread: every child and every
    `engine.pass_drain` lies inside an `engine.turn`, and
    `engine.wait_for_work` inside none. `engine.drained_late` runs from
    where the loop saw the device dry to its next dispatch, across a
    turn's end where that dispatch is the next turn's: it nests in
    nothing."""
    spans = _loop_spans(run)
    turns = [(s, e) for n, s, e in spans if n == "engine.turn"]
    assert len(turns) == (run["after"]["timing"]["turns"]
                          - run["before"]["timing"]["turns"])

    def inside_a_turn(s, e):
        return any(ts <= s and e <= te for ts, te in turns)

    names = {n for n, _s, _e in spans}
    assert names - {"engine.drained_late", "engine.lock_wait"} == (
        {f"engine.{k}" for k in _LEDGER_CHILDREN}
        | {"engine.turn", "engine.wait_for_work", "engine.pass_drain"})
    for n, s, e in spans:
        if n in ("engine.turn", "engine.drained_late", "engine.lock_wait"):
            continue
        assert inside_a_turn(s, e) == (n != "engine.wait_for_work"), n
    # The spans are the ledger's: as many of each as it counted.
    t0, t1 = run["before"]["timing"], run["after"]["timing"]
    for key in _LEDGER_CHILDREN:
        in_trace = sum(1 for n, _s, _e in spans if n == f"engine.{key}")
        assert in_trace == (t1["phases"][key]["n"]
                            - t0["phases"][key]["n"]), key


def _delta(t0, t1, path):
    def dig(doc):
        for key in path.split("."):
            doc = doc[key]
        return doc
    return dig(t1) - dig(t0)


def _ledger_a_first_token_fetch_drains_the_device(run):
    """A request alone: the one pass that ends its prompt is one
    `drained.fetch` stretch (the fetch returned with nothing queued) and
    one `pass_drain` span, which holds the wait and the dry stretch after
    it; the last step's drain, in a turn that dispatched nothing, may be
    one more."""
    t0, t1 = run["before"]["timing"], run["alone"]["timing"]
    assert _delta(t0, t1, "phases.prefill_first_token_wait.n") == 1
    idle_turns = (_delta(t0, t1, "turns")
                  - _delta(t0, t1, "phases.decode_dispatch.n"))
    assert 1 <= _delta(t0, t1, "drained.fetch.n") <= 1 + idle_turns
    assert (0 < _delta(t0, t1, "drained.fetch.ms_total")
            <= _delta(t0, t1, "turn_ms_total"))
    assert _delta(t0, t1, "pass_drain.n") == 1
    assert (_delta(t0, t1, "pass_drain.ms_total")
            >= _delta(t0, t1, "phases.prefill_first_token_wait.ms_total"))
    assert (_delta(t0, t1, "pass_drain.ms_total")
            <= _delta(t0, t1, "turn_ms_total"))


def _ledger_dry_time_lies_inside_the_turns(run):
    """Fetch-drained and late together are part of the turns' time, the
    late stretches are told apart by phase, a dispatch is late at most
    once, and an idle loop adds to `wait_for_work` and to neither cause."""
    t = run["after"]["timing"]
    dry = t["drained"]
    for cause in ("fetch", "late"):
        assert dry[cause]["n"] >= 0 and dry[cause]["ms_total"] >= 0
    assert (dry["fetch"]["ms_total"] + dry["late"]["ms_total"]
            <= t["turn_ms_total"])
    by_phase = dry["late_by_phase"]
    assert set(by_phase) == set(_LEDGER_CHILDREN) | {"other"}
    assert sum(v["n"] for v in by_phase.values()) == dry["late"]["n"]
    assert sum(v["ms_total"] for v in by_phase.values()) == pytest.approx(
        dry["late"]["ms_total"])
    # A fetch's own phase is never where the host was late.
    assert by_phase["prefill_first_token_wait"]["n"] == 0
    assert t["dispatches"] == (t["phases"]["decode_dispatch"]["n"]
                               + t["phases"]["prefill_dispatch"]["n"])
    assert 0 <= t["late_dispatches"] <= min(t["dispatches"],
                                            dry["late"]["n"])
    later = run["idle"]["timing"]
    assert later["drained"] == dry and later["turns"] == t["turns"]
    assert (later["phases"]["wait_for_work"]["ms_total"]
            >= t["phases"]["wait_for_work"]["ms_total"] + 400.0)
    assert t["stalls"]["n"] == 0 and not any(
        v["n"] for v in t["stalls"]["by_phase"].values())


def _ledger_pass_drain_spans_end_with_a_dispatch(run):
    """In the trace: as many `engine.pass_drain` spans as the ledger
    counted, each opened around a first-token fetch and closed just after
    the next dispatch's span (here the decode step that takes the new
    slot in), so it covers the whole gap the fetch leaves on the device."""
    spans = _loop_spans(run)
    drains = [(s, e) for n, s, e in spans if n == "engine.pass_drain"]
    t0, t1 = run["before"]["timing"], run["after"]["timing"]
    assert len(drains) == _delta(t0, t1, "pass_drain.n") > 0
    assert len(drains) == _delta(
        t0, t1, "phases.prefill_first_token_wait.n")
    fetches = [(s, e) for n, s, e in spans
               if n == "engine.prefill_first_token_wait"]
    dispatch_ends = [e for n, _s, e in spans
                     if n in ("engine.decode_dispatch",
                              "engine.prefill_dispatch")]
    for s, e in drains:
        assert sum(1 for fs, fe in fetches if s <= fs and fe <= e) == 1
        assert sum(1 for end in dispatch_ends if s < end <= e) == 1
    late = sum(1 for n, _s, _e in spans if n == "engine.drained_late")
    assert late == _delta(t0, t1, "drained.late.n")


def _ledger_keeps_the_totals_and_no_averages(run):
    """`timing` is cumulative: the ledger's totals stay; the three means
    nobody read are gone (a mean is a total over a count), and so are the
    four totals of the decode turns alone, which `phases.decode_dispatch`,
    `phases.decode_fetch_wait` and `turn_ms_total` hold for every turn:
    `_turn` hands the loop nothing to add up."""
    t = run["after"]["timing"]
    for key in ("turn", "work", "wait", "other"):
        assert t[f"{key}_ms_total"] >= 0.0, key
    assert not [k for k in t if k.endswith("_avg")]
    assert not {"steps_timed", "dispatch_ms_total", "fetch_ms_total",
                "host_ms_total"} & set(t)
    assert run["turn_returned"] and set(run["turn_returned"]) == {None}
    # Readers walk `a.b.c`: no key carries a dot.
    def keys(doc):
        for k, v in doc.items():
            yield k
            if isinstance(v, dict):
                yield from keys(v)
    assert not [k for k in keys(t) if "." in k]


def _ledger_counts_the_time_between_turns(run):
    """A turn's exit to the next turn's entry, where no idle wait lay
    between: at least once in a request's decode steps, at most once a
    turn, in no other total, and never falling."""
    shots = [run[k]["timing"] for k in ("before", "alone", "after", "idle")]
    assert shots[0]["between_turns"] == {"n": 0, "ms_total": 0.0}
    for a, b in zip(shots, shots[1:]):
        assert a["between_turns"]["n"] <= b["between_turns"]["n"]
        assert a["between_turns"]["ms_total"] <= b["between_turns"]["ms_total"]
    for t in shots[1:]:
        assert 1 <= t["between_turns"]["n"] < t["turns"]
        assert t["between_turns"]["ms_total"] > 0.0
    # An idle loop adds nothing: the wait ends the stretch.
    assert shots[3]["between_turns"] == shots[2]["between_turns"]


def _ledger_nobody_made_a_request_alone_wait_for_a_lock(run):
    """Nothing is stamped when the lock is free: a request alone, whose
    caller sleeps in `result()`, finds the engine's lock and its handle's
    condition free at every acquisition."""
    t0, t1 = run["before"]["timing"], run["alone"]["timing"]
    assert set(t1["lock_wait"]) == {"engine", "handle"}
    for kind in ("engine", "handle"):
        assert _delta(t0, t1, f"lock_wait.{kind}.n") == 0
        assert _delta(t0, t1, f"lock_wait.{kind}.ms_total") == 0.0


@pytest.mark.parametrize("check", [
    _ledger_sums_to_the_turn_total,
    _ledger_counts_prefill_chunks_and_passes,
    _ledger_counts_turns_without_a_decoding_slot,
    _ledger_spans_nest_in_the_profilers_trace,
    _ledger_a_first_token_fetch_drains_the_device,
    _ledger_dry_time_lies_inside_the_turns,
    _ledger_pass_drain_spans_end_with_a_dispatch,
    _ledger_keeps_the_totals_and_no_averages,
    _ledger_counts_the_time_between_turns,
    _ledger_nobody_made_a_request_alone_wait_for_a_lock,
], ids=lambda f: f.__name__.lstrip("_"))
def test_phase_ledger(ledger_run, check):
    check(ledger_run)


def _decoding(eng, steps, timeout=120):
    """Wait until one request decodes, none prefills and `steps` decode
    steps were distributed."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        s = eng.stats()
        if (s["active"] == 1 and s["prefilling"] == 0
                and s["steps"] >= steps):
            return
        time.sleep(0.01)
    pytest.fail(f"request never reached decode: {eng.stats()}")


def test_phase_ledger_names_the_phase_the_host_was_late_in():
    """Host work stretched past the step in flight: `_distribute` made to
    outlast it is seen at that phase's exit (`late_by_phase.distribute`)
    and the dispatch that follows is a late one; a chaos stretch between
    two phases, a step in flight, is late time in no phase (this model's
    step is over before `distribute` is, so the stretch it lengthens is
    one first seen there; the test below holds the device's side still
    and sees `other`)."""
    from ray_tpu._private import chaos
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=128)
    inner = eng._distribute

    def slow_distribute(*a, **kw):
        time.sleep(0.02)
        return inner(*a, **kw)

    chaos.enable()
    try:
        t0 = eng.stats()["timing"]
        eng._distribute = slow_distribute
        long_h = eng.submit([3, 7, 11, 2], max_new_tokens=24)
        _decoding(eng, steps=4)
        eng._distribute = inner
        t1 = eng.stats()["timing"]
        # The next pass sleeps before its first phase, a step in flight.
        chaos.delay_prefills(0.05, count=1)
        eng.submit([5, 1, 8, 2, 9, 4], max_new_tokens=4).result(timeout=120)
        long_h.result(timeout=120)
        t2 = eng.stats()["timing"]
    finally:
        chaos.disable()
        chaos.clear()
        eng.shutdown()
    assert _delta(t0, t1, "drained.late_by_phase.distribute.n") >= 1
    assert _delta(t0, t1, "drained.late_by_phase.distribute.ms_total") > 0
    assert _delta(t0, t1, "drained.late.n") >= 1
    assert _delta(t0, t1, "late_dispatches") >= 1
    assert _delta(t1, t2, "drained.late.ms_total") >= 45.0
    assert _delta(t1, t2, "other_ms_total") >= 45.0
    assert _delta(t1, t2, "late_dispatches") >= 1
    assert t2["late_dispatches"] <= t2["dispatches"]
    assert (t2["drained"]["fetch"]["ms_total"]
            + t2["drained"]["late"]["ms_total"]) <= t2["turn_ms_total"]


def test_phase_ledger_tells_late_from_fetched_and_between_from_inside():
    """The ledger alone, the device's side played by a result that is
    ready when told: seen complete at a work phase's exit it is late in
    that phase, at an entry late in `other`, at a wait phase's exit
    drained by the fetch; only a dispatch entered late is a late one, and
    an idle loop ends every stretch."""
    from ray_tpu.serve.llm import _PhaseLedger, _timing_of

    class Result:
        ready = False

        def is_ready(self):
            return self.ready

    ledger = _PhaseLedger()

    def dispatch(key="decode_dispatch"):
        with ledger(key):
            ledger.newest = result = Result()
        return result

    with ledger("turn"):
        step = dispatch()
        with ledger("distribute"):
            step.ready = True           # done while the host distributes
        assert ledger.newest is None    # seen: nothing more to poll
        with ledger("admit"):
            pass
        step = dispatch()               # entered late
        step.ready = True               # done between two phases
        with ledger("upload"):
            pass
        step = dispatch()               # entered late again
        with ledger("decode_fetch_wait"):
            step.ready = True           # the loop was made to wait
        with ledger("prefill_publish"):
            pass
        step = dispatch("prefill_dispatch")  # ends a fetch's stretch
        with ledger("prefill_first_token_wait"):
            step.ready = True
    with ledger("wait_for_work"):
        pass
    t = _timing_of(ledger.snapshot())
    dry = t["drained"]
    assert {k: v["n"] for k, v in dry["late_by_phase"].items() if v["n"]} \
        == {"distribute": 1, "other": 1}
    assert dry["late"]["n"] == 2 and dry["fetch"]["n"] == 2
    assert t["dispatches"] == 4 and t["late_dispatches"] == 2
    assert t["pass_drain"]["n"] == 1
    assert all(v["ms_total"] > 0 for v in (dry["late"], dry["fetch"],
                                            t["pass_drain"]))
    assert (dry["late"]["ms_total"] + dry["fetch"]["ms_total"]
            <= t["turn_ms_total"])
    # Idle: nothing in flight, nothing open, and no stretch grows.
    assert ledger.newest is None and not ledger._dry
    assert ledger._pass_span is None and ledger._late_span is None
    before = dict(ledger.s)
    with ledger("wait_for_work"):
        pass
    assert {k: v for k, v in ledger.s.items() if v != before[k]}.keys() \
        == {"wait_for_work"}


def test_phase_ledger_counts_and_logs_a_stall(monkeypatch, caplog):
    """One span of a phase past `STALL_S` (brought down here: nobody
    sleeps a quarter of a second) is one stall under its phase and one
    line of the module's log; `wait_for_work`, however long, is none."""
    import logging

    from ray_tpu.serve import llm

    params, cfg = _tiny_model()
    eng = llm.ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=64)
    inner = eng._distribute
    calls = []

    def stalling_distribute(*a, **kw):
        calls.append(1)
        if len(calls) == 3:
            time.sleep(0.16)
        return inner(*a, **kw)

    monkeypatch.setattr(llm, "STALL_S", 0.1)
    eng._distribute = stalling_distribute
    try:
        with caplog.at_level(logging.WARNING, logger="ray_tpu.serve.llm"):
            eng.submit([3, 7, 11], max_new_tokens=8).result(timeout=120)
            time.sleep(0.7)  # an idle stretch of 0.5 s: no stall
            t = eng.stats()["timing"]
    finally:
        eng.shutdown()
    stalls = t["stalls"]
    assert stalls["by_phase"]["distribute"]["n"] == 1
    assert 160.0 <= stalls["by_phase"]["distribute"]["ms_total"]
    assert stalls["n"] == sum(v["n"] for v in stalls["by_phase"].values())
    assert stalls["ms_total"] == pytest.approx(
        sum(v["ms_total"] for v in stalls["by_phase"].values()))
    assert t["phases"]["wait_for_work"]["ms_total"] >= 400.0
    lines = [r.getMessage() for r in caplog.records
             if r.name == "ray_tpu.serve.llm" and "stalled" in r.getMessage()]
    assert len([m for m in lines if m.endswith("in distribute "
                                                "(a healthy phase is under "
                                                "100 ms)")]) == 1, lines
    assert len(lines) == stalls["n"]


@pytest.mark.parametrize("how", ["engine_lock", "handle_push", "handle_fail"])
def test_lock_wait_stamps_nothing_when_free_and_the_wait_when_not(
        monkeypatch, how):
    """The loop's three ways to a lock. `_Held(ledger, lock)` (the engine's):
    free, the lock is taken with no clock read, no span and no count; held
    by another thread, the wait is one contended acquisition with what it
    waited, under one `engine.lock_wait` span. A handle's `_push` and
    `_fail` take its condition themselves and return what they waited
    (0.0 where it was free, with no stamp but `_push`'s own), which
    `lock_waited` counts; they open no span (`cancel()` calls `_fail` from
    a caller's thread). On a clock that ticks 50 ms a read, so that the
    wait is the two stamps' and no thread's luck."""
    import threading

    from ray_tpu.serve import llm

    spans, clock_reads = [], []

    class Span:
        def __init__(self, name):
            spans.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    def perf_counter():  # 50 ms a read
        clock_reads.append(1)
        return 0.05 * len(clock_reads)

    ledger = llm._PhaseLedger()
    handle = llm.GenerationHandle(0)
    lock = threading.Lock() if how == "engine_lock" else handle._cond
    kind = "engine" if how == "engine_lock" else "handle"
    own_stamps = 1 if how == "handle_push" else 0

    def take():
        if how == "engine_lock":
            with llm._Held(ledger, lock):
                assert lock.locked()
        elif how == "handle_push":
            ledger.lock_waited("handle", handle._push(5, False))
        else:
            ledger.lock_waited("handle", handle._fail(RuntimeError("x")))

    monkeypatch.setattr(llm.jax.profiler, "TraceAnnotation", Span)
    monkeypatch.setattr(llm.time, "perf_counter", perf_counter)
    free = {"n": 0, "ms_total": 0.0}
    if how != "handle_fail":  # a failed handle stays failed: its one take is below
        take()
        assert not spans and len(clock_reads) == own_stamps
        t = llm._timing_of(ledger.snapshot())
        assert t["lock_wait"] == {"engine": free, "handle": free}

    taken, release = threading.Event(), threading.Event()

    def hold():
        with lock:
            taken.set()
            release.wait(timeout=30)

    holder = threading.Thread(target=hold)
    holder.start()
    assert taken.wait(timeout=30)
    threading.Timer(0.05, release.set).start()
    reads = len(clock_reads)
    take()
    holder.join(timeout=30)
    # The wait's two stamps, the first of them `_push`'s own.
    assert len(clock_reads) == reads + 2
    t = llm._timing_of(ledger.snapshot())
    other = "handle" if kind == "engine" else "engine"
    assert t["lock_wait"][kind] == {"n": 1, "ms_total": pytest.approx(50.0)}
    assert t["lock_wait"][other] == free  # each under its own kind
    assert spans == ["engine.lock_wait"] * (how == "engine_lock")
    if how == "handle_fail":
        assert handle._error is not None and handle._done


def test_push_stamps_the_token_before_it_waits_for_the_condition(monkeypatch):
    """`_push` reads its stamp (first token, the observatory card's
    `push_t`) before it takes the condition, so a consumer that holds the
    condition delays the token's delivery and not its stamp, and the wait
    `_push` returns starts at that stamp."""
    import threading
    from types import SimpleNamespace

    from ray_tpu.serve import llm

    ticks = []

    def perf_counter():  # 50 ms a read
        ticks.append(1)
        return 0.05 * len(ticks)

    handle = llm.GenerationHandle(0)
    handle.obs = SimpleNamespace(push_t=[], marks={}, tokens_out=0)
    taken, release = threading.Event(), threading.Event()

    def hold():
        with handle._cond:
            taken.set()
            release.wait(timeout=30)

    holder = threading.Thread(target=hold)
    holder.start()
    assert taken.wait(timeout=30)
    threading.Timer(0.05, release.set).start()
    monkeypatch.setattr(llm.time, "perf_counter", perf_counter)
    waited = handle._push(5, False)
    holder.join(timeout=30)
    assert handle.obs.push_t == [pytest.approx(0.05)]  # the first read
    assert handle.obs.marks["first_token"] == handle._first_token_t
    assert handle._first_token_t == pytest.approx(0.05)
    assert waited == pytest.approx(0.05) and len(ticks) == 2
    assert list(handle._tokens) == [5]


def test_phase_reads_one_clock_twice_and_no_system_call(monkeypatch):
    """A phase of the ledger costs two `perf_counter` reads (the vDSO's)
    and never `time.thread_time()`, a system call a read on a sandboxed
    host, with which the loop stalled more often (`PERF.md`, PR 63)."""
    from ray_tpu.serve import llm

    reads = {"perf_counter": 0, "thread_time": 0}

    def clock(name, step):
        def read():
            reads[name] += 1
            return step * reads[name]
        return read

    monkeypatch.setattr(llm.time, "perf_counter", clock("perf_counter", 0.002))
    monkeypatch.setattr(llm.time, "thread_time", clock("thread_time", 0.001))
    ledger = llm._PhaseLedger()
    with ledger("wait_for_work"):
        pass
    with ledger("turn"):
        for key in llm._TURN_PHASES:
            with ledger(key):
                pass
    assert reads == {"perf_counter": 2 * (2 + len(llm._TURN_PHASES)),
                     "thread_time": 0}
    t = llm._timing_of(ledger.snapshot())
    assert all(set(p) == {"n", "ms_total"} for p in t["phases"].values())
    assert t["phases"]["distribute"]["ms_total"] == pytest.approx(2.0)
    assert not [k for k in t if "cpu" in k]


def _paced_engine():
    """A tiny engine whose `_distribute` sleeps 2 ms first, so that a
    request of two hundred tokens decodes for half a second and more."""
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=256)
    inner = eng._distribute

    def paced(*a, **kw):
        time.sleep(0.002)
        return inner(*a, **kw)

    eng._distribute = paced
    return eng


def _published(eng, path, t0, timeout=30):
    """stats()["timing"] once the loop has published a turn in which the
    count at `path` grew past `t0`'s (the ledger's copy is a turn old),
    or the last one read."""
    deadline = time.monotonic() + timeout
    while True:
        t1 = eng.stats()["timing"]
        if _delta(t0, t1, path) >= 1 or time.monotonic() > deadline:
            return t1
        time.sleep(0.01)


def test_phase_ledger_counts_the_loops_wait_for_the_engines_lock():
    """Another thread holds the engine's lock across a turn (0.25 s: a
    few paced turns even on a loaded machine): the loop's next acquisition
    is a contended one, and what it waited is in `lock_wait.engine` and,
    wherever the loop stood, in a turn's wall time or between two."""
    eng = _paced_engine()
    try:
        h = eng.submit([3, 7, 11, 2], max_new_tokens=200)
        _decoding(eng, steps=4)
        t0 = eng.stats()["timing"]
        with eng._lock:
            time.sleep(0.25)
        t1 = _published(eng, "lock_wait.engine.n", t0)
        h.cancel()
    finally:
        eng.shutdown()
    assert _delta(t0, t1, "lock_wait.engine.n") >= 1
    assert _delta(t0, t1, "lock_wait.engine.ms_total") >= 40.0
    assert (_delta(t0, t1, "turn_ms_total")
            + _delta(t0, t1, "between_turns.ms_total")
            >= _delta(t0, t1, "lock_wait.engine.ms_total"))
    assert _delta(t0, t1, "lock_wait.handle.n") == 0


def test_phase_ledger_counts_the_loops_wait_for_a_handles_condition():
    """`GenerationHandle.__iter__` yields holding its condition: a
    consumer that takes 0.25 s over a token makes the loop's next `_push`
    to it wait, which is `lock_wait.handle`."""
    eng = _paced_engine()
    try:
        t0 = eng.stats()["timing"]
        h = eng.submit([3, 7, 11, 2], max_new_tokens=24)
        # stats() takes the engine's lock, which the loop holds while it
        # waits to push: nothing reads it before the consumer is through
        # (a suspended generator that called stats() would wait for the
        # loop that waits for it).
        for i, _ in enumerate(h):
            if i == 0:
                time.sleep(0.25)  # suspended inside `with self._cond`
        t1 = _published(eng, "lock_wait.handle.n", t0)
    finally:
        eng.shutdown()
    assert _delta(t0, t1, "lock_wait.handle.n") >= 1
    assert _delta(t0, t1, "lock_wait.handle.ms_total") >= 40.0


def test_continuous_batching_tp_sharded():
    """The engine over a tp=8 mesh (KV heads sharded, params via
    shard_params) decodes bit-identically to the single-device engine —
    the pod-serving layout with collectives inside the compiled step."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import configs, init_params, param_logical_axes
    from ray_tpu.parallel import MeshConfig, build_mesh, shard_params
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    devices = jax.devices()[:8]
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    cfg = replace(configs.tiny, d_model=64, d_ff=128, vocab_size=128,
                  n_layers=2, n_heads=8, n_kv_heads=8, max_seq=64,
                  remat=False, dtype=np.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)

    base_eng = ContinuousBatchingEngine(params, cfg, num_slots=2,
                                        max_len=48)
    try:
        base = base_eng.submit([3, 7, 5], max_new_tokens=6).result(
            timeout=180
        )
    finally:
        base_eng.shutdown()

    mesh = build_mesh(MeshConfig(tp=8), devices)
    sharded = shard_params(params, param_logical_axes(cfg), mesh)
    tp_eng = ContinuousBatchingEngine(sharded, cfg, num_slots=2,
                                      max_len=48, mesh=mesh)
    try:
        tp = tp_eng.submit([3, 7, 5], max_new_tokens=6).result(timeout=180)
    finally:
        tp_eng.shutdown()
    assert tp == base


def test_llm_deployment_tp_via_loader(rt_serve):
    """Tensor-parallel serving through serve.run: the loader builds the
    mesh and shards params inside the replica (a Mesh cannot cross the
    actor boundary) and returns (params, cfg, mesh)."""
    import jax.numpy as jnp

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import llm_deployment

    def loader():
        import jax

        from ray_tpu.models import configs, init_params, param_logical_axes
        from ray_tpu.parallel import MeshConfig, build_mesh, shard_params

        cfg = replace(configs.tiny, d_model=64, d_ff=128, vocab_size=128,
                      n_layers=2, n_heads=8, n_kv_heads=8, max_seq=64,
                      remat=False, dtype=np.float32)
        params = init_params(jax.random.PRNGKey(0), cfg)
        mesh = build_mesh(MeshConfig(tp=8), jax.devices()[:8])
        return shard_params(params, param_logical_axes(cfg), mesh), cfg, mesh

    app = llm_deployment(loader, num_slots=2, max_len=48,
                         default_max_new_tokens=5)
    handle = serve.run(app, name="tpllm")
    out = rt.get(handle.remote([3, 7, 5]), timeout=180)

    import jax

    from ray_tpu.models import configs, init_params

    cfg = replace(configs.tiny, d_model=64, d_ff=128, vocab_size=128,
                  n_layers=2, n_heads=8, n_kv_heads=8, max_seq=64,
                  remat=False, dtype=np.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ref = np.asarray(
        generate(params, jnp.asarray([[3, 7, 5]], dtype=jnp.int32), cfg,
                 max_new_tokens=5)
    )[0].tolist()
    assert out == ref


def test_chunked_prefill_parity_and_interleaving():
    """A multi-chunk prompt decodes bit-identically to generate(), and
    a short request arriving during the long prompt's prefill is served
    WITHOUT waiting for it (chunks interleave with decode steps)."""
    import jax.numpy as jnp

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    long_prompt = [(7 * i) % 250 + 1 for i in range(90)]  # 12 chunks @ 8
    eng = ContinuousBatchingEngine(params, cfg, num_slots=3, max_len=128,
                                   prefill_chunk=8)
    try:
        long_h = eng.submit(long_prompt, max_new_tokens=6)
        short_h = eng.submit([5, 9], max_new_tokens=4)
        short = short_h.result(timeout=180)
        long_out = long_h.result(timeout=180)
        ref_long = np.asarray(
            generate(params, jnp.asarray([long_prompt], dtype=jnp.int32),
                     cfg, max_new_tokens=6)
        )[0].tolist()
        ref_short = np.asarray(
            generate(params, jnp.asarray([[5, 9]], dtype=jnp.int32), cfg,
                     max_new_tokens=4)
        )[0].tolist()
        assert long_out == ref_long
        assert short == ref_short
        # The short request's single chunk completed while the long
        # prompt was still chunking — STRICTLY earlier admission is the
        # interleaving property (whole-prompt prefill would admit both
        # in the same iteration): a pass gives every mid-prefill slot a
        # row before it gives the long prompt the rows left, and twelve
        # chunks are three passes at least.
        assert short_h.admitted_at_step < long_h.admitted_at_step
    finally:
        eng.shutdown()


# -- a prefill pass is one program over rows ------------------------------

_ROWS_CHUNK = 8
_ROWS_PROMPTS = [[(5 * i + 3 * j) % 250 + 1 for j in range(n)]
                 for i, n in enumerate((19, 3, 12, 30, 8))]


@pytest.fixture(scope="module")
def rows_engine():
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=5, max_len=128,
                                   prefill_chunk=_ROWS_CHUNK)
    yield eng, params, cfg
    eng.shutdown()


@pytest.mark.parametrize("together", [1, 2, 3, 5])
def test_prompts_sent_together_share_prefill_passes(rows_engine, together):
    """Prompts of mixed lengths submitted together, their chunks rows of
    shared passes (more than one row a dispatch), give, greedy, the
    tokens `generate` gives one at a time, and nothing compiles after
    warm-up at either width."""
    import jax.numpy as jnp

    from ray_tpu.models import generate

    eng, params, cfg = rows_engine
    # A first token of its own a case: the prefix cache skips nothing.
    prompts = [[100 + together] + p for p in _ROWS_PROMPTS[:together]]
    before = eng.stats()
    handles = [eng.submit(p, max_new_tokens=6) for p in prompts]
    outs = [h.result(timeout=180) for h in handles]
    stats = eng.stats()
    refs = [np.asarray(generate(params, jnp.asarray([p], dtype=jnp.int32),
                                cfg, max_new_tokens=6))[0].tolist()
            for p in prompts]
    assert outs == refs
    timing, was = stats["timing"], before["timing"]
    rows = timing["prefill_rows"] - was["prefill_rows"]
    dispatches = timing["prefill_chunks"] - was["prefill_chunks"]
    assert rows == sum(-(-len(p) // _ROWS_CHUNK) for p in prompts)
    assert rows / dispatches > 1
    # (The counter is the process's: `generate` above compiles after it
    # was read.)
    assert stats["recompiles_post_warm"] == before["recompiles_post_warm"]


def test_short_prompts_sent_alone_are_passes_of_one_row(rows_engine):
    """A prompt under a chunk, sent when nothing else prefills, is a
    pass of one row: the one-row program, which costs what a chunk cost
    before a pass had rows."""
    eng, _, _ = rows_engine
    before = eng.stats()
    for prompt in ([4, 2], [9, 9, 1, 7], [250]):
        assert len(eng.submit(prompt, max_new_tokens=3).result(
            timeout=180)) == 3
    after = eng.stats()
    rows = after["timing"]["prefill_rows"] - before["timing"]["prefill_rows"]
    dispatches = (after["timing"]["prefill_chunks"]
                  - before["timing"]["prefill_chunks"])
    assert rows == dispatches == 3
    assert after["recompiles_post_warm"] == before["recompiles_post_warm"]


def test_a_pass_holds_at_most_pass_tokens(rows_engine):
    """The two widths a pass is compiled at follow the chunk: one row and
    `PASS_ROWS` at a chunk of 8, one and two rows at a chunk of 256
    (`PASS_TOKENS` 512), where a prompt of three chunks then prefills in
    two passes and decodes as `generate` does."""
    import jax.numpy as jnp

    from ray_tpu.models import generate
    from ray_tpu.serve import llm

    eng, params, cfg = rows_engine
    assert eng._pass_rows == (1, llm.PASS_ROWS) == (1, 4)
    assert llm.PASS_TOKENS == 512
    prompt = [(11 * i) % 250 + 1 for i in range(2 * 256 + 40)]
    ref = np.asarray(generate(params, jnp.asarray([prompt], dtype=jnp.int32),
                              cfg, max_new_tokens=4))[0].tolist()
    wide = llm.ContinuousBatchingEngine(params, cfg, num_slots=2,
                                        max_len=640, prefill_chunk=256)
    try:
        assert wide._pass_rows == (1, 2)
        assert wide.submit(prompt, max_new_tokens=4).result(
            timeout=180) == ref
        timing = wide.stats()["timing"]
    finally:
        wide.shutdown()
    assert timing["prefill_rows"] == 3 and timing["prefill_chunks"] == 2


def test_a_hybrid_gets_one_row_a_slot_a_pass():
    """A model with recurrent layers: a chunk starts from the state the
    chunk before it left, so a pass never holds two rows of one prompt;
    several prompts' chunks still share passes, and each request's greedy
    tokens are what it gets sent alone."""
    import jax

    from ray_tpu.models import configs, init_params
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    cfg = replace(configs.get_config("tiny_granite_h"), dtype=np.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompts = [p[:n] for p, n in zip(_ROWS_PROMPTS, (19, 3, 12, 30))]
    eng = ContinuousBatchingEngine(params, cfg, num_slots=4, max_len=128,
                                   prefill_chunk=_ROWS_CHUNK)
    passes = []
    dispatch = eng._dispatch_prefill

    def recorded(tokens, n_valid, slots, offsets):
        passes.append([int(s) for s, n in zip(slots, n_valid) if n])
        return dispatch(tokens, n_valid, slots, offsets)

    eng._dispatch_prefill = recorded
    try:
        alone = [eng.submit(p, max_new_tokens=5).result(timeout=180)
                 for p in prompts]
        assert all(len(slots) == 1 for slots in passes)
        assert len(passes) == sum(-(-len(p) // _ROWS_CHUNK) for p in prompts)
        del passes[:]
        handles = [eng.submit(p, max_new_tokens=5) for p in prompts]
        assert [h.result(timeout=180) for h in handles] == alone
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert all(len(set(slots)) == len(slots) for slots in passes)
    assert max(len(slots) for slots in passes) > 1
    # The recurrent counters sum over a pass's rows: real tokens, and
    # every row the program computed, the inert ones too.
    assert stats["ssm"]["prefill_tokens_valid"] == 2 * sum(map(len, prompts))
    assert stats["ssm"]["prefill_tokens_computed"] % _ROWS_CHUNK == 0
    assert stats["recompiles_post_warm"] == 0


def test_chunked_prefill_non_multiple_max_len():
    """Regression: a final chunk whose padding runs past the cache end
    must DROP the overflow rows, not clamp the write start over earlier
    chunks (dynamic_update_slice clamping corrupted the cache when
    max_len was not a multiple of prefill_chunk)."""
    import jax.numpy as jnp

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    prompt = [(3 * i) % 250 + 1 for i in range(35)]
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=40,
                                   prefill_chunk=16)  # 40 % 16 != 0
    try:
        out = eng.submit(prompt, max_new_tokens=4).result(timeout=180)
    finally:
        eng.shutdown()
    ref = np.asarray(
        generate(params, jnp.asarray([prompt], dtype=jnp.int32), cfg,
                 max_new_tokens=4)
    )[0].tolist()
    assert out == ref


def test_engine_recovers_after_decode_failure():
    """A decode-step failure fails the in-flight handles with the error
    and the engine keeps serving: the donated cache buffers rebuild
    (mesh placement included) and later requests succeed."""
    import jax.numpy as jnp

    from ray_tpu.models import generate
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=48)
    try:
        boom = RuntimeError("injected decode failure")
        real = eng._decode_greedy
        calls = {"n": 0}

        def flaky(*args, **kwargs):
            calls["n"] += 1
            if calls["n"] == 1:
                raise boom
            return real(*args, **kwargs)

        eng._decode_greedy = flaky
        h = eng.submit([3, 1, 4], max_new_tokens=6)
        with pytest.raises(RuntimeError, match="injected"):
            h.result(timeout=120)
        # The engine recovered: a fresh request decodes correctly.
        out = eng.submit([3, 1, 4], max_new_tokens=6).result(timeout=180)
        ref = np.asarray(
            generate(params, jnp.asarray([[3, 1, 4]], dtype=jnp.int32),
                     cfg, max_new_tokens=6)
        )[0].tolist()
        assert out == ref
    finally:
        eng.shutdown()


@pytest.mark.slow
def test_llm_replica_killed_and_replaced(rt_serve):
    """Fault tolerance for the continuous-batching serving path: kill
    the LLM replica actor; the controller's reconcile replaces it (a
    fresh engine boots in the new actor) and later requests succeed."""
    import time as _time

    from ray_tpu.serve.controller import CONTROLLER_NAME
    from ray_tpu.serve.llm import llm_deployment

    app = llm_deployment(_tiny_model, num_slots=2, max_len=48,
                         default_max_new_tokens=4)
    handle = serve.run(app, name="killable")
    first = rt.get(handle.remote([1, 2, 3]), timeout=180)
    assert len(first) == 4

    ctrl = rt.get_actor(CONTROLLER_NAME)
    (replica,) = rt.get(
        ctrl.get_replicas.remote("killable"), timeout=60
    )["replicas"]
    rt.kill(replica)

    deadline = _time.monotonic() + 120
    out = None
    while _time.monotonic() < deadline:
        try:
            out = rt.get(handle.remote([1, 2, 3]), timeout=60)
            break
        except Exception:  # noqa: BLE001 — replica still rebooting
            _time.sleep(0.5)
    assert out == first, (
        "replacement replica never served (or served differently)"
    )
