"""Inference benchmark: KV-cache decode throughput on the flagship model.

Prints one JSON line per batch size: prefill tokens/s and steady-state
decode tokens/s/chip for the 0.8B Llama config (the serving-side
counterpart of bench.py's training MFU; decode is memory-bandwidth-bound,
so tokens/s scales with batch until HBM saturates), then the serving
probes: continuous batching vs the static path, the engine's stepwise
breakdown (dispatch/fetch/host per step + compile/upload counts), and
the engine-vs-raw decode throughput ratio. Writes BENCH_INFER.json.

One process, on the chip: where `jax.devices()` shows no TPU it exits
non-zero and writes nothing. Run it through the builder's chip tool:
python bench_infer.py
"""

from __future__ import annotations

import json
import sys
import time
from dataclasses import replace

import jax
import jax.numpy as jnp


def _engine_stepwise_probe(params, cfg):
    """Decompose the continuous-batching engine's steady-state step and
    compare it with a raw jitted batch=num_slots decode at the same
    shapes (same cache length, same batch rows).

    Two entries: (1) the per-step breakdown — raw floor, engine step,
    overhead, and where the overhead goes (dispatch / fetch / host),
    plus compile and sampling-param-upload counts inside the window
    (both must be 0: sampling params live on device and re-upload only
    on admission/eviction, the token fetch is double-buffered, and the
    step programs never retrace after warmup); (2) the engine-vs-raw
    throughput ratio for an all-greedy full-occupancy run.

    Residual gap, by construction: the engine's decode step stays
    intrinsically heavier than a raw argmax decode — masked per-slot
    cache writes at per-slot offsets, the on-device pick with
    per-slot temperature/top-k/top-p gathers, and per-step host
    bookkeeping (slot table, handle queues, timing) that no amount of
    device residency removes.
    """
    from ray_tpu.models.generate import decode_step, init_kv_cache, prefill
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    num_slots = 4
    plen = 8
    n_tok = 256
    max_len = plen + n_tok + 8
    raw_steps = 60
    window = 48
    rounds = 3  # min-of-N: this box's wall clock is noisy (factor ~2)

    # Raw floor: jitted decode at batch=num_slots over a cache of the
    # engine's [num_slots, max_len] shape, greedy argmax picks. The
    # cache is donated (as the engine's decode jit donates its k/v
    # buffers) so the floor measures in-place appends, not a
    # copy-the-cache-per-step strawman.
    jprefill = jax.jit(lambda p, t, c: prefill(p, t, c, cfg))

    def _raw_step(p, t, c):  # decode + greedy pick in ONE program,
        logits, c = decode_step(p, t, c, cfg)  # like the engine's step
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), c

    jdecode = jax.jit(_raw_step, donate_argnums=(2,))
    prompt = jax.random.randint(
        jax.random.PRNGKey(2), (num_slots, plen), 0, cfg.vocab_size
    )
    logits, c = jprefill(params, prompt,
                         init_kv_cache(cfg, num_slots, max_len))
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    tok, c = jdecode(params, tok, c)  # warm (donates + replaces c)
    jax.device_get(tok)
    raw_step_ms = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(raw_steps):
            tok, c = jdecode(params, tok, c)
        jax.device_get(tok)  # rtlint: disable=RT001 — stepwise probe: the per-step sync IS the measured quantity
        raw_s = time.perf_counter() - t0
        raw_step_ms = min(raw_step_ms, raw_s / raw_steps * 1e3)
    raw_tps = num_slots / raw_step_ms * 1e3

    prompts = [
        list(map(int, jax.device_get(jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(3), i), (plen,),
            0, cfg.vocab_size
        ))))
        for i in range(num_slots)
    ]
    eng = ContinuousBatchingEngine(
        params, cfg, num_slots=num_slots, max_len=max_len,
        prefill_chunk=plen,
    )
    try:
        t_submit = time.perf_counter()
        handles = [eng.submit(p, max_new_tokens=n_tok) for p in prompts]
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:  # full occupancy
            s0 = eng.stats()
            if s0["active"] == num_slots and s0["prefilling"] == 0:
                break
            time.sleep(0.002)
        settle = s0["steps"] + 2  # let the last admission's upload land
        while time.monotonic() < deadline:
            s0 = eng.stats()
            if s0["steps"] >= settle:
                break
            time.sleep(0.002)
        t0 = time.perf_counter()
        windows = []
        for _ in range(rounds):
            target = s0["steps"] + window
            s1 = s0
            while time.monotonic() < deadline:
                s1 = eng.stats()
                if s1["steps"] >= target:
                    break
                time.sleep(0.002)
            t1 = time.perf_counter()
            windows.append((s0, s1, t0, t1))
            s0, t0 = s1, t1
        for h in handles:
            h.result(timeout=600)
        t_done = time.perf_counter()
    finally:
        eng.shutdown()

    # Best window = the least-preempted one (same min-of-N as raw).
    s0, s1, t0, t1 = min(
        windows,
        key=lambda x: (x[3] - x[2]) / max(x[1]["steps"] - x[0]["steps"], 1),
    )
    w = max(s1["steps"] - s0["steps"], 1)
    wt = max(s1["timing"]["steps_timed"] - s0["timing"]["steps_timed"], 1)
    engine_step_ms = (t1 - t0) / w * 1e3

    def part(name):
        key = f"{name}_ms_total"
        return round((s1["timing"][key] - s0["timing"][key]) / wt, 3)

    breakdown = {
        "metric": "engine step breakdown",
        "num_slots": num_slots,
        "window_steps": w,
        "raw_decode_step_ms": round(raw_step_ms, 3),
        "engine_step_ms": round(engine_step_ms, 3),
        "engine_overhead_ms": round(engine_step_ms - raw_step_ms, 3),
        "dispatch_ms": part("dispatch"),
        "fetch_ms": part("fetch"),
        "host_ms": part("host"),
        "compiles_in_window": s1["compiles"] - s0["compiles"],
        "param_uploads_in_window": (
            s1["param_uploads"] - s0["param_uploads"]
        ),
    }
    engine_tps = num_slots * n_tok / (t_done - t_submit)
    ratio = {
        "metric": "engine vs raw decode throughput",
        "num_slots": num_slots,
        "tokens_per_request": n_tok,
        "raw_decode_tokens_per_s": round(raw_tps, 1),
        "engine_tokens_per_s": round(engine_tps, 1),
        "engine_vs_raw_throughput_ratio": round(engine_tps / raw_tps, 3),
    }
    return [breakdown, ratio]


def main():
    from ray_tpu.models import configs, init_params
    from ray_tpu.models.generate import decode_step, init_kv_cache, prefill

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[bench_infer] no TPU: jax.devices()[0] is {dev.platform}/"
              f"{dev.device_kind}; this benchmark only runs on the chip",
              file=sys.stderr, flush=True)
        return 1
    cfg = replace(configs.get_config("llama2-1b"), n_layers=12,
                  max_seq=1024, remat=False)
    batches = (1, 8, 32)
    prompt_len, decode_steps = 512, 64

    params = init_params(jax.random.PRNGKey(0), cfg)
    results = []
    for batch in batches:
        max_len = prompt_len + decode_steps
        prompt = jax.random.randint(
            jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab_size
        )
        cache = init_kv_cache(cfg, batch, max_len)
        jprefill = jax.jit(lambda p, t, c: prefill(p, t, c, cfg))  # rtlint: disable=RT002 — per-config rebuild is intended; each config needs its own wrapper
        jdecode = jax.jit(lambda p, t, c: decode_step(p, t, c, cfg))  # rtlint: disable=RT002 — per-config rebuild is intended

        # Warm both compilations.
        logits, cache1 = jprefill(params, prompt, cache)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        _, cache2 = jdecode(params, tok, cache1)
        jax.device_get(logits)  # rtlint: disable=RT001 — timed section deliberately syncs to measure true step latency

        t0 = time.perf_counter()
        logits, cache1 = jprefill(params, prompt, init_kv_cache(cfg, batch, max_len))
        jax.device_get(logits)  # rtlint: disable=RT001 — timed section deliberately syncs
        prefill_s = time.perf_counter() - t0

        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t0 = time.perf_counter()
        c = cache1
        for _ in range(decode_steps):
            logits, c = jdecode(params, tok, c)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        jax.device_get(tok)  # rtlint: disable=RT001 — timed section deliberately syncs
        decode_s = time.perf_counter() - t0

        entry = {
            "metric": "llama2(0.8B) decode tokens/s/chip",
            "batch": batch,
            "prefill_tokens_per_s": round(batch * prompt_len / prefill_s, 1),
            "decode_tokens_per_s": round(batch * decode_steps / decode_s, 1),
            "ms_per_decode_step": round(decode_s / decode_steps * 1e3, 2),
        }
        print(json.dumps(entry), flush=True)
        results.append(entry)

    # Continuous batching at mixed arrivals vs static batch=1 (the
    # serving north-star, BASELINE.json configs[4]): requests join a
    # running decode loop at step boundaries instead of waiting for the
    # current batch to finish.
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    n_req, n_tok = 8, 32
    cb_prompt_len = min(prompt_len, 64)
    rng = jax.random.PRNGKey(7)
    prompts = [
        list(map(int, jax.device_get(jax.random.randint(
            jax.random.fold_in(rng, i), (cb_prompt_len,), 0, cfg.vocab_size
        ))))
        for i in range(n_req)
    ]
    from ray_tpu.models.generate import generate

    # Warm the static path's compilation before timing it (the engine's
    # warmup request below plays the same role for the continuous path).
    jax.device_get(generate(
        params, jnp.asarray([prompts[0]], dtype=jnp.int32), cfg,
        max_new_tokens=n_tok,
    ))
    t0 = time.perf_counter()
    for p in prompts:
        jax.device_get(generate(  # rtlint: disable=RT001 — end-to-end timing requires draining the whole generation
            params, jnp.asarray([p], dtype=jnp.int32), cfg,
            max_new_tokens=n_tok,
        ))
    static_s = time.perf_counter() - t0

    eng = ContinuousBatchingEngine(
        params, cfg, num_slots=4, max_len=cb_prompt_len + n_tok + 1,
        prefill_chunk=cb_prompt_len,
    )
    try:
        eng.submit(prompts[0], max_new_tokens=n_tok).result(timeout=600)
        t0 = time.perf_counter()
        handles = [eng.submit(p, max_new_tokens=n_tok) for p in prompts]
        for h in handles:
            h.result(timeout=600)
        cont_s = time.perf_counter() - t0
    finally:
        eng.shutdown()
    entry = {
        "metric": "continuous batching tokens/s/chip",
        "requests": n_req,
        "tokens_per_request": n_tok,
        "static_batch1_tokens_per_s": round(n_req * n_tok / static_s, 1),
        "continuous_tokens_per_s": round(n_req * n_tok / cont_s, 1),
        "speedup_vs_static": round(static_s / cont_s, 2),
    }
    print(json.dumps(entry), flush=True)
    results.append(entry)

    for entry in _engine_stepwise_probe(params, cfg):
        print(json.dumps(entry), flush=True)
        results.append(entry)

    from ray_tpu.util.device_peaks import device_report

    for entry in results:
        entry["device"] = device_report()
    with open("BENCH_INFER.json", "w") as f:
        json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
