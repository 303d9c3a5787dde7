"""A serving cell: deploy the engine through `serve.run`, warm the request
path, offer the mix's load for the window through
`handle.options(stream=True, method_name="stream")`, and check the served
model against the configuration's float32 reference outside the window.

`BenchReplica` is the benchmark's subclass of the program's `LLMReplica`:
the same engine, constructed the same way, plus what a measurement needs
from inside the process that holds the chip (seeded weights made on the
device, the compile listener, the profiler, the reference comparison).
It changes nothing the engine does.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List

from ray_tpu.serve.llm import LLMReplica

# rms(system - reference) / rms(reference) of last-position logits after
# all layers, bf16 weights and activations against float32. Two bf16
# layouts of this model differ by 0.034-0.040 and unrelated logits by 1.4
# (my chip runs, PR 21); an 8-bit weight or cache path would give 0.1-0.3.
LOGITS_TOLERANCE = 0.08
# How far the reference's logit of a served greedy token may lie under the
# reference's largest logit, in units of the reference logits' rms. bf16
# rounding moves a logit by about 0.04 rms, so near-ties flip the argmax
# (token equality is not the criterion); a token read through a wrong
# cache row is a random one, some 4 rms under the top of 151,936 logits.
# Measured on the chip: 0.030-0.032 for the logits, margins up to 0.023.
MARGIN_TOLERANCE = 0.25
# How long `offer` waits, after the callers' end, for the thread that
# started and stopped the profiler in the middle of the window.
TRACER_JOIN_S = 120.0


class BenchReplica(LLMReplica):
    def __init__(self, config: Dict, seed: int, platform: str):
        import listener

        listener.install()  # before the first compilation
        import spec
        import weights

        cfg = spec.program_config(config, platform)
        self._dims = spec.dims_of(cfg, config)
        self._reference = spec.named_module(config, "reference")
        self._probe = spec.named_module(config, "probe")
        leaf_rule = spec.leaf_rules(cfg, config)
        t0 = time.monotonic()

        def loader():
            from dataclasses import replace

            return (weights.make_params(cfg, seed, leaf_rule),
                    replace(cfg, remat=False))

        super().__init__(loader, **config["engine"])
        self._ready_s = time.monotonic() - t0
        self._trace = None

    def about(self) -> Dict:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        return {"pid": os.getpid(), "construct_s": self._ready_s,
                "dims": self._dims,
                "memory_peak_bytes": max(peaks),
                "device": self.engine.stats()["device"]}

    def window(self, is_open: bool) -> Dict:
        """Open or close the measured window in this process: the compile
        count between the two calls, and the engine's counters at each."""
        import listener

        if is_open:
            listener.open_window()
            counts = {}
        else:
            counts = listener.close_window()
        return {"stats": self.engine.stats(), "listener": counts,
                "t": time.time()}

    def observatory_records(self) -> List[Dict]:
        from ray_tpu.serve import observatory

        return observatory.profiler().records()

    def trace_start(self, trace_dir: str) -> bool:
        import jax

        from xplane import reduce as xr

        # Host spans around the engine loop's own calls, so that an idle
        # gap on the device can be named by what the host was doing. The
        # loop looks these methods up on the instance at every call.
        eng = self.engine
        for name in ("_advance_prefills", "_upload_sampling_state",
                     "_upload_block_table"):
            inner = getattr(eng, name, None)
            if inner is None or getattr(inner, "_bench_span", False):
                continue

            def spanned(*a, _inner=inner, _name=name, **kw):
                with jax.profiler.TraceAnnotation(f"engine.{_name}"):
                    return _inner(*a, **kw)

            spanned._bench_span = True
            setattr(eng, name, spanned)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        t_in = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # The window's two ends are events in the trace itself, made after
        # the profiler has started and before it is stopped: the engine's
        # thread dispatches through both calls, and the reduction clips
        # what it records to the span between the marks. Two short marks,
        # since this call and `trace_stop` may run on different threads
        # and an annotation is its thread's.
        with jax.profiler.TraceAnnotation(xr.WINDOW_START):
            t0 = time.perf_counter()
        self._trace = {"dir": trace_dir, "t0": t0, "start_trace_s": t0 - t_in}
        return True

    def trace_stop(self) -> bool:
        """Mark the window's end and stop the profiler. The trace is read
        and reduced by `trace_reduce`, once the `stats` window has closed:
        seconds of Python that would hold the interpreter against the
        engine's thread inside the window."""
        import jax

        from xplane import reduce as xr

        with jax.profiler.TraceAnnotation(xr.WINDOW_END):
            t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self._trace.update(clocked_window_s=t1 - self._trace.pop("t0"),
                           stop_trace_s=time.perf_counter() - t1)
        return True

    def trace_reduce(self) -> Dict:
        from xplane import reduce as xr

        took, self._trace = self._trace, None
        t_in = time.perf_counter()
        # BENCH_KEEP_TRACE=1 leaves the .xplane.pb in the run's directory,
        # for a look by hand; the line is the same.
        out = xr.reduce_dir(took.pop("dir"),
                            keep=bool(os.environ.get("BENCH_KEEP_TRACE")))
        # For the log alone: the host's clock between the two marks, which
        # was the window until PR 66, and what the profiler's two calls
        # (inside the `stats` window) and this one (outside it) took.
        out.update(took, reduce_s=time.perf_counter() - t_in)
        return out

    def prefill_logits(self, prompt: List[int]):
        """Next-token logits, float32 [vocab], of `prompt` from the
        program's own prefill, through the probe the configuration names
        (bench/probes/): what the cache is made of is that file's to know."""
        return self._probe.prefill_logits(self.engine, prompt)

    def reference_check(self, prompts: List[List[int]],
                        served: List[List[int]]) -> Dict:
        """Prefill logits against the reference's last-position logits,
        and every served greedy token against the reference's full forward
        pass over prompt + tokens so far (by margin, see above)."""
        import jax.numpy as jnp
        import numpy as np

        params, out, reference = self.engine.params, [], self._reference
        # Every sample padded to one length (causal: what follows a
        # position cannot reach it), so the reference compiles one layer.
        width = -(-max(len(p) + len(t) for p, t in zip(prompts, served)) // 64) * 64
        for prompt, tokens in zip(prompts, served):
            seq = list(prompt) + list(tokens[:-1])
            padded = jnp.asarray(seq + [0] * (width - len(seq)), jnp.int32)
            hidden = reference.hidden_layerwise(params, padded, self._dims)
            rows = hidden[len(prompt) - 1:len(seq)]
            ref = np.asarray(reference.logits_rows(params, rows, self._dims))
            sys_first = self.prefill_logits(prompt)
            rms = float(np.sqrt(np.mean(ref[0] ** 2)))
            rel = float(np.sqrt(np.mean((sys_first - ref[0]) ** 2))) / rms
            margins = [float(ref[i].max() - ref[i][t]) / rms
                       for i, t in enumerate(tokens)]
            out.append({"prompt_len": len(prompt), "logits_rel_rms": rel,
                        "max_margin_rms": max(margins),
                        "tokens_equal": int(sum(
                            int(ref[i].argmax()) == t
                            for i, t in enumerate(tokens))),
                        "tokens": len(tokens)})
        return {"samples": out,
                "logits_ok": all(s["logits_rel_rms"] <= LOGITS_TOLERANCE
                                 for s in out),
                "margin_ok": all(s["max_margin_rms"] <= MARGIN_TOLERANCE
                                 for s in out)}


def reduced_trace(handle, traced: Dict, tracer: threading.Thread) -> Dict:
    """The traced stretch reduced inside the replica, after the `stats`
    window has closed; `{"error": reason}` where there is no trace to
    reduce or the reduction fails (`run.trace_fault` ends the run on it)."""
    if tracer.is_alive():
        return {"error": "the tracer thread had not returned "
                         f"{TRACER_JOIN_S:g} s after the callers' end"}
    if not traced.get("stopped"):
        return {"error": traced.get("error", "the profiler was not stopped")}
    try:
        return _call(handle, "trace_reduce", timeout=300.0)
    except Exception as e:  # noqa: BLE001
        return {"error": f"the trace could not be reduced: {_brief(e)}"}


def _brief(e: BaseException) -> str:
    """A remote failure carries the worker's whole traceback; its last
    line says what failed."""
    rows = [row for row in str(e).splitlines() if row.strip()]
    return f"{type(e).__name__}: {rows[-1].strip() if rows else ''}"


def _call(handle, method: str, *args, timeout: float = 300.0):
    return handle.options(method_name=method).remote(*args).result(
        timeout=timeout)


def deploy(ctx: Dict):
    """`serve.run` of the cell's configuration; returns the handle once
    the replica has answered and the request path is warm."""
    import random

    from ray_tpu import serve
    from ray_tpu.serve.deployment import deployment

    from traffic import client

    cell, say = ctx["cell"], ctx["say"]
    config, mix = cell["config"], cell["traffic"]
    app = deployment(
        BenchReplica, name="LLMReplica",
        # llm_deployment's own default: admission lives in the engine.
        max_ongoing_requests=64,
        ray_actor_options={"resources": {"TPU": cell["chips"]}},
    ).bind(config, ctx["seed"], ctx["platform"])
    t0 = time.monotonic()
    handle = serve.run(app, name=cell["name"])
    ctx["note_processes"]()
    about = _call(handle, "about")
    say(f"deployed in {time.monotonic() - t0:.1f}s (replica constructed in "
        f"{about['construct_s']:.1f}s, pid {about['pid']}), device "
        f"{about['device']}")
    # Warm the request path with the window's own kind of request: the
    # engine warmed its programs when it was built, but the first request
    # through the handle, the replica's stream threads and the engine's
    # small eager programs each run once before they are fast.
    rng = random.Random(ctx["seed"])
    vocab = config["vocab_size"]
    warm = [{"prompt": [rng.randrange(vocab) for _ in range(n)],
             "max_new": 4, "prompt_len": n, "t": 0.0}
            for n in (mix["prompt"]["lo"], mix["prompt"]["median"])]
    stamps = client.drive_open(_caller(handle, mix), [], warm,
                               time.perf_counter(), vocab, drain_s=120.0)
    bad = [s.error for s in stamps if not s.ok]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad}")
    return handle


def _caller(handle, mix: Dict):
    sampling = mix["sampling"]
    stream = handle.options(stream=True, method_name="stream",
                            deadline_s=float(mix["deadline_s"]))

    def call(request: Dict):
        return stream.remote(request["prompt"], request["max_new"],
                             temperature=sampling["temperature"],
                             top_p=sampling["top_p"])

    return call


def offer(ctx: Dict, handle, mix: Dict, seconds: float, trace: bool) -> Dict:
    """Offer the mix's load for one window: the ramp, then `seconds`
    measured. Returns the client's summary, the engine's counters at the
    window's two ends, and the reduced trace of a traced run."""
    from traffic import client, generate

    vocab = ctx["cell"]["config"]["vocab_size"]
    requests = generate.serve_requests(mix, ctx["seed"], seconds, vocab)
    ramp_s = requests["ramp_s"]
    traced, opened = {}, {}
    t_window = time.perf_counter() + ramp_s + 0.2
    started_at = time.time() + ramp_s + 0.2

    def tracer():
        """One traced stretch in the middle of the window."""
        trace_dir = os.path.join(ctx["run_dir"], "trace")
        time.sleep(max(0.0, t_window + 0.4 * seconds - time.perf_counter()))
        try:
            _call(handle, "trace_start", trace_dir)
            time.sleep(float(mix.get("trace_s", 3.0)))
            traced["stopped"] = _call(handle, "trace_stop")
        except Exception as e:  # noqa: BLE001 — the run says why it has no trace
            traced["error"] = f"the profiler could not be run: {_brief(e)}"

    def open_window():
        time.sleep(max(0.0, t_window - time.perf_counter()))
        opened.update(_call(handle, "window", True))

    side = [threading.Thread(target=open_window, daemon=True)]
    if trace:
        side.append(threading.Thread(target=tracer, daemon=True))
    for th in side:
        th.start()
    drain_s = float(mix["deadline_s"]) + 10.0
    call = _caller(handle, mix)
    if requests["kind"] == "open":
        stamps = client.drive_open(call, requests["ramp"], requests["window"],
                                   t_window, vocab, drain_s)
    else:
        stamps = client.drive_closed(call, requests["pool"],
                                     requests["clients"], t_window, seconds,
                                     vocab, drain_s,
                                     float(mix.get("stagger_s", 0.0)))
    for th in side:
        th.join(timeout=TRACER_JOIN_S)
    closed = _call(handle, "window", False)
    if trace:
        traced = reduced_trace(handle, traced, side[-1])
    summary = client.summarize(stamps, t_window, seconds, mix.get("limits"))
    end = t_window + seconds
    summary["decode_tokens_in_window"] = sum(
        1 for s in stamps for t in s.token_t[1:] if t_window <= t < end)
    # Requests due and not yet done, half way and at the window's end: a
    # backlog that grows over the window is a rate past the knee.
    for name, at in (("outstanding_half", t_window + seconds / 2),
                     ("outstanding_end", end)):
        summary[name] = sum(1 for s in stamps if s.due <= at
                            and (s.done is None or s.done > at))
    return {"summary": summary, "started_at": started_at, "trace": traced,
            "listener": closed["listener"],
            "stats": {"before": opened.get("stats"), "after": closed["stats"],
                      "window_s": closed["t"] - opened.get("t", closed["t"])},
            "opened_t": opened.get("t", closed["t"])}


def run(ctx: Dict) -> Dict:
    import random

    cell, say = ctx["cell"], ctx["say"]
    config, mix = cell["config"], cell["traffic"]
    vocab = config["vocab_size"]
    handle = deploy(ctx)
    got = offer(ctx, handle, mix, ctx["seconds"], ctx["trace"])
    ctx["mark_window_start_at"](got["started_at"])
    summary, stats = got["summary"], got["stats"]
    say(f"client: {summary}")
    # Correctness, outside the window: greedy requests through the same
    # handle, then the reference inside the replica.
    rng = random.Random(ctx["seed"] + 1)
    sample = [[rng.randrange(vocab) for _ in range(n)]
              for n in config["check"]["prompt_lens"]]
    greedy = handle.options(stream=True, method_name="stream")
    served = [list(greedy.remote(p, config["check"]["new_tokens"]))
              for p in sample]
    check = _call(handle, "reference_check", sample, served, timeout=600.0)
    say(f"reference: {check} (tolerances: logits {LOGITS_TOLERANCE}, "
        f"margin {MARGIN_TOLERANCE})")
    about = _call(handle, "about")
    say(f"engine: steps {stats['after']['steps'] - stats['before']['steps']}, "
        f"shed {stats['after']['shed_total']}, recompiles_post_warm "
        f"{stats['after']['recompiles_post_warm']}, pages in use "
        f"{stats['after']['kv'].get('pages_in_use')} of "
        f"{stats['after']['kv'].get('pages_total')}, memory peak "
        f"{about['memory_peak_bytes'] / 1e9:.2f} GB")
    sources = {
        "client": summary, "stats": stats, "listener": got["listener"],
        "trace": got["trace"],
        "observatory": ([r for r in _call(handle, "observatory_records")
                         if got["opened_t"] <= r["ts"] - r["e2e_s"]
                         and r["method"] == "stream"]
                        if ctx["trace"] else []),
        "model": {"num_slots": config["engine"]["num_slots"],
                  "dims": about["dims"], "operations": config["operations"],
                  "device": about["device"]},
    }
    return {
        "correct": bool(check["logits_ok"] and check["margin_ok"]),
        "compared": {
            "logits_rel_rms_max": {
                "value": max(s["logits_rel_rms"] for s in check["samples"]),
                "limit": LOGITS_TOLERANCE, "holds": check["logits_ok"]},
            "greedy_margin_rms_max": {
                "value": max(s["max_margin_rms"] for s in check["samples"]),
                "limit": MARGIN_TOLERANCE, "holds": check["margin_ok"]}},
        "attempted": summary["attempted"], "failed": summary["failed"],
        "values": {
            "ttft_p95_ms": summary["ttft_ms"]["p95"],
            "tpot_p95_ms": summary["tpot_ms"]["p95"],
            "serve_tokens_per_s": summary["tokens_per_s"],
        },
        "sources": sources,
        "device": dict(about["device"],
                       memory_peak_bytes=about["memory_peak_bytes"]),
    }
