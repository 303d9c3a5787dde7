"""A training cell: `JaxTrainer.fit()` with one worker holding the cell's
chips, one ahead-of-time step program, fresh seeded batches made on the
device, the loss fetched every `loss_every` steps as a job logs it, and the
window closed by a sync.

The loop is the benchmark's own (it starts from `chip_smoke.py`'s), so
that the system's loss and gradients can be set against the float32
reference before the optimizer state exists, and so that the profiler and
the compile listener run in the process that holds the chips.
"""

from __future__ import annotations

import math
import os
import time
from typing import Dict

# |system loss - reference loss| on one sequence: bf16 weights and
# activations with float32 accumulation against float32 throughout. Two
# bf16 layouts of this step differed by up to 0.023 over seven steps (my
# chip runs, PR 21); a loss near ln(vocab) = 11.93 is resolved by bf16 to
# about 0.03.
LOSS_TOLERANCE = 0.02
# Relative difference of the global gradient norm, and the least cosine
# between the system's and the reference's gradient over the largest
# leaves. bf16 gradients of random-weight layers agree with float32 to a
# few percent in norm; a wrong mask, a dropped term or a lower-precision
# accumulation shows as a cosine well under 0.98. Measured on the chip at
# 4 layers: loss 12.3830 against 12.3836, norms 0.00014 apart, cosine 0.9997.
GRAD_NORM_TOLERANCE = 0.01
GRAD_COSINE_FLOOR = 0.995
# The first window loss against ln(vocab): random tokens, weights at the
# head's scale (PERF.md finding 7: 12.41 on the chip).
FIRST_LOSS_DISTANCE = 1.0


def _reference_check(reference, params, cfg, dims, mesh, seed: int,
                     check: Dict) -> Dict:
    """The system's loss (and gradients) on one seeded sequence against
    `reference`, the module the configuration file names."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import loss_fn

    import weights

    tokens = jax.random.randint(
        jax.random.fold_in(weights.seed_key(seed), 7),
        (check["seq"] + 1,), 0, cfg.vocab_size)
    batch = jnp.tile(tokens[None], (check.get("copies", 1), 1))
    if check["gradients"]:
        # Tokens are arguments, not constants of the program: one
        # compiled program serves every seed.
        sys_loss, sys_grads = jax.jit(jax.value_and_grad(
            lambda p, b: loss_fn(p, b, cfg, mesh)))(params, batch)
        ref_loss, ref_grads = reference.loss_and_grads(params, tokens, dims)
        flat_s = jax.tree.leaves(sys_grads)
        flat_r = jax.tree.leaves(ref_grads)
        norm = lambda t: math.sqrt(sum(  # noqa: E731
            float(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in t))
        ns, nr = norm(flat_s), norm(flat_r)
        big = sorted(range(len(flat_r)), key=lambda i: -flat_r[i].size)[:4]
        cos = []
        for i in big:
            a = np.asarray(flat_s[i].astype(jnp.float32)).ravel()
            b = np.asarray(flat_r[i]).ravel()
            cos.append(float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b))))
        out = {"grad_norm_rel": abs(ns - nr) / nr, "grad_cosine_min": min(cos)}
        out["ok"] = (out["grad_norm_rel"] <= GRAD_NORM_TOLERANCE
                     and out["grad_cosine_min"] >= GRAD_COSINE_FLOOR)
    else:
        sys_loss = jax.jit(lambda p, b: loss_fn(p, b, cfg, mesh))(params, batch)
        ref_loss = reference.loss_layerwise(params, tokens, dims)
        out = {"ok": True}
    out["loss_system"], out["loss_reference"] = float(sys_loss), float(ref_loss)
    out["ok"] = bool(out["ok"] and abs(out["loss_system"]
                                       - out["loss_reference"]) <= LOSS_TOLERANCE)
    return out


def train_loop(config: Dict):
    """JaxTrainer's per-worker loop."""
    import listener

    listener.install()
    from dataclasses import replace

    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import spec
    import weights
    from xplane import reduce as xr
    from ray_tpu import train
    from ray_tpu.models import loss_fn, param_logical_axes
    from ray_tpu.parallel import MeshConfig, build_mesh, logical_shardings
    from ray_tpu.util.device_peaks import device_report

    doc, mix, seed = config["config"], config["traffic"], config["seed"]
    step_doc = doc["step"]
    timings = {"loop_entered_t": time.time()}
    lap = time.monotonic()

    def took(what: str) -> None:
        nonlocal lap
        timings[what] = time.monotonic() - lap
        lap = time.monotonic()

    cfg = replace(
        spec.program_config(doc, config["platform"]), max_seq=mix["seq"],
        remat=True, remat_policy=step_doc["remat_policy"],
        ce_chunk=step_doc["ce_chunk"])
    dims = spec.dims_of(cfg, doc)
    mesh = build_mesh(MeshConfig(**doc["deployment"]["mesh"]), jax.devices())
    replicated = NamedSharding(mesh, P())
    params = weights.make_params(
        cfg, seed, spec.leaf_rules(cfg, doc),
        logical_shardings(param_logical_axes(cfg), mesh))
    jax.block_until_ready(params)
    took("mesh_and_weights_s")
    check = _reference_check(spec.named_module(doc, "reference"), params,
                             cfg, dims, mesh, seed, doc["check"])
    took("reference_check_s")
    optimizer = optax.adamw(step_doc["lr"])
    opt_state = optimizer.init(params)
    layouts = jax.tree.map(
        lambda x: (x.sharding if isinstance(x.sharding, NamedSharding)
                   else replicated), (params, opt_state))
    opt_state = jax.device_put(opt_state, layouts[1])

    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg, mesh)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    batch_sharding = NamedSharding(mesh, P(("dp", "fsdp"), None))
    data_key = jax.random.fold_in(weights.seed_key(seed), 1)
    make_batch = jax.jit(
        lambda i: jax.random.randint(
            jax.random.fold_in(data_key, i), (mix["batch"], mix["seq"] + 1),
            0, cfg.vocab_size),
        out_shardings=batch_sharding)
    t0 = time.monotonic()
    # The state leaves a step in the layout it entered with (left to the
    # partitioner, replicated norm scales come back sharded and the second
    # step is another program); an ahead-of-time executable refuses
    # instead of retracing.
    compiled = jax.jit(
        step, donate_argnums=(0, 1), out_shardings=(*layouts, replicated),
    ).lower(params, opt_state, make_batch(jax.numpy.int32(0))).compile()
    compile_s = time.monotonic() - t0
    hlo = compiled.as_text()
    took("optimizer_and_compile_s")
    n_step = 0

    def run_steps(n: int):
        nonlocal params, opt_state, n_step
        loss = None
        for _ in range(n):
            with jax.profiler.TraceAnnotation("bench.make_batch"):
                tokens = make_batch(jax.numpy.int32(n_step))
            with jax.profiler.TraceAnnotation("bench.dispatch_step"):
                params, opt_state, loss = compiled(params, opt_state, tokens)
            n_step += 1
        with jax.profiler.TraceAnnotation("bench.fetch_loss"):
            return float(loss)

    run_steps(2)  # warm: both programs have run, the loss has been fetched
    took("warm_steps_s")
    every = int(mix["loss_every"])
    tokens_per_step = mix["batch"] * mix["seq"]
    prof = train.StepProfiler(emit_metrics=False)
    losses, traced = [], {}
    trace_at = 0.4 * config["seconds"] if config["trace"] else None
    ready_t = time.time()
    listener.open_window()
    w0 = time.perf_counter()
    steps0 = n_step
    trace_dir = os.path.join(config["run_dir"], "trace")
    traced_span = (0.0, 0.0, 0)  # seconds of the window spent tracing
    while time.perf_counter() - w0 < config["seconds"]:
        if trace_at is not None and time.perf_counter() - w0 >= trace_at:
            # A few steps between two syncs, traced.
            t_in = time.perf_counter()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t_tr = time.perf_counter()
            n_tr = int(mix.get("trace_steps", 3))
            # The window is this span in the trace itself, inside the
            # profiler's two calls; the reduction clips to it.
            with jax.profiler.TraceAnnotation(xr.WINDOW):
                losses.append(run_steps(n_tr))
            tr_s = time.perf_counter() - t_tr
            jax.profiler.stop_trace()
            traced_span = (time.perf_counter() - t_in, tr_s, n_tr)
            trace_at = None
            continue
        with prof.step(tokens=every * tokens_per_step):
            losses.append(run_steps(every))  # the fetch is the sync
    # The rate is over the untraced part of the window: starting and
    # stopping the profiler costs seconds that no user pays.
    window_s = time.perf_counter() - w0 - traced_span[0]
    counts = listener.close_window()
    steps = n_step - steps0 - traced_span[2]
    if traced_span[2]:
        traced = xr.reduce_dir(trace_dir)
        traced["steps"] = traced_span[2]
        # For the log alone: the host's clock around the traced steps,
        # which was the window until PR 66.
        traced["clocked_window_s"] = traced_span[1]
    records = [dict(r, steps=every) for r in prof.records()]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    n_chips = len(jax.devices())
    train.report({
        "check": check, "compile_s": compile_s, "ready_t": ready_t,
        "timings": timings,
        "window_s": window_s, "steps": steps, "losses": losses,
        "tokens_per_s_chip": steps * tokens_per_step / window_s / n_chips,
        "kernels": hlo.count("tpu_custom_call"),
        "collectives": sum(hlo.count(op + "(") + hlo.count(op + "-start(")
                           for op in ("all-reduce", "all-gather",
                                      "reduce-scatter", "all-to-all")),
        "listener": counts, "recorder": records, "trace": traced,
        "device": dict(device_report(), memory_peak_bytes=max(peaks)),
        "dims": dims, "pid": os.getpid(),
        "ln_vocab": math.log(cfg.vocab_size),
    })


def run(ctx: Dict) -> Dict:
    from ray_tpu.parallel import MeshConfig
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cell, say = ctx["cell"], ctx["say"]
    config, mix = cell["config"], cell["traffic"]
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={
            "config": config, "traffic": mix, "seed": ctx["seed"],
            "seconds": ctx["seconds"], "trace": ctx["trace"],
            "platform": ctx["platform"], "run_dir": ctx["run_dir"]},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True, tpus_per_worker=cell["chips"],
            mesh=MeshConfig(**config["deployment"]["mesh"])),
        run_config=RunConfig(
            name=cell["name"],
            storage_path=os.path.join(ctx["run_dir"], "trainer")),
    )
    result = trainer.fit()
    ctx["note_processes"]()
    if result.error is not None:
        raise result.error
    out = result.metrics
    ctx["mark_window_start_at"](out["ready_t"])
    losses, check = out["losses"], out["check"]
    say("set-up inside the worker: " + ", ".join(
        f"{k} {v:.1f}" for k, v in out["timings"].items() if k.endswith("_s"))
        + f"; loop entered {out['timings']['loop_entered_t'] - ctx['t_start']:.1f}s "
        "after the run started")
    say(f"train: compile {out['compile_s']:.1f}s, {out['steps']} steps in "
        f"{out['window_s']:.2f}s, kernels in the step {out['kernels']}, "
        f"collectives {out['collectives']}, worker pid {out['pid']}")
    say("losses: " + ", ".join(f"{x:.4f}" for x in losses)
        + f" (ln vocab {out['ln_vocab']:.3f})")
    say(f"reference: {check} (tolerances: loss {LOSS_TOLERANCE}, gradient "
        f"norm {GRAD_NORM_TOLERANCE}, cosine {GRAD_COSINE_FLOOR})")
    finite = all(math.isfinite(x) for x in losses)
    near = bool(losses) and abs(losses[0] - out["ln_vocab"]) < FIRST_LOSS_DISTANCE
    device = out["device"]
    # Each number beside its limit; `correct` is that every one holds.
    loss_apart = abs(check["loss_system"] - check["loss_reference"])
    compared = {"loss_apart": {"value": loss_apart, "limit": LOSS_TOLERANCE,
                               "holds": loss_apart <= LOSS_TOLERANCE}}
    if "grad_norm_rel" in check:
        compared["grad_norm_rel"] = {
            "value": check["grad_norm_rel"], "limit": GRAD_NORM_TOLERANCE,
            "holds": check["grad_norm_rel"] <= GRAD_NORM_TOLERANCE}
        compared["grad_cosine_min"] = {  # a floor
            "value": check["grad_cosine_min"], "limit": GRAD_COSINE_FLOOR,
            "holds": check["grad_cosine_min"] >= GRAD_COSINE_FLOOR}
    compared["first_loss_from_ln_vocab"] = {
        "value": abs(losses[0] - out["ln_vocab"]) if losses else None,
        "limit": FIRST_LOSS_DISTANCE, "holds": near}
    return {
        "correct": bool(check["ok"] and finite
                        and all(c["holds"] for c in compared.values())),
        "compared": compared,
        "attempted": out["steps"],
        "failed": sum(1 for x in losses if not math.isfinite(x)),
        "values": {"train_tokens_per_s_chip": out["tokens_per_s_chip"]},
        "sources": {
            "client": {"tokens_per_s_chip": out["tokens_per_s_chip"],
                       "window_s": out["window_s"], "steps": out["steps"]},
            "recorder": out["recorder"], "trace": out["trace"],
            "listener": out["listener"],
            "model": {"dims": out["dims"], "seq": mix["seq"],
                      "operations": config["operations"], "device": device},
        },
        "device": device,
    }
