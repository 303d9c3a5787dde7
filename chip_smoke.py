#!/usr/bin/env python3
"""The quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py             one chip (what the driver runs)
    python chip_smoke.py --chips 4   one four-chip host (run by the builder)

One chip: the main path end to end through the entry points a user
calls, at the published widths of Qwen3-4B (2560 / 9728, 32 query and 8
KV heads of 128, vocabulary 151,936 tied), weights random from a seed.

  * serve: rt.init() -> serve.run(llm_deployment(loader, ...,
    ray_actor_options={"resources": {"TPU": 1}})) -> a few requests
    through the handle (three prompt lengths, one streamed, one
    sampled) -> serve.shutdown(). All 36 layers.
  * train: JaxTrainer(train_loop, ScalingConfig(num_workers=1,
    use_tpu=True, tpus_per_worker=1)).fit(): AdamW steps at 8x1024
    tokens with the flash and rmsnorm kernels in the program and the
    chunked loss on. Depth is the only cut (TRAIN_LAYERS below).

Four chips: only what exists across chips, each beside what it is
compared with: two one-chip actors on different chips; the train
configuration under MeshConfig(fsdp=2, tp=2) against the same steps on
one device; the engine tensor-parallel over four chips against a
one-chip engine, on first-step logits.

This process never initialises a JAX backend: a parent that holds the
chip starves the workers that need it. The device in the last line is
what the workers that held the chip reported. Any phase that fails
raises, so the exit code is non-zero and no result line is printed;
with no chip on the node the script stops before its first phase.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import signal
import subprocess
import sys
import time
from dataclasses import asdict, dataclass
from typing import Dict, List, Optional, Tuple

import ray_tpu as rt  # imports neither JAX nor the native store

REPO = os.path.dirname(os.path.abspath(__file__))

# Depth of the train phase, from the compiler's memory_analysis() of the
# whole step compiled for a described v5e (4.76 GB of donated arguments
# and 8.9 GB of temporaries at 4 layers; 7.2 + 12.8 GB at 8, which a
# 16.9 GB chip refuses). Widths, heads and vocabulary are never cut.
TRAIN_LAYERS = 4
# |loss(mesh) - loss(one device)| a step may show: same seed, same
# batches, bf16 parameters, another reduction order.
LOSS_TOLERANCE = 0.05
# rms(logits(tp) - logits(one chip)) / rms(logits(one chip)) at the first
# step, after 36 bf16 layers. Unrelated logits give about 1.4.
LOGITS_TOLERANCE = 0.1


@dataclass(frozen=True)
class Plan:
    """What one run drives. The defaults are the chip run; the CPU
    rehearsal in tests/ passes a tiny one."""

    model: str = "qwen3-4b"
    platform: str = "tpu"  # what every worker that held chips must report
    num_tpus: Optional[int] = None  # None: what the node detects
    seed: int = 0
    # serve
    serve_layers: Optional[int] = None  # None: the model's own depth
    slots: int = 4
    max_len: int = 512
    prompt_lens: Tuple[int, ...] = (7, 70, 200)
    new_tokens: int = 16
    # train
    train_layers: int = TRAIN_LAYERS
    batch: int = 8
    seq: int = 1024
    ce_chunk: int = 256
    lr: float = 3e-4
    fresh_steps: int = 2
    repeat_steps: int = 4


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


@contextlib.contextmanager
def deadline(seconds: int, waiting_for: str):
    """Every wait in this script ends: past `seconds` the main thread
    gets a TimeoutError that names what was being waited for."""

    def on_alarm(signum, frame):
        raise TimeoutError(f"not done within {seconds}s: {waiting_for}")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, previous)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# What runs inside the workers that hold chips
# ---------------------------------------------------------------------------


def sharded_init(cfg, mesh, seed: int):
    """init_params laid out on `mesh` by the model's logical axes, made
    where it lives (the values do not depend on the layout)."""
    import jax

    from ray_tpu.models import init_params, param_logical_axes
    from ray_tpu.parallel import logical_shardings

    return jax.jit(
        lambda key: init_params(key, cfg),
        out_shardings=logical_shardings(param_logical_axes(cfg), mesh),
    )(jax.random.PRNGKey(seed))


def run_train_steps(plan: Plan, mesh_axes: Dict[str, int], devices) -> Dict:
    """The train phase on `devices` under MeshConfig(**mesh_axes): one
    compile, then fresh batches, then one batch repeated."""
    from dataclasses import replace

    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import configs, loss_fn
    from ray_tpu.parallel import MeshConfig, build_mesh

    cfg = replace(
        configs.get_config(plan.model), n_layers=plan.train_layers,
        max_seq=plan.seq, remat=True, remat_policy="dots_nobatch",
        ce_chunk=plan.ce_chunk,
    )
    mesh = build_mesh(MeshConfig(**mesh_axes), devices)
    params = sharded_init(cfg, mesh, plan.seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    per_device: Dict[int, int] = {d.id: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] += shard.data.nbytes
    replicated = NamedSharding(mesh, P())
    optimizer = optax.adamw(plan.lr)
    # zeros_like keeps each moment in its parameter's layout; the step
    # count joins the mesh whole.
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

    def batch(i: int):
        key = jax.random.fold_in(jax.random.PRNGKey(plan.seed + 1), i)
        return jax.device_put(
            jax.random.randint(key, (plan.batch, plan.seq + 1), 0,
                               cfg.vocab_size), batch_sharding)

    t0 = time.monotonic()
    # The state leaves a step in the layout it entered with: left to
    # the partitioner, replicated norm scales come back sharded and the
    # second step is another program.
    compiled = jax.jit(
        step, donate_argnums=(0, 1), out_shardings=(*layouts, replicated),
    ).lower(params, opt_state, batch(0)).compile()
    compile_s = time.monotonic() - t0
    memory = compiled.memory_analysis()
    hlo = compiled.as_text()
    losses: List[float] = []
    t0 = time.monotonic()
    # Fresh batches of random tokens teach nothing; a repeated one does.
    order = list(range(plan.fresh_steps)) + (
        [plan.fresh_steps] * (plan.repeat_steps + 1))
    for i in order:
        params, opt_state, loss = compiled(params, opt_state, batch(i))
        losses.append(float(loss))
    return {
        "layers": cfg.n_layers,
        "params": n_params,
        "param_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
        "param_bytes_per_device": sorted(per_device.values()),
        "compile_s": compile_s,
        "compiles": 1,  # one ahead-of-time executable ran every step
        "steps_s": time.monotonic() - t0,
        "argument_gb": memory.argument_size_in_bytes / 1e9,
        "temp_gb": memory.temp_size_in_bytes / 1e9,
        "kernels": hlo.count("tpu_custom_call"),
        "collectives": sum(hlo.count(op + "(") + hlo.count(op + "-start(")
                           for op in ("all-reduce", "all-gather",
                                      "reduce-scatter", "all-to-all")),
        "losses": losses,
        "ln_vocab": math.log(cfg.vocab_size),
    }


def train_loop(config: Dict):
    """JaxTrainer's per-worker loop: the steps on this worker's chips
    and, when asked, the same steps on its first device alone."""
    import gc

    import jax

    from ray_tpu import train
    from ray_tpu.util.device_peaks import device_report

    plan = Plan(**config["plan"])
    devices = jax.devices()
    out = {"device": device_report(),
           "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS")}
    if config["compare_one_device"]:
        # First, while device 0 is empty: alone it holds the whole state.
        out["one_device"] = run_train_steps(plan, {}, devices[:1])
        gc.collect()  # and its arrays leave before the mesh run's arrive
    out["mesh"] = run_train_steps(plan, config["mesh"], devices)
    train.report(out)


def make_loader(plan: Plan, tp: int):
    """The model loader llm_deployment runs inside the replica: random
    weights from the plan's seed, built on the replica's chips; with
    tp > 1 in the (params, cfg, mesh) form."""

    def loader():
        from dataclasses import replace

        import jax

        from ray_tpu.models import configs, init_params
        from ray_tpu.parallel import MeshConfig, build_mesh

        cfg = configs.get_config(plan.model)
        cfg = replace(cfg, n_layers=plan.serve_layers or cfg.n_layers,
                      remat=False)
        if tp == 1:
            # Plain single-device arrays: the engine's warm-up compiles
            # for the layouts it is given, and parameters laid out on a
            # one-device mesh make its first real step compile again.
            return jax.jit(lambda key: init_params(key, cfg))(
                jax.random.PRNGKey(plan.seed)), cfg
        mesh = build_mesh(MeshConfig(tp=tp), jax.devices()[:tp])
        return sharded_init(cfg, mesh, plan.seed), cfg, mesh

    return loader


@rt.remote(num_cpus=0.1)
def bystander():
    """What a worker that was granted no chip sees."""
    import jax

    return jax.devices()[0].platform


@rt.remote(num_cpus=0.1, num_tpus=1)
class ChipProbe:
    def report(self) -> Dict:
        import jax
        import jax.numpy as jnp

        from ray_tpu.util.device_peaks import device_report

        return {
            "pid": os.getpid(),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "devices": [str(d) for d in jax.devices()],
            "device": device_report(),
            # The chip is really open: something ran on it.
            "sum": float(jnp.ones((8, 128)).sum()),
        }


# ---------------------------------------------------------------------------
# The driver's side
# ---------------------------------------------------------------------------


def build_native_store() -> None:
    """What runs is built from committed files: the object store's
    library is not one of them, and a copy does not keep the modification
    times its lazy rebuild goes by."""
    native = os.path.join(REPO, "ray_tpu", "native")
    subprocess.run(["make", "-B", "-C", native], check=True, timeout=300,
                   stdout=subprocess.DEVNULL)
    say(f"native store built from {native}/object_store.cc")


def start_runtime(plan: Plan, chips: int) -> None:
    from ray_tpu.util.compile_cache import place_compile_cache

    say(f"compile cache: {place_compile_cache()}")
    # A 4 B replica builds its weights and compiles for about three
    # minutes cold; serve.run's own limit is five.
    os.environ.setdefault("RT_SERVE_DEPLOY_TIMEOUT_S", "900")
    with deadline(120, "rt.init()"):
        rt.init(num_tpus=plan.num_tpus)
    detected = int(rt.cluster_resources().get("TPU", 0))
    say(f"node detected {detected} chip(s); this run needs {chips}")
    if detected < chips:
        rt.shutdown()
        raise SystemExit(
            f"chip_smoke: the node shows {detected} TPU chip(s), {chips} "
            "needed (device files /dev/vfio/<n> or /dev/accel<n>): "
            "nothing was run"
        )


def prompts_for(plan: Plan, vocab: int) -> List[List[int]]:
    import numpy as np

    rng = np.random.RandomState(plan.seed)
    return [rng.randint(0, vocab, size=n).tolist() for n in plan.prompt_lens]


def serve_phase(plan: Plan, tp: int, name: str) -> Dict:
    """Deploy the engine on `tp` chips, answer requests through the
    handle, and take the app down. Returns tokens, first-step logits of
    every prompt, the engine's counters and the replica's device."""
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.models import configs
    from ray_tpu.serve.llm import llm_deployment

    vocab = configs.get_config(plan.model).vocab_size
    prompts = prompts_for(plan, vocab)
    app = llm_deployment(
        make_loader(plan, tp), num_slots=plan.slots, max_len=plan.max_len,
        default_max_new_tokens=plan.new_tokens,
        ray_actor_options={"resources": {"TPU": tp}},
    )
    t0 = time.monotonic()
    try:
        with deadline(900, f"serve.run: the {name} replica on {tp} chip(s) "
                           "builds its weights and compiles its programs"):
            handle = serve.run(app, name=name)
        deploy_s = time.monotonic() - t0
        with deadline(300, f"requests to the {name} replica"):
            warm = rt.get(handle.options(method_name="stats").remote(),
                          timeout=120)
            # With the replica holding its chips, a worker that was
            # granted none sees the CPU and disturbs nothing.
            onlooker = rt.get(bystander.remote(), timeout=120)
            greedy = [rt.get(handle.remote(p), timeout=240) for p in prompts]
            streamed = list(handle.options(
                stream=True, method_name="stream").remote(prompts[0]))
            sampled = rt.get(handle.remote(
                prompts[1], temperature=0.8, top_k=40, top_p=0.95),
                timeout=240)
            logits = [
                np.asarray(rt.get(handle.options(
                    method_name="prefill_logits").remote(p), timeout=240))
                for p in prompts
            ]
            stats = rt.get(handle.options(method_name="stats").remote(),
                           timeout=120)
    finally:
        with deadline(120, "serve.shutdown()"):
            serve.shutdown()
    answers = greedy + [streamed, sampled]
    check(all(len(a) == plan.new_tokens for a in answers),
          f"{name}: every request returns {plan.new_tokens} tokens, got "
          f"{[len(a) for a in answers]}")
    check(all(0 <= t < vocab for a in answers for t in a),
          f"{name}: token ids in [0, {vocab})")
    check(all(lg.shape == (vocab,) and np.isfinite(lg).all()
              for lg in logits), f"{name}: first-step logits finite")
    # The served first token is the argmax of the prefill program's
    # logits: the probe below and the served path are one program.
    check(all(int(lg.argmax()) == g[0] for lg, g in zip(logits, greedy)),
          f"{name}: greedy first tokens equal the logits' argmax")
    check(stats["compiles"] == warm["compiles"] == stats["warm_compiles"]
          and stats["recompiles_post_warm"] == 0,
          f"{name}: compile counter flat after warm-up "
          f"({warm['compiles']} -> {stats['compiles']})")
    check(onlooker == "cpu",
          f"a worker granted no chip saw platform {onlooker!r}")
    say(f"serve[{name}]: {plan.model}, "
        f"{plan.serve_layers or configs.get_config(plan.model).n_layers} "
        f"layers, tp={tp}: deployed in {deploy_s:.1f}s (engine warm-up "
        f"{stats['warmup_s']:.1f}s, {stats['warm_compiles']} programs), "
        f"{len(answers)} requests answered "
        f"({sum(len(a) for a in answers)} tokens, prompts "
        f"{list(plan.prompt_lens)}), compiles after warm-up: "
        f"{stats['compiles'] - stats['warm_compiles']}, decode steps "
        f"{stats['steps']}, device {stats['device']}, "
        f"a worker granted no chip saw: {onlooker}")
    return {"greedy": greedy, "logits": logits, "device": stats["device"]}


def train_phase(plan: Plan, chips: int, mesh: Dict[str, int],
                compare_one_device: bool) -> Dict:
    from ray_tpu.parallel import MeshConfig
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"plan": asdict(plan), "mesh": mesh,
                           "compare_one_device": compare_one_device},
        # tpus_per_worker defaults to four: on a one-chip node a default
        # use_tpu=True gang would wait for chips that never come.
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True, tpus_per_worker=chips,
            mesh=MeshConfig(**mesh)),
        run_config=RunConfig(
            name=f"chip_smoke_{chips}",
            storage_path=os.path.join(REPO, ".cache", "chip_smoke")),
    )
    with deadline(900, f"JaxTrainer.fit() on {chips} chip(s): placement "
                       "group, worker start, compile, steps"):
        result = trainer.fit()
    if result.error is not None:
        raise result.error
    out = result.metrics
    run = out["mesh"]
    losses = run["losses"]
    first_repeat = plan.fresh_steps
    say(f"train[{chips} chip(s), mesh {mesh or 'one device'}]: "
        f"{plan.model} widths at {run['layers']} layers "
        f"({run['params'] / 1e9:.2f} B parameters), "
        f"{plan.batch}x{plan.seq} tokens, ce_chunk {plan.ce_chunk}: "
        f"compile {run['compile_s']:.1f}s ({run['compiles']} compile; "
        f"{run['argument_gb']:.2f} GB arguments + {run['temp_gb']:.2f} GB "
        f"temporaries per device), {len(losses)} steps in "
        f"{run['steps_s']:.1f}s, kernels in the step: {run['kernels']}, "
        f"collectives: {run['collectives']}, device {out['device']}, "
        f"TPU_VISIBLE_CHIPS={out['visible_chips']}")
    say("train losses: " + ", ".join(f"{x:.4f}" for x in losses)
        + f" (ln vocab {run['ln_vocab']:.3f}; batch "
        f"{first_repeat} repeats from step {first_repeat})")
    check(all(math.isfinite(x) for x in losses), "train: losses finite")
    check(abs(losses[0] - run["ln_vocab"]) < 1.0,
          f"train: first loss {losses[0]:.3f} near ln(vocab) "
          f"{run['ln_vocab']:.3f}")
    check(losses[-1] < losses[first_repeat] - 0.05,
          f"train: loss falls on the repeated batch "
          f"({losses[first_repeat]:.4f} -> {losses[-1]:.4f})")
    if out["device"]["platform"] == "tpu":
        check(run["kernels"] > 0,
              "train: tpu_custom_call in the compiled step (the Pallas "
              "kernels, not the reference path)")
    return out


def one_chip(plan: Plan) -> Dict:
    start_runtime(plan, 1)
    try:
        served = serve_phase(plan, 1, "smoke")
        trained = train_phase(plan, 1, {}, compare_one_device=False)
    finally:
        rt.shutdown()
    check(served["device"] == trained["device"],
          f"the two phases ran on one device: {served['device']} "
          f"vs {trained['device']}")
    return trained["device"]


def chip_assignment() -> None:
    """Chip assignment seen from outside: two one-chip actors alive at
    once, each on one device, on different chips."""
    with deadline(300, "two one-chip actors reporting their device"):
        probes = [ChipProbe.remote() for _ in range(2)]
        seen = rt.get([p.report.remote() for p in probes], timeout=240)
        for p in probes:
            rt.kill(p)
    for r in seen:
        say(f"one-chip actor pid {r['pid']}: TPU_VISIBLE_CHIPS="
            f"{r['visible_chips']}, devices {r['devices']}, "
            f"sum on device {r['sum']}")
    check(all(r["device"]["count"] == 1 for r in seen),
          "each one-chip actor sees exactly one device")
    chips = {r["visible_chips"] for r in seen}
    check(len(chips) == 2 and None not in chips,
          "the two actors hold different chips")


def mesh_train(plan: Plan) -> Dict:
    """The train configuration on a whole-host lease under fsdp x tp,
    against the same steps on one device of the same process."""
    trained = train_phase(plan, 4, {"fsdp": 2, "tp": 2},
                          compare_one_device=True)
    mesh, one = trained["mesh"], trained["one_device"]
    check(trained["visible_chips"] is None,
          "a whole-host lease leaves TPU_VISIBLE_CHIPS unset")
    shares = [b / mesh["param_bytes"]
              for b in mesh["param_bytes_per_device"]]
    say("parameter bytes per device: "
        f"{mesh['param_bytes_per_device']} of {mesh['param_bytes']} "
        f"(shares {', '.join(f'{s:.3f}' for s in shares)})")
    check(len(shares) == 4 and all(0.2 < s < 0.3 for s in shares),
          "every device holds about a quarter of the parameter bytes")
    check(mesh["collectives"] > 0,
          "the compiled mesh step contains collectives")
    gaps = [abs(a - b) for a, b in zip(mesh["losses"], one["losses"])]
    say("one-device losses: "
        + ", ".join(f"{x:.4f}" for x in one["losses"])
        + f"; largest gap to the mesh {max(gaps):.4f} "
        f"(tolerance {LOSS_TOLERANCE})")
    check(max(gaps) <= LOSS_TOLERANCE,
          "mesh and one-device losses agree step by step")
    return trained["device"]


def tp_serve(plan: Plan) -> Dict:
    """The engine tensor-parallel over four chips against a one-chip
    engine: the same greedy requests, compared on first-step logits
    (token equality is hostage to bf16 reduction order)."""
    import numpy as np

    tp = serve_phase(plan, 4, "smoke_tp4")
    single = serve_phase(plan, 1, "smoke_one")
    rel = [float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b ** 2)))
           for a, b in zip(tp["logits"], single["logits"])]
    same = [sum(x == y for x, y in zip(a, b))
            for a, b in zip(tp["greedy"], single["greedy"])]
    say("tp=4 against one chip, first-step logits: relative rms "
        "difference " + ", ".join(f"{r:.4f}" for r in rel)
        + f" (tolerance {LOGITS_TOLERANCE}); equal greedy tokens "
        f"{same} of {plan.new_tokens} each (reported, not required)")
    check(max(rel) <= LOGITS_TOLERANCE,
          "tensor-parallel and one-chip first-step logits agree")
    return tp["device"]


def four_chips(plan: Plan) -> Dict:
    start_runtime(plan, 4)
    try:
        chip_assignment()
        trained_on = mesh_train(plan)
        served_on = tp_serve(plan)
    finally:
        rt.shutdown()
    check(served_on == trained_on,
          f"the four-chip workers agree on the device: {served_on} "
          f"vs {trained_on}")
    return trained_on


def driver_stayed_off_jax() -> None:
    jax = sys.modules.get("jax")
    touched = False
    if jax is not None:
        from jax._src import xla_bridge

        touched = xla_bridge.backends_are_initialized()
    say(f"driver process initialised a JAX backend: {touched}")
    check(not touched, "the driver process never initialises a JAX backend")


def run(plan: Plan, chips: int) -> Dict:
    device = (four_chips if chips == 4 else one_chip)(plan)
    check(device["platform"] == plan.platform and device["count"] == chips,
          f"the workers ran on {device}, not on {chips} {plan.platform} "
          "device(s)")
    driver_stayed_off_jax()
    return device


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)
    t0 = time.monotonic()
    build_native_store()
    device = run(Plan(), args.chips)
    say(f"done in {time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
