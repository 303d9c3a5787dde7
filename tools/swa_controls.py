"""The controls of the cell `serve-swa-longdoc`: five models that are NOT
SmallThinker, each one edit away from the program or from the reference,
which the comparison that decides `correct` should fail.

    python tools/swa_controls.py make <control> <dest>
        a copy of `ray_tpu/`, `bench/` and `BENCHMARK.json` under <dest>
        that computes the control; run the cell there through the harness,
        unedited: `cd <dest> && python bench/run.py --workload
        serve-swa-longdoc --seed <n> --seconds 5 --trace 0`
    chiprun -- python tools/swa_controls.py where [--seeds ..] [--lens ..]
        what each control reads by the prompt's length, without the engine:
        the program's `forward` under the faulty config in the served dtype
        against the float32 reference on the same weights (for `e4m3` the
        sound program against the reference on weights rounded to e4m3),
        relative rms of the last position's logits, which is what
        `serve_cell.reference_check` compares. One JSON line a seed.
    python tools/swa_controls.py where --tiny      # here: control flow

The controls:
    no-window      window layers that see everything behind them
    rope-on-full   rope on the full layers too
    router-normed  the router on the normed stream behind attention
    silu           silu for relu on an expert's gate
    e4m3           the REFERENCE on weights rounded to float8_e4m3fn: the
                   nearest precision under the served bfloat16
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROLS = ("no-window", "rope-on-full", "router-normed", "silu", "e4m3")
CELL_CONFIG = "bench/configs/smallthinker-21b-a3b-serve.json"
CELL_TRAFFIC = "bench/traffic/longdoc-closed-48.json"


def faulty(cfg, control: str):
    """`cfg` as the control's program computes it (`sound` and `e4m3`: as
    it is). The two controls on a published list change what the program
    DOES with the list and leave the field: the configuration's file, and
    the sizes the reference is handed, stay the published model's."""
    if control == "router-normed":
        return dataclasses.replace(cfg, router_reads="mlp_input")
    if control == "silu":
        return dataclasses.replace(cfg, activation="silu")
    acts = {"no-window": {"window_layout": property(lambda self: ())},
            "rope-on-full": {"rope_layers": property(
                lambda self: (True,) * self.n_layers)}}
    if control not in acts:
        return cfg
    kind = type(type(cfg).__name__, (type(cfg),), acts[control])
    return kind(**{f.name: getattr(cfg, f.name)
                   for f in dataclasses.fields(cfg)})


def make(control: str, dest: str) -> None:
    """The tree under `dest`: the model's named configs replaced by the
    control's (`faulty`, this file copied in beside them), or the
    reference's upcast sent through e4m3; the cell's window cut to 5 s of
    traffic, since the comparison after the window is what a control is run
    for."""
    if control not in CONTROLS + ("sound",):
        raise SystemExit(f"unknown control {control!r}; have {CONTROLS}")
    shutil.rmtree(dest, ignore_errors=True)
    os.makedirs(dest)
    for part in ("ray_tpu", "bench"):
        shutil.copytree(os.path.join(ROOT, part), os.path.join(dest, part),
                        ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)

    def rewrite(path, edit):
        path = os.path.join(dest, path)
        with open(path) as f:
            text = f.read()
        with open(path, "w") as f:
            f.write(edit(text))

    if control == "e4m3":
        def through_e4m3(text):
            assert text.count(".astype(F32)") >= 5
            return text.replace(".astype(F32)",
                                ".astype(jnp.float8_e4m3fn).astype(F32)")
        rewrite("bench/reference/smallthinker.py", through_e4m3)
    elif control != "sound":
        shutil.copy(os.path.abspath(__file__),
                    os.path.join(dest, "ray_tpu/models/swa_controls.py"))
        rewrite("ray_tpu/models/configs.py", lambda text: text + f'''

from ray_tpu.models.swa_controls import faulty as _faulty  # noqa: E402

for _name in ("smallthinker-21b-a3b-l8", "tiny_smallthinker"):
    NAMED_CONFIGS[_name] = _faulty(NAMED_CONFIGS[_name], {control!r})
''')

    def short(text):
        mix = json.loads(text)
        mix.update(ramp_s=2.0, stagger_s=1.0)
        return json.dumps(mix, indent=1)
    rewrite(CELL_TRAFFIC, short)
    if control == "no-window":  # every layer a table of 16,384: 8 slots fit
        def eight_slots(text):
            doc = json.loads(text)
            doc["engine"]["num_slots"] = 8
            return json.dumps(doc, indent=1)
        rewrite(CELL_CONFIG, eight_slots)
    print(f"made {control} in {dest}")


def where(seeds, lens, tiny: bool) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import random

    import jax
    import jax.numpy as jnp
    import numpy as np

    import spec
    import weights
    from ray_tpu.models import forward

    with open(os.path.join(ROOT, CELL_CONFIG)) as f:
        doc = json.load(f)
    platform = "cpu" if tiny else jax.default_backend()
    if tiny:
        doc = spec._with_preset(doc, "cpu")
    cfg = dataclasses.replace(spec.program_config(doc, platform), remat=False)
    dims = spec.dims_of(cfg, doc)
    reference = spec.named_module(doc, "reference")
    programs = {c: jax.jit(lambda p, t, n, c=c: forward(
        p, t, faulty(cfg, c))[0][0, n - 1].astype(jnp.float32))
        for c in ("sound",) + CONTROLS if c != "e4m3"}
    # Two programs: inside one the compiler may drop a conversion there and
    # back as excess precision.
    down = jax.jit(lambda a: a.astype(jnp.float8_e4m3fn), donate_argnums=0)

    def through_e4m3(a):
        return jax.jit(lambda b: b.astype(a.dtype))(down(a))

    def padded(prompt):
        """Causal: what follows a position cannot reach it, so one width
        serves every length under it."""
        width = -(-len(prompt) // 64) * 64
        return jnp.asarray(prompt + [0] * (width - len(prompt)), jnp.int32)

    def reference_logits(params, prompt):
        rows = reference.hidden_layerwise(params, padded(prompt), dims)
        return np.asarray(reference.logits_rows(
            params, rows[len(prompt) - 1:len(prompt)], dims))[0]

    def rel(got, want):
        return float(np.sqrt(np.mean((np.asarray(got) - want) ** 2))
                     / np.sqrt(np.mean(want ** 2)))

    for seed in seeds:
        params = weights.make_params(cfg, seed, spec.leaf_rules(cfg, doc))
        rng = random.Random(seed + 1)
        prompts = [[rng.randrange(cfg.vocab_size) for _ in range(n)]
                   for n in lens]
        line = {"seed": seed, "device": jax.devices()[0].device_kind}
        served = []
        for prompt in prompts:
            want = reference_logits(params, prompt)
            got = {c: np.asarray(program(params, padded(prompt)[None],
                                         len(prompt)))
                   for c, program in programs.items()}
            served.append(got["sound"])
            line[str(len(prompt))] = {c: round(rel(v, want), 4)
                                      for c, v in got.items()}
        # The weights rounded where they lie (two copies do not fit), then
        # the reference on them against what the sound program served.
        params = jax.tree.map(through_e4m3, params)
        for prompt, sound in zip(prompts, served):
            line[str(len(prompt))]["e4m3"] = round(
                rel(sound, reference_logits(params, prompt)), 4)
        del params
        print(json.dumps(line), flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = parser.add_subparsers(dest="command", required=True)
    one = sub.add_parser("make")
    one.add_argument("control")
    one.add_argument("dest")
    two = sub.add_parser("where")
    two.add_argument("--seeds", type=int, nargs="+", default=[3700000501])
    two.add_argument("--lens", type=int, nargs="+",
                     default=[1, 2, 3, 4, 6, 8, 12, 16, 32, 64, 200])
    two.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    if args.command == "make":
        make(args.control, args.dest)
    else:
        where(args.seeds, args.lens, args.tiny)


if __name__ == "__main__":
    main()
