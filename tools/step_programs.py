"""Fingerprints of the engine's step programs and the train loss, for every
model the benchmark runs, from whatever tree is given: what a PR that
edits shared code (`paged_kv._layer_body`, `_scan_layers`, `_walk_hybrid`,
`moe_block`, `decode_paged`) compares between its parent and itself, before
any chip time is spent.

    python tools/step_programs.py [--tree DIR] [--compiled] [--models a b ..]

One JSON line: for each program `<model>:<program>` the hash of its jaxpr
(addresses out), traced on the CPU with the kernels' branch taken
(`jax.default_backend` answers "tpu", as on the chip), and `live`, the hash
of the same jaxpr with the equations no result depends on taken out (a mask
or a padded table that a model's branch never reads is traced all the same:
two programs that differ in such equations alone are the same program).
With `--compiled` each program is also compiled for a described, not
attached, v5e (`on-chip-measurement`, section 2) and the line carries its
temporaries' bytes and `ops`, a hash of WHAT it computes: the sorted
multiset of (opcode, result shape and layout) over every instruction but
the tuples, their elements and the parameters, so that two programs that
differ in the order of a loop's state alone hash alike. Equal jaxprs are
the same program; equal `ops` and temporaries are the same work laid out
the same way.

Shapes are small (8 slots x 512, chunks of 64) and the depth is cut to a few
periods of layers: structure, not size, is what is compared. `--tree` runs
another checkout's `ray_tpu` (the parent's, unpacked with `git archive`).
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import hashlib
import json
import os
import re
import sys

SERVED = ("qwen3-4b", "olmoe-1b-7b", "granite-4.0-h-micro", "dots-vlm1-ep16",
          "lfm2-24b-a2b-l10", "solar-open2-250b-ep8-l4", "sdar-30b-a3b-l6",
          "smallthinker-21b-a3b-l8")
TRAINED = ("qwen3-4b", "olmoe-1b-7b", "granite-4.0-h-micro")
SLOTS, MAX_LEN, PAGE, CHUNK = 8, 512, 16, 64


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _text(jaxpr) -> str:
    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))


def ops_hash(hlo: str) -> str:
    """The multiset of (opcode, result shape) of a compiled module's
    instructions, hashed; tuples, their elements, parameters and the loops
    that carry them are left out (they hold the ORDER of a loop's state)."""
    seen = collections.Counter()
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\S+) ([a-z][a-z0-9-]*)\(", line)
        if m and m.group(2) not in ("tuple", "get-tuple-element", "parameter",
                                    "while", "conditional", "call"):
            seen[m.group(2), m.group(1)] += 1
    return _sha(repr(sorted(seen.items())))


def programs(name: str):
    """`(tag, function, donated, argument shapes)` for each step program of
    the named model, as the engine calls them."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import configs
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve import paged_kv

    cfg = configs.get_config(name)
    if not cfg.layer_pattern:  # two periods of layers tell what 36 do
        period = getattr(cfg, "layer_period", 1)
        depth = {field: getattr(cfg, field)[:2 * period]
                 for field in ("sliding_window_layout", "rope_layout")
                 if getattr(cfg, field, ())}
        cfg = dataclasses.replace(cfg, n_layers=min(
            cfg.n_layers, max(2 * period, 2)), **depth)
    cfg = dataclasses.replace(cfg, remat=False)
    shape = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    per_slot = MAX_LEN // PAGE
    takes_chunk = "prefill_chunk" in paged_kv.init_paged_cache.__code__.co_varnames
    cache = jax.eval_shape(lambda: paged_kv.init_paged_cache(
        cfg, SLOTS, SLOTS * per_slot + 1, PAGE, per_slot,
        **({"prefill_chunk": CHUNK} if takes_chunk else {})))
    tail = {}
    if cfg.num_experts:
        tail["moe"] = jax.eval_shape(
            lambda: paged_kv.init_routing_counters(cfg))
    if cfg.layer_pattern:
        tail["rec"] = cache["rec"]
        tail["rec_count"] = jax.eval_shape(paged_kv.init_ssm_counters)
    if "ring" in cache:
        tail["ring"] = cache["ring"]
    pools = (cache["k"], cache["v"], cache["lengths"])
    active = shape((SLOTS,), jnp.bool_)
    sampling = (shape((SLOTS,), jnp.float32), shape((SLOTS,), jnp.int32),
                shape((SLOTS,), jnp.float32), shape((2,), jnp.uint32))
    if getattr(cfg, "block_length", 0):
        state = jax.eval_shape(lambda: paged_kv.init_block_state(cfg, SLOTS))
        yield ("block", lambda p, st, k, v, ln, a, bt, s, tail:
               paged_kv.block_pass_paged(p, st, k, v, ln, a, bt, *s, cfg,
                                         MAX_LEN, None, **tail),
               (2, 3), (params, state, *pools, active, cache["block_tables"],
                        sampling, tail))
    else:
        yield ("decode", lambda p, t, k, v, ln, a, bt, s, tail:
               paged_kv.decode_paged(p, t, k, v, ln, a, bt, *s, cfg, MAX_LEN,
                                     None, **tail),
               (2, 3), (params, shape((SLOTS,), jnp.int32), *pools, active,
                        cache["block_tables"], sampling, tail))
    for rows in (1, 2):
        row = shape((rows,), jnp.int32)
        yield (f"prefill{rows}", lambda p, t, n, s, o, k, v, ln, bt, tail:
               paged_kv.prefill_chunk_paged(p, t, n, s, o, k, v, ln, bt, cfg,
                                            MAX_LEN, None, **tail),
               (5, 6), (params, shape((rows, CHUNK), jnp.int32), row, row,
                        row, *pools, cache["block_tables"], tail))


def train_loss(name: str):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import configs
    from ray_tpu.models.transformer import init_params, loss_fn

    cfg = configs.get_config(name)
    if not cfg.layer_pattern:
        cfg = dataclasses.replace(cfg, n_layers=4)
    cfg = dataclasses.replace(cfg, ce_chunk=256)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return ("loss_grad", jax.grad(lambda p, t: loss_fn(p, t, cfg)), (),
            (params, jax.ShapeDtypeStruct((2, 257), jnp.int32)))


def _compiled(fn, donated, args, one) -> dict:
    """`fn` compiled for the chip `one` describes: its temporaries' bytes
    and the hash of its operations."""
    import jax

    placed = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one), args)
    exe = jax.jit(fn, donate_argnums=donated).lower(*placed).compile()
    return {"temporaries": exe.memory_analysis().temp_size_in_bytes,
            "ops": ops_hash(exe.as_text())}


def fingerprints(models, compiled: bool = False, one=None) -> dict:
    """`{"<model>:<program>": {"jaxpr": .., "live": .., ["temporaries": ..,
    "ops": ..]}}`;
    `one` is the described chip's sharding for `compiled`."""
    import jax
    from jax.interpreters import partial_eval as pe

    from ray_tpu.models import configs

    out = {}
    for name in models:
        if name not in configs.NAMED_CONFIGS:
            continue  # a tree from before the model
        todo = list(programs(name))
        if name in TRAINED:
            todo.append(train_loss(name))
        for tag, fn, donated, args in todo:
            traced = jax.make_jaxpr(fn)(*args)
            live, _ = pe.dce_jaxpr(traced.jaxpr,
                                   [True] * len(traced.out_avals))
            got = {"jaxpr": _sha(_text(traced)), "live": _sha(_text(live))}
            if compiled:
                got.update(_compiled(fn, donated, args, one))
            out[f"{name}:{tag}"] = got
    return out


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    parser.add_argument("--compiled", action="store_true")
    parser.add_argument("--models", nargs="+", default=SERVED)
    args = parser.parse_args()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    import jax

    import ray_tpu
    assert os.path.abspath(ray_tpu.__file__).startswith(tree), ray_tpu.__file__
    one = None
    if args.compiled:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        jax.config.update("jax_enable_compilation_cache", False)
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.default_backend = lambda: "tpu"  # the kernels' branch, as on the chip
    print(json.dumps(fingerprints(args.models, args.compiled, one)))


if __name__ == "__main__":
    main()
