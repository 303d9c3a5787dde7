"""Fingerprints of the engine's step programs and the train loss, for every
model the benchmark runs, from whatever tree is given: what a PR that
edits shared code (`transformer.attention_layer`, `mlp_half`, `latent_layer`,
`paged_kv._scan_layers`, `_walk_hybrid`, `moe_block`, `decode_paged`)
compares between its parent and itself, before any chip time is spent.

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

The train loss's gradient is traced twice for every model: on one device
(`loss_grad`) and under the four-chip cell's layout, a 2 x 2 "fsdp" x "tp"
mesh of four host devices (`loss_grad.fsdp2tp2`: the norm's `spec` and the
kernels' per-device regions are in the program only there; with
`--compiled` the mesh is of the described chips). A block-diffusion model
has no loss here: the gradient of its forward's hidden states stands in.

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
TRAINED = SERVED
SLOTS, MAX_LEN, PAGE, CHUNK = 8, 512, 16, 64


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _text(jaxpr) -> str:
    """A jaxpr's text with what differs between two runs of one program
    taken out: addresses, and the order a set of axis names prints in."""
    return re.sub(
        r"frozenset\(\{([^}]*)\}\)",
        lambda m: "frozenset({%s})" % ", ".join(sorted(m[1].split(", "))),
        re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr)))


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


def _shallow(cfg, periods: int):
    """`cfg` at `periods` periods of its per-layer lists (1 layer a period
    where the layers are all alike), which tell what the whole depth does;
    a hybrid, whose pattern is its depth, as it is."""
    if cfg.layer_pattern:
        return cfg
    depth = periods * getattr(cfg, "layer_period", 1)
    lists = {field: getattr(cfg, field)[:depth]
             for field in ("sliding_window_layout", "rope_layout")
             if getattr(cfg, field, ())}
    return dataclasses.replace(cfg, n_layers=min(cfg.n_layers, depth),
                               **lists)


def programs(name: str):
    """`(tag, function, donated, argument shapes)` for each step program of
    the named model, as the engine calls them."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import configs
    from ray_tpu.models.transformer import init_params
    from ray_tpu.serve import paged_kv

    cfg = dataclasses.replace(_shallow(configs.get_config(name), 2),
                              remat=False)
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


def train_loss(name: str, mesh=None):
    """The gradient of the named model's train loss, on one device or
    under `mesh`: parameters and tokens lie as the train step places them."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import configs, param_logical_axes
    from ray_tpu.models.transformer import forward, init_params, loss_fn
    from ray_tpu.parallel import logical_shardings

    cfg = dataclasses.replace(_shallow(configs.get_config(name), 4),
                              ce_chunk=256)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 257), jnp.int32)
    if getattr(cfg, "block_length", 0):
        def loss(p, t):
            hidden, aux = forward(p, t[:, :-1], cfg, mesh, return_hidden=True)
            return hidden.astype(jnp.float32).sum() + aux
    else:
        def loss(p, t):
            return loss_fn(p, t, cfg, mesh)
    if mesh is None:
        return "loss_grad", jax.grad(loss), (), (params, tokens)
    from jax.sharding import NamedSharding, PartitionSpec as P

    placed = (logical_shardings(param_logical_axes(cfg), mesh),
              NamedSharding(mesh, P(("dp", "fsdp"), None)))
    return "loss_grad.fsdp2tp2", jax.grad(loss), (), (params, tokens), placed


def _compiled(fn, donated, args, placed) -> dict:
    """`fn` compiled for the described chips: its temporaries' bytes and
    the hash of its operations. `placed`: one sharding for every argument,
    or a tree of them as `args`'s."""
    import jax

    if isinstance(placed, jax.sharding.Sharding):
        placed = jax.tree.map(lambda _: placed, args)
    args = jax.tree.map(lambda a, sharding: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), args, placed)
    exe = jax.jit(fn, donate_argnums=donated).lower(*args).compile()
    return {"temporaries": exe.memory_analysis().temp_size_in_bytes,
            "ops": ops_hash(exe.as_text())}


def fingerprints(models, compiled: bool = False, one=None,
                 mesh=None) -> dict:
    """`{"<model>:<program>": {"jaxpr": .., "live": .., ["temporaries": ..,
    "ops": ..]}}`; `one` is the described chip's sharding for `compiled`,
    `mesh` the 2 x 2 mesh the train loss is also traced under (None: on one
    device alone)."""
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
            if mesh is not None:
                todo.append(train_loss(name, mesh))
        for tag, fn, donated, args, *placed in todo:
            traced = jax.make_jaxpr(fn)(*args)
            live, _ = pe.dce_jaxpr(traced.jaxpr,
                                   [True] * len(traced.out_avals))
            got = {"jaxpr": _sha(_text(traced)), "live": _sha(_text(live))}
            # The grouped matmul of a model with experts is a kernel that
            # no mesh partitions: traced under the mesh, compiled on one chip.
            if compiled and not (placed and configs.get_config(
                    name).num_experts):
                got.update(_compiled(fn, donated, args, *placed or [one]))
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
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    tree = os.path.abspath(args.tree)
    sys.path.insert(0, tree)
    import jax

    import ray_tpu
    assert os.path.abspath(ray_tpu.__file__).startswith(tree), ray_tpu.__file__
    from ray_tpu.parallel import MeshConfig, build_mesh

    one, devices = None, jax.devices()[:4]
    if args.compiled:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        jax.config.update("jax_enable_compilation_cache", False)
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices
        one = SingleDeviceSharding(devices[0])
    mesh = build_mesh(MeshConfig(fsdp=2, tp=2), devices)
    jax.default_backend = lambda: "tpu"  # the kernels' branch, as on the chip
    print(json.dumps(fingerprints(args.models, args.compiled, one, mesh)))


if __name__ == "__main__":
    main()
