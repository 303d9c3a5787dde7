"""The grouped expert product alone on the chip, over tilings, at the sizes
the five sparse serving cells launch it with (`parallel/moe.py`
`grouped_matmul`, `%gmm.*` in a trace).

    chiprun -- python tools/gmm_sweep.py                  # every product
    chiprun -- python tools/gmm_sweep.py solar lfm2       # two cells' own
    python tools/gmm_sweep.py --compile                   # here: which fit
    python tools/gmm_sweep.py --tiny                      # here: control flow

A product is one `gmm` call over the whole `[L*E, k, n]` stack as
`moe_block` passes it, with one layer's groups holding rows; a decode step
and a prefill pass of each cell differ in the rows `m`, in how many of them
belong to a group held here (`live`; the rest sort behind the last group
and are not multiplied) and in how many groups have rows (`hit`). The loop
walks the layers, so every launch reads other weights. Each candidate
`(tm, tk, tn)` is checked against the first of its product and timed twice:
the kernel's own events in a profiler trace (`kernel_us`, what
`moe.expert_roofline_share*` divides by) and the host's clock around the
loop (`wall_us`, with the group metadata XLA computes before each launch).
`GB/s` is the weights of the groups with rows, once each, over the kernel's
time, beside the chip's 819. Prints one JSON line a candidate and a table;
`chiprun_out/gmm_sweep.jsonl` keeps the lines.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm

from ray_tpu.parallel import moe

PEAK_GBS = 819.0
PLAIN = 1024  # the weight tile's side until PR 64: min(k, 1024), min(n, 1024)

# cell: (d_model, ff, expert layers, experts held a layer, then per phase
# (rows m, rows of held groups, groups with rows)). Decode: slots x k
# assignments; a pass: 256 tokens x k (dots: its chunk of 512). `hit` is
# what the cells' traced windows read (`moe.*experts_hit_share*`).
CELLS = {
    "olmoe": (2048, 1024, 16, 64, {"decode": (128, 128, 56),
                                   "pass": (2048, 2048, 64)}),
    "dots": (7168, 2048, 5, 16, {"decode": (512, 32, 14),
                                 "pass": (4096, 256, 16)}),
    "lfm2": (2048, 1536, 8, 64, {"decode": (384, 384, 60),
                                 "pass": (1024, 1024, 64)}),
    "solar": (4096, 1280, 4, 40, {"decode": (1024, 130, 28),
                                  "pass": (2048, 256, 40)}),
    # A block pass of 96 slots x 4 rows x 8 choices, every expert hit.
    "sdar": (2048, 768, 6, 128, {"decode": (3072, 3072, 128),
                                 "pass": (2048, 2048, 128)}),
}
TINY = {"tiny": (256, 384, 2, 4, {"decode": (32, 20, 3),
                                  "pass": (64, 64, 4)})}


def divisors(x: int):
    """The multiples of 128 that divide `x`, largest first."""
    return [t for t in range(x, 0, -128) if t % 128 == 0 and x % t == 0]


def candidates(m: int, k: int, n: int, wide: bool):
    """The plain triple every width had until PR 64, the one `moe.py`'s
    rule works out, then every pair of a divisor of `k` and of `n` no
    smaller than 512 whose weight tile is at most 6 MiB in bfloat16, then
    (with `wide`) smaller row tiles under the rule's weight tile. A triple
    is run once, under the first of its names (two programs of one HLO are
    one executable, and the trace could not tell them apart)."""
    rule = moe.gmm_tiling(m, k, n, 2)
    tm = rule[0]
    out = [("plain", (tm, min(k, PLAIN), min(n, PLAIN))), ("rule", rule)]
    floor = 512 if min(k, n) >= 512 else 128
    for tk in divisors(k):
        for tn in divisors(n):
            if tk >= floor and tn >= floor and tk * tn * 2 <= 6 << 20:
                out.append((f"{tk}x{tn}", (tm, tk, tn)))
    if wide:
        out += [(f"tm{rows}", (rows,) + rule[1:]) for rows in (64, 32, 16)
                if rows < tm]
    names = {}
    for name, c in out:
        names.setdefault(c, []).append(name)
    return [("=".join(ns[:2]) if ns[0] in ("plain", "rule") else ns[0], c)
            for c, ns in names.items()]


def group_sizes(rng, held: int, live: int, hit: int) -> np.ndarray:
    """`live` rows over `hit` of `held` groups, every one hit at least once."""
    sizes = np.zeros(held, np.int32)
    which = rng.choice(held, size=hit, replace=False)
    sizes[which] = 1 + rng.multinomial(live - hit, np.ones(hit) / hit)
    return sizes


def loop_fn(tiling, layers: int, held: int, reps: int, interpret: bool):
    def loop(x, w, sizes):
        def body(i, acc):
            at = (i % layers) * held
            spread = jax.lax.dynamic_update_slice(
                jnp.zeros((layers * held,), jnp.int32), sizes, (at,))
            out = gmm(x, w, spread, preferred_element_type=jnp.float32,
                      tiling=tiling, interpret=interpret)
            return acc + out[0, :8].sum()
        return jax.lax.fori_loop(0, reps, body, jnp.float32(0))
    return loop


def one_fn(tiling, interpret: bool):
    def one(x, w, spread):
        return gmm(x, w, spread, preferred_element_type=jnp.float32,
                   tiling=tiling, interpret=interpret)
    return one


def kernel_seconds(trace_dir: str, names):
    """The kernels' time inside each named program of the newest trace under
    `trace_dir`, by the benchmark's own reader of a trace (`bench/xplane`):
    {name: seconds}; {} where there is no trace or no device plane."""
    from bench.xplane import reduce

    path = reduce.find_xplane(trace_dir)
    out = {}
    for plane in reduce.load(path) if path else []:
        if not plane["name"].startswith("/device:TPU:0"):
            continue
        kernels = [(s, d) for n, s, d in reduce._line(plane, reduce.OPS_LINE)
                   if reduce._is_kernel(n)]
        for module, lo, length in reduce._line(plane, reduce.MODULES_LINE):
            name = reduce._short(module).removeprefix("jit_")
            if name in names:
                out[name] = out.get(name, 0.0) + sum(
                    d for s, d in kernels if lo <= s < lo + length)
    return out


def compile_only(cands, shape, groups, one_chip, row, emit):  # rtlint: disable=RT002 — every candidate is a program of its own, compiled once
    """Each candidate compiled for the described chip, none run."""
    m, k, n = shape
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16, sharding=one_chip)
    gs = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one_chip)
    for name, tiling in cands:
        try:
            jax.jit(one_fn(tiling, False)).lower(x, w, gs).compile()
            ok = "compiles"
        except Exception as e:  # noqa: BLE001 — the compiler's refusal is the finding
            ok = "refused: " + str(e).splitlines()[0][:160]
        emit({**row, "m": m, "k": k, "n": n, "name": name, "tiling": tiling,
              "compile": ok})


def sweep(cell: str, spec, args, emit):  # rtlint: disable=RT001,RT002 — a micro-benchmark: every candidate is a program of its own, timed by a sync
    d, ff, layers, held, phases = spec
    interpret = jax.default_backend() != "tpu"
    dtype = jnp.bfloat16
    for product, (k, n) in (("gate_up", (d, ff)), ("down", (ff, d))):
        w = None if args.compile else jax.random.normal(
            jax.random.PRNGKey(k + n), (layers * held, k, n), dtype) * 0.02
        for phase, (m, live, hit) in phases.items():
            rng = np.random.default_rng(64)
            sizes = group_sizes(rng, held, live, hit)
            weights_bytes = hit * k * n * 2
            cands = candidates(m, k, n, wide=phase == "decode")
            if args.compile:
                compile_only(cands, (m, k, n), layers * held, args.one_chip,
                             {"cell": cell, "product": product,
                              "phase": phase}, emit)
                continue
            x = jax.random.normal(jax.random.PRNGKey(m), (m, k), dtype)
            sizes_dev = jnp.asarray(sizes)
            spread = jnp.zeros((layers * held,), jnp.int32).at[:held].set(
                sizes_dev)
            want, rows, fns = None, [], {}
            for i, (name, tiling) in enumerate(cands):
                try:
                    got = np.asarray(jax.jit(one_fn(tiling, interpret))(
                        x, w, spread))[:live]
                except Exception as e:  # noqa: BLE001 — a tile the compiler refuses is a finding
                    emit({"cell": cell, "product": product, "phase": phase,
                          "name": name, "tiling": tiling,
                          "error": str(e).splitlines()[0][:200]})
                    continue
                want = got if want is None else want
                err = float(np.abs(got - want).max() / np.abs(want).max())
                fn = loop_fn(tiling, layers, held, args.reps, interpret)
                fn.__name__ = f"sweep{i}"
                fn = jax.jit(fn)
                fn(x, w, sizes_dev).block_until_ready()
                fns[f"sweep{i}"] = fn
                rows.append({"cell": cell, "product": product, "phase": phase,
                             "m": m, "k": k, "n": n, "live": live, "hit": hit,
                             "name": name, "tiling": tiling, "fn": f"sweep{i}",
                             "max_err_vs_first": err})
            for row in rows:  # the host's clock, every candidate twice
                fn, best = fns[row["fn"]], float("inf")
                for _ in range(2):
                    t0 = time.perf_counter()
                    fn(x, w, sizes_dev).block_until_ready()
                    best = min(best, time.perf_counter() - t0)
                row["wall_us"] = best / args.reps * 1e6
            trace_dir = os.path.join(args.out, "trace", f"{cell}-{product}-{phase}")
            kernel = {}
            if not interpret:
                with jax.profiler.trace(trace_dir):
                    for row in rows:
                        fns[row["fn"]](x, w, sizes_dev).block_until_ready()
                kernel = kernel_seconds(trace_dir, list(fns))
                shutil.rmtree(trace_dir, ignore_errors=True)
            for row in rows:
                if row["fn"] in kernel:
                    us = kernel[row["fn"]] / args.reps * 1e6
                    row["kernel_us"] = us
                    row["GB/s"] = weights_bytes / us * 1e-3
                    row["of_peak_%"] = 100 * row["GB/s"] / PEAK_GBS
                emit({k_: v for k_, v in row.items() if k_ != "fn"})
        del w


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*", help=f"of {sorted(CELLS)}; default all")
    ap.add_argument("--reps", type=int, default=32)
    ap.add_argument("--tiny", action="store_true",
                    help="a toy product in the interpreter, on the CPU")
    ap.add_argument("--compile", action="store_true",
                    help="compile every candidate for a described v5e, run none")
    ap.add_argument("--out", default="chiprun_out")
    args = ap.parse_args()
    cells = TINY if args.tiny else {c: CELLS[c] for c in (args.cells or CELLS)}
    if args.tiny:
        args.reps = 2
    if args.compile:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        args.one_chip = SingleDeviceSharding(topo.devices[0])
    elif not args.tiny and jax.default_backend() != "tpu":
        sys.exit("the sweep measures a chip: run it through chiprun "
                 "(or --tiny / --compile here)")
    os.makedirs(args.out, exist_ok=True)
    lines = []

    def emit(row):
        lines.append(row)
        print(json.dumps(row), flush=True)

    for cell, spec in cells.items():
        sweep(cell, spec, args, emit)
    with open(os.path.join(args.out, "gmm_sweep.jsonl"), "a") as f:
        for row in lines:
            f.write(json.dumps(row) + "\n")
    if not args.compile:
        print(f"\n{'cell':6} {'product':8} {'phase':7} {'m':>5} {'tiling':>18} "
              f"{'name':>12} {'kernel_us':>10} {'wall_us':>9} {'GB/s':>7} "
              f"{'%peak':>6}  device {jax.devices()[0].device_kind}")
        for r in lines:
            if "wall_us" in r:
                print(f"{r['cell']:6} {r['product']:8} {r['phase']:7} "
                      f"{r['m']:5d} {str(tuple(r['tiling'])):>18} "
                      f"{r['name']:>12} {r.get('kernel_us', float('nan')):10.1f} "
                      f"{r['wall_us']:9.1f} {r.get('GB/s', float('nan')):7.1f} "
                      f"{r.get('of_peak_%', float('nan')):6.1f}")


if __name__ == "__main__":
    main()
