"""A prefill pass's attention alone on the chip: the walk over blocks of a
slot's table (`paged_kv._paged_attention`) over block sizes, beside the plain
form it replaced (the whole table gathered, cast and scored:
`grouped_attention`), at the shapes the serving cells' passes have.

    chiprun -- python tools/walk_sweep.py                 # every shape
    chiprun -- python tools/walk_sweep.py swa ring qwen   # three
    python tools/walk_sweep.py --tiny                     # here: control flow

A shape is a pass's rows and chunk, the model's heads, and the width of the
table (or ring) its rows walk; `live` is how many of the table's rows lie
under the chunk's end, the chunk the last of them, every row of the pass
alike. Each candidate is the walk with `latent_block_pages` answering a
fixed number of rows (128 to 1,024) or its own rule (`rule`), checked against
the plain form's result and timed by the host's clock around one program
that runs it `REPEATS` times (a launch alone is under the host's 0.4 ms a
dispatch). Prints one JSON line a reading;
`chiprun_out/walk_sweep.jsonl` keeps the lines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.paged_attention import grouped_attention
from ray_tpu.serve import paged_kv

PAGE = 16
REPEATS = 20
BLOCKS = (128, 256, 512, 1024)

# shape: (rows of a pass, chunk, heads, key-value heads, head size, the
# table's rows, the `live` lengths read). The window model's full layer and
# its ring, then the widest pass of each other cell with keys and values.
SHAPES = {
    "swa": (1, 512, 28, 4, 128, 16384, (512, 2048, 3584, 8192, 14336)),
    "ring": (1, 512, 28, 4, 128, 4608, (512, 2048, 3584, 4608)),
    "qwen": (4, 64, 32, 8, 128, 1024, (64, 256, 1024)),
    "qwen1": (1, 64, 32, 8, 128, 1024, (64, 256, 1024)),
    "olmoe": (2, 256, 16, 16, 128, 1024, (256, 512, 1024)),
    "granite": (2, 256, 32, 8, 64, 1024, (256, 512, 1024)),
    "lfm2": (2, 256, 32, 8, 64, 2560, (256, 1024, 2560)),
    "sdar": (2, 256, 32, 4, 128, 2560, (256, 1024, 2560)),
    "solar": (2, 256, 64, 8, 128, 2560, (256, 1024, 2560)),
}
TINY = {"tiny": (2, 8, 4, 2, 16, 96, (8, 40, 96))}


def _plain(q, kc, vc, tables, live, positions, scale):
    p_, width = tables.shape[0], tables.shape[1] * kc.shape[2]
    d = q.shape[-1]
    k_pos = jnp.arange(width, dtype=jnp.int32)[None, None]
    valid = (k_pos <= positions[:, :, None]) & (k_pos < live[:, None, None])
    k = kc[0, tables].reshape(p_, width, -1, d).astype(jnp.float32)
    v = vc[0, tables].reshape(p_, width, -1, d).astype(jnp.float32)
    return grouped_attention(q, k, v, valid, scale)


def _walk(q, kc, vc, tables, live, positions, scale):
    return paged_kv._paged_attention(
        q, kc, vc, 0, tables, live,
        lambda k_pos: k_pos <= positions[:, :, None], scale)


def _programs(form):
    """`form` as one program, and run `REPEATS` times in one program, each
    launch's queries hanging on the one before (nothing hoists out of the
    loop), so that the host's dispatch, 0.4 ms a call, is paid once. Traced
    anew for every candidate: the block's size is read at trace time."""
    def many(q, *rest):
        def again(_, out):
            return form(q + (out[:, :1, :1, :1] * 0).astype(q.dtype), *rest)

        return jax.lax.fori_loop(0, REPEATS, again, jnp.zeros_like(q))

    return jax.jit(form), jax.jit(many)


def _timed(many, args) -> float:
    many(*args).block_until_ready()
    start = time.perf_counter()
    many(*args).block_until_ready()
    return (time.perf_counter() - start) / REPEATS * 1e3


def sweep(name, shape, blocks, out):
    p_, c, h, kvh, d, width, lives = shape
    page = PAGE if width % PAGE == 0 and width > 64 * PAGE else 4
    mp = width // page
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = (1, 1 + p_ * mp, page, kvh * d)
    kc = jax.random.normal(keys[0], pool, jnp.bfloat16)
    vc = jax.random.normal(keys[1], pool, jnp.bfloat16)
    q = jax.random.normal(keys[2], (p_, c, h, d), jnp.bfloat16)
    tables = 1 + jnp.asarray(np.random.default_rng(0).permutation(
        p_ * mp).reshape(p_, mp), jnp.int32)
    scale = d ** -0.5
    rule = paged_kv.latent_block_pages
    plain, _ = _programs(_plain)
    for rows in ("plain", "rule") + tuple(blocks):
        form = _plain if rows == "plain" else _walk
        if rows not in ("plain", "rule"):
            if rows > width:
                continue
            paged_kv.latent_block_pages = lambda ps, n, r=rows: r // ps
        fn, many = _programs(form)
        for live_rows in lives:
            live = jnp.full((p_,), live_rows, jnp.int32)
            positions = (live[:, None] - c
                         + jnp.arange(c, dtype=jnp.int32)[None])
            args = (q, kc, vc, tables, live, positions, scale)
            apart = float(jnp.max(jnp.abs(
                fn(*args).astype(jnp.float32)
                - plain(*args).astype(jnp.float32))))
            line = {"shape": name, "form": rows,
                    "block_rows": (rule(page, mp) * page if rows == "rule"
                                   else rows), "width": width,
                    "live": live_rows, "ms": round(_timed(many, args), 4),
                    "apart": apart,
                    "device": jax.devices()[0].device_kind}
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
        paged_kv.latent_block_pages = rule


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("shapes", nargs="*")
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args()
    shapes, blocks = (TINY, (16, 32)) if args.tiny else (SHAPES, BLOCKS)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/walk_sweep.jsonl", "a") as out:
        for name in args.shapes or shapes:
            sweep(name, shapes[name], blocks, out)


if __name__ == "__main__":
    main()
