"""The ring's decode-attention kernel against its plain form, on the chip,
at the shapes the cell `serve-swa-longdoc` launches it with
(`ops/paged_attention.py` `window_decode_attention`,
`%window_decode_attention.*` in a trace).

    chiprun -- python tools/ring_kernel_check.py
    python tools/ring_kernel_check.py --tiny     # here: control flow

32 slots, 28 query and 4 key-value heads of 128, a window of 4,096 over
rings of 288 pages of 16 rows (4,608), six layers of bfloat16 pool; the
slots' newest positions lie on both sides of the window's edge, of a page's
edge and of the ring's wrap, once and three times round, with idle slots
between. Keys are drawn large, so that a query's weight falls on a few rows
and one row wrongly seen or missed moves the result by far more than
rounding. The plain form gathers a slot's whole ring, works each row's
position out of `newest` and masks by the window, in float32 under
`jax.default_matmul_precision("highest")`: another route to the same rows.
The kernel multiplies bfloat16 values exactly and accumulates in float32,
so the two agree to float32 rounding. Prints one JSON line: the largest
difference, the kernel's mean time a call and the bytes a second it reads
the window's rows at, beside the chip's 819 GB/s.
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

from ray_tpu.ops.paged_attention import window_decode_attention

NEWEST = (-1, 0, 14, 15, 16, 700, 4094, 4095, 4096, 4097, 4110, 4111, 4112,
          -1, 4606, 4607, 4608, 4609, 4623, 4624, 6000, 8190, 9215, 9216,
          9217, 12000, 13823, 13824, 13825, 15000, 16382, 16383)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--calls", type=int, default=50)
    args = parser.parse_args()
    if args.tiny:
        slots, heads, kv, dim, page, window, per_ring, layers = (
            8, 8, 8, 16, 8, 20, 5, 2)
        newest = (-1, 3, 19, 20, 27, 39, 40, 101)
        dtype, interpret = jnp.float32, True
    else:
        slots, heads, kv, dim, page, window, per_ring, layers = (
            32, 28, 4, 128, 16, 4096, 288, 6)
        newest, dtype, interpret = NEWEST, jnp.bfloat16, False
    key = jax.random.split(jax.random.PRNGKey(70), 3)
    shape = (layers, 1 + slots * per_ring, page, kv * dim)
    # Queries a bfloat16 holds, kept as float32: the kernel's result is
    # then float32 too, and is compared before any rounding to the model's.
    q = jax.random.normal(key[0], (slots, heads, dim), jnp.float32).astype(
        dtype).astype(jnp.float32)
    k_ring = (3.0 * jax.random.normal(key[1], shape, jnp.float32)).astype(dtype)
    v_ring = jax.random.normal(key[2], shape, jnp.float32).astype(dtype)
    newest = jnp.asarray(newest, jnp.int32)
    scale = dim ** -0.5

    # The pools are arguments: closed over, they would be constants of the
    # compiled program (3.8 GB of them).
    kernel = jax.jit(lambda q, k, v, layer: window_decode_attention(
        q, k, v, layer, newest, window, scale, interpret=interpret))

    @jax.jit
    def plain(q, k, v, layer):
        with jax.default_matmul_precision("highest"):
            return window_decode_attention(q, k, v, layer, newest, window,
                                           scale, use_pallas=False)

    live = np.asarray(newest) >= 0
    worst, largest = 0.0, 0.0
    for layer in range(layers):
        got = np.asarray(kernel(q, k_ring, v_ring, jnp.int32(layer)))[live]
        want = np.asarray(plain(q, k_ring, v_ring, jnp.int32(layer)))[live]
        worst = max(worst, float(np.abs(got - want).max()))
        largest = max(largest, float(np.abs(want).max()))
    jax.block_until_ready(kernel(q, k_ring, v_ring, jnp.int32(0)))
    t0 = time.perf_counter()
    for i in range(args.calls):
        out = kernel(q, k_ring, v_ring, jnp.int32(i % layers))
    jax.block_until_ready(out)
    seconds = (time.perf_counter() - t0) / args.calls
    rows = int(np.minimum(np.asarray(newest)[live] + 1, window).sum())
    row_bytes = 2 * kv * dim * jnp.dtype(dtype).itemsize
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "slots": slots,
        "window": window, "ring_rows": per_ring * page, "layers": layers,
        "max_abs_difference": worst, "largest_value": largest,
        "float32_rounding": float(jnp.finfo(jnp.float32).eps),
        "rows_in_the_windows": rows, "wall_us_a_call": 1e6 * seconds,
        "GB_per_s_by_the_host_clock": rows * row_bytes / seconds / 1e9,
    }))
    return 0 if worst <= 2e-5 * max(largest, 1.0) else 1


if __name__ == "__main__":
    sys.exit(main())
