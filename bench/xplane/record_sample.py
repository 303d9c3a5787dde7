#!/usr/bin/env python3
"""Record the small trace the reduction is checked against, on the chip:

    python bench/xplane/record_sample.py <out_dir>

Three launches of one small jitted program (a three-turn scan of matrix
products, then one more) between two syncs, traced with the Python tracer off, plus one
host span. Writes `sample.xplane.pb` and `sample.json` (the host-clock
window, and what `reduce()` found, which bench/tests/test_xplane.py holds
every later reduction of the same file to). One process, holds the chip.
"""

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from xplane import reduce as xr

    if jax.devices()[0].platform != "tpu":
        print("record_sample: no TPU here; nothing recorded")
        return 1

    @jax.jit
    def sample_step(x):
        # A scan, so that the trace has a `while` spanning its body's
        # operations, as the engine's and the trainer's programs have.
        def body(c, _):
            return jnp.tanh(c @ c).astype(c.dtype), None

        y, _ = jax.lax.scan(body, x, None, length=3)
        return y @ x

    x = jnp.ones((1024, 1024), jnp.bfloat16)
    sample_step(x).block_until_ready()
    trace_dir = os.path.join(out_dir, "sample_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.sample_span"):
        for _ in range(3):
            x = sample_step(x)
        x.block_until_ready()
    window_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = xr.find_xplane(trace_dir)
    planes = xr.load(path)
    got = xr.reduce(planes, window_s)
    print(xr.describe(planes))
    shutil.copy(path, os.path.join(out_dir, "sample.xplane.pb"))
    with open(os.path.join(out_dir, "sample.json"), "w") as f:
        json.dump({
            "recorded": "bench/xplane/record_sample.py on one TPU v5e chip, "
                        f"JAX {jax.__version__}",
            "device_kind": jax.devices()[0].device_kind,
            "window_s": window_s, "n_devices": got["n_devices"],
            "busy_s": got["busy_s"],
            "modules": {k: {"launches": v["launches"], "total_s": v["total_s"]}
                        for k, v in got["modules"].items()},
            "by_opcode": got["by_opcode"],
            "leaf_ops": got["devices"]["/device:TPU:0"]["ops"],
        }, f, indent=1)
    print("bytes", os.path.getsize(path))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
