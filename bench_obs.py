"""Flight-recorder overhead benchmarks. Writes BENCH_OBS.json.

An always-on recorder is only defensible if it is effectively free, so
this bench measures exactly that — the same jitted-compute step loop run
bare vs wrapped in a StepProfiler (full configuration: phase timer,
fence, compile watching, rank-tagged metric emission), plus the cost of
one unified memory sample:

  1. step recorder overhead: a jitted matmul chain calibrated to a few
     ms per call (a small-but-realistic training step: async dispatch,
     GIL released while the device computes, fenced at step end), timed
     per step; arms run interleaved and compared on MEDIANS so OS
     scheduler tails don't masquerade as recorder cost. MIGRATION.md
     pins overhead_pct < 2% from this entry.
  2. recorder cost in isolation: zero-work steps — the absolute
     per-step price (record + ring append + metrics), in microseconds.
  3. journal overhead: the same calibrated step bare vs emitting one
     cluster-black-box journal event per step (util/journal.py), plus
     emit() priced in isolation. MIGRATION.md pins overhead_pct < 2%
     from this entry.
  4. memory accountant: one sample_once() walking a few hundred live
     arrays and publishing the per-device gauges.

Run: python bench_obs.py [--quick]   (--quick: fewer steps, no artifact)
"""

from __future__ import annotations

import json
import statistics
import sys
import time

STEPS = 300
TARGET_WORK_MS = 4.0
ROUNDS = 4
EMPTY_STEPS = 2000
LIVE_ARRAYS = 256


def _make_work(target_ms: float):
    """Calibrate a jitted matmul chain to >= target_ms per call."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((512, 512), dtype=jnp.float32)
    n = 1
    while True:
        g = jax.jit(_matmul_chain, static_argnums=1)  # rtlint: disable=RT002 — fresh wrapper per round generates the retrace events the observatory probe asserts on
        g(x, n).block_until_ready()  # compile  # rtlint: disable=RT001 — warm-up/measured sync is the point of the probe
        t0 = time.perf_counter()
        g(x, n).block_until_ready()  # rtlint: disable=RT001 — measured sync is the point
        dt_ms = (time.perf_counter() - t0) * 1e3
        if dt_ms >= target_ms or n >= 256:
            return g, x, n, dt_ms
        n *= 2


def _matmul_chain(a, n):
    for _ in range(n):
        a = a @ a / 512.0
    return a


def _steps_off(g, x, n, steps):
    out = []
    for _ in range(steps):
        t0 = time.perf_counter()
        g(x, n).block_until_ready()  # rtlint: disable=RT001 — measured sync is the point
        out.append(time.perf_counter() - t0)
    return out


def _steps_on(prof, g, x, n, steps):
    out = []
    for _ in range(steps):
        t0 = time.perf_counter()
        with prof.step(tokens=1024) as s:
            with prof.phase("compute"):
                y = g(x, n)
            s.fence(y)
        out.append(time.perf_counter() - t0)
    return out


def probe_recorder_overhead(results, quick: bool):
    from ray_tpu.train import StepProfiler

    steps = 50 if quick else STEPS
    rounds = 2 if quick else ROUNDS
    g, x, n, work_ms = _make_work(TARGET_WORK_MS)

    prof = StepProfiler(ring=512, rank=0, flops_per_step=n * 2 * 512**3)
    # Warm both paths, then run the arms INTERLEAVED (off, on, off, on,
    # ...) so load/clock drift lands on both equally.
    _steps_off(g, x, n, 5)
    _steps_on(prof, g, x, n, 5)
    off_ts, on_ts = [], []
    for _ in range(rounds):
        off_ts.extend(_steps_off(g, x, n, steps))
        on_ts.extend(_steps_on(prof, g, x, n, steps))

    off_med = statistics.median(off_ts)
    on_med = statistics.median(on_ts)
    overhead_pct = (on_med - off_med) / off_med * 100.0
    entry = {
        "metric": "step recorder overhead",
        "steps_per_arm": len(off_ts),
        "work_ms_calibrated": round(work_ms, 3),
        "matmul_chain_len": n,
        "off_ms_per_step_p50": round(off_med * 1e3, 4),
        "on_ms_per_step_p50": round(on_med * 1e3, 4),
        "off_ms_per_step_mean": round(statistics.mean(off_ts) * 1e3, 4),
        "on_ms_per_step_mean": round(statistics.mean(on_ts) * 1e3, 4),
        "overhead_pct": round(overhead_pct, 3),
        "recorder_cost_us_per_step": round((on_med - off_med) * 1e6, 2),
    }
    print(json.dumps(entry))
    results.append(entry)

    # Absolute per-step price on empty steps (no work to hide behind).
    m = 200 if quick else EMPTY_STEPS
    t0 = time.perf_counter()
    for _ in range(m):
        with prof.step():
            pass
    bare_us = (time.perf_counter() - t0) / m * 1e6
    entry = {
        "metric": "recorder cost, empty steps",
        "steps": m,
        "cost_us_per_step": round(bare_us, 2),
    }
    print(json.dumps(entry))
    results.append(entry)


def _steps_journal(g, x, n, steps):
    from ray_tpu.util import journal

    out = []
    for i in range(steps):
        t0 = time.perf_counter()
        g(x, n).block_until_ready()  # rtlint: disable=RT001 — measured sync is the point
        journal.emit("train.step", step=i, wall_s=0.005, compiles=0,
                     tokens=1024)
        out.append(time.perf_counter() - t0)
    return out


def probe_journal_overhead(results, quick: bool):
    """Cluster-black-box cost on the train step: the same calibrated
    ~5ms jitted step bare vs emitting one journal event per step (the
    exact record flight_recorder._finish appends). Paired medians over
    interleaved arms; MIGRATION.md pins overhead_pct < 2% from this
    entry. Also prices emit() in isolation (ring append + HLC tick +
    keyed counter), in nanoseconds-scale microseconds."""
    from ray_tpu.util import journal

    steps = 50 if quick else STEPS
    rounds = 2 if quick else ROUNDS
    g, x, n, work_ms = _make_work(TARGET_WORK_MS)

    _steps_off(g, x, n, 5)
    _steps_journal(g, x, n, 5)
    off_ts, on_ts = [], []
    for _ in range(rounds):
        off_ts.extend(_steps_off(g, x, n, steps))
        on_ts.extend(_steps_journal(g, x, n, steps))

    off_med = statistics.median(off_ts)
    on_med = statistics.median(on_ts)
    overhead_pct = (on_med - off_med) / off_med * 100.0
    entry = {
        "metric": "journal overhead",
        "steps_per_arm": len(off_ts),
        "work_ms_calibrated": round(work_ms, 3),
        "off_ms_per_step_p50": round(off_med * 1e3, 4),
        "on_ms_per_step_p50": round(on_med * 1e3, 4),
        "overhead_pct": round(overhead_pct, 3),
        "journal_cost_us_per_step": round((on_med - off_med) * 1e6, 2),
    }
    print(json.dumps(entry))
    results.append(entry)

    # emit() in isolation: the absolute per-event price.
    m = 2000 if quick else 20000
    t0 = time.perf_counter()
    for i in range(m):
        journal.emit("bench.tick", i=i)
    emit_us = (time.perf_counter() - t0) / m * 1e6
    events, dropped = journal.counts()
    entry = {
        "metric": "journal emit cost",
        "emits": m,
        "emit_us": round(emit_us, 3),
        "ring": journal._ring_max,
        "events_total": events,
        "dropped_total": dropped,
    }
    print(json.dumps(entry))
    results.append(entry)


def probe_memory_sample(results, quick: bool):
    import jax.numpy as jnp

    from ray_tpu.util import memory

    n = 32 if quick else LIVE_ARRAYS
    arrays = [jnp.full((64, 64), float(i)) for i in range(n)]
    memory.sample_once()  # warm the gauge registry
    rounds = 3 if quick else 20
    t0 = time.perf_counter()
    for _ in range(rounds):
        sample = memory.sample_once()
    sample_ms = (time.perf_counter() - t0) / rounds * 1e3
    entry = {
        "metric": "memory accountant sample",
        "live_arrays": len(arrays),
        "sample_ms": round(sample_ms, 3),
        "devices": len(sample),
    }
    print(json.dumps(entry))
    results.append(entry)


def main():
    quick = "--quick" in sys.argv
    results = []
    probe_recorder_overhead(results, quick)
    probe_journal_overhead(results, quick)
    probe_memory_sample(results, quick)
    if not quick:
        with open("BENCH_OBS.json", "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
