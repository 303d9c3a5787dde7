"""Flight recorder tests: per-step breakdown, straggler attribution,
unified memory accounting, CLI rendering, serving latency histograms.

The contract under test (PAPER.md observability story): every training
step decomposes into data/compute/collective/checkpoint/other that sums
to the step wall time; per-rank records ride the existing report/poll
stream so the DRIVER names the slowest rank; `rt top` and `rt memory
--devices` render the same numbers from the GCS metrics stream.
"""

import time
from dataclasses import replace

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu._private import chaos
from ray_tpu._private import worker as worker_mod


def _wait_for(fn, timeout=10.0, poll=0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = fn()
        if out:
            return out
        time.sleep(poll)
    raise TimeoutError("condition not met")


# -- StepProfiler core (no runtime needed) -------------------------------

def test_step_breakdown_sums_to_wall():
    """Named phases + other == wall, per record, by construction."""
    from ray_tpu.train import StepProfiler

    prof = StepProfiler(ring=16, rank=0, emit_metrics=False)
    for _ in range(5):
        with prof.step(tokens=64):
            with prof.phase("data"):
                time.sleep(0.002)
            with prof.phase("compute"):
                time.sleep(0.004)
    recs = prof.records()
    assert len(recs) == 5
    for r in recs:
        named = (r["data_s"] + r["compute_s"] + r["collective_s"]
                 + r["checkpoint_s"] + r["other_s"])
        assert abs(r["wall_s"] - named) < 1e-6
        assert r["compute_s"] >= 0.004
        assert r["data_s"] >= 0.002
        assert r["tokens_per_s"] > 0


def test_ring_buffer_bounds_memory():
    from ray_tpu.train import StepProfiler

    prof = StepProfiler(ring=4, rank=0, emit_metrics=False)
    for _ in range(10):
        with prof.step():
            pass
    assert len(prof.records()) == 4
    assert prof.summary()["steps"] == 10
    # Pending drains at most ring entries, then empties.
    assert len(prof.drain_records()) == 4
    assert prof.drain_records() == []


def test_collective_time_attributed_via_observer():
    """The collective op wrappers report wall time into the active step
    through the observer hook — no loop annotation needed."""
    from ray_tpu.train import StepProfiler
    from ray_tpu.util.collective import collective as col

    prof = StepProfiler(ring=4, rank=0, emit_metrics=False)
    with prof.step():
        col._observed("allreduce", lambda: time.sleep(0.01))
    rec = prof.records()[-1]
    assert rec["collective_s"] >= 0.01
    assert rec["collective_s"] <= rec["wall_s"] + 1e-9


def test_feed_wait_lands_in_data_phase():
    """attach_feed: the pipeline's measured consumer wait becomes the
    step's data_s when the loop doesn't time data explicitly."""
    from ray_tpu.data.feed import FeedStats
    from ray_tpu.train import StepProfiler

    stats = FeedStats()
    prof = StepProfiler(ring=4, rank=0, emit_metrics=False)
    prof.attach_feed(stats)
    with prof.step():
        # The stall happens inside the step (blocked in next(batch)).
        stats.add_wait(0.03)
        time.sleep(0.035)
    rec = prof.records()[-1]
    assert abs(rec["feed_wait_s"] - 0.03) < 1e-9
    assert rec["feed_stalls"] == 1
    assert abs(rec["data_s"] - 0.03) < 1e-9
    # The breakdown still sums to wall: the remainder is other_s.
    assert rec["other_s"] == pytest.approx(rec["wall_s"] - 0.03, abs=1e-6)
    # Next step: no new wait -> no data time.
    with prof.step():
        pass
    assert prof.records()[-1]["data_s"] == 0.0


def test_compile_counting_flags_retraces():
    import jax
    import jax.numpy as jnp

    from ray_tpu.train import StepProfiler

    f = jax.jit(lambda x: x * 2)
    prof = StepProfiler(ring=8, rank=0, emit_metrics=False)
    # Counted from JAX's own compile events, whatever compiles: make the
    # inputs outside the steps (jnp.ones is a small program of its own).
    four, eight = jnp.ones((4,)), jnp.ones((8,))
    with prof.step():
        f(four)
    assert prof.records()[-1]["compiles"] == 1
    with prof.step():
        f(four)
    assert prof.records()[-1]["compiles"] == 0
    with prof.step():
        f(eight)  # new shape: retrace
    assert prof.records()[-1]["compiles"] == 1
    with prof.step():
        jax.jit(lambda x: x + 3)(four)  # nobody registered it: counted too
    assert prof.records()[-1]["compiles"] == 1


def test_mfu_estimate_uses_given_peak():
    from ray_tpu.train import StepProfiler
    from ray_tpu.train import flight_recorder

    assert flight_recorder.peak_flops_per_s() is None  # CPU test mesh
    prof = StepProfiler(ring=4, rank=0, emit_metrics=False,
                        flops_per_step=1e9, peak_flops=1e12)
    with prof.step():
        time.sleep(0.002)
    rec = prof.records()[-1]
    # mfu = 1e9 / (wall * 1e12); wall >= 2ms -> mfu <= 0.5
    assert 0 < rec["mfu"] <= 1e9 / (0.002 * 1e12) + 1e-6


def test_chaos_delay_steps_consumed_once():
    from ray_tpu.train import StepProfiler

    chaos.enable()
    try:
        chaos.delay_steps(0.05, count=1)
        prof = StepProfiler(ring=4, rank=0, emit_metrics=False)
        t0 = time.perf_counter()
        with prof.step():
            pass
        assert time.perf_counter() - t0 >= 0.05
        t0 = time.perf_counter()
        with prof.step():
            pass
        assert time.perf_counter() - t0 < 0.04  # injection exhausted
    finally:
        chaos.disable()


def test_compute_skew_names_slowest_rank():
    from ray_tpu.train import compute_skew

    fast = {"steps": 10, "wall_s": 1.0, "compute_s": 0.9}
    slow = {"steps": 10, "wall_s": 3.0, "compute_s": 0.9,
            "collective_s": 2.0}
    out = compute_skew([fast, slow, None])
    assert out["straggler_rank"] == 1
    assert abs(out["skew_s"] - 0.2) < 1e-9
    assert out["straggler_breakdown"]["collective_s"] == pytest.approx(0.2)
    # Fewer than two reporting ranks: no attribution.
    assert compute_skew([fast, None]) is None


# -- end-to-end: gang straggler attribution ------------------------------

def _profiled_loop(config):
    import time as _t

    from ray_tpu import train
    from ray_tpu._private import chaos as _chaos

    prof = train.StepProfiler(ring=64)
    rank = train.get_world_rank()
    if rank == config["slow_rank"]:
        _chaos.enable()
        _chaos.delay_steps(config["delay_s"], count=config["steps"])
    for step in range(config["steps"]):
        with prof.step(tokens=32):
            with prof.phase("compute"):
                _t.sleep(0.004)
        train.report({"step": step, "rank": rank})


def test_straggler_attribution_two_node_gang():
    """A chaos-slowed rank on a 2-node gang is named as the straggler in
    Result.metrics_history, with per-phase breakdown and per-rank walls.
    The delay is injected INSIDE rank 1's step loop (process-local,
    deterministic), exactly where a real straggler would lose time."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cluster = Cluster()
    cluster.add_node(num_cpus=2)
    cluster.add_node(num_cpus=2)
    cluster.connect()
    try:
        trainer = JaxTrainer(
            _profiled_loop,
            train_loop_config={"steps": 8, "slow_rank": 1,
                               "delay_s": 0.05},
            scaling_config=ScalingConfig(
                num_workers=2, placement_strategy="SPREAD"
            ),
        )
        result = trainer.fit()
        assert result.error is None
        enriched = [m for m in result.metrics_history
                    if "train_straggler_rank" in m]
        assert enriched, (
            f"no skew-enriched entries in {result.metrics_history}"
        )
        last = enriched[-1]
        assert last["train_straggler_rank"] == 1
        # ~50ms injected per step dominates the ~4ms compute.
        assert last["train_step_skew_s"] > 0.02
        walls = last["train_step_wall_by_rank"]
        assert set(walls) == {0, 1}
        assert walls[1] > walls[0]
        # Per-phase breakdown of the straggler: the injected delay is
        # un-attributed time (it models unknown slowness), so it shows
        # up as other_s, not compute_s.
        br = last["train_straggler_breakdown"]
        assert br["other_s"] > br["compute_s"]
        # Rank-0 reports carry per-step records -> breakdown in history.
        with_br = [m for m in result.metrics_history
                   if "train_step_breakdown" in m]
        assert with_br
        b = with_br[-1]["train_step_breakdown"]
        assert abs(
            b["wall_s"] - (b["data_s"] + b["compute_s"] + b["collective_s"]
                           + b["checkpoint_s"] + b["other_s"])
        ) < 1e-4
    finally:
        cluster.shutdown()


# -- memory accountant + CLI against a live runtime ----------------------

def test_memory_accounting_and_cli(rt_start, capsys):
    """sample_once() publishes HBM gauges; rt top / rt memory --devices
    render training + memory state from the live GCS."""
    import jax.numpy as jnp

    from ray_tpu.scripts.scripts import build_parser
    from ray_tpu.train import StepProfiler
    from ray_tpu.util import memory, metrics

    # Hold live device arrays and an object-store object.
    arr = jnp.ones((256, 256), dtype=jnp.float32)
    ref = rt.put(np.zeros(100_000, dtype=np.uint8))
    sample = memory.sample_once()
    assert sample and sample[0]["live_bytes"] >= arr.nbytes

    # A profiled "training" step in this process, rank-tagged.
    prof = StepProfiler(ring=8, rank=0)
    for _ in range(3):
        with prof.step(tokens=16):
            with prof.phase("compute"):
                time.sleep(0.002)
    metrics._flush_once()

    addr = worker_mod._global_node.gcs_address
    parser = build_parser()

    def gauges_visible():
        args = parser.parse_args(["memory", "--devices", "--address", addr])
        args.fn(args)
        out = capsys.readouterr().out
        return out if "MB live" in out else None

    out = _wait_for(gauges_visible, timeout=15.0)
    assert "HBM (live jax arrays)" in out
    assert "object store" in out

    summary = memory.memory_summary(address=addr)
    assert summary["hbm_live_bytes"] >= arr.nbytes
    assert summary["objects"]["count"] >= 1
    assert summary["objects"]["bytes"] >= 100_000

    args = parser.parse_args(["top", "--address", addr])
    args.fn(args)
    top_out = capsys.readouterr().out
    assert "nodes alive" in top_out
    assert "rank 0: 3 steps" in top_out
    assert "hbm" in top_out
    del ref


# -- serving latency histograms ------------------------------------------

def test_serve_ttft_tpot_and_occupancy():
    """TTFT/TPOT histograms and the occupancy gauge populate from real
    engine traffic, riding the existing stats() plumbing."""
    import jax

    from ray_tpu.models import configs, init_params
    from ray_tpu.serve.llm import ContinuousBatchingEngine, _engine_metrics

    cfg = replace(configs.tiny, dtype=np.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    m = _engine_metrics()
    ttft_before = m["ttft_s"].summary()["count"]
    tpot_before = m["tpot_s"].summary()["count"]

    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=64)
    try:
        handles = [eng.submit([1 + i, 7, 3], max_new_tokens=6)
                   for i in range(2)]
        for h in handles:
            toks = h.result(timeout=180)
            assert len(toks) >= 1
        stats = eng.stats()
        lat = stats["latency"]
        assert lat["ttft"]["count"] >= ttft_before + 2
        assert lat["ttft"]["max"] > 0
        assert lat["tpot"]["count"] >= tpot_before + 2
        assert lat["tpot"]["avg"] > 0
        assert 0.0 <= lat["occupancy"] <= 1.0
    finally:
        eng.shutdown()
