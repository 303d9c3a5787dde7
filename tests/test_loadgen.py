"""Loadgen harness tests: trace determinism / byte-identical replay,
open- vs closed-loop runner semantics (stub call_fn — no cluster),
client<->server reconciliation math, the gap gate, and schedule-
anchored chaos replay.
"""

import threading
import time

import pytest

from ray_tpu.loadgen import (
    GAP_FRACTION_LIMIT,
    LengthMix,
    RateCurve,
    StampCard,
    TenantBlend,
    TraceSpec,
    apply_chaos_schedule,
    closed_loop_think_times,
    default_blend,
    open_loop_arrivals,
    reconcile,
    run_trace,
)
from ray_tpu.loadgen import trace as trace_mod


def _spec(**kw):
    kw.setdefault("seed", 42)
    kw.setdefault("duration_s", 10.0)
    kw.setdefault("curve", RateCurve(
        base_qps=20.0, ramp_to_qps=60.0, ramp_s=6.0,
        diurnal_amplitude=0.3, diurnal_period_s=20.0,
        flash=[(4.0, 1.5, 3.0)]))
    return TraceSpec(**kw)


# ---------------------------------------------------------------------------
# trace determinism / byte-identical replay
# ---------------------------------------------------------------------------


class TestTraceDeterminism:
    def test_same_seed_identical_bytes(self):
        h1, r1 = trace_mod.generate(_spec())
        h2, r2 = trace_mod.generate(_spec())
        assert trace_mod.dumps(h1, r1) == trace_mod.dumps(h2, r2)

    def test_different_seed_differs(self):
        h1, r1 = trace_mod.generate(_spec(seed=1))
        h2, r2 = trace_mod.generate(_spec(seed=2))
        assert trace_mod.dumps(h1, r1) != trace_mod.dumps(h2, r2)

    def test_replay_from_own_header_is_byte_identical(self, tmp_path):
        spec = _spec(chaos=[
            {"kind": "kill_replica", "t": 3.0, "kwargs": {"app": "A"}},
            {"kind": "drop_controller", "t": 5.0,
             "kwargs": {"restart": True}},
        ])
        header, records = trace_mod.generate(spec)
        path = str(tmp_path / "t.jsonl")
        trace_mod.write(path, header, records)
        with open(path, "rb") as f:
            on_disk = f.read()
        assert trace_mod.regenerate_bytes(path) == on_disk

    def test_header_roundtrips_through_spec(self):
        spec = _spec(kind="closed", num_requests=17, mean_think_s=0.2,
                     concurrency=4)
        assert TraceSpec.from_header(spec.header()).header() == \
            spec.header()

    def test_pareto_trace_deterministic_and_distinct(self):
        hp1, rp1 = trace_mod.generate(_spec(process="pareto"))
        hp2, rp2 = trace_mod.generate(_spec(process="pareto"))
        assert trace_mod.dumps(hp1, rp1) == trace_mod.dumps(hp2, rp2)
        _, rpois = trace_mod.generate(_spec(process="poisson"))
        assert [r["t"] for r in rp1] != [r["t"] for r in rpois]

    def test_shapes_independent_of_arrival_process(self):
        # Same seed, different arrival process: the request SHAPES
        # (tenant, lengths) must not reshuffle — the shape rng is
        # salted independently of the arrival rng.
        _, ra = trace_mod.generate(_spec(process="poisson"))
        _, rb = trace_mod.generate(_spec(process="pareto"))
        n = min(len(ra), len(rb))
        keep = ("tenant", "prompt_tokens", "max_tokens")
        assert [{k: r[k] for k in keep} for r in ra[:n]] == \
            [{k: r[k] for k in keep} for r in rb[:n]]

    def test_closed_loop_records_carry_think_times(self):
        spec = _spec(kind="closed", num_requests=25, mean_think_s=0.1)
        _, records = trace_mod.generate(spec)
        assert len(records) == 25
        assert [r["t"] for r in records] == \
            closed_loop_think_times(25, 42, 0.1)

    def test_unknown_schema_rejected(self, tmp_path):
        path = str(tmp_path / "bad.jsonl")
        with open(path, "w") as f:
            f.write('{"schema":99}\n')
        with pytest.raises(ValueError, match="schema"):
            trace_mod.read(path)


# ---------------------------------------------------------------------------
# arrival processes
# ---------------------------------------------------------------------------


class TestArrivals:
    def test_open_loop_offsets_sorted_in_range(self):
        for process in ("poisson", "pareto"):
            ts = open_loop_arrivals(RateCurve(30.0), 5.0, seed=3,
                                    process=process)
            assert ts == sorted(ts)
            assert all(0.0 <= t < 5.0 for t in ts)

    def test_poisson_tracks_rate(self):
        ts = open_loop_arrivals(RateCurve(50.0), 10.0, seed=1)
        assert 350 <= len(ts) <= 650  # ~500 expected

    def test_flash_crowd_concentrates_arrivals(self):
        curve = RateCurve(10.0, flash=[(2.0, 1.0, 5.0)])
        ts = open_loop_arrivals(curve, 4.0, seed=7)
        in_flash = sum(1 for t in ts if 2.0 <= t < 3.0)
        before = sum(1 for t in ts if 0.0 <= t < 1.0)
        assert in_flash > 2 * before

    def test_pareto_is_burstier_than_poisson(self):
        # Same mean load; the Pareto renewal process should show a
        # heavier-tailed gap distribution (larger max inter-arrival).
        pois = open_loop_arrivals(RateCurve(20.0), 20.0, seed=5)
        par = open_loop_arrivals(RateCurve(20.0), 20.0, seed=5,
                                 process="pareto")
        gap = lambda ts: max(  # noqa: E731
            b - a for a, b in zip(ts, ts[1:]))
        assert gap(par) > gap(pois)

    def test_bad_process_and_alpha_rejected(self):
        with pytest.raises(ValueError, match="arrival process"):
            open_loop_arrivals(RateCurve(1.0), 1.0, 0, process="uniform")
        with pytest.raises(ValueError, match="pareto_alpha"):
            open_loop_arrivals(RateCurve(1.0), 1.0, 0, process="pareto",
                               pareto_alpha=1.0)

    def test_think_times(self):
        assert closed_loop_think_times(4, 1, 0.0) == [0.0] * 4
        a = closed_loop_think_times(10, 1, 0.5)
        assert a == closed_loop_think_times(10, 1, 0.5)
        assert all(t > 0 for t in a)


# ---------------------------------------------------------------------------
# runner semantics (stub call_fn, no cluster)
# ---------------------------------------------------------------------------


class _ConcurrencyProbe:
    """A call_fn that services requests with a fixed sleep and records
    the peak number of in-flight calls."""

    def __init__(self, service_s: float):
        self.service_s = service_s
        self.cur = 0
        self.peak = 0
        self.lock = threading.Lock()

    def __call__(self, request, card):
        with self.lock:
            self.cur += 1
            self.peak = max(self.peak, self.cur)
        time.sleep(self.service_s)
        with self.lock:
            self.cur -= 1
        card.first_byte_p = time.perf_counter()
        card.done_p = time.perf_counter()
        card.chunks = 1
        return card


class TestRunnerSemantics:
    def test_open_loop_does_not_wait_for_completions(self):
        # 10 arrivals in a burst, each taking 0.3s: an open-loop driver
        # must overlap them (exogenous arrivals), not serialize.
        records = [{"i": i, "t": 0.01 * i, "tenant": "t"}
                   for i in range(10)]
        header = {"kind": "open", "duration_s": 0.1}
        probe = _ConcurrencyProbe(0.3)
        t0 = time.perf_counter()
        result = run_trace(header, records, probe, workers=16,
                           emit_metrics=False)
        wall = time.perf_counter() - t0
        assert probe.peak >= 5
        assert wall < 10 * 0.3  # far below the serialized time
        assert result.summary()["ok"] == 10

    def test_open_loop_respects_schedule(self):
        records = [{"i": i, "t": 0.25 * i, "tenant": "t"}
                   for i in range(4)]
        header = {"kind": "open", "duration_s": 1.0}
        sends = {}

        def call(request, card):
            sends[request["i"]] = time.perf_counter()
            card.first_byte_p = card.done_p = time.perf_counter()
            return card

        t0 = time.perf_counter()
        run_trace(header, records, call, workers=4, emit_metrics=False)
        for i in range(4):
            offset = sends[i] - t0
            assert offset == pytest.approx(0.25 * i, abs=0.2)

    def test_closed_loop_bounds_concurrency(self):
        records = [{"i": i, "t": 0.0, "tenant": "t"} for i in range(12)]
        header = {"kind": "closed", "duration_s": 0.0, "concurrency": 3}
        probe = _ConcurrencyProbe(0.05)
        result = run_trace(header, records, probe, emit_metrics=False)
        assert probe.peak <= 3
        assert result.summary()["ok"] == 12

    def test_call_fn_exception_lands_on_card(self):
        records = [{"i": i, "t": 0.0, "tenant": "t"} for i in range(3)]
        header = {"kind": "closed", "duration_s": 0.0, "concurrency": 1}

        def boom(request, card):
            raise RuntimeError("injected")

        result = run_trace(header, records, boom, emit_metrics=False)
        assert result.summary()["errors"] == 3
        assert all("RuntimeError" in c.error for c in result.cards)


# ---------------------------------------------------------------------------
# reconciliation
# ---------------------------------------------------------------------------


def _card(idx, rid, e2e_s, tenant="t", ttfb_s=0.01, error=None):
    c = StampCard(idx, tenant)
    c.rid = rid
    c.send_p = 100.0
    if error is None:
        c.first_byte_p = 100.0 + ttfb_s
        c.done_p = 100.0 + e2e_s
    else:
        c.error = error
    return c


def _server(rid, phases, ttft_s=0.01):
    return {"rid": rid, "tenant": "t", "method": "__call__",
            "ts": 0.0, "phases": dict(phases),
            "e2e_s": sum(phases.values()), "ttft_s": ttft_s,
            "tpot_s": 0.0, "tokens_in": 1, "tokens_out": 1}


class TestReconcile:
    def test_gap_is_exactly_e2e_minus_phase_sum(self):
        cards = [_card(0, "r0", 1.0)]
        server = [_server("r0", {"handle_queue": 0.125, "dispatch": 0.125,
                                 "exec": 0.5})]
        report = reconcile(cards, server)
        row = report["requests"][0]
        assert row["server_attributed_s"] == 0.75
        assert row["gap_s"] == 0.25
        assert row["gap_fraction"] == 0.25
        assert report["summary"]["matched"] == 1

    def test_negative_gap_clamped_to_zero(self):
        # Server attributes MORE than the client saw (sub-ms clock
        # disagreement): the gap must clamp at zero, not go negative.
        cards = [_card(0, "r0", 0.5)]
        server = [_server("r0", {"exec": 0.6})]
        row = reconcile(cards, server)["requests"][0]
        assert row["gap_s"] == 0.0
        assert row["gap_fraction"] == 0.0

    def test_gate_passes_on_well_attributed_run(self):
        cards, server = [], []
        for i in range(50):
            e2e = 0.2 + 0.001 * i
            cards.append(_card(i, f"r{i}", e2e))
            server.append(_server(f"r{i}", {"exec": e2e * 0.99}))
        s = reconcile(cards, server)["summary"]
        assert s["matched"] == 50
        assert s["gap_fraction"]["p99"] <= GAP_FRACTION_LIMIT
        assert s["gate_pass"] is True

    def test_gate_trips_on_injected_unattributed_stall(self):
        # 50 clean requests plus a handful whose client e2e carries a
        # 500ms stall the server never attributed — the p99 gate must
        # catch them.
        cards, server = [], []
        for i in range(50):
            cards.append(_card(i, f"r{i}", 0.2))
            server.append(_server(f"r{i}", {"exec": 0.199}))
        for i in range(50, 55):
            cards.append(_card(i, f"r{i}", 0.7))  # 0.5s stall
            server.append(_server(f"r{i}", {"exec": 0.2}))
        s = reconcile(cards, server)["summary"]
        assert s["gap_fraction"]["p99"] > GAP_FRACTION_LIMIT
        assert s["gate_pass"] is False

    def test_unmatched_and_errors_counted_not_hidden(self):
        cards = [
            _card(0, "r0", 0.2),
            _card(1, "gone", 0.2),       # replica died with its ring
            _card(2, "", 0.0, error="ServeOverloadedError: shed"),
        ]
        server = [_server("r0", {"exec": 0.199})]
        s = reconcile(cards, server)["summary"]
        assert s["matched"] == 1
        assert s["unmatched"] == 1
        assert s["errors"] == 1

    def test_no_matches_is_a_failure_not_a_vacuous_pass(self):
        s = reconcile([_card(0, "x", 0.1)], [])["summary"]
        assert s["gate_pass"] is False


# ---------------------------------------------------------------------------
# schedule-anchored chaos replay
# ---------------------------------------------------------------------------


class TestChaosSchedule:
    def test_apply_requires_known_kinds(self):
        from ray_tpu._private import chaos

        chaos.enable()
        try:
            with pytest.raises(ValueError, match="unknown chaos kind"):
                apply_chaos_schedule(
                    {"chaos": [{"kind": "meteor", "t": 1.0}]})
        finally:
            chaos.disable()

    def test_scheduled_fault_fires_at_anchor_offset(self):
        from ray_tpu._private import chaos

        chaos.enable()
        try:
            apply_chaos_schedule({"chaos": [
                {"kind": "kill_replica", "t": 0.05,
                 "kwargs": {"app": "NoSuchApp"}},
            ]})
            faults = chaos.scheduled_faults()
            assert len(faults) == 1 and not faults[0]["fired"]
            chaos.anchor_schedule()
            deadline = time.time() + 2.0
            while time.time() < deadline:
                faults = chaos.scheduled_faults()
                if faults[0]["fired"]:
                    break
                time.sleep(0.02)
            assert faults[0]["fired"]
            # No cluster here: the executor errored, and the schedule
            # recorded it instead of crashing the scheduler thread.
            assert str(faults[0]["result"]).startswith("error")
        finally:
            chaos.disable()

    def test_clear_cancels_pending_faults(self):
        from ray_tpu._private import chaos

        chaos.enable()
        try:
            apply_chaos_schedule({"chaos": [
                {"kind": "drop_controller", "t": 30.0,
                 "kwargs": {"restart": True}},
            ]})
            assert len(chaos.scheduled_faults()) == 1
            chaos.clear()
            assert chaos.scheduled_faults() == []
        finally:
            chaos.disable()


# ---------------------------------------------------------------------------
# workload shapes
# ---------------------------------------------------------------------------


class TestWorkload:
    def test_blend_draw_respects_bounds(self):
        import random

        blend = default_blend()
        rng = random.Random(0)
        for _ in range(500):
            r = blend.draw(rng)
            assert r["tenant"] in ("interactive", "batch")
            assert r["prompt_tokens"] >= 1
            assert r["max_tokens"] >= 1

    def test_length_mix_tail_bucket(self):
        import random

        mix = LengthMix(median=10, sigma=0.1, lo=1, hi=2000,
                        tail_p=1.0, tail_lo=1000, tail_hi=2000)
        rng = random.Random(0)
        assert all(1000 <= mix.draw(rng) <= 2000 for _ in range(50))

    def test_rate_curve_peak_catches_flash_edges(self):
        curve = RateCurve(10.0, flash=[(1.05, 0.02, 10.0)])
        assert curve.peak(5.0) == pytest.approx(100.0)

    def test_blend_needs_a_tenant(self):
        with pytest.raises(ValueError):
            TenantBlend([])
