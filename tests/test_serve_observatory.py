"""Serve request observatory: phase attribution, SLO burn, ServeSignals.

The request-path mirror of test_flight_recorder.py: every request gets a
phase vector that sums to its e2e wall, tenants get SLO burn accounting,
the controller publishes ServeSignals to the GCS KV, and the engine's
HOL watchdog attributes decode stalls to the prefill that caused them.
"""

import time
from dataclasses import replace

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu import serve
from ray_tpu.serve import observatory
from ray_tpu.serve.deployment import SloConfig


@pytest.fixture
def serve_session(rt_start):
    yield rt_start
    serve.shutdown()


@pytest.fixture
def fresh_observatory():
    observatory.reset_for_tests()
    yield
    observatory.reset_for_tests()


def _tiny_model():
    import jax

    from ray_tpu.models import configs, init_params

    cfg = replace(configs.tiny, dtype=np.float32)
    return init_params(jax.random.PRNGKey(0), cfg), cfg


def _fabricated_request(tenant="t", e2e_parts=(0.001, 0.002), tokens_out=0):
    """Drive one synthetic request through begin/finish with real clocks
    (sleeps are ms-scale; the phase math never sees wall-clock jitter
    because it telescopes over its own stamps)."""
    w = observatory.make_wire_ctx(tenant)
    w["disp_t"] = time.time()
    ctx = observatory.begin(w, "synth", "__call__")
    if tokens_out:
        ctx.mark("engine_enqueue")
        ctx.mark("slot_grant")
        time.sleep(e2e_parts[0])
        ctx.mark("first_token")
        time.sleep(e2e_parts[1])
        ctx.mark("engine_done")
        ctx.tokens_out = tokens_out
    else:
        time.sleep(sum(e2e_parts))
    return observatory.finish(ctx)


# -- phase attribution --------------------------------------------------

def test_engine_phase_vector_sums_to_e2e(fresh_observatory):
    """The tentpole invariant: through a REAL engine (submit -> slot
    grant -> prefill -> decode -> done), the six-phase vector sums to
    the request's e2e wall by construction, and every engine phase is
    attributed (no 'exec' fallback)."""
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    observatory.configure("llm-test", None)
    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=64)
    try:
        w = observatory.make_wire_ctx("acme")
        time.sleep(0.002)
        w["disp_t"] = time.time()
        ctx = observatory.begin(w, "llm-test", "__call__")
        h = eng.submit([3, 7, 11, 2], max_new_tokens=8)
        toks = h.result(timeout=120)
        rec = observatory.finish(ctx)
    finally:
        eng.shutdown()
    assert len(toks) == 8
    phases = rec["phases"]
    for p in ("handle_queue", "dispatch", "engine_admission_wait",
              "prefill", "decode", "stream"):
        assert p in phases, f"missing phase {p}"
    assert "exec" not in phases
    # Telescoping: the sum IS the e2e wall (not approximately).
    assert abs(sum(phases.values()) - rec["e2e_s"]) < 1e-9
    assert rec["e2e_s"] > 0
    assert phases["handle_queue"] >= 0.002
    assert rec["tokens_in"] == 4
    assert rec["tokens_out"] == 8
    # TTFT covers everything before the first token; TPOT the decode rate.
    assert rec["ttft_s"] is not None and rec["ttft_s"] > 0
    assert rec["tpot_s"] is not None and rec["tpot_s"] > 0
    snap = observatory.profiler().snapshot()
    assert snap["app"] == "llm-test"
    assert snap["phase_sum_fraction"] == pytest.approx(1.0)
    assert snap["tenants"]["acme"]["tokens_out"] == 8


def test_non_engine_requests_collapse_to_exec(fresh_observatory):
    """Deployments that never touch the engine get {handle_queue,
    dispatch, exec} — still summing to e2e."""
    observatory.configure("plain", None)
    rec = _fabricated_request(tenant="z", e2e_parts=(0.002, 0.003))
    assert set(rec["phases"]) == {"handle_queue", "dispatch", "exec"}
    assert abs(sum(rec["phases"].values()) - rec["e2e_s"]) < 1e-9
    assert rec["phases"]["exec"] >= 0.004


def test_observatory_disabled_is_inert(fresh_observatory, monkeypatch):
    from ray_tpu._private.config import get_config

    monkeypatch.setattr(get_config(), "serve_observatory", False)
    assert observatory.make_wire_ctx("t") is None
    assert observatory.begin(None, "app") is None
    assert observatory.finish(None) is None


# -- SLO burn-rate math -------------------------------------------------

def test_burn_rate_unit_math():
    # 2 violations / 100 requests at objective 0.99 -> burn 2.0.
    assert observatory.burn_rate(98, 100, 0.99) == pytest.approx(2.0)
    # Clean window burns nothing; empty window burns nothing.
    assert observatory.burn_rate(50, 50, 0.99) == 0.0
    assert observatory.burn_rate(0, 0, 0.99) == 0.0
    # Exactly on budget: 1 violation / 100 at 0.99 -> 1.0.
    assert observatory.burn_rate(99, 100, 0.99) == pytest.approx(1.0)


def test_slo_accounting_on_synthetic_traffic(fresh_observatory):
    """Feed known-good and known-violating requests through the real
    scoring path; the tenant window must count them exactly and the
    burn rate must equal violation_rate / error_budget."""
    observatory.configure(
        "slo-app", SloConfig(e2e_ms=50.0, objective=0.9)
    )
    # 3 fast requests (~2ms each, pass) + 2 slow (~60ms, violate e2e).
    for _ in range(3):
        _fabricated_request(tenant="acme", e2e_parts=(0.001, 0.001))
    for _ in range(2):
        _fabricated_request(tenant="acme", e2e_parts=(0.03, 0.03))
    snap = observatory.profiler().snapshot()
    t = snap["tenants"]["acme"]
    assert t["requests"] == 5
    fast_w = str(snap["slo_windows_s"][0])
    counts = t["slo_windows"][fast_w]["e2e"]
    assert counts["total"] == 5
    assert counts["good"] == 3
    # burn = (2/5) / (1 - 0.9) = 4.0
    assert counts["burn"] == pytest.approx(4.0)
    # TTFT was never declared -> never scored.
    assert "ttft" not in t["slo_windows"][fast_w]


# -- head-of-line watchdog ----------------------------------------------

def test_hol_watchdog_attributes_chaos_prefill(fresh_observatory):
    """Chaos-stretch a prefill pass while another request is decoding:
    the watchdog must record the stall, count the decoding victim, and
    blame the prefilling request by id."""
    from ray_tpu._private import chaos
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=2, max_len=128)
    chaos.enable()
    try:
        long_h = eng.submit([3, 7, 11, 2], max_new_tokens=80)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            s = eng.stats()
            if s["active"] == 1 and s["prefilling"] == 0:
                break
            time.sleep(0.01)
        else:
            pytest.fail(f"request never reached decode: {eng.stats()}")
        # Inject: the NEXT prefill pass sleeps well past the threshold.
        chaos.delay_prefills(0.2, count=1)
        victim_steps = eng.stats()["steps"]
        blocker = eng.submit([5, 1, 8, 2, 9, 4], max_new_tokens=4)
        blocker.result(timeout=120)
        long_h.result(timeout=120)
        stats = eng.stats()
    finally:
        chaos.disable()
        chaos.clear()
        eng.shutdown()
    hol = stats["hol"]
    assert hol["blocked_slot_seconds"] >= 0.2
    assert hol["events"], "no HOL event recorded"
    ev = hol["events"][0]
    assert ev["prefill_s"] >= 0.2
    assert ev["victims"] == 1
    culprit_ids = [c["request_id"] for c in ev["culprits"]]
    assert blocker.request_id in culprit_ids
    assert stats["steps"] > victim_steps


# -- ServeSignals + CLI over a live cluster -----------------------------

def test_serve_signals_roundtrip_and_cli(serve_session):
    """Two replicas, tenant-tagged traffic, declared SLO: the controller
    must publish a merged ServeSignals doc to the GCS KV that rt serve
    can fetch (pure kv_get) and render."""
    from ray_tpu.scripts.scripts import _fetch_serve_signals, _render_serve

    @serve.deployment(num_replicas=2,
                      slo={"e2e_ms": 30_000.0, "objective": 0.99})
    def echo(x=0):
        return x * 2

    handle = serve.run(echo.bind(), name="echo")
    acme = handle.options(tenant="acme")
    globex = handle.options(tenant="globex")
    for i in range(6):
        assert rt.get(acme.remote(i), timeout=60) == i * 2
    for i in range(3):
        assert rt.get(globex.remote(i), timeout=60) == i * 2

    deadline = time.monotonic() + 30
    doc = None
    while time.monotonic() < deadline:
        doc = _fetch_serve_signals(None)
        app = (doc or {}).get("apps", {}).get("echo")
        if app and app.get("tenants", {}).get("acme", {}).get(
            "requests", 0
        ) >= 6 and app.get("tenants", {}).get("globex"):
            break
        time.sleep(0.5)
    else:
        pytest.fail(f"signals never converged: {doc}")

    app = doc["apps"]["echo"]
    assert doc["schema"] == observatory.SIGNALS_SCHEMA_VERSION
    assert len(app["replicas"]) == 2
    assert app["qps"] > 0
    # Phase vector explains the request wall (>= 95% acceptance gate).
    assert app["phase_sum_fraction"] >= 0.95
    assert app["tenants"]["acme"]["requests"] == 6
    assert app["tenants"]["globex"]["requests"] == 3
    windows = app["tenants"]["acme"]["slo_windows"]
    assert any(
        kinds.get("e2e", {}).get("total", 0) >= 6
        for kinds in windows.values()
    )
    # Nothing violated a 30s e2e budget.
    assert all(
        kinds["e2e"]["burn"] == 0.0
        for kinds in windows.values() if "e2e" in kinds
    )
    assert app["slo"]["e2e"] == 30_000.0

    # A second publish must bump seq (versioned snapshots).
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        doc2 = _fetch_serve_signals(None)
        if doc2 and doc2["seq"] > doc["seq"]:
            break
        time.sleep(0.5)
    else:
        pytest.fail("signals seq never advanced")

    # CLI rendering against the live doc.
    out = _render_serve(doc)
    assert "app echo" in out
    assert "tenant acme" in out
    assert "tenant globex" in out
    assert out.count("replica ") == 2
    assert "burn" in out
    # Empty-state rendering.
    assert "no serve signals" in _render_serve(None)


def test_phase_metrics_flow_through_handle(serve_session):
    """Handle-path wiring: requests dispatched via DeploymentHandle land
    in the replica's observatory ring with caller-side stamps."""
    @serve.deployment
    class Doubler:
        def __call__(self, x):
            return x * 2

    handle = serve.run(Doubler.bind(), name="doubler")
    for i in range(4):
        assert rt.get(handle.remote(i), timeout=60) == i * 2
    handle._refresh(force=True)
    replica = handle._shared["replicas"][0]
    snap = rt.get(replica.observatory_snapshot.remote(), timeout=30)
    assert snap["app"] == "doubler"
    assert snap["requests_total"] == 4
    assert snap["phase_sum_fraction"] >= 0.95
    # Caller-side stamps crossed the wire: handle_queue attributed.
    assert "handle_queue" in snap["phases"]
    assert snap["phases"]["exec"]["count"] == 4


# -- delivery stamps and where a stream's record closes ------------------

def _local_replica(target, *init_args, **init_kwargs):
    """The replica's class, constructed in this process (no runtime): its
    stream threads, buffers and observatory are what a worker would run."""
    from ray_tpu.serve.replica import ReplicaActor

    return ReplicaActor._cls(target, init_args, init_kwargs, app_name="s")


def _count_to(n, gap=0.0):
    for i in range(n):
        if gap:
            time.sleep(gap)
        yield i


def _wait_until(cond, what, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.005)
    pytest.fail(f"timed out waiting until {what}")


def _poll_to_the_end(replica, sid, sleep_s=0.0):
    got, start = [], 0
    while True:
        out = replica.next_chunks(sid, start, max_wait_s=5.0)
        got += out["chunks"]
        start += len(out["chunks"])
        if out["done"]:
            return got, out["error"]
        time.sleep(sleep_s)


def test_slow_poller_shows_as_delivery_lag(fresh_observatory):
    """An engine stream polled every 0.25 s: the tokens wait in the buffer
    and the record says so, measured from the engine's push stamps; the
    first poll was already waiting, so the first token does not. The
    record closes at the last pickup, so `stream` holds the tail and the
    six phases still sum to e2e."""
    from ray_tpu.serve.llm import LLMReplica

    replica = _local_replica(LLMReplica, _tiny_model, num_slots=2,
                             max_len=64)
    try:
        w = observatory.make_wire_ctx("acme")
        w["disp_t"] = time.time()
        sid = replica.start_stream("stream", ([3, 7, 11, 2], 24), {},
                                   obs_ctx=w)
        toks, err = _poll_to_the_end(replica, sid, sleep_s=0.25)
    finally:
        replica.callable.engine.shutdown()
    assert err is None and len(toks) == 24
    (rec,) = observatory.profiler().records()
    d = rec["deliver"]
    assert d["chunks"] == rec["tokens_out"] == 24
    assert d["lag_s_max"] >= 0.2
    assert d["first_lag_s"] < 0.1
    assert d["lag_s_max"] <= d["lag_s_sum"] <= 24 * d["lag_s_max"]
    assert set(rec["phases"]) == {"handle_queue", "dispatch",
                                  "engine_admission_wait", "prefill",
                                  "decode", "stream"}
    assert abs(sum(rec["phases"].values()) - rec["e2e_s"]) < 1e-9
    # The engine is far faster than the poller: most of the request's
    # life is the stream phase, which the old closing point read as ~0.
    assert rec["phases"]["stream"] >= 0.1


def test_plain_generator_is_stamped_at_append(fresh_observatory):
    """A generator the engine does not feed: chunks are stamped when they
    are appended, the record still closes at the last pickup."""
    replica = _local_replica(_count_to)
    sid = replica.start_stream("", (5,), {})
    _wait_until(lambda: replica.ongoing == 0, "the producer ended")
    assert replica.total_served == 1
    assert observatory.profiler().records() == [], (
        "the record closed when the producer ended, before any pickup")
    time.sleep(0.1)
    toks, err = _poll_to_the_end(replica, sid)
    assert (toks, err) == ([0, 1, 2, 3, 4], None)
    (rec,) = observatory.profiler().records()
    assert rec["deliver"]["chunks"] == 5
    assert rec["deliver"]["first_lag_s"] >= 0.1
    assert set(rec["phases"]) == {"handle_queue", "dispatch", "exec"}
    assert abs(sum(rec["phases"].values()) - rec["e2e_s"]) < 1e-9
    assert rec["phases"]["exec"] >= 0.1


def _close_by_last_pickup(replica):
    sid = replica.start_stream("", (4,), {})
    assert _poll_to_the_end(replica, sid) == ([0, 1, 2, 3], None)
    return sid


def _close_by_cancel_mid_stream(replica):
    sid = replica.start_stream("", (200, 0.01), {})
    assert replica.next_chunks(sid, 0, max_wait_s=5.0)["chunks"]
    assert replica.cancel_stream(sid)
    _wait_until(lambda: replica.ongoing == 0, "the producer noticed")
    return sid


def _close_by_cancel_after_the_end(replica):
    sid = replica.start_stream("", (4,), {})
    _wait_until(lambda: replica.ongoing == 0, "the producer ended")
    assert observatory.profiler().records() == []
    assert replica.cancel_stream(sid)
    return sid


def _close_by_collection_as_abandoned(replica):
    sid = replica.start_stream("", (4,), {})
    _wait_until(lambda: replica.ongoing == 0, "the producer ended")
    assert observatory.profiler().records() == []
    replica._gc_streams(idle_s=0.0)
    assert sid not in replica._streams
    return sid


@pytest.mark.parametrize("close", [
    _close_by_last_pickup, _close_by_cancel_mid_stream,
    _close_by_cancel_after_the_end, _close_by_collection_as_abandoned,
], ids=lambda f: f.__name__.lstrip("_"))
def test_stream_record_closes_exactly_once(fresh_observatory, close):
    """However a stream ends, its record is written once: later polls,
    cancels and collections find it closed."""
    replica = _local_replica(_count_to)
    sid = close(replica)
    _wait_until(lambda: len(observatory.profiler().records()) == 1,
                "the record closed")
    replica.cancel_stream(sid)
    replica.next_chunks(sid, 0, max_wait_s=0.01)
    replica._gc_streams(idle_s=0.0)
    time.sleep(0.05)
    (rec,) = observatory.profiler().records()
    assert abs(sum(rec["phases"].values()) - rec["e2e_s"]) < 1e-9
    assert replica.total_served == 1 and replica.ongoing == 0
