"""Serve survival plane: overload shed, deadline propagation, replica
death recovery, graceful drain, and controller failover.

The fault-tolerance mirror of test_serve.py: every scenario kills,
overloads, or expires something mid-flight and asserts the plane degrades
with a TYPED answer — 429-shaped ServeOverloadedError, 504-shaped
RequestCancelledError, streams that resume at the delivered-chunk offset,
replicas that drain before dying, handles that keep routing on cached
routes while the controller is down — instead of a generic failure.
"""

import json
import os
import signal
import time
import urllib.error
import urllib.request
from dataclasses import replace

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu import serve
from ray_tpu._private import chaos
from ray_tpu._private.config import get_config
from ray_tpu.exceptions import (
    RequestCancelledError,
    ServeOverloadedError,
    TaskError,
)
from ray_tpu.serve import context as request_context


@pytest.fixture
def serve_session(rt_start):
    yield rt_start
    serve.shutdown()


@pytest.fixture
def cfg_override():
    """Mutate the config singleton for this (test) process; restore on
    exit. Worker processes are unaffected — use for handle/engine-side
    knobs only."""
    cfg = get_config()
    saved = {}

    def override(**kw):
        for k, v in kw.items():
            if k not in saved:
                saved[k] = getattr(cfg, k)
            setattr(cfg, k, v)

    yield override
    for k, v in saved.items():
        setattr(cfg, k, v)


def _tiny_model():
    import jax

    from ray_tpu.models import configs, init_params

    cfg = replace(configs.tiny, dtype=np.float32)
    return init_params(jax.random.PRNGKey(0), cfg), cfg


# -- admission control + deadline, at the engine ------------------------

def test_engine_admission_shed_wfq_and_deadline(cfg_override, monkeypatch):
    """One engine, three survival behaviors: (1) the bounded WFQ
    admission queue sheds past serve_max_queued_per_engine with a typed,
    Retry-After-carrying error; (2) per-tenant queues exist (WFQ
    accounting visible in stats); (3) deadlines reach the engine — a
    pre-expired submit is refused, an in-flight request whose deadline
    passes mid-decode is cancelled and its slot evicted."""
    from ray_tpu.serve.llm import ContinuousBatchingEngine

    cfg_override(serve_max_queued_per_engine=3)
    params, cfg = _tiny_model()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=1, max_len=512)
    handles = []
    try:
        # Occupy the single slot so subsequent submits stay queued.
        with request_context.bind(request_context.RequestMeta(tenant="a")):
            h0 = eng.submit([3, 7, 11], max_new_tokens=256)
        handles.append(h0)
        deadline = time.time() + 60
        while eng.stats()["active"] < 1:
            assert time.time() < deadline, "slot was never granted"
            time.sleep(0.01)
        # Fill the admission queue to its bound, split across tenants.
        for tenant in ("a", "b", "a"):
            with request_context.bind(
                    request_context.RequestMeta(tenant=tenant)):
                handles.append(eng.submit([1, 2], max_new_tokens=1))
        st = eng.stats()
        assert st["waiting"] == 3
        assert set(st["waiting_tenants"]) == {"a", "b"}
        # Past the bound: typed shed, never enqueued.
        with request_context.bind(request_context.RequestMeta(tenant="c")):
            with pytest.raises(ServeOverloadedError) as ei:
                eng.submit([1, 2], max_new_tokens=1)
        assert ei.value.retry_after_s > 0
        assert eng.stats()["shed_total"] >= 1
        # Pre-expired deadline: refused at submit, not executed.
        with request_context.bind(
                request_context.RequestMeta(deadline_ts=time.time() - 1.0)):
            with pytest.raises(RequestCancelledError):
                eng.submit([1, 2], max_new_tokens=1)
        # In-flight expiry: a chaos prefill stretch burns the request's
        # budget inside the engine, so the post-stretch deadline check
        # cancels it and evicts the slot — deterministically, regardless
        # of how fast the tiny model decodes.
        for h in handles:
            h.cancel()
        deadline = time.time() + 60
        while eng.stats()["active"] > 0:
            assert time.time() < deadline, "cancelled slots never evicted"
            time.sleep(0.01)
        monkeypatch.setenv("RT_CHAOS", "1")
        chaos.delay_prefills(0.8, count=1)
        with request_context.bind(
                request_context.RequestMeta(deadline_ts=time.time() + 0.3)):
            h_exp = eng.submit([5, 9], max_new_tokens=8)
        with pytest.raises(RequestCancelledError):
            h_exp.result(timeout=60)
        assert eng.stats()["deadline_expired"] >= 1
    finally:
        chaos.clear()
        for h in handles:
            if not h._done:
                h.cancel()
        eng.shutdown()


def test_engine_queue_bound_of_its_own_and_the_replica_admits_up_to_it():
    """An engine built with `max_queued` sheds at ITS bound, whatever the
    process-wide default says, and the replica around an LLM replica
    admits up to what the engine takes (slots + its queue) where the
    deployment's own bound is lower: 128 callers on 64 slots wait in the
    engine's queue, and are not shed by the router at 64 + 32."""
    from ray_tpu.serve.llm import LLMReplica
    from ray_tpu.serve.replica import ReplicaActor

    params, cfg = _tiny_model()
    rep = ReplicaActor._cls(
        LLMReplica, (lambda: (params, cfg),),
        {"num_slots": 1, "max_len": 512, "max_queued": 2},
        app_name="bound", max_ongoing=1)
    eng, handles = rep.callable.engine, []
    try:
        assert get_config().serve_max_queued_per_engine > 2
        assert rep.callable.admission_bound == 3
        handles.append(eng.submit([3, 7, 11], max_new_tokens=256))
        deadline = time.time() + 60
        while eng.stats()["active"] < 1:
            assert time.time() < deadline, "slot was never granted"
            time.sleep(0.01)
        handles += [eng.submit([1, 2], max_new_tokens=1) for _ in range(2)]
        with pytest.raises(ServeOverloadedError, match="2 waiting >= 2"):
            eng.submit([1, 2], max_new_tokens=1)
        # The replica's gate: max_ongoing 1 + the default 32 queued, as it
        # was, since the engine's own 3 is lower; with a longer queue the
        # engine's bound is the gate.
        meta = request_context.RequestMeta()
        rep.ongoing = 1 + get_config().serve_max_queued_per_replica
        with pytest.raises(ServeOverloadedError):
            rep._admit(meta)
        eng.max_queued = 64
        rep._admit(meta)
        rep.ongoing = 1 + 64
        with pytest.raises(ServeOverloadedError):
            rep._admit(meta)
    finally:
        for h in handles:
            h.cancel()
        eng.shutdown()


# -- admission control at the handle ------------------------------------

def test_handle_shed_is_synchronous_and_typed(serve_session, cfg_override):
    """When every replica is past max_ongoing + queue bound by this
    handle's own in-flight counts, .remote() sheds synchronously (zero
    RPCs) with ServeOverloadedError; the already-admitted requests still
    complete."""
    cfg_override(serve_max_queued_per_replica=1)

    @serve.deployment(max_ongoing_requests=1)
    class Slow:
        def __call__(self, s):
            time.sleep(s)
            return s

    h = serve.run(Slow.bind())
    admitted = [h.remote(1.0), h.remote(1.0)]  # bound = 1 ongoing + 1 queued
    t0 = time.perf_counter()
    with pytest.raises(ServeOverloadedError) as ei:
        h.remote(1.0)
    shed_ms = (time.perf_counter() - t0) * 1e3
    assert ei.value.retry_after_s > 0
    assert shed_ms < 50, f"shed decision took {shed_ms:.1f} ms"
    assert [r.result(timeout=60) for r in admitted] == [1.0, 1.0]


def test_handle_deadline_bounds_result(serve_session):
    """options(deadline_s=...) propagates an absolute deadline;
    .result() without an explicit timeout stops at the deadline with the
    typed cancellation instead of the fixed 60 s wait."""

    @serve.deployment
    def napper(s):
        time.sleep(s)
        return s

    h = serve.run(napper.bind())
    assert h.remote(0.01).result(timeout=60) == 0.01  # warm route cache
    r = h.options(deadline_s=0.3).remote(10.0)
    t0 = time.monotonic()
    with pytest.raises(RequestCancelledError):
        r.result()
    assert time.monotonic() - t0 < 5.0


# -- replica death recovery ---------------------------------------------

def test_stream_resumes_at_offset_after_replica_death(serve_session):
    """Kill the replica serving a stream mid-flight: the handle restarts
    the request on another replica and resumes AT THE CHUNK OFFSET
    already delivered — the client sees every value exactly once."""

    @serve.deployment(num_replicas=2)
    class Gen:
        def __call__(self, n):
            yield os.getpid()  # chunk 0 identifies the serving replica
            for i in range(n):
                time.sleep(0.05)
                yield i

    h = serve.run(Gen.bind())
    it = iter(h.options(stream=True).remote(12))
    pid = next(it)
    out = [next(it) for _ in range(3)]  # deliver chunks 1..3 -> [0, 1, 2]
    os.kill(pid, signal.SIGKILL)
    out.extend(it)  # resume replays deterministically, skips 4 delivered
    assert out == list(range(12))


def test_unary_redispatch_after_replica_kill(serve_session, monkeypatch):
    """chaos.kill_replica murders one of two replicas while unary
    requests are in flight: every request still resolves (redispatch to
    the surviving replica under a stable idempotency key) — zero lost."""
    monkeypatch.setenv("RT_CHAOS", "1")

    @serve.deployment(num_replicas=2)
    class Echo:
        def __call__(self, x):
            time.sleep(0.4)
            return x * 2

    h = serve.run(Echo.bind())
    rs = [h.remote(i) for i in range(6)]
    time.sleep(0.15)  # let dispatches land on both replicas
    chaos.kill_replica("Echo", 0)
    assert sorted(r.result(timeout=90) for r in rs) == [0, 2, 4, 6, 8, 10]


# -- graceful drain ------------------------------------------------------

def test_drain_completes_inflight_then_sheds(rt_start):
    """drain() stops new admissions, waits for in-flight work, and
    reports the drain duration; the in-flight request completes normally
    and post-drain requests are refused with ReplicaDrainingError."""
    from ray_tpu.serve.replica import ReplicaActor

    def napper(s):
        time.sleep(s)
        return s

    rep = ReplicaActor.options(max_concurrency=4).remote(napper, (), {})
    ref = rep.handle_request.remote("__call__", (0.8,), {})
    time.sleep(0.2)  # the request is admitted and executing
    d = rt.get(rep.drain.remote(10.0), timeout=30)
    assert d["drained"] is True and d["remaining"] == 0
    assert d["duration_s"] >= 0.3  # it actually waited for the request
    assert rt.get(ref, timeout=10) == 0.8  # in-flight work was NOT lost
    with pytest.raises(TaskError) as ei:
        rt.get(rep.handle_request.remote("__call__", (0.1,), {}), timeout=10)
    assert ei.value.cause_cls_name == "ReplicaDrainingError"
    rt.kill(rep)


# -- controller failover -------------------------------------------------

def test_traffic_survives_controller_death(serve_session, monkeypatch):
    """Kill the controller under traffic: handles keep routing on cached
    routes while it is down, and the restarted controller restores its
    checkpoint so FRESH handles (no cache) route again."""
    monkeypatch.setenv("RT_CHAOS", "1")

    @serve.deployment
    def echo(x):
        return x + 1

    h = serve.run(echo.bind())
    assert h.remote(1).result(timeout=60) == 2  # populate the route cache
    chaos.drop_controller(restart=True)
    for i in range(5):  # cached routes carry traffic through the outage
        assert h.remote(i).result(timeout=60) == i + 1
    deadline = time.time() + 60
    while True:  # the restarted controller restores from its checkpoint
        try:
            if "echo" in serve.status():
                break
        except Exception:  # noqa: BLE001 — restart races are the test
            pass
        assert time.time() < deadline, "controller never came back"
        time.sleep(0.2)
    h2 = serve.get_app_handle("echo")
    assert h2.remote(7).result(timeout=60) == 8


# -- proxy error mapping -------------------------------------------------

def _post(addr, app, body, headers=None):
    req = urllib.request.Request(
        f"{addr}/{app}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


def test_proxy_maps_typed_errors_to_status_codes(serve_session):
    """429 + Retry-After for shed, 504 for deadline expiry (enforced by
    the proxy's bounded await via the serve_deadline_ms header), 200 for
    success — never a generic 500 for a typed failure."""

    @serve.deployment
    def overloaded():
        raise ServeOverloadedError("busy", retry_after_s=3.0)

    @serve.deployment
    def napper(s=0.0):
        time.sleep(s)
        return s

    serve.run(overloaded.bind())
    serve.run(napper.bind())
    addr = serve.start_http_proxy(port=0)

    code, hdrs, body = _post(addr, "overloaded", {})
    assert code == 429
    assert body["kind"] == "shed"
    assert int(hdrs["Retry-After"]) >= 3

    code, _, body = _post(addr, "napper", {"s": 5.0},
                          {"serve_deadline_ms": "200"})
    assert code == 504
    assert body["kind"] == "deadline"

    code, _, body = _post(addr, "napper", {"s": 0.0})
    assert code == 200
    assert body["result"] == 0.0


def test_concurrent_reconciles_start_each_replica_once(monkeypatch):
    """deploy() reconciles on an actor-call thread while the controller's
    loop reconciles on its own: two passes that both read "0 of 1
    replicas" each started one, and on one chip the second stayed PENDING
    for ever with serve.run waiting for it (about one deploy in 16 on the
    chip). The passes are serialized: one app, target 1, two passes at
    once, one replica started and recorded."""
    import threading
    from types import SimpleNamespace

    from ray_tpu.serve import controller as controller_mod

    started = []

    class SlowReplicaActor:
        """`ReplicaActor.options(...).remote(...)`, slow enough that the
        second pass reads the count before the first records its own."""

        @classmethod
        def options(cls, **_):
            return cls

        @classmethod
        def remote(cls, *_):
            time.sleep(0.2)
            started.append(object())
            return started[-1]

    monkeypatch.setattr(controller_mod, "ReplicaActor", SlowReplicaActor)
    ctl = object.__new__(controller_mod.ServeController._cls)
    ctl._lock = threading.Lock()
    ctl._reconcile_lock = threading.Lock()
    ctl.apps = {"app": {
        "deployment": SimpleNamespace(
            ray_actor_options={}, max_ongoing_requests=8,
            func_or_class=object, user_config=None),
        "init_args": (), "init_kwargs": {}, "replicas": [], "version": 0,
        "target": 1,
    }}
    for name in ("_publish_routes", "_checkpoint", "_drain_then_kill"):
        monkeypatch.setattr(ctl, name, lambda *a, **k: None, raising=False)
    barrier = threading.Barrier(2)

    def one_pass():
        barrier.wait()
        ctl._reconcile_once("app")

    threads = [threading.Thread(target=one_pass) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert len(started) == 1
    assert ctl.apps["app"]["replicas"] == started
