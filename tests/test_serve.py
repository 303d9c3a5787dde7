"""Serve tests: deployments, handles, composition, scaling, HTTP.

Reference model: python/ray/serve/tests (handle path + real HTTP against
local proxies).
"""

import time

import pytest

import ray_tpu as rt
from ray_tpu import serve


@pytest.fixture
def serve_session(rt_start):
    yield rt_start
    serve.shutdown()


def test_function_deployment(serve_session):
    @serve.deployment
    def echo(x):
        return {"echo": x}

    handle = serve.run(echo.bind())
    assert rt.get(handle.remote("hi"), timeout=60) == {"echo": "hi"}


def test_class_deployment_with_state(serve_session):
    @serve.deployment
    class Greeter:
        def __init__(self, greeting):
            self.greeting = greeting
            self.count = 0

        def __call__(self, name):
            self.count += 1
            return f"{self.greeting}, {name}!"

        def stats(self):
            return self.count

    handle = serve.run(Greeter.bind("Hello"))
    assert rt.get(handle.remote("TPU"), timeout=60) == "Hello, TPU!"
    assert rt.get(handle.options(method_name="stats").remote(), timeout=60) >= 1


def test_multiple_replicas_balance(serve_session):
    @serve.deployment(num_replicas=2)
    class Worker:
        def __init__(self):
            import os

            self.pid = os.getpid()

        def __call__(self):
            return self.pid

    handle = serve.run(Worker.bind(), name="workers")
    pids = {rt.get(handle.remote(), timeout=60) for _ in range(12)}
    assert len(pids) == 2  # both replicas served


def test_composition(serve_session):
    """Model composition via handles (reference: DeploymentHandle
    composition, serve/handle.py)."""

    @serve.deployment
    class Preprocess:
        def __call__(self, x):
            return x * 2

    @serve.deployment
    class Pipeline:
        def __init__(self, pre_app_name):
            from ray_tpu.serve import get_app_handle

            self.pre = get_app_handle(pre_app_name)

        def __call__(self, x):
            doubled = rt.get(self.pre.remote(x), timeout=30)
            return doubled + 1

    serve.run(Preprocess.bind(), name="pre")
    handle = serve.run(Pipeline.bind("pre"), name="pipe")
    assert rt.get(handle.remote(5), timeout=60) == 11


def test_status_and_delete(serve_session):
    @serve.deployment
    def f():
        return 1

    serve.run(f.bind(), name="app1")
    st = serve.status()
    assert "app1" in st
    assert st["app1"]["running_replicas"] == 1
    serve.delete("app1")
    st = serve.status()
    assert "app1" not in st


def test_redeploy_replaces(serve_session):
    @serve.deployment
    def v1():
        return "v1"

    @serve.deployment
    def v2():
        return "v2"

    h = serve.run(v1.bind(), name="app")
    assert rt.get(h.remote(), timeout=60) == "v1"
    h2 = serve.run(v2.bind(), name="app")
    time.sleep(0.2)
    assert rt.get(h2.remote(), timeout=60) == "v2"


def test_http_proxy(serve_session):
    @serve.deployment
    def adder(a, b):
        return a + b

    serve.run(adder.bind(), name="adder")
    addr = serve.start_http_proxy(port=18123)

    import json
    import urllib.request

    req = urllib.request.Request(
        addr + "/adder",
        data=json.dumps({"a": 2, "b": 3}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        body = json.loads(resp.read())
    assert body == {"result": 5}

    # Health endpoint
    with urllib.request.urlopen(addr + "/-/healthz", timeout=30) as resp:
        assert json.loads(resp.read())["status"] == "ok"


def test_serve_timeout_knobs_registered_and_env_overridable(monkeypatch):
    """The serve data/control-plane timeouts ride the RT_* config registry
    (reference: RAY_CONFIG env-overridable entries, ray_config_def.h)."""
    import ray_tpu._private.config as config_mod

    for name, default in (
        ("serve_rpc_timeout_s", 60.0),
        ("serve_ready_timeout_s", 30.0),
        ("serve_deploy_timeout_s", 300.0),
        ("serve_result_timeout_s", 120.0),
        ("serve_admin_timeout_s", 60.0),
        ("serve_probe_timeout_s", 5.0),
        ("serve_health_wait_s", 10.0),
        ("object_directory_rpc_timeout_s", 30.0),
    ):
        assert getattr(config_mod.Config(), name) == default
    monkeypatch.setenv("RT_SERVE_RPC_TIMEOUT_S", "7.5")
    assert config_mod.Config().serve_rpc_timeout_s == 7.5


def test_bind_composition_injects_handles(serve_session):
    """The reference composition idiom: nested .bind() applications
    deploy automatically and arrive as DeploymentHandles
    (serve.run(Pipeline.bind(Preprocess.bind())))."""

    @serve.deployment
    class Embed:
        def __call__(self, x):
            return x * 10

    @serve.deployment
    class Rank:
        def __call__(self, x):
            return x + 1

    @serve.deployment
    class Pipeline:
        def __init__(self, embed, rank):
            self.embed = embed  # DeploymentHandles, injected
            self.rank = rank

        def __call__(self, x):
            e = self.embed.remote(x).result(timeout=30)
            return self.rank.remote(e).result(timeout=30)

    handle = serve.run(
        Pipeline.bind(Embed.bind(), Rank.bind()), name="pipe2"
    )
    assert rt.get(handle.remote(4), timeout=60) == 41
    # The nested apps are live, individually addressable deployments.
    st = serve.status()
    assert "Embed" in st and "Rank" in st


def test_bind_composition_nested_containers(serve_session):
    """Bound apps inside lists/dicts resolve to handles too (the
    reference's DAG scanner traverses containers)."""

    @serve.deployment
    class M1:
        def __call__(self, x):
            return x + 1

    @serve.deployment
    class M2:
        def __call__(self, x):
            return x + 2

    @serve.deployment
    class Ensemble:
        def __init__(self, models):
            self.models = models

        def __call__(self, x):
            return sum(
                m.remote(x).result(timeout=30) for m in self.models
            )

    handle = serve.run(Ensemble.bind([M1.bind(), M2.bind()]), name="ens")
    assert rt.get(handle.remote(10), timeout=60) == 23  # 11 + 12


def test_redeploy_with_array_init_args(serve_session):
    """Redeploying an app bound with numpy args must not crash the
    user_config-comparison path (regression: ambiguous array ==)."""
    import numpy as np

    @serve.deployment
    class Weighted:
        def __init__(self, w):
            self.w = w

        def __call__(self, x):
            return float((self.w * x).sum())

    serve.run(Weighted.bind(np.ones(4)), name="warr")
    h = serve.run(Weighted.bind(np.ones(4) * 2), name="warr")
    assert rt.get(h.remote(3), timeout=60) == 24.0


def test_duplicate_bind_names_uniquified(serve_session):
    """Two bound instances of the same deployment class in one graph
    must become two deployments (the reference's DAG builder appends
    _1/_2 on name collisions) — not the second silently replacing the
    first so both handles route to one instance."""

    @serve.deployment
    class Scale:
        def __init__(self, w):
            self.w = w

        def __call__(self, x):
            return x * self.w

    @serve.deployment
    class Ensemble:
        def __init__(self, models):
            self.models = models

        def __call__(self, x):
            return [m.remote(x).result(timeout=30) for m in self.models]

    handle = serve.run(
        Ensemble.bind([Scale.bind(3), Scale.bind(5)]), name="ens_dup"
    )
    assert rt.get(handle.remote(2), timeout=60) == [6, 10]
    st = serve.status()
    assert "Scale" in st and "Scale_1" in st


def test_noop_redeploy_keeps_replicas(serve_session):
    """Redeploying with nothing changed must not restart healthy
    replicas (reference: same-version redeploys are no-ops)."""

    @serve.deployment
    class P:
        def __init__(self):
            import os

            self.pid = os.getpid()

        def __call__(self):
            return self.pid

    h = serve.run(P.bind(), name="noop")
    pid1 = rt.get(h.remote(), timeout=60)
    h2 = serve.run(P.bind(), name="noop")
    pid2 = rt.get(h2.remote(), timeout=60)
    assert pid1 == pid2


def test_buried_application_raises(serve_session):
    """An Application hidden where resolution cannot inject a handle
    (an object attribute) fails fast with a clear error instead of
    shipping a raw graph node to the replica."""

    @serve.deployment
    class Inner:
        def __call__(self, x):
            return x

    class Holder:
        def __init__(self, app):
            self.app = app

    @serve.deployment
    class Outer:
        def __init__(self, holder):
            self.holder = holder

    with pytest.raises(Exception, match="Application"):
        serve.run(Outer.bind(Holder(Inner.bind())), name="buried")


def test_shared_application_object_deploys_once(serve_session):
    """The same bound Application OBJECT used twice in a graph is one
    shared deployment (a diamond dependency), not two copies — only
    distinct .bind() calls get uniquified."""

    @serve.deployment
    class Counter:
        def __init__(self):
            self.n = 0

        def __call__(self):
            self.n += 1
            return self.n

    @serve.deployment
    class Pair:
        def __init__(self, models):
            self.models = models

        def __call__(self):
            return [m.remote().result(timeout=30) for m in self.models]

    shared = Counter.bind()
    handle = serve.run(Pair.bind([shared, shared]), name="pair_shared")
    # Both handles hit the SAME replica: counts are 1 then 2.
    assert rt.get(handle.remote(), timeout=60) == [1, 2]
    st = serve.status()
    assert "Counter" in st and "Counter_1" not in st


def test_replica_that_constructs_for_a_while_is_waited_for_not_replaced(
        monkeypatch, tmp_path):
    """A replica that loads a model constructs for minutes. serve.run
    returns when it has answered once, and the controller's health pass
    leaves a replica alone while it is still constructing: it used to
    count three unanswered probes and replace it, for ever."""
    # Probes give up fast, so three of them fit inside the constructor.
    monkeypatch.setenv("RT_SERVE_HEALTH_WAIT_S", "0.2")
    births = tmp_path / "births"

    @serve.deployment
    class Slow:
        def __init__(self, log):
            with open(log, "a") as f:
                f.write("born\n")
            time.sleep(4.0)

        def __call__(self):
            return "ready"

    rt.init(num_cpus=4)
    try:
        t0 = time.monotonic()
        handle = serve.run(Slow.bind(str(births)), name="slow")
        assert time.monotonic() - t0 >= 4.0
        t0 = time.monotonic()
        assert rt.get(handle.remote(), timeout=30) == "ready"
        assert time.monotonic() - t0 < 2.0
        assert births.read_text() == "born\n"
    finally:
        serve.shutdown()
        rt.shutdown()
