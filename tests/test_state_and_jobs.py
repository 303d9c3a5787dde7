"""State API, timeline, CLI, and job submission tests.

Reference analogs: python/ray/tests/test_state_api*.py,
dashboard/modules/job/tests, and the state CLI (util/state/state_cli.py).
"""

import json
import subprocess
import sys
import time

import pytest

import ray_tpu as rt
from ray_tpu._private import worker as worker_mod


def _gcs_address():
    node = worker_mod._global_node
    return node.gcs_address


def _wait_for(fn, timeout=10.0, poll=0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = fn()
        if out:
            return out
        time.sleep(poll)
    raise TimeoutError("condition not met")


def test_state_api_lists(rt_start):
    from ray_tpu.util import state as state_api

    @rt.remote
    def add(a, b):
        return a + b

    @rt.remote
    class Holder:
        def get(self):
            return 1

    rt.get([add.remote(i, i) for i in range(3)])
    h = Holder.remote()
    assert rt.get(h.get.remote()) == 1
    import numpy as np

    # Hold the ref: owner-side reference GC frees dropped objects now.
    big_ref = rt.put(np.ones(300_000))  # big enough for the shared store
    assert big_ref is not None

    nodes = state_api.list_nodes()
    assert len(nodes) == 1 and nodes[0]["state"] == "ALIVE"
    assert nodes[0]["resources_total"]["CPU"] == 4

    # Task events flush on the heartbeat (0.5s period).
    tasks = _wait_for(
        lambda: [
            t
            for t in state_api.list_tasks()
            if t["name"].endswith("add") and t.get("state") == "FINISHED"
        ]
    )
    assert all(t["type"] == "NORMAL_TASK" for t in tasks)

    actor_tasks = _wait_for(
        lambda: [t for t in state_api.list_tasks() if t["name"] == "get"]
    )
    assert actor_tasks[0]["type"] == "ACTOR_TASK"

    actors = state_api.list_actors()
    assert len(actors) == 1 and actors[0]["class_name"] == "Holder"

    objs = state_api.list_objects()
    assert any(o["size"] > 1_000_000 for o in objs)

    summary = state_api.summarize_tasks()
    add_key = next(k for k in summary if k.endswith("add"))
    assert summary[add_key]["FINISHED"] == 3

    workers = state_api.list_workers()
    assert len(workers) >= 1

    trace = state_api.get_timeline()
    ev = next(ev for ev in trace if ev["name"].endswith("add"))
    assert ev["ph"] == "X" and ev["dur"] >= 0


def test_failed_task_event(rt_start):
    from ray_tpu.util.state import list_tasks

    @rt.remote(max_retries=0)
    def broken():
        raise RuntimeError("nope")

    with pytest.raises(rt.exceptions.TaskError):
        rt.get(broken.remote())
    tasks = _wait_for(
        lambda: [
            t
            for t in list_tasks()
            if t["name"].endswith("broken") and t.get("state") == "FAILED"
        ]
    )
    assert tasks


def test_job_submission_lifecycle(rt_start):
    from ray_tpu.job import JobSubmissionClient

    client = JobSubmissionClient(_gcs_address())
    try:
        sid = client.submit_job(
            entrypoint=f"{sys.executable} -c \"print('hello from job')\""
        )
        state = client.wait_until_finished(sid, timeout=60)
        assert state == "SUCCEEDED"
        assert "hello from job" in client.get_job_logs(sid)
        info = client.get_job_info(sid)
        assert info["entrypoint"].endswith('"print(\'hello from job\')"')
        assert any(j.get("submission_id") == sid for j in client.list_jobs())
    finally:
        client.close()


def test_job_stop(rt_start):
    from ray_tpu.job import JobSubmissionClient

    client = JobSubmissionClient(_gcs_address())
    try:
        sid = client.submit_job(
            entrypoint=f"{sys.executable} -c \"import time; time.sleep(60)\""
        )
        _wait_for(lambda: client.get_job_status(sid) == "RUNNING", timeout=30)
        assert client.stop_job(sid)
        state = client.wait_until_finished(sid, timeout=30)
        assert state == "STOPPED"
    finally:
        client.close()


def test_job_failure_reported(rt_start):
    from ray_tpu.job import JobSubmissionClient

    client = JobSubmissionClient(_gcs_address())
    try:
        sid = client.submit_job(
            entrypoint=f"{sys.executable} -c \"raise SystemExit(3)\""
        )
        assert client.wait_until_finished(sid, timeout=60) == "FAILED"
    finally:
        client.close()


def test_cli_status_list_timeline(rt_start, tmp_path):
    @rt.remote
    def noop():
        return 0

    rt.get(noop.remote())
    time.sleep(1.2)  # let events flush

    addr = _gcs_address()
    env = {"PYTHONPATH": ":".join(sys.path)}
    import os

    env.update(os.environ)

    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu", "status", "--address", addr],
        capture_output=True, text=True, env=env, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert "nodes alive" in out.stdout and "CPU" in out.stdout

    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu", "list", "nodes", "--address", addr],
        capture_output=True, text=True, env=env, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout)[0]["state"] == "ALIVE"

    tl = tmp_path / "trace.json"
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu", "timeline", "-o", str(tl),
         "--address", addr],
        capture_output=True, text=True, env=env, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    trace = json.loads(tl.read_text())
    assert any(ev["name"].endswith("noop") for ev in trace)

def test_user_profiling_spans_in_timeline(rt_start):
    """rt.util.profiling.profile spans appear in the chrome-trace timeline
    (reference: ray.profiling.profile, _private/profiling.py:84)."""
    import time as _time

    from ray_tpu.util import profiling
    from ray_tpu.util import state as state_api

    @rt.remote
    def work():
        from ray_tpu.util import profiling as prof

        with prof.profile("inner-phase"):
            _time.sleep(0.05)
        prof.flush()
        return 1

    with profiling.profile("driver-phase", extra={"k": "v"}):
        assert rt.get(work.remote(), timeout=60) == 1
    profiling.flush()

    deadline = _time.monotonic() + 15
    names = set()
    while _time.monotonic() < deadline:
        trace = state_api.get_timeline()
        names = {e["name"] for e in trace if e["cat"] == "user_span"}
        if {"driver-phase", "inner-phase"} <= names:
            break
        _time.sleep(0.3)
    assert {"driver-phase", "inner-phase"} <= names, names


def test_worker_stacks(rt_start):
    """`rt stack` backend: live thread stacks from every worker
    (reference: on-demand py-spy dumps via the reporter agent)."""
    import time as _time

    from ray_tpu.util.state import get_worker_stacks

    @rt.remote
    class Sleeper:
        def busy(self):
            import time

            time.sleep(1.5)
            return 1

    s = Sleeper.remote()
    ref = s.busy.remote()  # in flight while we sample
    _time.sleep(0.5)
    stacks = get_worker_stacks()
    workers = [w for w in stacks if "threads" in w]
    assert workers, stacks
    blob = "\n".join(
        t["stack"] for w in workers for t in w["threads"]
    )
    # The sleeping actor method's frame is visible in some worker.
    assert "busy" in blob
    assert all("pid" in w for w in workers)
    rt.get(ref, timeout=120)


def test_list_and_get_logs(rt_start):
    """Per-node log listing + tail through the state API (reference:
    `ray logs` via the per-node log agents)."""
    import os
    import tempfile

    from ray_tpu.util.state import get_log, list_logs

    logdir = os.path.join(tempfile.gettempdir(), "ray_tpu", "logs")
    os.makedirs(logdir, exist_ok=True)
    marker = os.path.join(logdir, "rt-logs-test.log")
    with open(marker, "w") as f:
        f.write("alpha\n" * 100 + "OMEGA-LINE\n")
    try:
        entries = list_logs()
        names = {e.get("name") for e in entries}
        assert "rt-logs-test.log" in names
        tail = get_log("rt-logs-test.log", tail_bytes=32)
        assert tail.endswith("OMEGA-LINE\n")
        assert len(tail) <= 32
        import pytest as _p

        with _p.raises(FileNotFoundError):
            get_log("no-such-file.log")
        with _p.raises(FileNotFoundError):
            get_log("../../../etc/passwd")  # path traversal sanitized
    finally:
        os.remove(marker)
