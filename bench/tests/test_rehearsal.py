"""Every cell's command end to end on the CPU preset: exit 0, the result
line last with exactly the contract's keys, no process of the run left,
and again back to back as the driver runs a cell."""

import json
import os
import subprocess
import sys

import pytest

import procs
import spec

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}


def cells():
    with open(os.path.join(spec.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(name, seed, trace, cwd=spec.REPO, seconds=3):
    done = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace),
         "--platform", "cpu"],
        cwd=cwd, capture_output=True, text=True, timeout=600)
    return done


def check_line(done, bench, name, trace):
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == LINE_KEYS | ({"breakdown"} if trace else set())
    # What decided `correct`, each number beside its limit: last in the
    # line and the last lines of standard error.
    assert list(line)[-1] == "compared" and line["compared"]
    assert all(set(c) == {"value", "limit", "holds"} and c["holds"]
               for c in line["compared"].values())
    last = done.stderr.strip().splitlines()[-len(line["compared"]):]
    assert [row.split(":")[0] for row in last] == [
        f"compared {name}" for name in line["compared"]]
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"] for m in bench[kind]
               if name in m.get("workloads", [name])}
    assert set(line["metrics"]) <= allowed and line["metrics"]
    if not trace:
        assert set(line["metrics"]) == allowed
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())
    assert procs.tagged() == [], "a process of the run is still alive"
    return line


@pytest.mark.parametrize("name", [w["name"] for w in cells()["workloads"]])
def test_cell_rehearsal_back_to_back(name):
    bench = cells()
    first = check_line(run_cell(name, 2**31 + 11, 0), bench, name, 0)
    second = check_line(run_cell(name, 12, 1), bench, name, 1)
    assert "busy_s" in second["device"] and "window_s" in second["device"]
    assert first["metrics"]["setup_s"]["value"] > 0


def test_fewer_chips_than_the_cell_needs_is_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", RT_TPU_CHIPS="0")
    done = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train-d4-8x1024",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.REPO, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert "nothing was run" in done.stdout
    assert not done.stdout.strip().splitlines()[-1].startswith("{")
    assert procs.tagged() == []
