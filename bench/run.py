#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this new process:

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

wait for a free chip, start the runtime, deploy or start the gang, warm
up, measure for `--seconds`, check correctness outside the window, tear
down, wait until every process of the run has ended, and print the result
line last. Exit 0 whenever there is a result: failed, shed and late
requests are in `failed`, a comparison that does not hold is
`correct: false`. A non-zero exit means no result could be produced (no
chip, set-up failed, a worker died); the traceback and the tail of the
workers' log are printed first. See bench/README.md.

This process never initialises a JAX backend: only the worker granted the
chips does, and the device in the line is what that worker reported.
"""

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
sys.path[:0] = [p for p in (BENCH, REPO) if p not in sys.path]

# How long a run waits, at its start and at its end, for a free chip: no
# process holding a device file and every device file opening. A replica
# holding 13 GB exits in a second or two once signalled, and the kernel
# took 2-5 s to give back the chip of a run that had gone and 14-22 s to
# give back four (PERF.md section 6, PR 49); past this something is wrong
# and the run says what.
CHIP_FREE_LIMIT_S = 60.0
# serve.run's own deploy limit is 300 s. A cold 36-layer deploy with a
# 24 x 1024 cache took 87 s and a warm one 23 s (my chip runs, PR 23); the
# first run of a cell in a checkout may take 1200 s in all, so half that.
DEPLOY_LIMIT_S = 600.0


def say(msg: str) -> None:
    print(f"[bench +{time.time() - T_START:6.1f}s] {msg}", flush=True)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                   help="cpu: a rehearsal of the control flow at the "
                        "files' cpu_preset sizes; its line names the CPU")
    return p.parse_args(argv)


def redirect_children_output(log_path: str) -> None:
    """Workers inherit this process's stdout and stderr (a zygote-forked
    worker writes both to fd 2). Send what they inherit to the run's log,
    and keep this process's own prints on the real streams, so that the
    result line is the last line of standard output."""
    sys.stdout.flush()
    sys.stderr.flush()
    out, err = os.dup(1), os.dup(2)
    log = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o644)
    os.dup2(log, 1)
    os.dup2(log, 2)
    os.close(log)
    sys.stdout = os.fdopen(out, "w", buffering=1)
    sys.stderr = os.fdopen(err, "w", buffering=1)


def tail(path: str, nbytes: int = 6000) -> str:
    try:
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - nbytes))
            return f.read().decode("utf-8", "replace")
    except OSError as e:
        return f"(cannot read {path}: {e})"


def driver_touched_jax() -> bool:
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def setup_seconds(window_t: float, t_start: float, start_wait: float) -> float:
    """`setup_s`: from this process's start to the window's, less the wait
    before `rt.init()` for a chip to come free. That wait is an earlier
    run's teardown finishing in the kernel (the other tree's as often as
    this one's), no set-up of this run; everything else counts."""
    return window_t - t_start - start_wait


def metrics_of(cell, result, setup_s: float, traced: bool):
    """The line's metrics: the cell's end-to-end metrics, or with
    --trace 1 its per-layer metrics, each through its own reader."""
    import readers
    import spec

    out = {}
    if not traced:
        values = dict(result["values"], setup_s=setup_s)
        for m in cell["end_to_end"]:
            if values.get(m["name"]) is not None:
                out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        return out
    for m in cell["per_layer"]:
        how = spec.layer_metric_spec(m["name"])
        value = readers.read(how["reader"], result["sources"], how)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def prepare_environment(token: str, platform: str, chips: int) -> None:
    """What the runtime and its workers read from the environment."""
    import procs

    os.environ[procs.TOKEN_ENV] = token
    # One fixed compile cache inside the checkout unless the machine
    # places it, and every program in it, the small ones too, so that
    # only the first run of a cell compiles.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO, ".cache", "jax"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("RT_SERVE_DEPLOY_TIMEOUT_S", str(DEPLOY_LIMIT_S))
    os.environ.setdefault("RT_SERVE_OBS_RING", "8192")
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={chips}")


def teardown(rt, token: str, pid_file: str):
    """The runtime's own shutdown, then every process the run started
    waited for (killed after a grace period, and waited for again), then
    the chip seen free: the run ends only when the next one could take
    its chips. Returns what was done and, if the chip stayed held or its
    device files would not open, that failure."""
    import procs

    try:
        from ray_tpu import serve

        if rt.is_initialized():
            serve.shutdown()
    except Exception:  # noqa: BLE001 — teardown goes on
        say("serve.shutdown() raised:\n" + traceback.format_exc())
    try:
        rt.shutdown()
    except Exception:  # noqa: BLE001
        say("rt.shutdown() raised:\n" + traceback.format_exc())
    try:
        from ray_tpu._private.zygote_client import get_shared_manager

        get_shared_manager().stop()  # else it lives until atexit
    except Exception:  # noqa: BLE001
        pass
    ended = procs.end_run(token, pid_file)
    busy = None
    try:
        freed = procs.wait_chip_free(CHIP_FREE_LIMIT_S).parts()
    except procs.ChipBusy as e:
        freed, busy = "-", (e, str(e))
    say(f"after shutdown: {ended}; chip free after {freed}; driver "
        f"initialised a JAX backend: {driver_touched_jax()}")
    return ended, busy


def main(argv=None) -> int:
    args = parse(argv)
    import procs
    import spec

    cell = spec.load_cell(args.workload, args.platform)
    run_dir = os.path.join(spec.CACHE, "runs", cell["name"],
                           f"seed{args.seed}-trace{args.trace}")
    os.makedirs(run_dir, exist_ok=True)
    pid_file = os.path.join(spec.CACHE, "pids.json")
    log_path = os.path.join(run_dir, "workers.log")
    token = procs.new_token()
    prepare_environment(token, args.platform, cell["chips"])
    redirect_children_output(log_path)
    say(f"{cell['name']}: config {cell['config_name']} (reference "
        f"{cell['config']['reference']}, operations "
        f"{cell['config']['operations']}, probe "
        f"{cell['config']['probe']}), traffic "
        f"{cell['traffic']['name']}, {cell['chips']} chip(s), seed "
        f"{args.seed}, {args.seconds:g}s, trace {args.trace}, platform "
        f"{args.platform}; detail in {os.path.relpath(run_dir, REPO)}")

    import ray_tpu as rt  # imports neither JAX nor the native store

    result, failure, window = None, None, {}
    try:
        reaped = procs.reap_previous(pid_file)
        waited = procs.wait_chip_free(CHIP_FREE_LIMIT_S)
        say(f"before init: earlier runs' processes {reaped}; the chip was "
            f"free after {waited.parts()}, left out of setup_s")
        subprocess.run(["make", "-s", "-C",
                        os.path.join(REPO, "ray_tpu", "native")],
                       check=True, timeout=300, stdout=subprocess.DEVNULL)
        rt.init(num_tpus=cell["chips"] if args.platform == "cpu" else None)
        procs.write_pid_file(pid_file, token)
        chips = int(rt.cluster_resources().get("TPU", 0))
        if chips < cell["chips"]:
            raise SystemExit(
                f"bench: the node shows {chips} TPU chip(s), the cell needs "
                f"{cell['chips']} (device files /dev/vfio/<n> or "
                "/dev/accel<n>): nothing was run")
        ctx = {
            "cell": cell, "seed": args.seed, "seconds": args.seconds,
            "trace": bool(args.trace), "platform": args.platform,
            "run_dir": run_dir, "say": say, "t_start": T_START,
            "note_processes": lambda: procs.write_pid_file(pid_file, token),
            "mark_window_start_at": lambda t: window.update(t=t),
        }
        kind = "train" if cell["traffic"]["kind"] == "train" else "serve"
        result = importlib.import_module(f"{kind}_cell").run(ctx)
    except BaseException as e:  # noqa: BLE001 — reported below, after teardown
        failure = (e, traceback.format_exc())
    finally:
        ended, busy = teardown(rt, token, pid_file)
        failure = failure or busy
    if failure is not None or ended["left"]:
        if failure is not None:
            say("no result: " + failure[1])
        else:
            say(f"no result: {ended['left']} process(es) of the run would "
                "not end")
        say(f"tail of {log_path}:\n{tail(log_path)}")
        return 1
    if driver_touched_jax():
        say("no result: the driver process initialised a JAX backend")
        return 1
    device = dict(result["device"])
    if (device["platform"] != args.platform
            or device["count"] != cell["chips"]):
        say(f"no result: the workers ran on {device}, the cell needs "
            f"{cell['chips']} {args.platform} device(s)")
        return 1
    return finish(args, cell, result, run_dir,
                  setup_seconds(window["t"], T_START, waited))


def trace_fault(trace, platform: str):
    """Why a traced run has no result, or None: `--trace 1` was asked and
    there is no reduced trace to print `busy_s` and `window_s` from."""
    if not trace:
        return "the run gathered no trace"
    if trace.get("error"):
        return str(trace["error"])
    if "busy_s" not in trace or "window_s" not in trace:
        return "the reduced trace has no busy_s or no window_s"
    if platform == "tpu" and not (0.0 < trace["busy_s"] <= trace["window_s"]):
        return (f"the reduced trace reads busy_s {trace['busy_s']!r} over "
                f"window_s {trace['window_s']!r} on {trace.get('n_devices')} "
                "device plane(s): no operation ran on a device in the window")
    return None


def finish(args, cell, result, run_dir: str, setup_s: float) -> int:
    """From a cell's result to the line, printed last; a traced run with
    no reduced trace says why and prints none."""
    device = dict(result["device"])
    line = {
        "correct": result["correct"], "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": metrics_of(cell, result, setup_s, bool(args.trace)),
        "device": device,
    }
    if args.trace:
        trace = result["sources"].get("trace")
        fault = trace_fault(trace, args.platform)
        if fault is not None:
            say(f"no result: --trace 1 and no reduced trace: {fault}")
            return 1
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace.get("device_ops", []),
                             "idle_gaps": trace.get("idle_gaps", [])}
        say(f"trace: window {trace['window_s']:.6f}s "
            + ("marked in the trace" if trace.get("window_marked")
               else "by the host's clock (no span in the trace)")
            + f" (the host's clock between its ends "
            f"{trace.get('clocked_window_s')}), busy {trace['busy_s']:.6f}s "
            f"inside it of {trace.get('busy_unclipped_s')} recorded, "
            f"outside_s {trace.get('outside_s')} before and after; by device "
            + str({n: {k: d.get(k) for k in (
                "busy_s", "outside_s", "busy_unclipped_s", "edge_idle_s")}
                for n, d in (trace.get("devices") or {}).items()})
            + "; the profiler's calls took "
            + str({k: trace.get(k) for k in (
                "start_trace_s", "stop_trace_s", "reduce_s")}))
        say(f"trace: device time by opcode {trace.get('by_opcode')}; programs "
            + str({m["name"]: (m["launches"], round(m["total_s"], 4))
                   for m in (trace.get("modules") or {}).values()}))
        if trace.get("describe"):
            with open(os.path.join(run_dir, "trace_shape.txt"), "w") as f:
                f.write(trace["describe"])
    say(f"setup_s {setup_s:.2f}")
    # What decided `correct`, each number beside its limit: last in the
    # line, and the last lines of standard error.
    line["compared"] = result["compared"]
    with open(os.path.join(run_dir, "result.json"), "w") as f:
        json.dump(line, f)
    print(json.dumps(line), flush=True)
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']} against the limit {c['limit']}"
              f": {'holds' if c['holds'] else 'DOES NOT HOLD'}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
