#!/usr/bin/env python3
"""The sweep that finds a serving cell's knee: one deployment, a few fixed
rates, one window each. The knee is the highest rate whose backlog does not
grow over the window; the cell's mix file then fixes the rate at four
fifths of it, as a number. Run once, by the builder, on the chip:

    python bench/sweep.py --workload serve-chat-steady --rates 2,3,4,5,6 --seconds 30

Prints one line per rate and exits 0; it judges nothing and is not part of
the benchmark's command.
"""

import argparse
import json
import os
import sys
import traceback

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    args = p.parse_args()
    import run as harness

    import procs
    import serve_cell
    import spec

    cell = spec.load_cell(args.workload, args.platform)
    run_dir = os.path.join(spec.CACHE, "runs", cell["name"], "sweep")
    os.makedirs(run_dir, exist_ok=True)
    pid_file = os.path.join(spec.CACHE, "pids.json")
    token = procs.new_token()
    harness.prepare_environment(token, args.platform, cell["chips"])
    harness.redirect_children_output(os.path.join(run_dir, "workers.log"))
    import ray_tpu as rt

    ctx = {"cell": cell, "seed": args.seed, "seconds": args.seconds,
           "trace": False, "platform": args.platform, "run_dir": run_dir,
           "say": harness.say,
           "note_processes": lambda: procs.write_pid_file(pid_file, token)}
    rc = 0
    try:
        procs.reap_previous(pid_file)
        procs.wait_chip_free(harness.CHIP_FREE_LIMIT_S)
        rt.init(num_tpus=cell["chips"] if args.platform == "cpu" else None)
        handle = serve_cell.deploy(ctx)
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(cell["traffic"],
                       arrival=dict(cell["traffic"]["arrival"], rate_per_s=rate))
            got = serve_cell.offer(ctx, handle, mix, args.seconds, False)
            s, st = got["summary"], got["stats"]
            steps = st["after"]["steps"] - st["before"]["steps"]
            print("SWEEP " + json.dumps({
                "rate_per_s": rate, "attempted": s["attempted"],
                "failed": s["failed"], "ttft_ms": s["ttft_ms"],
                "tpot_ms": s["tpot_ms"], "tokens_per_s": s["tokens_per_s"],
                "outstanding_half": s["outstanding_half"],
                "outstanding_end": s["outstanding_end"],
                "lateness_ms": s["lateness_ms"],
                "step_ms": st["window_s"] * 1e3 / max(steps, 1),
                "waiting_at_end": st["after"]["waiting"],
                "shed": st["after"]["shed_total"]}), flush=True)
    except BaseException:  # noqa: BLE001 — a tool: say why, tear down
        harness.say("sweep failed:\n" + traceback.format_exc())
        harness.say(harness.tail(os.path.join(run_dir, "workers.log")))
        rc = 1
    finally:
        harness.teardown(rt, token, pid_file)
    return rc


if __name__ == "__main__":
    sys.exit(main())
