"""Three tenants on one cluster surviving each other's demand spikes.
Writes BENCH_MULTITENANT.json.

The multi-tenancy story is only real if one run shows all three tenants'
SLOs while chips move between them, so this bench builds an in-process
cluster (1 CPU head + 2 simulated TPU hosts, 4 chips each) and runs a
training gang, a serve app, and CPU rollout actors feeding an RL learner
SIMULTANEOUSLY — then takes the chips away and gives them back:

  1. graceful reclamation: the training gang (priority 0) holds all 8
     chips; a latency-critical serve spike (priority 10, TPU:4) deploys.
     The GCS reclamation pass drains the gang's nodes, the trainer
     checkpoints and stops (PR 2 proactive migration), the spike places
     on the fenced chips. When the spike is deleted, the gang's
     re-queued placement group places at its original priority and
     training resumes FROM THE NEWEST CHECKPOINT and completes. Gates:
     spike served within 30 s of deploy, training completed every step,
     resumed step > 0 (not from scratch), victim record outcome
     "graceful".
  2. chips returned: after the spike subsides and training finishes,
     both TPU hosts report all chips available and nothing is left
     draining or fenced. Gate: 8/8 chips free, zero open preemptions.
  3. three-tenant SLO accounting: closed-loop chat traffic runs the
     whole time under tenant labels "train"/"serve"/"rl"; the metrics
     snapshot must carry per-tenant request series and SLO burn for all
     three in ONE run. Gates: all three tenants present, zero lost
     non-shed requests across both phases.
  4. hard-kill deadline under mid-drain chaos: a "deaf" gang (ignores
     drain) holds all chips; a second spike triggers reclamation;
     chaos.kill_victim_mid_drain() kills a victim actor mid-drain. The
     grace deadline must still converge: remaining actors killed, group
     force-released, spike placed, no wedged placement groups. Gates:
     release within grace + slack, outcome "hard_kill", spike placed,
     zero PENDING groups at the end.
  5. elastic resize vs evict-and-restart: the same 8->4->8 partial
     reclamation (chaos claims 4 of the gang's 8 chips, holds them,
     lets go) hits two identical training runs. The elastic gang
     shrinks in place (survivor keeps stepping on 4 chips, state
     re-sharded through the object store) and grows back on the fence
     lift; the fixed gang checkpoints, evicts, and sits idle until the
     chips return. Gates: elastic run's step history is gapless across
     both resizes (zero lost steps), its victim record closes with the
     elastic outcome "resized", final loss matches the evict-restart
     run exactly, and goodput (steps per wall-second through the
     incident) beats the evict-and-restart baseline.

Run: python bench_multitenant.py [--quick]  (--quick: shorter phases,
no artifact). Exits non-zero when a gate fails.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("RT_TPU_CHIPS", "0")

TRAIN_STEPS = 60          # full-run training step target
STEP_S = 0.15             # per-step work (gang must outlive the spike)
SPIKE_HOLD_S = 2.5        # how long the serve spike keeps the chips
HARD_GRACE_S = 3.0        # phase-B grace window (deaf gang hard kill)


def _train_loop(config):
    """Checkpoint-every-step cooperative loop: on drain it saves and
    returns at the next should_stop() check (zero lost steps)."""
    import time as _t

    from ray_tpu import train
    from ray_tpu.train import Checkpoint

    start = 0
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        start = ckpt.to_dict()["step"] + 1
    for step in range(start, config["steps"]):
        _t.sleep(config["step_s"])
        train.report({"step": step, "start": start},
                     checkpoint=Checkpoint.from_dict({"step": step}))
        if train.should_stop():
            return  # checkpointed above; migrate with zero lost work
    return


def _elastic_vs_restart_loop(config):
    """One loop, two failure modes. Elastic gangs resize through
    train.sync_resize (live state handoff); fixed gangs checkpoint every
    step and honor should_stop (the PR 2 migrate path). Reporting and
    checkpoint cadence are identical so the goodput comparison is
    fair."""
    import time as _t

    import numpy as np

    from ray_tpu import train
    from ray_tpu.train import Checkpoint

    state = {"w": np.zeros(4, dtype=np.float64), "steps_done": 0}
    ckpt = train.get_checkpoint()
    if ckpt is not None:
        d = ckpt.to_dict()
        state = {"w": np.asarray(d["w"]), "steps_done": d["steps_done"]}
    shards = train.shard_state(
        {"m": np.arange(32, dtype=np.float64)}, name="opt")
    while state["steps_done"] < config["steps"]:
        ev = train.sync_resize(state, shards)
        if ev.exiting:
            return  # departing rank: slice persisted, exit clean
        state, shards = ev.state, ev.shards
        _t.sleep(config["step_s"])
        state["w"] += 1.0
        state["steps_done"] += 1
        ck = Checkpoint.from_dict(
            {"w": state["w"].tolist(), "steps_done": state["steps_done"]})
        if train.get_world_rank() == 0:
            train.report(
                {"step": state["steps_done"], "world": ev.world_size,
                 "loss": abs(float(state["w"].mean())
                             - state["steps_done"])},
                checkpoint=ck)
        else:
            train.report({"step": state["steps_done"]})
        if train.should_stop():
            return  # fixed-size path: checkpointed above, migrate


def _wait_for(pred, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


def main():
    quick = "--quick" in sys.argv
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu._private import chaos
    from ray_tpu._private.config import get_config
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.serve.deployment import SloConfig
    from ray_tpu.train.backend import JaxConfig
    from ray_tpu.train.config import (
        FailureConfig,
        ResizePolicy,
        RunConfig,
        ScalingConfig,
    )
    from ray_tpu.train.trainer import DataParallelTrainer
    from ray_tpu.util.placement_group import (
        placement_group,
        remove_placement_group,
    )
    from ray_tpu.util.scheduling_strategies import (
        PlacementGroupSchedulingStrategy,
    )

    steps = 35 if quick else TRAIN_STEPS
    cfg = get_config()
    cfg.preempt_grace_s = 20.0  # phase A: graceful path must win

    results = []
    cluster = Cluster()
    cluster.add_node(num_cpus=8)  # head: CPU tenants only
    w1 = cluster.add_node(num_cpus=4, num_tpus=4)
    w2 = cluster.add_node(num_cpus=4, num_tpus=4)
    client = cluster.connect()
    gcs = cluster.gcs
    tpu_nodes = (w1.node_id.binary(), w2.node_id.binary())

    trial_dir = f"/tmp/bench_multitenant_{os.getpid()}"

    # -- tenant 2: serve "chat" app, traffic under 3 tenant labels ------
    @serve.deployment(num_replicas=2,
                      ray_actor_options={"num_cpus": 0.5},
                      slo=SloConfig(e2e_ms=500.0, objective=0.99))
    def chat(x):
        time.sleep(0.005)
        return x + 1

    chat_h = serve.run(chat.bind())
    assert chat_h.remote(0).result(timeout=60) == 1  # warm routes

    chat_ok = {"train": 0, "serve": 0, "rl": 0}
    chat_lost, chat_shed = [], [0]
    stop_traffic = threading.Event()

    def chat_client(tenant):
        from ray_tpu.exceptions import ServeOverloadedError

        h = chat_h.options(tenant=tenant)
        i = 0
        while not stop_traffic.is_set():
            try:
                if h.remote(i).result(timeout=60) == i + 1:
                    chat_ok[tenant] += 1
                else:
                    chat_lost.append("wrong result")
            except ServeOverloadedError:
                chat_shed[0] += 1
            except Exception as e:  # noqa: BLE001 — tally, gate below
                chat_lost.append(f"{type(e).__name__}: {e}")
            i += 1
            time.sleep(0.02)

    traffic = [threading.Thread(target=chat_client, args=(t,), daemon=True)
               for t in ("train", "serve", "rl")]
    for t in traffic:
        t.start()

    # -- tenant 3: RL rollout actors feeding a learner ------------------
    @rt.remote(num_cpus=1)
    class Rollout:
        def step(self, i):
            return [i] * 8

    rollouts = [Rollout.remote() for _ in range(2)]
    rl_steps = [0]
    stop_rl = threading.Event()

    def learner():
        i = 0
        while not stop_rl.is_set():
            try:
                batches = rt.get(
                    [r.step.remote(i) for r in rollouts], timeout=60
                )
                assert all(b == [i] * 8 for b in batches)
                rl_steps[0] += 1
            except Exception:  # noqa: BLE001 — rl gate counts progress
                pass
            i += 1
            time.sleep(0.02)

    rl_thread = threading.Thread(target=learner, daemon=True)
    rl_thread.start()

    # -- tenant 1: training gang on all 8 chips --------------------------
    trainer = DataParallelTrainer(
        _train_loop,
        train_loop_config={"steps": steps, "step_s": STEP_S},
        backend_config=JaxConfig(dp_sync="none"),
        scaling_config=ScalingConfig(
            num_workers=2,
            resources_per_worker={"CPU": 1, "TPU": 4},
            priority=0,
        ),
        run_config=RunConfig(
            name="gang", storage_path=trial_dir,
            failure_config=FailureConfig(max_failures=6, backoff_s=0.2,
                                         backoff_max_s=1.0),
        ),
    )
    fit_result = {}

    def fit():
        fit_result["result"] = trainer.fit()

    fit_thread = threading.Thread(target=fit, daemon=True)
    fit_thread.start()
    ckpt_index = os.path.join(trial_dir, "gang", "checkpoints",
                              "checkpoints.json")

    def _ckpts_registered():
        try:
            with open(ckpt_index) as f:
                return len(json.load(f))
        except (OSError, ValueError):
            return 0

    _wait_for(lambda: _ckpts_registered() >= 4, timeout=60,
              what="training checkpoints before the spike")

    # -- probe 1: serve spike reclaims chips gracefully ------------------
    @serve.deployment(ray_actor_options={"num_cpus": 0.5,
                                         "resources": {"TPU": 4},
                                         "priority": 10})
    def spike(x):
        return x * 2

    t0 = time.perf_counter()
    spike_h = serve.run(spike.bind())
    assert spike_h.remote(21).result(timeout=60) == 42  # placed + serving
    reclaim_s = time.perf_counter() - t0
    rl_at_spike = rl_steps[0]
    recs = [r for r in gcs.preemptions.values()
            if r["victim_tenant"] == "train"]
    time.sleep(SPIKE_HOLD_S if not quick else 1.0)
    serve.delete("spike")
    rl_during_spike = rl_steps[0] - rl_at_spike

    fit_thread.join(timeout=180)
    result = fit_result.get("result")
    history = result.metrics_history if result else []
    final_step = max((m.get("step", -1) for m in history), default=-1)
    resumed_from = max((m.get("start", 0) for m in history), default=0)
    victim_graceful = bool(recs) and recs[0]["outcome"] == "graceful"
    entry = {
        "metric": "graceful reclamation: serve spike evicts training gang",
        "spike_deploy_to_first_response_s": round(reclaim_s, 3),
        "train_steps_target": steps,
        "train_final_step": final_step,
        "train_resumed_from_step": resumed_from,
        "train_error": str(result.error) if result and result.error
        else None,
        "victim_outcome": recs[0]["outcome"] if recs else None,
        "gate": "spike served < 30 s; training completed all steps, "
                "resumed from checkpoint > 0; victim released gracefully",
        "pass": bool(
            reclaim_s < 30.0 and result is not None
            and result.error is None and final_step == steps - 1
            and resumed_from > 0 and victim_graceful
        ),
    }
    print(json.dumps(entry))
    results.append(entry)

    # -- probe 2: chips returned after the spike subsides ----------------
    def chips_free():
        return all(
            gcs.nodes[nid]["resources_available"].get("TPU", 0) == 4.0
            and not gcs.nodes[nid].get("draining")
            and gcs.nodes[nid].get("fenced_for") is None
            for nid in tpu_nodes
        )

    try:
        _wait_for(chips_free, timeout=30, what="chips returned")
        returned = True
    except AssertionError:
        returned = False
    open_recs = [r for r in gcs.preemptions.values()
                 if r["state"] != "released"]
    entry = {
        "metric": "chips returned to the pool after the spike",
        "tpu_free": sum(
            gcs.nodes[nid]["resources_available"].get("TPU", 0)
            for nid in tpu_nodes
        ),
        "tpu_total": 8,
        "open_preemptions": len(open_recs),
        "gate": "8/8 chips free, no node draining/fenced, zero open "
                "preemption records",
        "pass": returned and not open_recs,
    }
    print(json.dumps(entry))
    results.append(entry)

    # -- probe 5: elastic resize vs evict-and-restart --------------------
    el_steps = 60 if quick else 100
    el_step_s = 0.08
    el_hold_s = 2.0 if quick else 4.0

    def _ckpt_count(path):
        try:
            with open(path) as f:
                return len(json.load(f))
        except (OSError, ValueError):
            return 0

    def run_incident(name, elastic):
        """One training run through the same reclamation incident:
        warm up, chaos claims half the chips, holds them el_hold_s,
        lets go. Returns the run's scorecard."""
        trainer = DataParallelTrainer(
            _elastic_vs_restart_loop,
            train_loop_config={"steps": el_steps, "step_s": el_step_s},
            backend_config=JaxConfig(dp_sync="none"),
            scaling_config=ScalingConfig(
                num_workers=2,
                resources_per_worker={"CPU": 1, "TPU": 4},
                priority=0,
                elastic=ResizePolicy(min_world_size=1) if elastic
                else None,
            ),
            run_config=RunConfig(
                name=name, storage_path=trial_dir,
                failure_config=FailureConfig(max_failures=6, backoff_s=0.2,
                                             backoff_max_s=1.0),
            ),
        )
        holder = {}
        th = threading.Thread(
            target=lambda: holder.update(r=trainer.fit()), daemon=True)
        t0 = time.perf_counter()
        th.start()
        idx = os.path.join(trial_dir, name, "checkpoints",
                           "checkpoints.json")
        _wait_for(lambda: _ckpt_count(idx) >= 3, timeout=60,
                  what=f"{name}: warm-up steps before reclamation")
        victims = chaos.reclaim_chips(4, bundle_chips=4)
        time.sleep(el_hold_s)
        chaos.lift_fence()
        th.join(timeout=180)
        wall = time.perf_counter() - t0
        r = holder.get("r")
        history = r.metrics_history if r else []
        steps_seen = [m["step"] for m in history if "step" in m]
        rec = gcs.preemptions.get(victims[0]["victim_pg_id"]) if victims \
            else None
        return {
            "wall_s": round(wall, 3),
            "goodput_steps_per_s": round(el_steps / wall, 2),
            "final_step": max(steps_seen, default=-1),
            "steps_lost": el_steps - len(set(steps_seen)),
            "steps_replayed": len(steps_seen) - len(set(steps_seen)),
            "worlds": sorted({m["world"] for m in history
                              if "world" in m}),
            "final_loss": next((m["loss"] for m in reversed(history)
                                if "loss" in m), None),
            "victim_outcome": rec["outcome"] if rec else None,
            "error": str(r.error) if r and r.error else None,
        }

    chaos.enable()
    try:
        el = run_incident("elastic_gang", elastic=True)
        ev = run_incident("evict_gang", elastic=False)
    finally:
        chaos.disable()
        chaos.clear()
    goodput_ratio = (
        round(el["goodput_steps_per_s"] / ev["goodput_steps_per_s"], 2)
        if ev["goodput_steps_per_s"] else None
    )
    entry = {
        "metric": "elastic resize vs evict-and-restart under partial "
                  "reclamation",
        "steps": el_steps,
        "chips_held_s": el_hold_s,
        "elastic": el,
        "evict_restart": ev,
        "goodput_ratio": goodput_ratio,
        "gate": "elastic: zero lost steps, gapless history through "
                "2->1->2, victim outcome 'resized', final loss matches "
                "the evict-restart run; goodput_ratio > 1.0",
        "pass": bool(
            el["error"] is None and ev["error"] is None
            and el["steps_lost"] == 0 and el["steps_replayed"] == 0
            and el["final_step"] == el_steps
            and ev["final_step"] == el_steps
            and el["worlds"] == [1, 2]
            and el["victim_outcome"] == "resized"
            and el["final_loss"] is not None
            and ev["final_loss"] is not None
            and abs(el["final_loss"] - ev["final_loss"]) < 1e-9
            and goodput_ratio is not None and goodput_ratio > 1.0
        ),
    }
    print(json.dumps(entry))
    results.append(entry)

    # -- probe 4 (runs while chat traffic continues): hard-kill chaos ----
    cfg.preempt_grace_s = HARD_GRACE_S
    chaos.enable()
    deaf_killed_mid_drain = None
    try:
        deaf = placement_group([{"TPU": 4}, {"TPU": 4}], strategy="SPREAD",
                               name="deaf", priority=0)
        assert deaf.ready(timeout=15)

        @rt.remote(num_cpus=0, resources={"TPU": 1})
        class Deaf:
            def ping(self):
                return "ok"

        deaf_actor = Deaf.options(
            scheduling_strategy=PlacementGroupSchedulingStrategy(
                placement_group=deaf, placement_group_bundle_index=0
            )
        ).remote()
        assert rt.get(deaf_actor.ping.remote(), timeout=60) == "ok"

        t0 = time.perf_counter()
        spike2 = placement_group([{"TPU": 4}], name="spike2", priority=10)
        _wait_for(
            lambda: (gcs.preemptions.get(deaf.id.binary()) or {})
            .get("state") == "draining",
            timeout=15, what="deaf gang draining",
        )
        deaf_killed_mid_drain = chaos.kill_victim_mid_drain()
        assert spike2.ready(timeout=HARD_GRACE_S + 15)
        released_s = time.perf_counter() - t0
        rec = gcs.preemptions[deaf.id.binary()]
        pending = [p for p in gcs.placement_groups.values()
                   if p["state"] == "PENDING"]
        entry = {
            "metric": "hard-kill deadline honored under mid-drain chaos",
            "grace_s": HARD_GRACE_S,
            "spike_wait_to_placed_s": round(released_s, 3),
            "victim_outcome": rec["outcome"],
            "mid_drain_kill_actor": deaf_killed_mid_drain,
            "wedged_pending_pgs": len(pending),
            "gate": f"placed within grace+6 s; outcome hard_kill; a "
                    f"victim actor was chaos-killed mid-drain; zero "
                    f"PENDING groups left",
            "pass": bool(
                released_s <= HARD_GRACE_S + 6.0
                and rec["outcome"] == "hard_kill"
                and deaf_killed_mid_drain is not None
                and not pending
            ),
        }
        print(json.dumps(entry))
        results.append(entry)
        remove_placement_group(spike2)
    finally:
        chaos.disable()
        chaos.clear()

    # -- probe 3: three tenants' SLO accounting in one run ---------------
    stop_traffic.set()
    stop_rl.set()
    for t in traffic:
        t.join(timeout=60)
    rl_thread.join(timeout=60)
    time.sleep(1.5)  # metrics flushers drain to the GCS
    snap = client._run(client._gcs_call("metrics_snapshot", {}))["metrics"]
    by_name = {m["name"]: m for m in snap}

    def tenants_of(metric):
        out = set()
        for tags, _ in (by_name.get(metric) or {}).get("series", []):
            t = dict(tuple(x) for x in tags).get("tenant")
            if t:
                out.add(t)
        return out

    req_tenants = tenants_of("serve_requests_total")
    burn_tenants = tenants_of("serve_slo_burn_rate")
    pre = by_name.get("preempt_total", {}).get("series", [])
    grace_hist = by_name.get("preempt_grace_seconds", {}).get("series", [])
    entry = {
        "metric": "three-tenant SLO accounting in one run",
        "chat_requests_ok": dict(chat_ok),
        "chat_shed": chat_shed[0],
        "lost_non_shed": len(chat_lost),
        "lost_samples": chat_lost[:5],
        "rl_steps_total": rl_steps[0],
        "rl_steps_during_spike": rl_during_spike,
        "request_series_tenants": sorted(req_tenants),
        "slo_burn_tenants": sorted(burn_tenants),
        "preempt_total_series": len(pre),
        "preempt_grace_observations": sum(
            s[1]["count"] for s in grace_hist
        ) if grace_hist else 0,
        "gate": "zero lost non-shed chat requests through both phases; "
                "request + SLO-burn series for train/serve/rl; RL made "
                "progress during the spike; preempt metrics populated",
        "pass": bool(
            not chat_lost
            and {"train", "serve", "rl"} <= req_tenants
            and {"train", "serve", "rl"} <= burn_tenants
            and rl_during_spike > 0
            and len(pre) >= 1
        ),
    }
    print(json.dumps(entry))
    results.append(entry)

    serve.shutdown()
    cluster.shutdown()

    summary = {
        "metric": "multi-tenant survival summary",
        "lost_requests_total": len(chat_lost),
        "gate": "lost_requests_total == 0",
        "pass": not chat_lost,
    }
    print(json.dumps(summary))
    results.append(summary)
    if not quick:
        with open("BENCH_MULTITENANT.json", "w") as f:
            json.dump(results, f, indent=1)
    failed = [r["metric"] for r in results if r.get("pass") is False]
    if failed:
        print(f"GATE FAILURES: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
