"""Multi-node scheduling, placement groups, and fault-tolerance tests.

Modeled on the reference's python/ray/tests/test_scheduling*.py,
test_placement_group*.py, and the Cluster harness usage
(cluster_utils.py:108).
"""

import time

import pytest

import ray_tpu as rt
from ray_tpu.util.placement_group import placement_group, remove_placement_group
from ray_tpu.util.scheduling_strategies import (
    NodeAffinitySchedulingStrategy,
    PlacementGroupSchedulingStrategy,
)


def test_two_node_scheduling(rt_cluster):
    cluster = rt_cluster
    n1 = cluster.add_node(num_cpus=2)
    n2 = cluster.add_node(num_cpus=2)
    cluster.connect()

    @rt.remote
    def where():
        import os
        import time as _t

        _t.sleep(1)  # hold the slot so later tasks must spill
        return os.environ["RT_NODE_ID"]

    # Saturate: 2-CPU tasks on 2-CPU nodes; overlap forces spillover.
    refs = [where.options(num_cpus=2).remote() for _ in range(4)]
    nodes = set(rt.get(refs, timeout=120))
    assert len(nodes) == 2  # spilled over to the second node


def test_node_affinity(rt_cluster):
    cluster = rt_cluster
    cluster.add_node(num_cpus=2)
    n2 = cluster.add_node(num_cpus=2)
    cluster.connect()

    @rt.remote
    def where():
        import os

        return os.environ["RT_NODE_ID"]

    strategy = NodeAffinitySchedulingStrategy(node_id=n2.node_id.binary())
    got = rt.get(where.options(scheduling_strategy=strategy).remote())
    assert got == n2.node_id.hex()


def test_custom_resources(rt_cluster):
    cluster = rt_cluster
    cluster.add_node(num_cpus=1)
    special = cluster.add_node(num_cpus=1, resources={"special": 2})
    cluster.connect()

    @rt.remote(resources={"special": 1})
    def on_special():
        import os

        return os.environ["RT_NODE_ID"]

    assert rt.get(on_special.remote()) == special.node_id.hex()


def test_placement_group_strict_spread(rt_cluster):
    cluster = rt_cluster
    for _ in range(3):
        cluster.add_node(num_cpus=2)
    cluster.connect()

    pg = placement_group([{"CPU": 1}] * 3, strategy="STRICT_SPREAD")
    assert pg.ready(timeout=10)
    nodes = pg.bundle_node_ids()
    assert len(set(nodes)) == 3

    @rt.remote
    def where():
        import os

        return os.environ["RT_NODE_ID"]

    refs = [
        where.options(
            scheduling_strategy=PlacementGroupSchedulingStrategy(
                placement_group=pg, placement_group_bundle_index=i
            )
        ).remote()
        for i in range(3)
    ]
    got = rt.get(refs)
    assert [bytes.fromhex(g) for g in got] == nodes
    remove_placement_group(pg)


def test_placement_group_strict_pack(rt_cluster):
    cluster = rt_cluster
    cluster.add_node(num_cpus=4)
    cluster.add_node(num_cpus=4)
    cluster.connect()

    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="STRICT_PACK")
    assert pg.ready(timeout=10)
    nodes = pg.bundle_node_ids()
    assert len(set(nodes)) == 1


def test_placement_group_infeasible(rt_cluster):
    cluster = rt_cluster
    cluster.add_node(num_cpus=1)
    cluster.connect()

    pg = placement_group([{"CPU": 16}], strategy="PACK")
    assert not pg.ready(timeout=1.5)


def test_tpu_gang_resources(rt_cluster):
    """TPU pod topology: head resource + per-host pod-name resource
    (reference pattern: _private/accelerators/tpu.py:335)."""
    cluster = rt_cluster
    pod = "my-tpu-pod"
    # 2-host v5e slice: worker 0 advertises the head resource.
    cluster.add_node(
        num_cpus=1,
        resources={"TPU": 8, pod: 1, "TPU-v5litepod-16-head": 1},
    )
    cluster.add_node(num_cpus=1, resources={"TPU": 8, pod: 1})
    cluster.connect()

    @rt.remote(resources={"TPU-v5litepod-16-head": 1}, num_cpus=0)
    def on_head():
        import os

        return os.environ["RT_NODE_ID"]

    @rt.remote(num_cpus=0)
    def on_pod_host():
        import os

        return os.environ["RT_NODE_ID"]

    head_node = rt.get(on_head.remote())
    # Fan out one whole-host task per pod worker via the pod-name resource.
    refs = [
        on_pod_host.options(resources={pod: 1, "TPU": 8}).remote()
        for _ in range(2)
    ]
    hosts = set(rt.get(refs))
    assert len(hosts) == 2
    assert head_node in hosts


def test_object_transfer_between_nodes(rt_cluster):
    cluster = rt_cluster
    n1 = cluster.add_node(num_cpus=2)
    n2 = cluster.add_node(num_cpus=2)
    cluster.connect()

    import numpy as np

    @rt.remote
    def produce():
        return np.ones(500_000)  # ~4MB -> goes to the shared store

    @rt.remote
    def consume(arr):
        return float(arr.sum())

    strategy1 = NodeAffinitySchedulingStrategy(node_id=n1.node_id.binary())
    strategy2 = NodeAffinitySchedulingStrategy(node_id=n2.node_id.binary())
    ref = produce.options(scheduling_strategy=strategy1).remote()
    out = rt.get(consume.options(scheduling_strategy=strategy2).remote(ref))
    assert out == 500_000.0


def test_actor_restart_after_kill(rt_cluster):
    cluster = rt_cluster
    cluster.add_node(num_cpus=2)
    cluster.connect()

    @rt.remote(max_restarts=1, max_task_retries=2)
    class Phoenix:
        def __init__(self):
            self.calls = 0

        def call(self):
            self.calls += 1
            return self.calls

        def die(self):
            import os

            os._exit(1)

    p = Phoenix.remote()
    assert rt.get(p.call.remote()) == 1
    # A poison call must not be retried onto the restarted actor
    # (at-least-once retries would replay the kill).
    p.die.options(max_task_retries=0).remote()
    time.sleep(1.0)
    # Restarted actor: state reset, calls start over.
    assert rt.get(p.call.remote(), timeout=30) == 1


def test_gang_tasks_spread_not_pipelined(rt_cluster):
    """Two concurrent node-saturating tasks ({pod:1, TPU:8}) must run on
    TWO hosts: the direct transport may not queue a resource-bearing task
    behind a running one on a held worker while the raylet could spill it
    to idle capacity (lease depth is CPU-only; reference keeps leases 1:1
    with running tasks, direct_task_transport.cc)."""
    pod = "tpu-pod-spread"
    for _ in range(2):
        rt_cluster.add_node(
            num_cpus=2, resources={"TPU": 8, pod: 1}
        )
    rt_cluster.connect()

    @rt.remote
    def hold_and_report():
        import time as _t

        _t.sleep(1.0)  # force overlap: the first holds its lease
        return rt.get_runtime_context().node_id

    refs = [
        hold_and_report.options(resources={pod: 1, "TPU": 8}).remote()
        for _ in range(2)
    ]
    hosts = set(rt.get(refs, timeout=120))
    assert len(hosts) == 2, f"gang tasks serialized on one host: {hosts}"


@pytest.mark.slow
def test_graceful_node_drain(rt_cluster):
    """rt drain semantics (reference: `ray drain-node`): cordon a node ->
    new work avoids it while running work finishes -> once idle it is
    removed from the cluster."""
    import time as _t

    cluster = rt_cluster
    cluster.add_node(num_cpus=2)
    n2 = cluster.add_node(num_cpus=2)
    cluster.connect()

    @rt.remote
    def where(sleep_s=0.0):
        import os
        import time as _tt

        _tt.sleep(sleep_s)
        return os.environ["RT_NODE_ID"]

    # Place one long task on n2 by affinity, then cordon n2 mid-flight.
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    n2_id = n2.node_id.binary()
    busy = where.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(node_id=n2_id),
    ).remote(4.0)
    _t.sleep(0.5)

    from ray_tpu.util.state import drain_node

    hexid = n2_id.hex()
    # Kick off the drain in a thread: it must wait for `busy` to finish.
    import threading

    result = {}

    def run_drain():
        result["r"] = drain_node(hexid, timeout=60, poll_s=0.3)

    th = threading.Thread(target=run_drain)
    th.start()

    _t.sleep(1.0)  # cordon has propagated via heartbeat by now
    # New tasks land on the OTHER node even though n2 has free CPU.
    spots = set(rt.get([where.remote() for _ in range(6)], timeout=60))
    assert hexid not in spots, "cordoned node still received work"
    # The long task is still running on n2 (drain waits).
    assert th.is_alive()

    assert rt.get(busy, timeout=60) == hexid  # ran to completion
    th.join(timeout=60)
    assert result["r"].get("ok"), result["r"]

    # Node removed from the cluster view.
    from ray_tpu.util.state import list_nodes

    states = {n["node_id"]: n["state"] for n in list_nodes()}
    assert states.get(hexid) == "DEAD"
    # The survivors still run work.
    assert rt.get(where.remote(), timeout=60) != hexid


@pytest.mark.slow
def test_drain_guards(rt_cluster):
    """Drain edge semantics: the head node refuses to drain; hard
    node-affinity work aimed at a draining node fails fast instead of
    landing on it; --undo mid-drain aborts the removal."""
    import time as _t

    cluster = rt_cluster
    cluster.add_node(num_cpus=2)
    n2 = cluster.add_node(num_cpus=2)
    cluster.connect()

    from ray_tpu.util.state import StateApiClient, drain_node

    head_id = cluster.head.node_id.binary().hex()
    r = drain_node(head_id, timeout=5)
    assert not r.get("ok") and "head" in r.get("error", "")

    # Cordon n2 (no removal yet), wait for its raylet to learn of it.
    c = StateApiClient()
    n2_id = n2.node_id.binary()
    assert c.call("cordon_node", {"node_id": n2_id}).get("ok")
    _t.sleep(1.2)

    @rt.remote
    def where():
        import os

        return os.environ["RT_NODE_ID"]

    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    with pytest.raises(Exception, match="draining"):
        rt.get(
            where.options(
                scheduling_strategy=NodeAffinitySchedulingStrategy(
                    node_id=n2_id
                ),
            ).remote(),
            timeout=30,
        )

    # Lift the cordon: affinity works again (drain aborted cleanly).
    assert c.call("cordon_node", {"node_id": n2_id, "undo": True}).get("ok")
    _t.sleep(1.2)
    out = rt.get(
        where.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=n2_id
            ),
        ).remote(),
        timeout=30,
    )
    assert out == n2_id.hex()
    c.close()


@pytest.mark.slow
def test_drain_revokes_direct_leases(rt_cluster):
    """A driver colocated with a cordoned node must stop streaming
    direct-transport tasks to it: the lease path bypasses h_submit's
    drain spill, so the raylet refuses NEW leases while draining and
    revokes the ones already granted (owners return them and fall back
    to the submit path, which spills remote)."""
    import time as _t

    cluster = rt_cluster
    cluster.add_node(num_cpus=2)
    n2 = cluster.add_node(num_cpus=2)
    cluster.connect()

    from ray_tpu._private.ids import JobID
    from ray_tpu._private.worker import CoreClient

    # Second driver attached to n2 — the colocated-driver scenario.
    client2 = CoreClient(
        cluster.io.loop,
        ("127.0.0.1", cluster.gcs_port),
        ("127.0.0.1", n2.port),
        n2.store_name,
        n2.node_id.binary(),
        JobID.from_random(),
        mode="driver",
    )
    client2.connect()
    try:
        def node_of():
            import os

            return os.environ["RT_NODE_ID"]

        def run_one(timeout=30):
            [ref] = client2.submit_task(node_of, (), {})
            return client2.get([ref], timeout=timeout)[0]

        hexid = n2.node_id.binary().hex()
        # Warm the direct-lease path on the local (n2) raylet.
        pre = {run_one() for _ in range(8)}
        assert hexid in pre, "expected the colocated lease path on n2"

        from ray_tpu.util.state import StateApiClient

        c = StateApiClient()
        assert c.call(
            "cordon_node", {"node_id": n2.node_id.binary()}
        ).get("ok")
        _t.sleep(1.5)  # cordon propagates via the resource sync

        # The warm lease must be revoked: post-cordon tasks land on the
        # other nodes even though n2 has free CPU and held a lease.
        post = {run_one() for _ in range(8)}
        assert hexid not in post, "cordoned node still served leased tasks"
    finally:
        client2.disconnect()
