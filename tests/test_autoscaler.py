"""Autoscaler: bin-packing, fake provider, scale-up/down against demand.

Reference test models: python/ray/tests/test_autoscaler_fake_multinode.py,
test_resource_demand_scheduler.py.
"""
import time

import pytest

import ray_tpu
from ray_tpu.autoscaler import AutoscalingCluster
from ray_tpu.autoscaler.autoscaler import bin_pack_new_nodes


def test_bin_pack_basic():
    types = {
        "cpu4": {"resources": {"CPU": 4}},
        "tpu_v5e_8": {"resources": {"CPU": 8, "TPU": 8}},
    }
    launchable = {"cpu4": 10, "tpu_v5e_8": 2}
    # 6 single-CPU tasks → 2 cpu4 nodes.
    out = bin_pack_new_nodes([{"CPU": 1}] * 6, types, launchable)
    assert out == {"cpu4": 2}
    # A TPU slice demand → the TPU node type.
    out = bin_pack_new_nodes([{"TPU": 8, "CPU": 1}], types, launchable)
    assert out == {"tpu_v5e_8": 1}
    # Infeasible demand launches nothing.
    assert bin_pack_new_nodes([{"GPU": 1}], types, launchable) == {}


def test_bin_pack_respects_max():
    types = {"cpu2": {"resources": {"CPU": 2}}}
    out = bin_pack_new_nodes([{"CPU": 2}] * 5, types, {"cpu2": 3})
    assert out == {"cpu2": 3}


@pytest.mark.slow
def test_autoscaling_cluster_scales_up_and_down():
    cluster = AutoscalingCluster(
        head_resources={"CPU": 1},
        worker_node_types={
            "cpu2": {"resources": {"CPU": 2}, "min_workers": 0, "max_workers": 3},
        },
        interval_s=0.5,
        idle_timeout_s=2.0,
    )
    try:
        ray_tpu.init(address=cluster.address)

        @ray_tpu.remote(num_cpus=2)
        def heavy(x):
            time.sleep(1.0)
            return x

        # Head has 1 CPU; each task needs 2 → must autoscale.
        refs = [heavy.remote(i) for i in range(4)]
        assert sorted(ray_tpu.get(refs, timeout=180)) == [0, 1, 2, 3]
        n_nodes = len([n for n in ray_tpu.nodes() if n["state"] == "ALIVE"])
        assert n_nodes >= 2  # head + at least one autoscaled node

        # Idle long enough → scale back down.
        deadline = time.monotonic() + 120  # generous: shared box under load
        while time.monotonic() < deadline:
            if not cluster.provider.non_terminated_nodes():
                break
            time.sleep(0.5)
        assert not cluster.provider.non_terminated_nodes(), "idle nodes never reaped"
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_instance_manager_fsm():
    """Ledger transitions with a scripted provider (reference:
    autoscaler/v2/tests/test_instance_manager.py)."""
    from ray_tpu.autoscaler.v2 import InstanceManager, InstanceStatus

    class ScriptProvider:
        def __init__(self):
            self.nodes = {}
            self.n = 0

        def create_node(self, node_type, resources):
            self.n += 1
            pid = f"p{self.n}"
            self.nodes[pid] = node_type
            return pid

        def terminate_node(self, pid):
            self.nodes.pop(pid, None)

        def non_terminated_nodes(self):
            return list(self.nodes)

        def node_type_of(self, pid):
            return self.nodes.get(pid)

    prov = ScriptProvider()
    im = InstanceManager(prov, {"cpu2": {"resources": {"CPU": 2}}})
    (iid,) = im.queue_instances("cpu2", 1)
    assert im.instances()[0].status == InstanceStatus.QUEUED
    # one observed transition per reconcile tick
    im.reconcile(cluster_alive_count=1)
    assert im.instances()[0].status == InstanceStatus.REQUESTED
    im.reconcile(cluster_alive_count=1)
    assert im.instances()[0].status == InstanceStatus.ALLOCATED
    im.reconcile(cluster_alive_count=2)
    assert im.instances()[0].status == InstanceStatus.RAY_RUNNING
    # terminate path
    im.request_terminate(iid)
    im.reconcile(cluster_alive_count=2)
    inst = im.instances({InstanceStatus.TERMINATED})
    assert len(inst) == 1 and not prov.nodes
    assert "QUEUED->REQUESTED" in inst[0].history[0]
    # provider-side disappearance → TERMINATED
    (iid2,) = im.queue_instances("cpu2", 1)
    im.reconcile(1)
    im.reconcile(1)
    prov.nodes.clear()  # simulate preemption
    im.reconcile(1)
    inst2 = [i for i in im.instances({InstanceStatus.TERMINATED}) if i.instance_id == iid2]
    assert len(inst2) == 1


def test_autoscaler_v2_scales_up_and_down():
    from ray_tpu.autoscaler.v2 import AutoscalerV2, InstanceStatus

    cluster = AutoscalingCluster(
        head_resources={"CPU": 1},
        worker_node_types={
            "cpu2": {"resources": {"CPU": 2}, "min_workers": 0, "max_workers": 3},
        },
        autoscaler_cls=AutoscalerV2,
        interval_s=0.5,
        idle_timeout_s=2.0,
    )
    try:
        ray_tpu.init(address=cluster.address)

        @ray_tpu.remote(num_cpus=2)
        def heavy(x):
            time.sleep(1.0)
            return x

        refs = [heavy.remote(i) for i in range(4)]
        assert sorted(ray_tpu.get(refs, timeout=180)) == [0, 1, 2, 3]
        im = cluster.autoscaler.instance_manager
        assert im.instances()  # ledger populated
        assert any(
            i.status == InstanceStatus.RAY_RUNNING for i in im.instances()
        ) or any(i.status == InstanceStatus.TERMINATED for i in im.instances(None))

        # The provider loses a node one `reconcile` (interval_s) before its
        # instance moves RAY_STOPPING -> TERMINATED: wait for both.
        def histories():
            return {i.instance_id: (i.status, i.history) for i in im.instances(None)}

        deadline = time.monotonic() + 120  # generous: shared box under load
        while time.monotonic() < deadline:
            if not cluster.provider.non_terminated_nodes() and all(
                i.status == InstanceStatus.TERMINATED for i in im.instances(None)
            ):
                break
            time.sleep(0.5)
        assert not cluster.provider.non_terminated_nodes(), (
            "idle nodes never reaped", histories())
        # every instance ends terminal, with a coherent history
        for inst in im.instances(None):
            assert inst.status == InstanceStatus.TERMINATED, histories()
            assert inst.history[0].startswith("QUEUED->"), histories()
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_autoscaler_v2_partial_idle_scale_down():
    """Per-node identity: ONE idle node is reaped while another node of
    the same type stays busy (pre-identity, scale-down required FULL
    cluster idleness)."""
    import ray_tpu
    from ray_tpu.autoscaler.v2 import AutoscalerV2

    cluster = AutoscalingCluster(
        head_resources={"CPU": 1},
        worker_node_types={
            "cpu2": {"resources": {"CPU": 2, "slot": 1}, "min_workers": 0, "max_workers": 2},
        },
        autoscaler_cls=AutoscalerV2,
        interval_s=0.5,
        idle_timeout_s=3.0,
    )
    try:
        ray_tpu.init(address=cluster.address)

        @ray_tpu.remote(num_cpus=2, resources={"slot": 1})
        def burst(x):
            time.sleep(1.0)
            return x

        # force two nodes up (each fits one 'burst' at a time)
        assert sorted(ray_tpu.get([burst.remote(i) for i in range(2)], timeout=90)) == [0, 1]
        assert len(cluster.provider.non_terminated_nodes()) == 2

        @ray_tpu.remote(num_cpus=2, resources={"slot": 1})
        class Holder:
            def ping(self):
                return "pong"

        # pin ONE node busy; the other goes idle
        h = Holder.remote()
        assert ray_tpu.get(h.ping.remote(), timeout=60) == "pong"
        deadline = time.monotonic() + 40
        while time.monotonic() < deadline:
            if len(cluster.provider.non_terminated_nodes()) == 1:
                break
            time.sleep(0.5)
        assert len(cluster.provider.non_terminated_nodes()) == 1, (
            "idle node not individually reaped while sibling busy"
        )
        # the busy node survives the whole window
        assert ray_tpu.get(h.ping.remote(), timeout=60) == "pong"
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
