"""TPU accelerator manager, chip isolation, memory monitor policies.

Reference test models: python/ray/tests/accelerators/test_tpu.py,
python/ray/tests/test_memory_pressure.py (policy parts unit-tested as in
src/ray/raylet/worker_killing_policy_test.cc).
"""
import os
import time

import pytest

import ray_tpu
from ray_tpu.accelerators import TPUAcceleratorManager, get_accelerator_manager
from ray_tpu.core.memory_monitor import (
    KillCandidate,
    MemoryMonitor,
    group_by_owner_policy,
    retriable_fifo_policy,
    system_memory,
)


def test_manager_registry():
    assert get_accelerator_manager("TPU") is not None
    assert get_accelerator_manager("GPU") is None


def test_tpu_chip_validation():
    ok, _ = TPUAcceleratorManager.validate_resource_request_quantity(4)
    assert ok
    ok, msg = TPUAcceleratorManager.validate_resource_request_quantity(3)
    assert not ok and "num_tpus" in msg
    ok, _ = TPUAcceleratorManager.validate_resource_request_quantity(16)
    assert ok  # multi-host slice


def test_visible_chips_env(monkeypatch):
    env = TPUAcceleratorManager.visible_chips_env([0, 2], chips_on_host=4)
    assert env["TPU_VISIBLE_CHIPS"] == "0,2"
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", env["TPU_VISIBLE_CHIPS"])
    assert TPUAcceleratorManager.get_current_process_visible_accelerator_ids() == [0, 2]
    monkeypatch.delenv("TPU_VISIBLE_CHIPS")
    assert TPUAcceleratorManager.get_current_process_visible_accelerator_ids() is None
    # every chip of the host: libtpu's own defaults, nothing to set
    assert TPUAcceleratorManager.visible_chips_env([0, 1, 2, 3], 4) == {}


def test_pod_resources(monkeypatch):
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5p-16")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    res = TPUAcceleratorManager.get_current_node_additional_resources()
    assert res == {"TPU-v5p-16": 1.0, "TPU-v5p-16-head": 1.0}
    monkeypatch.setenv("TPU_WORKER_ID", "1")
    res = TPUAcceleratorManager.get_current_node_additional_resources()
    assert res == {"TPU-v5p-16": 1.0}
    assert TPUAcceleratorManager.num_hosts_in_slice("v5p-16") == 4
    assert TPUAcceleratorManager.num_hosts_in_slice("v5e-16") == 2


def test_actor_gets_visible_chips(ray_start_regular):
    """Actors requesting TPUs receive disjoint TPU_VISIBLE_CHIPS."""

    @ray_tpu.remote(num_tpus=2)
    class TpuActor:
        def chips(self):
            return os.environ.get("TPU_VISIBLE_CHIPS")

    a, b = TpuActor.remote(), TpuActor.remote()
    ca = ray_tpu.get(a.chips.remote(), timeout=120)
    cb = ray_tpu.get(b.chips.remote(), timeout=120)
    assert ca and cb
    assert set(ca.split(",")).isdisjoint(set(cb.split(",")))
    assert len(ca.split(",")) == 2
    # Kill one: its chips return to the pool for the next actor.
    ray_tpu.kill(a)
    time.sleep(0.5)
    c = TpuActor.remote()
    cc = ray_tpu.get(c.chips.remote(), timeout=120)
    assert len(cc.split(",")) == 2


# ---------------------------------------------------------------------------
def _cand(wid, retriable, start, owner="o1"):
    return KillCandidate(worker_id=wid, pid=0, is_retriable=retriable, start_time=start, owner_id=owner)


def test_retriable_fifo_policy():
    assert retriable_fifo_policy([]) is None
    # Retriable beats non-retriable regardless of age.
    v = retriable_fifo_policy([_cand("old_r", True, 1), _cand("new_n", False, 9)])
    assert v.worker_id == "old_r"
    # Among retriable, newest dies.
    v = retriable_fifo_policy([_cand("a", True, 1), _cand("b", True, 5)])
    assert v.worker_id == "b"


def test_group_by_owner_policy():
    cands = [
        _cand("a1", True, 1, "alice"),
        _cand("a2", True, 2, "alice"),
        _cand("a3", True, 3, "alice"),
        _cand("b1", True, 9, "bob"),
    ]
    v = group_by_owner_policy(cands)
    assert v.worker_id == "a3"  # newest of the largest group


def test_memory_monitor_threshold_and_cooldown():
    usage = {"v": (50, 100)}
    m = MemoryMonitor(threshold=0.8, reader=lambda: usage["v"], min_kill_interval_s=0.2)
    assert m.usage_fraction() == 0.5
    assert not m.should_kill()
    usage["v"] = (90, 100)
    assert m.should_kill()
    assert not m.should_kill()  # cooldown
    time.sleep(0.25)
    assert m.should_kill()


def test_system_memory_sane():
    used, total = system_memory()
    assert 0 < used <= total


@pytest.mark.slow
def test_oom_kill_end_to_end():
    """Force the threshold below current usage: the monitor must kill the
    retriable task's worker and surface OutOfMemoryError after retries."""
    import ray_tpu

    ray_tpu.init(
        num_cpus=2,
        _system_config={"memory_usage_threshold": 0.001, "memory_monitor_refresh_ms": 100},
    )
    try:

        @ray_tpu.remote(max_retries=1)
        def hog():
            time.sleep(30)
            return 1

        with pytest.raises(ray_tpu.exceptions.OutOfMemoryError):
            ray_tpu.get(hog.remote(), timeout=60)
    finally:
        ray_tpu.shutdown()


def test_node_over_memory_rpc_picks_node_local_victim():
    """Per-node OOM path (reference: every raylet runs its own memory
    monitor): an agent reporting memory pressure gets back the pid of a
    victim among ITS OWN node's workers; killing it drives the normal
    OOM retry/error flow."""
    import os
    import signal
    import time

    import ray_tpu
    from ray_tpu.core.cluster_utils import Cluster
    from ray_tpu.utils.ids import NodeID

    cluster = Cluster({"CPU": 1})
    cluster.add_node(num_cpus=2, resources={"mem_node": 2})
    cluster.connect()
    try:

        @ray_tpu.remote(resources={"mem_node": 1}, max_retries=0)
        def hog():
            time.sleep(30)
            return "survived"

        ref = hog.remote()
        core = ray_tpu.core.api._require_worker()
        node_id = next(
            NodeID.from_hex(n["node_id"]) for n in ray_tpu.nodes() if not n["is_head"]
        )
        deadline = time.time() + 30
        pid = None
        while time.time() < deadline and pid is None:
            pid = core._call("node_over_memory", node_id)
            if pid is None:
                time.sleep(0.3)  # task not yet running on that node
        assert pid, "no victim chosen on the pressured node"
        os.kill(pid, signal.SIGKILL)  # what the agent does with the reply
        with pytest.raises(Exception) as ei:
            ray_tpu.get(ref, timeout=60)
        assert "memory" in str(ei.value).lower() or "OutOfMemory" in type(ei.value).__name__
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
