"""Chaos tests: workloads survive random component kills.

Reference test model: release/nightly_tests/chaos_test/ +
python/ray/_private/test_utils.py killer actors — run a retriable
workload while a killer actor randomly destroys workers/nodes, then
assert the workload still completes correctly.
"""
import time

import pytest

import ray_tpu
from ray_tpu.util.chaos import NodeKillerActor, WorkerKillerActor


def test_worker_chaos_tasks_complete(ray_start_regular):
    """Retriable tasks complete correctly while workers are being
    SIGKILLed underneath them."""
    killer = WorkerKillerActor.remote(kill_interval_s=0.4, max_kills=4, seed=0)
    ray_tpu.get(killer.run.remote(), timeout=120)

    @ray_tpu.remote(max_retries=10)
    def chunk(i):
        time.sleep(0.15)
        return i * i

    refs = [chunk.remote(i) for i in range(40)]
    results = ray_tpu.get(refs, timeout=180)
    assert results == [i * i for i in range(40)]
    killed = ray_tpu.get(killer.stop_run.remote(), timeout=120)
    assert killed, "chaos killer never killed anything"


def test_worker_chaos_actor_restarts(ray_start_regular):
    """A restartable actor keeps serving across worker kills."""
    killer = WorkerKillerActor.remote(kill_interval_s=0.5, max_kills=2, seed=1)

    @ray_tpu.remote(max_restarts=10, max_task_retries=10)
    class Service:
        def work(self, x):
            time.sleep(0.1)
            return x + 1

    svc = Service.remote()
    assert ray_tpu.get(svc.work.remote(0), timeout=30) == 1
    ray_tpu.get(killer.run.remote(), timeout=120)
    ok = 0
    for i in range(30):
        try:
            assert ray_tpu.get(svc.work.remote(i), timeout=60) == i + 1
            ok += 1
        except ray_tpu.exceptions.ActorDiedError:
            pytest.fail("actor permanently died despite max_restarts")
    killed = ray_tpu.get(killer.stop_run.remote(), timeout=120)
    assert ok == 30


def test_node_chaos_retriable_workload(ray_start_cluster):
    """Tasks pinned off-head survive a node agent being SIGKILLed."""
    cluster = ray_start_cluster
    for _ in range(2):
        cluster.add_node(num_cpus=2, resources={"slot": 4})
    ray = cluster.connect()

    killer = NodeKillerActor.remote(kill_interval_s=0.5, max_kills=1, seed=2)
    ray_tpu.get(killer.run.remote(), timeout=120)

    @ray_tpu.remote(max_retries=10, resources={"slot": 1})
    def shard(i):
        time.sleep(0.2)
        return i

    refs = [shard.remote(i) for i in range(24)]
    # Ensure the chaos actually fired before declaring victory (a warm
    # cluster can drain the workload before the first kill interval).
    deadline = time.time() + 30
    while time.time() < deadline:
        if ray_tpu.get(killer.get_total_killed.remote(), timeout=120):
            break
        time.sleep(0.2)
    assert ray_tpu.get(refs, timeout=180) == list(range(24))
    killed = ray_tpu.get(killer.stop_run.remote(), timeout=120)
    assert any(k.startswith("node:") for k in killed), killed
