"""Lease-based direct normal-task submission (reference:
normal_task_submitter.cc + local_task_manager.cc + lease_policy.cc).

Covers: the direct path actually being used (no controller TaskRecord),
lease reuse + release of resources, locality-aware placement of a task
with a large arg, retries on worker death, cancellation, and PG tasks
through the lease path.
"""
import os
import signal
import time

import numpy as np
import pytest

import ray_tpu


@pytest.fixture
def rt():
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


def test_direct_path_used_and_results_owner_local(rt):
    @ray_tpu.remote
    def f(x):
        return x + 1

    refs = [f.remote(i) for i in range(20)]
    assert ray_tpu.get(refs, timeout=120) == list(range(1, 21))
    # The direct path keeps normal tasks out of the controller's
    # TaskRecord table (they surface via event-derived rows instead).
    core = ray_tpu.core.api._global_worker
    assert core._normal_sub is not None
    rows = core.list_state("tasks")
    normal_rows = [r for r in rows if r["name"].endswith("f")]
    assert all(r["state"] in ("FINISHED", "FAILED") for r in normal_rows)


def test_lease_resources_released(rt):
    @ray_tpu.remote(num_cpus=1)
    def hold():
        time.sleep(0.2)
        return 1

    before = ray_tpu.available_resources()["CPU"]
    refs = [hold.remote() for _ in range(8)]
    assert sum(ray_tpu.get(refs, timeout=120)) == 8
    # queue drained → leases released → resources return
    deadline = time.time() + 10
    while time.time() < deadline:
        if ray_tpu.available_resources().get("CPU", 0) == before:
            break
        time.sleep(0.05)
    assert ray_tpu.available_resources()["CPU"] == before


def test_retry_on_worker_death(rt):
    marker = f"/tmp/rt_direct_retry_{os.getpid()}"
    if os.path.exists(marker):
        os.unlink(marker)

    @ray_tpu.remote(max_retries=2)
    def die_once(path):
        import os as _os

        if not _os.path.exists(path):
            open(path, "w").close()
            _os._exit(1)  # simulates a worker crash mid-task
        return "survived"

    assert ray_tpu.get(die_once.remote(marker), timeout=60) == "survived"
    os.unlink(marker)


def test_no_retry_exhausted_fails(rt):
    @ray_tpu.remote(max_retries=0)
    def die():
        os._exit(1)

    with pytest.raises(Exception):
        ray_tpu.get(die.remote(), timeout=60)


def test_cancel_queued_and_running(rt):
    @ray_tpu.remote(num_cpus=4)
    def slow():
        time.sleep(30)
        return 1

    r = slow.remote()
    # a second task of the same shape queues behind the first's lease
    r2 = slow.remote()
    time.sleep(0.3)
    ray_tpu.cancel(r2)
    with pytest.raises(Exception):
        ray_tpu.get(r2, timeout=10)
    ray_tpu.cancel(r)
    with pytest.raises(Exception):
        ray_tpu.get(r, timeout=10)


def test_error_propagation_with_retry_exceptions(rt):
    calls = f"/tmp/rt_direct_retryexc_{os.getpid()}"
    if os.path.exists(calls):
        os.unlink(calls)

    @ray_tpu.remote(max_retries=2, retry_exceptions=True)
    def flaky(path):
        import os as _os

        if not _os.path.exists(path):
            open(path, "w").close()
            raise RuntimeError("transient")
        return "ok"

    assert ray_tpu.get(flaky.remote(calls), timeout=60) == "ok"
    os.unlink(calls)


def test_pg_tasks_through_lease_path(rt):
    from ray_tpu.util.placement_group import placement_group, remove_placement_group
    from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy

    pg = placement_group([{"CPU": 2}], strategy="PACK")
    assert pg.wait(10)

    @ray_tpu.remote(
        num_cpus=1,
        scheduling_strategy=PlacementGroupSchedulingStrategy(placement_group=pg),
    )
    def inside():
        return "pg-ok"

    assert ray_tpu.get([inside.remote() for _ in range(4)], timeout=120) == ["pg-ok"] * 4
    remove_placement_group(pg)


def test_caller_death_releases_leases_and_workers(rt):
    """A driver that dies holding worker leases must not strand resources
    or pool workers: the controller's disconnect cleanup releases the
    lease resources and relays the release to the agents' pools."""
    import subprocess
    import sys
    import textwrap

    core = ray_tpu.core.api._require_worker()
    addr = core.address
    before = ray_tpu.available_resources()["CPU"]
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {repo_root!r})
        import os, time
        import ray_tpu
        ray_tpu.init(address={addr!r})

        @ray_tpu.remote(num_cpus=1)
        def hold(tag):
            import time
            while True:  # heartbeat until killed
                open(f"/tmp/rt_orphan_{{tag}}", "w").write(str(time.time()))
                time.sleep(0.2)

        refs = [hold.remote(i) for i in range(4)]  # leases all 4 CPUs
        time.sleep(2.5)  # leases granted, tasks running
        os._exit(1)  # die WITHOUT releasing anything
    """)
    env = dict(__import__("os").environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", child], env=env, timeout=120,
        capture_output=True,
    )
    assert proc.returncode == 1
    # resources come back once the controller processes the disconnect
    # (and kills/reclaims the orphaned task workers)
    deadline = time.time() + 60
    while time.time() < deadline:
        if ray_tpu.available_resources().get("CPU", 0) == before:
            break
        time.sleep(0.25)
    assert ray_tpu.available_resources()["CPU"] == before
    # the pool still serves new work promptly
    @ray_tpu.remote(num_cpus=1)
    def ping():
        return "ok"

    assert ray_tpu.get([ping.remote() for _ in range(4)], timeout=60) == ["ok"] * 4
    # the orphaned tasks' workers were KILLED, not pooled busy: their
    # heartbeats stop (a pooled busy worker would strand the next push)
    import glob

    deadline = time.time() + 30
    while time.time() < deadline:
        time.sleep(1.0)
        now = time.time()
        beats = [float(open(p).read()) for p in glob.glob("/tmp/rt_orphan_*")]
        if beats and all(b < now - 0.8 for b in beats):
            break
    else:
        pytest.fail(f"orphaned workers still heartbeating: {beats}")
    for path in glob.glob("/tmp/rt_orphan_*"):
        os.unlink(path)


class TestMultiNode:
    def test_locality_aware_placement(self):
        """A task whose only big arg lives on node B must schedule onto
        node B (reference: lease_policy.cc best-node-by-arg-bytes)."""
        from ray_tpu.core.cluster_utils import Cluster

        cluster = Cluster()
        cluster.add_node(num_cpus=2, resources={"nodeA": 1})
        cluster.add_node(num_cpus=2, resources={"nodeB": 1})
        cluster.connect()
        try:

            @ray_tpu.remote(num_cpus=1, resources={"nodeB": 0.01})
            def produce():
                import numpy as _np

                return _np.ones(100 * 1024 * 1024, dtype=_np.uint8)

            @ray_tpu.remote(num_cpus=1)
            def consume(arr):
                from ray_tpu import runtime_context

                return (int(arr[0]), runtime_context.get_runtime_context().get_node_id())

            big = produce.remote()
            ray_tpu.wait([big], timeout=120)
            nodes = {n["node_id"]: n for n in ray_tpu.nodes()}
            holder = [
                nid for nid, n in nodes.items()
                if n["resources"]["total"].get("nodeB")
            ][0]
            one, ran_on = ray_tpu.get(consume.remote(big), timeout=120)
            assert one == 1
            assert ran_on == holder, (
                f"task with 100MB arg ran on {ran_on[:8]}, arg lives on {holder[:8]}"
            )
        finally:
            cluster.shutdown()

    def test_agent_owned_worker_pool(self):
        """Leases on non-head nodes get workers from the AGENT's pool."""
        from ray_tpu.core.cluster_utils import Cluster

        cluster = Cluster()
        cluster.add_node(num_cpus=2, resources={"only_here": 1})
        cluster.connect()
        try:

            @ray_tpu.remote(num_cpus=1, resources={"only_here": 0.01})
            def where():
                from ray_tpu import runtime_context

                return runtime_context.get_runtime_context().get_node_id()

            nodes = {n["node_id"]: n for n in ray_tpu.nodes()}
            target = [
                nid for nid, n in nodes.items()
                if n["resources"]["total"].get("only_here")
            ][0]
            outs = ray_tpu.get([where.remote() for _ in range(6)], timeout=120)
            assert all(o == target for o in outs)
        finally:
            cluster.shutdown()


def test_pack_normal_task_preserves_strategy_for_lineage():
    """The lineage record on the worker side must carry the original
    scheduling strategy: a PG-pinned task whose shm result is lost would
    otherwise be reconstructed with DEFAULT placement (advisor r3)."""
    from ray_tpu.core.task_spec import (
        SchedulingStrategy, TaskSpec, TaskType, pack_normal_task,
        unpack_normal_task,
    )
    from ray_tpu.core.resources import ResourceSet
    from ray_tpu.utils.ids import PlacementGroupID, TaskID

    pgid = PlacementGroupID.from_random()
    spec = TaskSpec(
        task_id=TaskID.from_random(),
        task_type=TaskType.NORMAL_TASK,
        name="t",
        func_digest=b"d",
        func_blob=b"f",
        args_blob=b"a",
        dependencies=[],
        num_returns=1,
        resources=ResourceSet({"CPU": 1}),
        owner_id=None,
        scheduling_strategy=SchedulingStrategy(
            kind="PLACEMENT_GROUP", placement_group_id=pgid, bundle_index=2
        ),
        retry_exceptions=True,
    )
    out = unpack_normal_task(pack_normal_task(spec))
    assert out.scheduling_strategy.kind == "PLACEMENT_GROUP"
    assert out.scheduling_strategy.placement_group_id == pgid
    assert out.scheduling_strategy.bundle_index == 2
    assert out.retry_exceptions is True
    # DEFAULT stays cheap on the wire (None slot)
    spec2 = TaskSpec(
        task_id=TaskID.from_random(), task_type=TaskType.NORMAL_TASK,
        name="t", func_digest=b"d", func_blob=b"f", args_blob=b"a",
        dependencies=[], num_returns=1, resources=ResourceSet(),
        owner_id=None,
    )
    packed = pack_normal_task(spec2)
    assert packed[11] is None
    assert unpack_normal_task(packed).scheduling_strategy.kind == "DEFAULT"
