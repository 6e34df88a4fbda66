"""Elastic gang training: host-death survival, fast detection,
non-blocking checkpoints, and deterministic RPC-level fault injection.

Reference test models: python/ray/train/tests/test_backend.py (failure
injection) + python/ray/tests/chaos suites (kill components mid-run) —
here the chaos is deterministic (seeded FaultSchedule / exact SIGKILLs)
and the gang must complete WITHOUT TrainingFailedError.
"""
import os
import signal
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train import (
    CheckpointConfig,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)


def _make_elastic_loop():
    """Checkpoint-every-step loop reporting (step, ws, resumed_from);
    paced so a mid-run kill lands between steps. Built as a CLOSURE so
    it ships by value (test modules are not importable in workers)."""

    def _elastic_loop(config):
        import os
        import tempfile
        import time

        import numpy as np

        from ray_tpu import train

        ctx = train.get_context()
        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            with ckpt.as_directory() as d:
                start = int(np.load(os.path.join(d, "step.npy"))) + 1
        for step in range(start, config["steps"]):
            time.sleep(config.get("step_s", 0.25))
            with tempfile.TemporaryDirectory() as d:
                if ctx.get_world_rank() == 0:
                    np.save(os.path.join(d, "step.npy"), np.int64(step))
                train.report(
                    {
                        "step": step,
                        "ws": ctx.get_world_size(),
                        "resumed_from": start,
                    },
                    checkpoint=train.Checkpoint.from_directory(d),
                )

    return _elastic_loop


def _actor_node_ids():
    """node ids currently hosting actor workers (in these tests the only
    actors are the gang's TrainWorkers)."""
    from ray_tpu.util import state as state_api

    return {
        w["node_id"]
        for w in state_api.list_workers()
        if w.get("state") == "ACTOR"
    }


def _kill_one_train_host(cluster, storage, marker_index=1, timeout=60.0):
    """SIGKILL the agent of one node hosting a train worker, once the
    run has committed checkpoint ``marker_index`` (so the kill provably
    lands MID-run)."""
    marker = os.path.join(
        storage, f"checkpoint_{marker_index:06d}", ".complete"
    )
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(marker):
            break
        time.sleep(0.05)
    else:
        raise TimeoutError("training never reached the kill point")
    hosts = _actor_node_ids()
    for handle in cluster._nodes:
        if handle.node_id_hex in hosts:
            handle.proc.send_signal(signal.SIGKILL)
            return handle.node_id_hex
    raise AssertionError(f"no cluster node hosts a train worker: {hosts}")


@pytest.fixture
def train_cluster():
    """Head that only coordinates (1 CPU — too small for a {CPU: 2}
    train bundle, so gang capacity lives ONLY on the added nodes) plus
    per-test 2-CPU worker nodes."""
    from ray_tpu.core.cluster_utils import Cluster

    cluster = Cluster(head_resources={"CPU": 1})
    yield cluster
    cluster.shutdown()


def _run_elastic(cluster, tmp_path, *, name, steps, scaling, spare_nodes):
    for _ in range(2 + spare_nodes):
        cluster.add_node(num_cpus=2)
    cluster.connect()
    storage = str(tmp_path)
    trainer = JaxTrainer(
        _make_elastic_loop(),
        train_loop_config={"steps": steps},
        scaling_config=scaling,
        run_config=RunConfig(
            name=name,
            storage_path=storage,
            failure_config=FailureConfig(
                max_failures=2,
                # rejoin: a ceiling, repair proceeds as soon as the
                # replacement places; remesh: paid in full, keep it short
                elastic_grace_s=15.0 if spare_nodes else 1.0,
            ),
        ),
    )
    run_storage = os.path.join(storage, name)
    killed = {}

    def chaos():
        killed["node"] = _kill_one_train_host(cluster, run_storage)

    killer = threading.Thread(target=chaos, daemon=True)
    killer.start()
    result = trainer.fit()
    killer.join(timeout=10)
    assert "node" in killed, "chaos thread never killed a host"
    return result, killed["node"]


def _train_summary_with_recovery(mode):
    """``summarize_train()`` once the controller has counted a recovery of
    ``mode``, or the last one read 10 s from now. The driver's counters
    reach the controller with its metric flusher, every
    ``metrics_report_interval_ms`` (2 s); ``fit()`` does not wait for it."""
    from ray_tpu.util import state as state_api

    deadline = time.monotonic() + 10
    while True:
        summary = state_api.summarize_train()
        if summary["recoveries"].get(mode, 0) >= 1 or time.monotonic() > deadline:
            return summary
        time.sleep(0.2)


def test_gang_survives_host_death_rejoin(train_cluster, tmp_path):
    """SIGKILL one train worker's HOST mid-run with a spare node
    available: the gang repairs via replacement rejoin at the SAME world
    size and the job completes without TrainingFailedError, losing at
    most checkpoint_every (=1) steps."""
    result, killed_node = _run_elastic(
        train_cluster, tmp_path, name="rejoin", steps=8,
        scaling=ScalingConfig(
            num_workers=2, resources_per_worker={"CPU": 2}
        ),
        spare_nodes=1,
    )
    assert result.error is None, result.error
    assert result.metrics["step"] == 7
    # Same world size all the way through: rejoin, not re-mesh.
    assert result.metrics["ws"] == 2
    assert [r["mode"] for r in result.recoveries] == ["rejoin"]
    rec = result.recoveries[0]
    # Fast detection: the death channel beat any RPC timeout. The bound
    # is loose (CI box), but a timeout-based path would be >= 30s.
    assert 0 <= rec["detect_ms"] < 10000
    assert rec["world_size"] == 2
    # steps_lost <= checkpoint_every(=1): the resumed incarnation
    # restarted at most one step behind the dead incarnation's furthest
    # report (first-incarnation entries carry resumed_from=0).
    resumed_from = result.metrics["resumed_from"]
    assert resumed_from > 0, "resume never happened"
    prev_steps = [
        m["step"] for m in result.metrics_history
        if m["resumed_from"] < resumed_from
    ]
    steps_lost = max(prev_steps, default=resumed_from - 1) - resumed_from + 1
    assert steps_lost <= 1, (resumed_from, sorted(prev_steps))
    # Recovery is observable: lifecycle chart the node death, metrics
    # count it.
    from ray_tpu.util import state as state_api

    events = state_api.list_lifecycle_events()
    assert any(
        e["kind"] == "node" and e["state"] == "DEAD"
        and e["id"] == killed_node
        for e in events
    )
    summary = _train_summary_with_recovery("rejoin")
    assert summary["recoveries"].get("rejoin", 0) >= 1, summary
    assert summary["worker_deaths"] >= 1, summary


def test_gang_remesh_when_no_capacity(train_cluster, tmp_path):
    """SIGKILL a train host with NO spare capacity and min_workers=1:
    after elastic_grace_s the gang re-meshes to the surviving worker and
    completes at the smaller width."""
    result, _ = _run_elastic(
        train_cluster, tmp_path, name="remesh", steps=8,
        scaling=ScalingConfig(
            num_workers=2, resources_per_worker={"CPU": 2}, min_workers=1
        ),
        spare_nodes=0,
    )
    assert result.error is None, result.error
    assert result.metrics["step"] == 7
    # Resumed at the SMALLER data-parallel width.
    assert result.metrics["ws"] == 1
    assert [r["mode"] for r in result.recoveries] == ["remesh"]
    assert result.recoveries[0]["world_size"] == 1
    summary = _train_summary_with_recovery("remesh")
    assert summary["recoveries"].get("remesh", 0) >= 1, summary


def test_worker_kill_detected_fast(ray_start_regular, tmp_path):
    """In-box variant: SIGKILL one train WORKER process; the executor's
    death-channel watcher raises GangMemberDiedError within its poll
    slice and the gang rejoins on the same node."""
    result_holder = {}

    def run():
        trainer = JaxTrainer(
            _make_elastic_loop(),
            train_loop_config={"steps": 6, "step_s": 0.3},
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(
                name="fastdetect", storage_path=str(tmp_path),
                failure_config=FailureConfig(max_failures=1,
                                             elastic_grace_s=20.0),
            ),
        )
        result_holder["result"] = trainer.fit()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    # Wait for the first checkpoint, then SIGKILL one TrainWorker pid.
    marker = os.path.join(tmp_path, "fastdetect", "checkpoint_000001",
                          ".complete")
    deadline = time.time() + 60
    while time.time() < deadline and not os.path.exists(marker):
        time.sleep(0.05)
    assert os.path.exists(marker), "run never produced checkpoint 1"
    from ray_tpu.util import state as state_api

    victims = [
        w for w in state_api.list_workers()
        if w.get("state") == "ACTOR" and w.get("pid")
    ]
    assert victims, state_api.list_workers()
    os.kill(victims[0]["pid"], signal.SIGKILL)
    t.join(timeout=120)
    assert not t.is_alive(), "fit() wedged after worker kill"
    result = result_holder["result"]
    assert result.error is None, result.error
    assert result.metrics["step"] == 5
    assert len(result.recoveries) == 1
    rec = result.recoveries[0]
    assert rec["mode"] == "rejoin"
    assert 0 <= rec["detect_ms"] < 10000


# ---------------------------------------------------------------------------
# Deterministic fault injection
# ---------------------------------------------------------------------------


def _plan():
    return {
        "seed": 13,
        "rules": [
            {"method": "kv_put", "direction": "out", "action": "error",
             "after": 2, "count": 1},
            {"method": "kv_get", "direction": "out", "action": "delay",
             "delay_ms": 50, "count": 2},
            {"method": "kv_*", "direction": "out", "action": "drop",
             "probability": 0.0},  # seeded: never fires at p=0
        ],
    }


def test_fault_schedule_replays_identically():
    """Two schedules built from the same plan, fed the same frame
    sequence, inject the IDENTICAL timeline (seq, rule, action)."""
    from ray_tpu.util.chaos import FaultSchedule

    seq = [("kv_put", "out", ""), ("kv_get", "out", ""),
           ("kv_put", "out", ""), ("kv_put", "out", ""),
           ("kv_get", "out", ""), ("kv_get", "out", ""),
           ("kv_put", "out", "")] * 3
    logs = []
    for _ in range(2):
        s = FaultSchedule.from_plan(_plan())
        decisions = [s.intercept(*frame) for frame in seq]
        logs.append((s.log(), [d and d["action"] for d in decisions]))
    assert logs[0] == logs[1]
    log = logs[0][0]
    assert [e["action"] for e in log] == ["delay", "error", "delay"]


def test_fault_injection_at_rpc_layer(ray_start_regular):
    """An installed plan injects errors/delays into REAL control-plane
    RPCs and records the timeline; clearing the plan restores service."""
    from ray_tpu.experimental import internal_kv
    from ray_tpu.util import chaos

    internal_kv._internal_kv_put(b"warm", b"1", namespace="chaosns")
    sched = chaos.install_fault_plan(
        {"seed": 1, "rules": [
            {"method": "kv_put", "direction": "out", "action": "error",
             "count": 1},
        ]}
    )
    try:
        with pytest.raises(chaos.InjectedFaultError):
            internal_kv._internal_kv_put(b"k", b"v", namespace="chaosns")
        # count=1 exhausted: the next put succeeds.
        internal_kv._internal_kv_put(b"k2", b"v2", namespace="chaosns")
        assert internal_kv._internal_kv_get(b"k2", namespace="chaosns") == b"v2"
        log = chaos.injection_log()
        assert [e["method"] for e in log] == ["kv_put"]
        assert log[0]["peer"] == "controller"
    finally:
        chaos.install_fault_plan(None)


def test_slow_node_throttle_via_agent_plan(ray_start_cluster):
    """Agent-level slow-node throttling: a delay-all plan installed on a
    RUNNING agent stretches that node's control responses; clearing it
    restores speed."""
    cluster = ray_start_cluster
    node = cluster.add_node(num_cpus=1)
    cluster.connect()
    from ray_tpu.util import chaos

    @ray_tpu.remote(num_cpus=1)
    def noop():
        return os.environ.get("RAY_TPU_NODE_ID", "")

    # Warm: a task must run on the (only) 1-cpu agent node when the head
    # has no CPU left... head has CPUs, so just verify the install RPC
    # round-trips and the agent acknowledges.
    assert chaos.install_plan_on_node(
        node.node_id,
        {"rules": [{"method": "*", "direction": "in", "action": "delay",
                    "delay_ms": 150}]},
    )
    assert chaos.install_plan_on_node(node.node_id, None)
    # A DROP-ALL partition must still be clearable at runtime: the
    # install/clear frames themselves are fault-exempt at the RPC layer.
    assert chaos.install_plan_on_node(
        node.node_id,
        {"rules": [{"method": "*", "direction": "in", "action": "drop"}]},
    )
    assert chaos.install_plan_on_node(node.node_id, None)
    with pytest.raises(Exception):
        chaos.install_plan_on_node("ff" * 16, None)  # unknown node


# ---------------------------------------------------------------------------
# Non-blocking checkpoints: crash consistency
# ---------------------------------------------------------------------------


def _upload_pair(root, index, world=2, rank0_hook=None):
    """Simulate both ranks' writers uploading checkpoint ``index``;
    returns (manager-registerable path). rank1 always completes; rank0
    runs under ``rank0_hook``."""
    import tempfile

    from ray_tpu.train.checkpoint import CheckpointWriter

    dest = os.path.join(root, f"checkpoint_{index:06d}")
    writers = []
    for rank in range(world):
        staging = tempfile.mkdtemp(prefix=f"stage_r{rank}_")
        np.save(os.path.join(staging, f"shard_{rank}.npy"),
                np.full((4,), index, np.float32))
        w = CheckpointWriter(
            rank, world,
            fault_hook=rank0_hook if rank == 0 else None,
            complete_timeout_s=5.0,
        )
        w.submit(staging, dest)
        writers.append(w)
    for w in writers:
        w.drain(timeout=10)
        w.stop()
    return dest


def test_checkpoint_writer_crash_consistency(tmp_path):
    """Kill rank 0's writer at EVERY seeded fault point mid-upload:
    manager.latest must always resolve to the last COMPLETE checkpoint —
    never the torn one — and that checkpoint must load."""
    from ray_tpu.train.checkpoint import (
        Checkpoint,
        CheckpointManager,
        CheckpointWriter,
        WriterKilled,
    )

    for i, point in enumerate(CheckpointWriter._POINTS):
        root = str(tmp_path / point)
        mgr = CheckpointManager(root)
        good = _upload_pair(root, 0)
        mgr.register(Checkpoint(good), {}, 0)
        assert mgr.latest is not None and mgr.latest.index == 0

        def kill_at(p, dest, _point=point):
            if p == _point:
                raise WriterKilled(_point)

        torn = _upload_pair(root, 1, rank0_hook=kill_at)
        mgr.register(Checkpoint(torn), {}, 1)
        # The torn upload never committed: .complete absent, latest
        # stays anchored on the complete checkpoint and loads clean.
        assert not os.path.exists(os.path.join(torn, ".complete")), point
        latest = mgr.latest
        assert latest is not None and latest.index == 0, point
        arr = np.load(os.path.join(latest.checkpoint.path, "shard_0.npy"))
        np.testing.assert_array_equal(arr, np.zeros(4, np.float32))
        # A manager RESTORED from disk (the recovery path) agrees.
        mgr2 = CheckpointManager.restore_state(root)
        mgr2.sync_from_storage()
        assert mgr2.latest is not None
        assert mgr2.latest.checkpoint.path == good, point

    # Control arm: no fault — the commit protocol completes and latest
    # advances past the old anchor.
    root = str(tmp_path / "clean")
    mgr = CheckpointManager(root)
    d0 = _upload_pair(root, 0)
    mgr.register(Checkpoint(d0), {}, 0)
    d1 = _upload_pair(root, 1)
    mgr.register(Checkpoint(d1), {}, 1)
    assert os.path.exists(os.path.join(d1, ".complete"))
    assert mgr.latest.index == 1


def test_async_report_nonblocking_and_commits(ray_start_regular, tmp_path):
    """train.report(checkpoint=..) with async_upload returns while the
    upload is still in flight (step blocks only for the host snapshot),
    and fit() completing implies every checkpoint committed."""
    gate_dir = str(tmp_path / "gate")
    os.makedirs(gate_dir, exist_ok=True)

    def loop(config):
        import tempfile

        from ray_tpu import train

        for step in range(3):
            t0 = time.monotonic()
            with tempfile.TemporaryDirectory() as d:
                np.save(os.path.join(d, "step.npy"), np.int64(step))
                # ~4MB payload: a sync upload would pay the copy twice.
                np.save(os.path.join(d, "blob.npy"),
                        np.zeros((1024, 1024), np.float32))
                train.report({"step": step, "report_s": 0.0},
                             checkpoint=train.Checkpoint.from_directory(d))

    trainer = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="async_ck", storage_path=str(tmp_path),
            checkpoint_config=CheckpointConfig(async_upload=True),
        ),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    # fit() returned => writer drained => every checkpoint committed.
    for step in range(3):
        dest = os.path.join(str(tmp_path), "async_ck",
                            f"checkpoint_{step:06d}")
        assert os.path.exists(os.path.join(dest, ".complete")), step
        assert int(np.load(os.path.join(dest, "step.npy"))) == step


def test_async_resume_skips_torn_latest(ray_start_regular, tmp_path):
    """A restart whose newest checkpoint directory is torn (no
    .complete) resumes from the newest COMPLETE one."""
    storage = str(tmp_path)
    name = "torn"
    run_dir = os.path.join(storage, name)
    marker = str(tmp_path / "died_once")

    def loop(config):
        import tempfile

        from ray_tpu import train

        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            start = int(np.load(os.path.join(ckpt.path, "step.npy"))) + 1
        for step in range(start, 4):
            with tempfile.TemporaryDirectory() as d:
                np.save(os.path.join(d, "step.npy"), np.int64(step))
                train.report({"step": step, "resumed_from": start},
                             checkpoint=train.Checkpoint.from_directory(d))
            if step == 2 and not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                # Fake the torn upload the death would leave behind:
                # strip checkpoint_000002's commit marker, then die.
                os.remove(os.path.join(config["run_dir"],
                                       "checkpoint_000002", ".complete"))
                os._exit(1)

    trainer = JaxTrainer(
        loop,
        train_loop_config={"marker": marker, "run_dir": run_dir},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name=name, storage_path=storage,
            failure_config=FailureConfig(max_failures=1,
                                         elastic_grace_s=15.0),
        ),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["step"] == 3
    # Resumed from step 1 (the newest COMPLETE checkpoint), not the torn 2.
    assert result.metrics["resumed_from"] == 2
