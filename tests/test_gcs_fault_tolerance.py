"""Controller (GCS) fault tolerance: journal persistence + restart
recovery.

Reference test model: python/ray/tests/test_gcs_fault_tolerance.py —
kill the GCS, restart it against persistent storage, verify KV /
named-detached-actor / PG state survives.
"""
import json
import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.core import api as core_api
from ray_tpu.core.persistence import GcsJournal, RestoredState


# ---------------------------------------------------------------------------
# Journal unit tests
# ---------------------------------------------------------------------------


def test_journal_roundtrip(tmp_path):
    j = GcsJournal(str(tmp_path))
    j.kv_put("ns1", b"k1", b"v1")
    j.kv_put("ns1", b"k2", b"v2")
    j.kv_del("ns1", b"k1")
    j.pg_create("aa" * 8, [{"CPU": 1}], "PACK", "mypg")
    j.pg_create("bb" * 8, [{"CPU": 2}], "SPREAD", "gone")
    j.pg_remove("bb" * 8)
    j.close()

    state = GcsJournal(str(tmp_path)).replay()
    assert state.kv == {"ns1": {b"k2": b"v2"}}
    assert list(state.pgs) == ["aa" * 8]
    assert state.pgs["aa" * 8]["strategy"] == "PACK"


def test_journal_torn_tail(tmp_path):
    j = GcsJournal(str(tmp_path))
    j.kv_put("ns", b"a", b"1")
    j.close()
    # Simulate a crash mid-append: garbage partial line at the tail.
    with open(j.path, "a") as f:
        f.write('{"op": "kv_put", "ns": "ns", "key"')
    j2 = GcsJournal(str(tmp_path))
    state = j2.replay()
    assert state.kv == {"ns": {b"a": b"1"}}
    # Replay truncated the torn bytes: post-restart appends must not merge
    # into the partial line and must survive the NEXT replay.
    j2.kv_put("ns", b"b", b"2")
    j2.close()
    state2 = GcsJournal(str(tmp_path)).replay()
    assert state2.kv == {"ns": {b"a": b"1", b"b": b"2"}}


def test_invalid_lifetime_rejected(ray_start_regular):
    @ray_tpu.remote
    class A:
        pass

    with pytest.raises(ValueError, match="lifetime"):
        A.options(lifetime="Detached").remote()


def test_journal_compact(tmp_path):
    j = GcsJournal(str(tmp_path))
    for i in range(50):
        j.kv_put("ns", b"key", str(i).encode())  # 50 overwrites
    state = j.replay()
    j.compact(state)
    with open(j.path) as f:
        lines = [l for l in f if l.strip()]
    assert len(lines) == 1  # collapsed to latest value
    assert GcsJournal(str(tmp_path)).replay().kv == {"ns": {b"key": b"49"}}


# ---------------------------------------------------------------------------
# Controller restart integration
# ---------------------------------------------------------------------------


def _start_controller(session_dir, port=0, resources=None, config=None):
    from ray_tpu.core.node_agent import child_env

    os.makedirs(os.path.join(session_dir, "logs"), exist_ok=True)
    log = open(os.path.join(session_dir, "logs", "controller.log"), "ab")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "ray_tpu.core.controller",
            "--session-dir", session_dir,
            "--port", str(port),
            "--resources", json.dumps(resources or {"CPU": 4}),
            "--config", json.dumps(config or {}),
        ],
        env=child_env(),
        stdout=log,
        stderr=subprocess.STDOUT,
    )
    port_file = os.path.join(session_dir, "controller_port")
    deadline = time.time() + 30
    while time.time() < deadline:
        if os.path.exists(port_file):
            with open(port_file) as f:
                txt = f.read().strip()
            if txt:
                return proc, int(txt)
        time.sleep(0.05)
    raise TimeoutError("controller did not start")


def test_controller_restart_mid_training(tmp_path):
    """Kill -9 the controller while a train gang is between steps
    (persistence store intact) and restart it on the same port: agents,
    workers, and the driver all reconnect within
    ``controller_reconnect_window_s`` and training completes WITHOUT a
    gang restart — max_failures=0 makes any detect→repair cycle fail the
    job, so completion proves the restart was invisible to the gang."""
    import threading

    from ray_tpu.core.cluster_utils import Cluster
    from ray_tpu.train import (
        FailureConfig,
        JaxTrainer,
        RunConfig,
        ScalingConfig,
    )

    cluster = Cluster(
        head_resources={"CPU": 1},  # too small for a train bundle
        system_config={"controller_reconnect_window_s": 30.0},
    )
    restarted = {}
    try:
        for _ in range(2):
            cluster.add_node(num_cpus=2)
        cluster.connect()

        def loop(config):
            import os as _os
            import tempfile
            import time as _time

            import numpy as _np

            from ray_tpu import train

            ctx = train.get_context()
            start = 0
            ckpt = train.get_checkpoint()
            if ckpt is not None:
                with ckpt.as_directory() as d:
                    start = int(_np.load(_os.path.join(d, "step.npy"))) + 1
            for step in range(start, config["steps"]):
                _time.sleep(0.25)
                with tempfile.TemporaryDirectory() as d:
                    if ctx.get_world_rank() == 0:
                        _np.save(_os.path.join(d, "step.npy"),
                                 _np.int64(step))
                    train.report(
                        {"step": step, "resumed_from": start},
                        checkpoint=train.Checkpoint.from_directory(d),
                    )

        trainer = JaxTrainer(
            loop,
            train_loop_config={"steps": 8},
            scaling_config=ScalingConfig(
                num_workers=2, resources_per_worker={"CPU": 2}
            ),
            run_config=RunConfig(
                name="ctl_restart", storage_path=str(tmp_path),
                failure_config=FailureConfig(max_failures=0),
            ),
        )
        holder = {}

        def run():
            holder["result"] = trainer.fit()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        # Wait until the gang has committed checkpoint 1 — provably
        # mid-run, between steps (reports pace at ~0.25s).
        marker = os.path.join(str(tmp_path), "ctl_restart",
                              "checkpoint_000001", ".complete")
        deadline = time.time() + 60
        while time.time() < deadline and not os.path.exists(marker):
            time.sleep(0.05)
        assert os.path.exists(marker), "run never reached the kill point"

        # Hard-kill the control plane; the journal is the persistence
        # store and stays intact in the session dir.
        host, port = cluster.address.rsplit(":", 1)
        cluster._proc.send_signal(signal.SIGKILL)
        cluster._proc.wait(timeout=10)
        os.remove(os.path.join(cluster._session_dir, "controller_port"))
        proc2, port2 = _start_controller(
            cluster._session_dir, port=int(port), resources={"CPU": 1},
            config={"controller_reconnect_window_s": 30.0},
        )
        restarted["proc"] = proc2
        cluster._proc = proc2  # cluster.shutdown() reaps the new one
        assert port2 == int(port)

        t.join(timeout=120)
        assert not t.is_alive(), "fit() wedged across controller restart"
        result = holder["result"]
        assert result.error is None, result.error
        assert result.metrics["step"] == 7
        # No gang restart: zero recoveries and no checkpoint resume.
        assert result.recoveries == []
        assert result.metrics["resumed_from"] == 0
    finally:
        cluster.shutdown()


def test_closing_a_subscriber_rides_a_controller_restart(tmp_path):
    """A ``Subscriber.close()`` whose ``unsubscribe`` is the call that meets
    the lost connection reconnects ON ITS THREAD, and the reconnect re-issues
    the process's subscriptions under the module lock that ``close`` holds.
    It comes back, the lock is free for the next ``subscribe``, and what
    stayed subscribed is known to the new controller. (Until PR 56 it waited
    on itself for ever, with every later ``subscribe`` of the process, so
    every later ``fit()``, behind it: ``fit()``'s own close after
    ``test_controller_restart_mid_training``'s restart was such a call.)"""
    import threading

    from ray_tpu.experimental import pubsub

    session = str(tmp_path / "session")
    config = {"controller_reconnect_window_s": 30.0}
    proc, port = _start_controller(session, config=config)
    try:
        ray_tpu.init(address=f"127.0.0.1:{port}")
        kept = pubsub.subscribe("kept")
        closed = pubsub.subscribe("closed")
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)
        os.remove(os.path.join(session, "controller_port"))

        came_back = threading.Event()

        def close_then_subscribe():
            closed.close()  # meets ConnectionLost, dials until the restart below
            pubsub.subscribe("after").close()
            came_back.set()

        threading.Thread(target=close_then_subscribe, daemon=True).start()
        proc, port2 = _start_controller(session, port=port, config=config)
        assert port2 == port
        assert came_back.wait(60), "close() across the restart never came back"
        assert pubsub.publish("kept", "still here") == 1
        assert kept.get(timeout=10) == "still here"
        assert pubsub.publish("closed", "nobody") == 0
        kept.close()
    finally:
        proc.send_signal(signal.SIGKILL)
        ray_tpu.shutdown()


def test_controller_restart_recovers_state(tmp_path):
    """Kill -9 the controller; a restart on the same session dir restores
    KV entries, the PG table, and re-creates the named detached actor."""
    session = str(tmp_path / "session")
    os.makedirs(session, exist_ok=True)
    proc, port = _start_controller(session)
    try:
        ray_tpu.init(address=f"127.0.0.1:{port}")
        from ray_tpu.experimental import internal_kv

        internal_kv._internal_kv_put(b"persist_me", b"value1")

        @ray_tpu.remote
        class Keeper:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        k = Keeper.options(name="keeper", lifetime="detached").remote()
        assert ray_tpu.get(k.bump.remote(), timeout=30) == 1

        from ray_tpu.util.placement_group import placement_group
        pg = placement_group([{"CPU": 1}], strategy="PACK", name="ft_pg")
        assert pg.ready(timeout=30)

        # Hard-kill the control plane.
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)
        ray_tpu.shutdown()

        # Restart on the same session dir (same port so nothing cached
        # points at a stale address). Drop the dead controller's port file
        # first or the wait loop below would see the stale one.
        os.remove(os.path.join(session, "controller_port"))
        proc, port2 = _start_controller(session, port=port)
        ray_tpu.init(address=f"127.0.0.1:{port2}")
        from ray_tpu.experimental import internal_kv as kv2

        assert kv2._internal_kv_get(b"persist_me") == b"value1"

        # Detached actor was re-created from its journaled spec (fresh
        # state — the old process died with its memory).
        k2 = ray_tpu.get_actor("keeper")
        assert ray_tpu.get(k2.bump.remote(), timeout=60) == 1

        from ray_tpu.util.placement_group import placement_group_table
        table = placement_group_table()
        assert any(rec.get("name") == "ft_pg" for rec in table.values()), table
    finally:
        try:
            proc.send_signal(signal.SIGKILL)
        except Exception:
            pass
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
