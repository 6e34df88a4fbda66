"""Data-plane chaos: kills and latency injected INTO live transfers
(reference: python/ray/tests/chaos/ network-delay manifests +
pull_manager.h:43-52 failure handling). The chaos_fetch_delay_ms system
config stretches chunk serving so faults land mid-pull.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import ray_tpu
from ray_tpu.core.cluster_utils import Cluster


def _node_with(resource: str):
    for n in ray_tpu.nodes():
        if n["resources"]["total"].get(resource):
            return n["node_id"]
    raise AssertionError(f"no node with {resource}")


def test_source_node_dies_mid_pull_reconstructs():
    """A reader blocked on chunk N of a cross-node pull whose SOURCE dies
    must not hang: lineage reconstruction re-runs the producer elsewhere
    and the retried consumer completes with correct data."""
    from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy

    cluster = Cluster(
        head_resources={"CPU": 2},
        system_config={"chaos_fetch_delay_ms": 300},
    )
    src_handle = cluster.add_node(num_cpus=2, resources={"src": 1})
    cluster.add_node(num_cpus=2, resources={"dst": 1})
    cluster.connect()
    try:
        src_node = _node_with("src")

        @ray_tpu.remote(
            num_cpus=1,
            max_retries=2,
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=src_node, soft=True  # soft: reconstruction relocates
            ),
        )
        def produce():
            import numpy as _np

            return _np.full(40 * 1024 * 1024, 7, dtype=_np.uint8)

        @ray_tpu.remote(num_cpus=1, resources={"dst": 0.01},
                        max_retries=4, retry_exceptions=True)
        def consume(x):
            return int(x[0]), int(x[-1]), x.nbytes

        big = produce.remote()
        ray_tpu.wait([big], timeout=120)
        out_ref = consume.remote(big)
        # 40 MB at 8 MB chunks × 300 ms injected delay: the pull is in
        # flight for >= ~600 ms — kill the source while the reader is
        # blocked on a chunk.
        time.sleep(0.45)
        cluster.remove_node(src_handle)  # SIGKILL the source agent
        first, last, nbytes = ray_tpu.get(out_ref, timeout=240)
        assert (first, last, nbytes) == (7, 7, 40 * 1024 * 1024)
        # no leaked pull state: a fresh read of the (reconstructed)
        # object also completes
        arr = ray_tpu.get(big, timeout=240)
        assert arr[12345] == 7
    finally:
        cluster.shutdown()


def test_controller_dies_mid_transfer_then_journal_recovery(tmp_path):
    """Kill -9 the controller while a delayed cross-node pull is in
    flight: the blocked get must FAIL promptly (no hang), and a
    controller restarted on the same session dir recovers its journaled
    state."""
    cluster = Cluster(
        head_resources={"CPU": 2},
        # Short reconnect window: this test asserts the blocked get FAILS
        # promptly when the controller is gone for good — riding a
        # restart is test_controller_restart_mid_training's job.
        system_config={"chaos_fetch_delay_ms": 300,
                       "controller_reconnect_window_s": 1.0},
    )
    cluster.add_node(num_cpus=2, resources={"src": 1})
    cluster.connect()
    session = cluster._session_dir
    try:
        from ray_tpu.experimental import internal_kv

        internal_kv._internal_kv_put(b"chaos_persist", b"survives")

        @ray_tpu.remote(num_cpus=1, resources={"src": 0.01})
        def produce():
            import numpy as _np

            return _np.ones(40 * 1024 * 1024, dtype=_np.uint8)

        big = produce.remote()
        ray_tpu.wait([big], timeout=120)

        state = {}

        def reader():
            t0 = time.monotonic()
            try:
                ray_tpu.get(big, timeout=60)  # head pulls from src (delayed)
                state["outcome"] = "ok"
            except Exception as e:  # noqa: BLE001
                state["outcome"] = type(e).__name__
            state["dt"] = time.monotonic() - t0

        t = threading.Thread(target=reader)
        t.start()
        time.sleep(0.4)  # mid-pull
        cluster._proc.send_signal(signal.SIGKILL)
        t.join(timeout=45)
        assert not t.is_alive(), "get() hung after controller death"
        # either the value landed before the kill or the error surfaced
        # promptly — both are non-hangs
        assert state["dt"] < 45, state
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001
            pass
        cluster.shutdown()

    # restart the control plane on the SAME session dir → journal replay
    from ray_tpu.core.node_agent import child_env

    os.remove(os.path.join(session, "controller_port"))
    log = open(os.path.join(session, "logs", "controller.log"), "ab")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "ray_tpu.core.controller",
            "--session-dir", session, "--port", "0",
            "--resources", json.dumps({"CPU": 2}), "--config", "{}",
        ],
        env=child_env(), stdout=log, stderr=subprocess.STDOUT,
    )
    try:
        port_file = os.path.join(session, "controller_port")
        deadline = time.time() + 30
        while time.time() < deadline and not (
            os.path.exists(port_file) and open(port_file).read().strip()
        ):
            time.sleep(0.05)
        port = int(open(port_file).read().strip())
        ray_tpu.init(address=f"127.0.0.1:{port}")
        from ray_tpu.experimental import internal_kv as kv2

        assert kv2._internal_kv_get(b"chaos_persist") == b"survives"
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:  # noqa: BLE001
            pass
        proc.send_signal(signal.SIGKILL)


def test_delayed_links_concurrent_pulls_correct():
    """Latency injected into every agent↔agent chunk fetch: concurrent
    pulls of one object from multiple nodes (including the concurrent-
    create seal-wait path) still deliver correct bytes, within bounded
    time."""
    cluster = Cluster(
        head_resources={"CPU": 1},
        system_config={"chaos_fetch_delay_ms": 100},
    )
    cluster.add_node(num_cpus=2, resources={"src": 1})
    cluster.add_node(num_cpus=2, resources={"a": 1})
    cluster.add_node(num_cpus=2, resources={"b": 1})
    cluster.connect()
    try:

        @ray_tpu.remote(num_cpus=1, resources={"src": 0.01})
        def produce():
            import numpy as _np

            return _np.arange(16 * 1024 * 1024, dtype=_np.uint8)

        @ray_tpu.remote(num_cpus=1)
        def check(x, where):
            return (int(x[1]), int(x[255]), x.nbytes)

        big = produce.remote()
        ray_tpu.wait([big], timeout=120)
        refs = []
        for res in ("a", "b"):
            for i in range(2):  # 2 concurrent consumers per node → seal-wait
                refs.append(
                    check.options(resources={res: 0.01}).remote(big, f"{res}{i}")
                )
        outs = ray_tpu.get(refs, timeout=240)
        assert all(o == (1, 255, 16 * 1024 * 1024) for o in outs), outs
    finally:
        cluster.shutdown()
