"""Test-cluster utilities: multi-node clusters on one host.

Reference: python/ray/cluster_utils.py:135 ``Cluster`` / ``add_node`` :201 /
``remove_node`` :279 — the reference's workhorse for multi-node tests spawns
extra raylets with fake resources on localhost; we spawn extra node agents.
"""
from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

from ray_tpu.core import api
from ray_tpu.core.client import CoreWorker
from ray_tpu.utils import rpc


_CLUSTER_PROC = re.compile(r"ray_tpu\.core\.(controller|worker_main|node_agent)")


def cluster_processes() -> Dict[int, str]:
    """{pid: command line} of every live controller, node agent and
    worker on this host (from /proc; zombies have an empty cmdline)."""
    found = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue  # exited while we looked
        if _CLUSTER_PROC.search(cmd):
            found[int(pid)] = cmd.strip()
    return found


def wait_cluster_processes_gone(timeout_s: float = 60.0) -> None:
    """Block until none is left. ``shutdown()`` waits for the controller
    only; a worker that held a TPU still owns it until the process is
    gone, and the next process to ask for the chip fails. Call this
    between a cluster that used the chip and whatever opens it next."""
    deadline = time.monotonic() + timeout_s
    while True:
        left = cluster_processes()
        if not left:
            return
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"cluster processes still alive after {timeout_s:.0f}s: {left}"
            )
        time.sleep(0.2)


class NodeHandle:
    def __init__(self, proc: subprocess.Popen, node_id_hex: str):
        self.proc = proc
        self.node_id_hex = node_id_hex

    @property
    def node_id(self) -> str:
        return self.node_id_hex


class Cluster:
    def __init__(
        self,
        head_resources: Optional[Dict[str, float]] = None,
        system_config: Optional[Dict] = None,
    ):
        head_resources = dict(head_resources or {"CPU": 2})
        self.address, self._proc, self._session_dir = api._start_controller(
            head_resources, system_config or {}, owned=False
        )
        self._admin_runner = rpc.EventLoopThread("cluster-admin")
        self._admin = CoreWorker(self.address, mode="driver", loop_runner=self._admin_runner)
        self._nodes: List[NodeHandle] = []

    def _list_node_ids(self) -> set:
        return {n["node_id"] for n in self._admin.list_state("nodes") if n["state"] == "ALIVE"}

    def add_node(
        self,
        num_cpus: int = 1,
        resources: Optional[Dict[str, float]] = None,
        wait: bool = True,
        labels: Optional[Dict[str, str]] = None,
    ) -> NodeHandle:
        res = dict(resources or {})
        res.setdefault("CPU", num_cpus)
        from ray_tpu.core.node_agent import child_env

        before = self._list_node_ids()
        env = child_env()
        if labels:
            env["RAY_TPU_NODE_LABELS"] = json.dumps(labels)
        log = open(os.path.join(self._session_dir, "logs", f"agent-{len(self._nodes)}.log"), "ab")
        proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "ray_tpu.core.node_agent",
                "--controller",
                self.address,
                "--session-dir",
                self._session_dir,
                "--resources",
                json.dumps(res),
            ],
            env=env,
            stdout=log,
            stderr=subprocess.STDOUT,
        )
        node_id_hex = ""
        if wait:
            deadline = time.time() + 30
            while time.time() < deadline:
                new = self._list_node_ids() - before
                if new:
                    node_id_hex = next(iter(new))
                    break
                time.sleep(0.02)
            else:
                raise TimeoutError("node agent did not register")
        handle = NodeHandle(proc, node_id_hex)
        self._nodes.append(handle)
        return handle

    def remove_node(self, handle: NodeHandle, graceful: bool = False):
        """Kill a node (SIGKILL by default — simulates node failure,
        reference: cluster_utils.py:279)."""
        handle.proc.send_signal(signal.SIGTERM if graceful else signal.SIGKILL)
        deadline = time.time() + 30
        while time.time() < deadline:
            if handle.node_id_hex not in self._list_node_ids():
                return
            time.sleep(0.02)
        raise TimeoutError("node did not deregister")

    def wait_for_nodes(self, count: int, timeout: float = 30):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if len(self._list_node_ids()) >= count:
                return
            time.sleep(0.02)
        raise TimeoutError(f"cluster did not reach {count} nodes")

    def connect(self):
        return api.init(address=self.address)

    def shutdown(self):
        try:
            if api.is_initialized():
                api.shutdown()
        except Exception:
            pass
        try:
            # Deliberate teardown — don't ride the reconnect window.
            self._admin._reconnect_dead = True
            self._admin._call("shutdown_cluster", timeout=5)
        except Exception:
            pass
        self._admin.disconnect()
        self._admin_runner.stop()
        for h in self._nodes:
            try:
                h.proc.kill()
            except Exception:
                pass
        try:
            self._proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            self._proc.kill()
