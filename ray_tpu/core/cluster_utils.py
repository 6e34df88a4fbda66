"""Test-cluster utilities: multi-node clusters on one host.

Reference: python/ray/cluster_utils.py:135 ``Cluster`` / ``add_node`` :201 /
``remove_node`` :279 — the reference's workhorse for multi-node tests spawns
extra raylets with fake resources on localhost; we spawn extra node agents.
"""
from __future__ import annotations

import contextlib
import json
import os
import re
import signal
import subprocess
import sys
import time
from typing import Dict, List, NamedTuple, Optional

from ray_tpu.core import api
from ray_tpu.core.client import CoreWorker
from ray_tpu.utils import rpc


_CLUSTER_PROC = re.compile(r"ray_tpu\.core\.(controller|worker_main|node_agent)")

# ``end_cluster``: a process still running this long after it was told to
# exit is killed; one that is on its way out (a zombie leader whose threads
# are still in the kernel, giving back four chips' mappings: 16 s observed)
# is waited for up to the second bound.
KILL_AFTER_S = 5.0
GONE_BOUND_S = 120.0


class ProcStat(NamedTuple):
    state: str  # R, S, D, Z, ...
    pgrp: int
    threads: int
    start: int  # clock ticks since boot

    @property
    def dying(self) -> bool:
        """Exited, with threads still inside the kernel (``ps``: ``Zl``)."""
        return self.state == "Z" and self.threads > 1


def proc_stat(pid: int) -> Optional[ProcStat]:
    """What ``/proc/<pid>/stat`` says; None when /proc has no such entry."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            raw = f.read()
    except OSError:
        return None
    rest = raw[raw.rindex(b")") + 2:].split()  # the command may hold spaces
    return ProcStat(rest[0].decode(), int(rest[2]), int(rest[17]), int(rest[19]))


def process_start(pid: int) -> int:
    """The process's start time (0: no such process). With the pid it names
    ONE process for as long as the host is up: a reused pid starts later."""
    st = proc_stat(pid)
    return st.start if st else 0


def is_gone(pid: int, start: Optional[int] = None) -> bool:
    """THE definition of "gone": /proc has no entry for ``pid`` (or has one
    that started at another time than ``start``: the pid was reused), or the
    process is a zombie with one thread left. A plain ``Z`` holds nothing and
    may stay for ever (PID 1 of a container need not reap). A ``Zl``, a zombie
    LEADER whose other threads are still inside the kernel, holds every file,
    socket and device the process had (for a TPU worker ``/dev/vfio/<group>``)
    until the last of them is done, although its cmdline already reads empty."""
    st = proc_stat(pid)
    if st is None or (start is not None and st.start != start):
        return True
    return st.state == "Z" and not st.dying


def cluster_processes() -> Dict[int, str]:
    """{pid: command line} of every controller, node agent and worker on
    this host that is not gone (``is_gone``), whoever's cluster it is.
    A zombie's cmdline is empty, so one that still has threads is told
    from a stranger's by its process group: the cluster's processes are
    started in the caller's."""
    found = {}
    mine = os.getpgrp()
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue  # exited while we looked
        if _CLUSTER_PROC.search(cmd):
            found[int(pid)] = cmd.strip()
        elif not cmd:
            st = proc_stat(int(pid))
            if st and st.dying and st.pgrp == mine:
                found[int(pid)] = f"<defunct, {st.threads} threads>"
    return found


def wait_cluster_processes_gone(timeout_s: float = 60.0) -> None:
    """Block until ``cluster_processes()`` finds none: the host-wide barrier
    for a caller OUTSIDE a cluster, between a cluster that used the chip and
    whatever opens it next. After a ``shutdown()`` that ended its own cluster
    (``end_cluster``) it returns at once."""
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


def end_cluster(core: CoreWorker) -> None:
    """The ONE place that ends a cluster: tell the controller, which answers
    with (pid, start time) of every process on its host that it is about to
    tell to exit, itself among them, and return when each is gone
    (``is_gone``): nothing of THIS cluster holds a thread, a socket or a chip
    any more, and nobody else's cluster was looked at."""
    # Deliberate teardown: the controller dies on receipt, so never ride
    # the reconnect window on its way down.
    core._reconnect_dead = True
    try:
        procs = core._call("shutdown_cluster", timeout=5)
    except Exception:  # noqa: BLE001 — controller already gone: it names nothing
        return
    t0 = time.monotonic()
    killed = False
    pause = 0.005
    while True:
        left = [(pid, start) for pid, start in procs if not is_gone(pid, start)]
        if not left:
            return
        waited = time.monotonic() - t0
        if waited > GONE_BOUND_S:
            raise TimeoutError(
                f"processes of the cluster not gone {waited:.0f}s after "
                f"shutdown_cluster: {[(pid, proc_stat(pid)) for pid, _ in left]}"
            )
        if waited > KILL_AFTER_S and not killed:
            killed = True
            for pid, _ in left:  # nothing to one that is already on its way out
                with contextlib.suppress(ProcessLookupError):
                    os.kill(pid, signal.SIGKILL)
        time.sleep(pause)
        pause = min(pause * 2, 0.05)


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
            end_cluster(self._admin)
        finally:
            self._admin.disconnect()
            self._admin_runner.stop()
            # what was not gone within end_cluster's bound, and the reaping
            # of what was: all of them are this process's children
            for proc in [h.proc for h in self._nodes] + [self._proc]:
                proc.kill()
                proc.wait(timeout=10)
