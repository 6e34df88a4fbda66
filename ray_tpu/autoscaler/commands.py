"""Cluster launcher: ``ray-tpu up / down / attach / exec <cluster.yaml>``.

Reference: python/ray/scripts/scripts.py:2548-2579 (ray up/down/attach/
exec) driving python/ray/autoscaler/_private/commands.py
(create_or_update_cluster / teardown_cluster / exec_cluster / attach).

TPU reshape: the reference SSHes into a provisioned head VM; on TPU
fleets the operator's VM typically IS the head (pod slices attach as
workers), so ``up`` starts the head controller locally, spawns the
monitor process (autoscaler against the YAML's provider), and records
the cluster in ``~/.ray_tpu/clusters/<name>.json``. ``exec``/``attach``
run commands/shells against the head address from that record; remote
heads ride the provider (GCE: gcloud ssh) the way the reference rides
its auth config.

Cluster YAML schema::

    cluster_name: demo
    provider:
      type: fake            # or: gce_tpu
      # gce_tpu: project/zone/accelerator_type/runtime_version...
    head_resources: {CPU: 4}
    max_workers: 8          # global cap (reference: same key)
    idle_timeout_s: 60
    available_node_types:
      tpu_worker:
        resources: {CPU: 8, TPU: 4}
        labels: {pool: tpu}
        min_workers: 2
        max_workers: 4
"""
from __future__ import annotations

import json
import logging
import os
import signal
import subprocess
import sys
import time
from typing import Dict, Optional

logger = logging.getLogger("ray_tpu.autoscaler.commands")


def load_cluster_config(path_or_dict) -> dict:
    if isinstance(path_or_dict, dict):
        cfg = dict(path_or_dict)
    else:
        import yaml

        with open(path_or_dict) as f:
            cfg = yaml.safe_load(f)
    if not cfg.get("cluster_name"):
        raise ValueError("cluster config needs cluster_name")
    if not isinstance(cfg.get("provider"), dict) or "type" not in cfg["provider"]:
        raise ValueError("cluster config needs provider.type")
    cfg.setdefault("available_node_types", {})
    for tname, tcfg in cfg["available_node_types"].items():
        if "resources" not in tcfg:
            raise ValueError(f"node type {tname!r} needs resources")
    return cfg


def _state_dir() -> str:
    return os.path.join(os.path.expanduser("~"), ".ray_tpu", "clusters")


def cluster_state_path(name: str) -> str:
    return os.path.join(_state_dir(), f"{name}.json")


def read_cluster_state(name_or_path) -> dict:
    """Accepts a cluster name, a state .json path, or a cluster YAML.
    A bare name is ALWAYS a name — a same-named file/dir in the cwd must
    not shadow the cluster registry."""
    if isinstance(name_or_path, str) and name_or_path.endswith((".yaml", ".yml")) \
            and os.path.exists(name_or_path):
        name = load_cluster_config(name_or_path)["cluster_name"]
    elif isinstance(name_or_path, str) and name_or_path.endswith(".json") \
            and os.path.exists(name_or_path):
        with open(name_or_path) as f:
            return json.load(f)
    else:
        name = name_or_path
    p = cluster_state_path(name)
    if not os.path.exists(p):
        raise FileNotFoundError(
            f"no running cluster {name!r} (state file {p} missing) — "
            "run `ray-tpu up` first"
        )
    with open(p) as f:
        return json.load(f)


def _spawn_monitor(cfg: dict, address: str, session_dir: str) -> int:
    """Start the monitor process (autoscaler over the YAML's provider)."""
    from ray_tpu.core.node_agent import child_env

    provider_cfg = dict(cfg["provider"])
    # the provider needs the cluster identity: it labels/filters cloud
    # nodes by cluster so two clusters never reconcile each other's fleet
    provider_cfg.setdefault("cluster_name", cfg["cluster_name"])
    mon_cfg = {
        "provider": provider_cfg,
        "available_node_types": cfg["available_node_types"],
        "idle_timeout_s": cfg.get("idle_timeout_s", 60),
        "max_workers": cfg.get("max_workers"),
    }
    with open(os.path.join(session_dir, "logs", "monitor.log"), "ab") as log:
        mon = subprocess.Popen(
            [
                sys.executable, "-m", "ray_tpu.autoscaler.monitor",
                "--address", address, "--session-dir", session_dir,
                "--config-json", json.dumps(mon_cfg),
            ],
            env=child_env(),
            stdout=log, stderr=subprocess.STDOUT,
        )
    return mon.pid


def _pid_alive(pid) -> bool:
    try:
        os.kill(pid, 0)
    except (TypeError, ProcessLookupError, PermissionError):
        return False
    return True


def _terminate_monitor(pid, timeout: float = 300.0) -> bool:
    """SIGTERM the monitor and wait for it to gang-terminate its provider
    nodes and exit (cloud TPU slice deletes can take minutes). Returns
    True on clean exit; False if it had to be SIGKILLed (provider nodes
    may still be running)."""
    try:
        os.kill(pid, signal.SIGTERM)
    except ProcessLookupError:
        return True  # already gone
    except PermissionError:
        return False  # alive but not ours — we cannot manage it
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            # reap if the monitor is OUR child — a zombie would answer
            # kill(pid, 0) forever
            done, _ = os.waitpid(pid, os.WNOHANG)
            if done == pid:
                return True
        except ChildProcessError:
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                return True
        time.sleep(0.1)
    try:
        os.kill(pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    return False


def create_or_update_cluster(config_path, *, no_monitor: bool = False) -> dict:
    """``ray-tpu up``: start the head controller + the monitor process
    (autoscaler over the YAML's provider). With a live head, re-running
    ``up`` restarts a DEAD monitor (crash recovery) with the current
    YAML; live-monitor config changes need ``down`` + ``up`` (the
    monitor owns its provider's node handles)."""
    cfg = load_cluster_config(config_path)
    name = cfg["cluster_name"]
    os.makedirs(_state_dir(), exist_ok=True)
    state_path = cluster_state_path(name)
    if os.path.exists(state_path):
        state = read_cluster_state(name)
        if _head_alive(state):
            if not no_monitor and not _pid_alive(state.get("monitor_pid")):
                state["monitor_pid"] = _spawn_monitor(
                    cfg, state["address"], state["session_dir"]
                )
                with open(state_path, "w") as f:
                    json.dump(state, f, indent=1)
            return state  # already up
        # Head died but the monitor may have survived, still owning
        # provisioned provider nodes. Terminate it (SIGTERM →
        # provider.shutdown() gang-terminates its nodes) BEFORE discarding
        # the state record — unlinking first would orphan a node-owning
        # monitor with no recorded pid (a billing leak).
        mon_pid = state.get("monitor_pid")
        if mon_pid and _pid_alive(mon_pid):
            if not _terminate_monitor(mon_pid):
                raise RuntimeError(
                    f"stale monitor (pid {mon_pid}) for cluster {name!r} did "
                    "not exit within the teardown window; its provider nodes "
                    "may still be running. Refusing to re-up — investigate "
                    f"and tear down manually (state kept at {state_path})"
                )
        os.unlink(state_path)

    from ray_tpu.core import api

    head_resources = dict(cfg.get("head_resources") or {"CPU": os.cpu_count() or 1})
    address, head_proc, session_dir = api._start_controller(
        head_resources, cfg.get("system_config") or {}, owned=False
    )
    monitor_pid = None
    if not no_monitor:
        monitor_pid = _spawn_monitor(cfg, address, session_dir)
    state = {
        "cluster_name": name,
        "address": address,
        "session_dir": session_dir,
        "head_pid": head_proc.pid,
        "monitor_pid": monitor_pid,
        "provider_type": cfg["provider"]["type"],
        "created_at": time.time(),
    }
    with open(state_path, "w") as f:
        json.dump(state, f, indent=1)
    return state


def _head_alive(state: dict) -> bool:
    try:
        os.kill(state["head_pid"], 0)
    except (ProcessLookupError, PermissionError, KeyError):
        return False
    return True


def teardown_cluster(name_or_path) -> dict:
    """``ray-tpu down``: gang-terminate provider nodes (the monitor owns
    them and cleans up on SIGTERM), then stop the head."""
    state = read_cluster_state(name_or_path)
    # 1. monitor: SIGTERM → provider.shutdown() terminates every
    #    provisioned node, then the monitor exits. Node termination can
    #    take minutes (cloud TPU slice deletes), so wait generously —
    #    SIGKILLing mid-shutdown leaks running (billing!) nodes.
    pid = state.get("monitor_pid")
    unclean = False
    if pid:
        try:
            os.kill(pid, signal.SIGTERM)
            deadline = time.time() + 300
            while time.time() < deadline:
                try:
                    # reap if the monitor is OUR child — a zombie would
                    # answer kill(pid, 0) forever
                    done, _ = os.waitpid(pid, os.WNOHANG)
                    if done == pid:
                        break
                except ChildProcessError:
                    try:
                        os.kill(pid, 0)
                    except ProcessLookupError:
                        break
                time.sleep(0.1)
            else:
                unclean = True
                os.kill(pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    # 2. head: cluster-wide shutdown RPC, then kill the controller.
    try:
        from ray_tpu.core.client import CoreWorker
        from ray_tpu.core.cluster_utils import end_cluster
        from ray_tpu.utils import rpc as _rpc

        runner = _rpc.EventLoopThread("down-admin")
        admin = CoreWorker(state["address"], mode="driver", loop_runner=runner)
        try:
            end_cluster(admin)
        finally:
            admin.disconnect()
            runner.stop()
    except Exception:  # noqa: BLE001 — head already gone
        pass
    if state.get("head_pid"):
        try:
            os.kill(state["head_pid"], signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            pass
    if unclean:
        # the monitor may not have finished terminating provider nodes —
        # KEEP the state record so the operator can investigate/re-run
        state["teardown_incomplete"] = True
        with open(cluster_state_path(state["cluster_name"]), "w") as f:
            json.dump(state, f, indent=1)
        logger.warning(
            "monitor for %s did not exit cleanly; provider nodes may "
            "still be running — state kept at %s",
            state["cluster_name"], cluster_state_path(state["cluster_name"]),
        )
        return state
    try:
        os.unlink(cluster_state_path(state["cluster_name"]))
    except FileNotFoundError:
        pass
    return state


def exec_on_cluster(name_or_path, cmd: list, *, capture: bool = False):
    """``ray-tpu exec``: run a command against the cluster's head — the
    child gets RAY_TPU_ADDRESS so ``ray_tpu.init(address="auto")``
    connects (reference: exec_cluster runs the command on the head via
    the auth config; with a local head that IS this host)."""
    state = read_cluster_state(name_or_path)
    env = dict(os.environ)
    env["RAY_TPU_ADDRESS"] = state["address"]
    env["RAY_TPU_SESSION_DIR"] = state["session_dir"]
    return subprocess.run(
        cmd, env=env, capture_output=capture, text=capture
    )


def attach_cluster(name_or_path) -> int:
    """``ray-tpu attach``: an interactive shell wired to the cluster."""
    state = read_cluster_state(name_or_path)
    shell = os.environ.get("SHELL", "/bin/bash")
    env = dict(os.environ)
    env["RAY_TPU_ADDRESS"] = state["address"]
    env["RAY_TPU_SESSION_DIR"] = state["session_dir"]
    env["PS1"] = f"(ray-tpu {state['cluster_name']}) " + env.get("PS1", "$ ")
    if not sys.stdin.isatty():
        # shell-evaluable stdout contract (`eval $(ray-tpu attach ...)`)
        # — must stay raw on stdout, not a formatted/leveled logger line
        print(f"export RAY_TPU_ADDRESS={state['address']}")  # ray-tpu: lint-ignore[RTL007]
        return 0
    return subprocess.call([shell], env=env)
