"""Public core API: init/remote/get/put/wait/kill/cancel/get_actor.

Reference: python/ray/_private/worker.py (``ray.init`` :1240, ``get`` :2601,
``put`` :2737, ``wait`` :2802, ``kill`` :2983, ``cancel`` :3014,
``get_actor`` :2948).
"""
from __future__ import annotations

import atexit
import json
import logging
import os
import subprocess
import sys
import time
from typing import Any, Optional, Sequence

from ray_tpu.config import Config, get_config
from ray_tpu.core.actor import ActorClass, ActorHandle
from ray_tpu.core.client import CoreWorker
from ray_tpu.core.object_ref import ObjectRef
from ray_tpu.core.remote_function import RemoteFunction
from ray_tpu.utils import rpc

logger = logging.getLogger("ray_tpu")

_global_worker: Optional[CoreWorker] = None
_controller_proc: Optional[subprocess.Popen] = None
_session_dir: Optional[str] = None


def is_initialized() -> bool:
    return _global_worker is not None


def _require_worker() -> CoreWorker:
    if _global_worker is None:
        raise RuntimeError("ray_tpu.init() has not been called")
    return _global_worker


def _attach_worker(core: CoreWorker):
    """Called by worker processes so the public API works inside tasks."""
    global _global_worker
    _global_worker = core


def init(
    address: Optional[str] = None,
    *,
    num_cpus: Optional[int] = None,
    num_tpus: Optional[int] = None,
    resources: Optional[dict] = None,
    object_store_memory: Optional[int] = None,
    ignore_reinit_error: bool = False,
    _system_config: Optional[dict] = None,
) -> dict:
    """Start (or connect to) a cluster and connect this process as a driver."""
    global _global_worker, _controller_proc, _session_dir
    from ray_tpu.util import chaos, lockwatch

    lockwatch.maybe_install()  # RAY_TPU_LOCKWATCH=1: driver-side watchdog
    chaos.install_fault_plan_from_env()  # RAY_TPU_FAULT_PLAN: deterministic chaos
    if _global_worker is not None:
        if ignore_reinit_error:
            return {"address": _global_worker.address}
        raise RuntimeError("ray_tpu.init() called twice; use ignore_reinit_error=True")

    if address == "auto":
        # Reference: ray.init("auto") resolves the running cluster from the
        # env (set for job drivers) or the address file `ray start` wrote.
        address = os.environ.get("RAY_TPU_ADDRESS")
        if address is None:
            addr_file = os.path.join(get_config().temp_dir, "ray_current_cluster")
            if os.path.exists(addr_file):
                with open(addr_file) as f:
                    address = f.read().strip() or None
        if address is None:
            raise ConnectionError(
                "address='auto' but no running cluster found (no RAY_TPU_ADDRESS "
                "env var and no address file)"
            )

    # The driver's own jax (single-process loops) shares the workers'
    # compile cache. The variable only reaches a jax imported later.
    from ray_tpu.core.node_agent import place_compile_cache

    cache_dir = place_compile_cache(os.environ)
    jax = sys.modules.get("jax")
    if jax is not None and jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)

    if address is None:
        head_resources = dict(resources or {})
        head_resources.setdefault("CPU", num_cpus if num_cpus is not None else os.cpu_count() or 1)
        if num_tpus is None:
            from ray_tpu.accelerators.tpu import TPUAcceleratorManager

            num_tpus, how = TPUAcceleratorManager.detect_chips()
            logger.info("TPU chips on this host: %d (%s)", num_tpus, how)
        if num_tpus:
            head_resources.setdefault("TPU", num_tpus)
        cfg_overrides = dict(_system_config or {})
        if object_store_memory:
            cfg_overrides["object_store_memory"] = object_store_memory
        address, _controller_proc, _session_dir = _start_controller(
            head_resources, cfg_overrides, owned=True
        )

    loop_runner = rpc.EventLoopThread("driver-io")
    from ray_tpu.core.client import DriverHandler

    _global_worker = CoreWorker(
        address, mode="driver", loop_runner=loop_runner, handler=DriverHandler()
    )
    # Drivers run jax too (single-process training/bench loops): give
    # them the same device-telemetry + compile-tracking reporting.
    from ray_tpu.core.node_telemetry import start_process_telemetry

    start_process_telemetry(_global_worker)
    # Structured log plane, driver leg: logging records (incl. exception
    # tracebacks the driver logs) get a driver-<pid>.jsonl sidecar and
    # ERROR shipping to the controller's error index. Handler-only — the
    # driver's console streams stay untouched (core/log_plane.py).
    if _global_worker.config.get("log_structured", True):
        from ray_tpu.core import log_plane

        log_plane.install(
            _global_worker.session_dir,
            node_id=_global_worker.node_id.hex(),
            worker_id=None,
            proc=f"driver-{os.getpid()}",
            capture_streams=False,
            rotate_bytes=int(
                _global_worker.config.get("log_rotate_bytes", 64 << 20)
            ),
        )
        log_plane.start_ship_loop(_global_worker)
    # Continuous low-rate CPU sampling for incident auto-capture (no-op
    # unless profiling_continuous_hz is configured).
    from ray_tpu.util import profiling

    profiling.ensure_continuous()
    atexit.register(shutdown)
    return {"address": address, "session_dir": _global_worker.session_dir}


def _start_controller(head_resources: dict, cfg_overrides: dict, owned: bool):
    session_dir = os.path.join(
        get_config().temp_dir, f"session_{int(time.time()*1000)}_{os.getpid()}"
    )
    os.makedirs(os.path.join(session_dir, "logs"), exist_ok=True)
    from ray_tpu.core.node_agent import child_env

    env = child_env()
    log = open(os.path.join(session_dir, "logs", "controller.log"), "ab")
    cmd = [
        sys.executable,
        "-m",
        "ray_tpu.core.controller",
        "--session-dir",
        session_dir,
        "--resources",
        json.dumps(head_resources),
        "--config",
        json.dumps(cfg_overrides),
    ]
    if owned:
        cmd.append("--owned")
    proc = subprocess.Popen(cmd, env=env, stdout=log, stderr=subprocess.STDOUT)
    port_file = os.path.join(session_dir, "controller_port")
    deadline = time.time() + 30
    while time.time() < deadline:
        if os.path.exists(port_file):
            with open(port_file) as f:
                content = f.read().strip()
            if content:
                return f"127.0.0.1:{content}", proc, session_dir
        if proc.poll() is not None:
            raise RuntimeError(
                f"controller exited with {proc.returncode}; see {session_dir}/logs/controller.log"
            )
        time.sleep(0.02)
    raise RuntimeError("timed out waiting for controller to start")


def shutdown():
    """Disconnect; where this process started the cluster, end it too, and
    return only when no process of it is left (``cluster_utils.end_cluster``:
    no live thread, so no socket and no chip still held)."""
    global _global_worker, _controller_proc, _session_dir
    if _global_worker is None:
        return
    try:
        if _controller_proc is not None:
            from ray_tpu.core.cluster_utils import end_cluster

            end_cluster(_global_worker)
    finally:
        from ray_tpu.core import log_plane

        log_plane.uninstall()  # driver leg: handler off, sidecar closed
        _global_worker.disconnect()
        _global_worker.loop_runner.stop()
        _global_worker = None
        if _controller_proc is not None:
            # gone already, or past end_cluster's bound: either way reap it
            _controller_proc.kill()
            _controller_proc.wait(timeout=10)
            _controller_proc = None
        atexit.unregister(shutdown)


def remote(*args, **kwargs):
    """``@remote`` / ``@remote(num_cpus=..., num_tpus=...)`` decorator for
    functions (→ RemoteFunction) and classes (→ ActorClass)."""

    def wrap(target, options):
        if isinstance(target, type):
            return ActorClass(target, options)
        return RemoteFunction(target, options)

    if len(args) == 1 and not kwargs and (callable(args[0]) or isinstance(args[0], type)):
        return wrap(args[0], {})
    if args:
        raise TypeError("@remote only accepts keyword options")
    return lambda target: wrap(target, kwargs)


def get(refs, timeout: Optional[float] = None):
    return _require_worker().get(refs, timeout=timeout)


def put(value: Any) -> ObjectRef:
    return _require_worker().put(value)


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1, timeout: Optional[float] = None):
    if num_returns > len(refs):
        raise ValueError(
            f"num_returns ({num_returns}) cannot exceed the number of refs ({len(refs)})"
        )
    return _require_worker().wait(refs, num_returns=num_returns, timeout=timeout)


def kill(actor: ActorHandle, *, no_restart: bool = True):
    _require_worker().kill_actor(actor._actor_id, no_restart)


def cancel(ref: ObjectRef, *, force: bool = False):
    # The return object id embeds the producing task id only server-side;
    # look the task up by its return object.
    core = _require_worker()
    core.cancel_by_object(ref.id, force)


def get_actor(name: str) -> ActorHandle:
    info = _require_worker().get_actor_by_name(name)
    if info is None:
        raise ValueError(f"Failed to look up actor with name '{name}'")
    spec = info["creation_spec"]
    return ActorHandle(info["actor_id"], max_task_retries=spec.max_task_retries)


def free(refs: Sequence[ObjectRef]):
    _require_worker().free(refs)


def wait_actor_ready(actor: ActorHandle, timeout: Optional[float] = None):
    """Block until the actor finished __init__ (handy in tests)."""
    return _require_worker().wait_actor_ready(actor._actor_id, timeout=timeout)


def cluster_resources() -> dict:
    return _require_worker().cluster_resources()


def available_resources() -> dict:
    return _require_worker().available_resources()


def nodes() -> list:
    return _require_worker().list_state("nodes")


def drain_node(node_id, timeout_s: float = 300.0) -> bool:
    """Gracefully drain a node: no new placements, running work finishes,
    then the node retires (reference: `ray drain-node` / rpc::DrainNode)."""
    from ray_tpu.utils.ids import NodeID

    if isinstance(node_id, str):
        node_id = NodeID.from_hex(node_id)
    return _require_worker().drain_node(node_id, timeout_s)


def timeline() -> list:
    """Task state-transition events (reference: `ray timeline` CLI →
    chrome_tracing_dump, python/ray/_private/state.py:438)."""
    return _require_worker().list_state("events")
