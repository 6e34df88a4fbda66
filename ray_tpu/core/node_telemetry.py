"""Node/host/device telemetry sampling.

Reference: the raylet's NodeManager heartbeats (resources + load) and the
dashboard reporter agent (python/ray/dashboard/modules/reporter/
reporter_agent.py — psutil host stats + per-GPU gauges). TPU twist: HBM
occupancy comes from jax's per-device ``memory_stats()`` (bytes_in_use /
peak_bytes_in_use / bytes_limit), which only the process that owns the
chips can read — so DEVICE samples are taken by workers (shipped via
``device_telemetry``) while HOST samples are taken by each node agent
(shipped inside its telemetry heartbeat) and by the controller for the
head node.

Sampling never takes a chip: ``sample_devices`` reads devices only when
this process has ALREADY initialized a jax backend. Asking jax for its
devices initializes the backend, and a TPU belongs to one process — a
driver that merely did ``import jax`` must not open the chip its train
worker or serve replica is about to ask for.
"""
from __future__ import annotations

import logging
import os
import sys
import threading
import time
from typing import Dict, List, Optional

logger = logging.getLogger("ray_tpu.telemetry")


def sample_host(cpu_sampler=None) -> Dict:
    """Host CPU/memory snapshot (cgroup-aware via memory_monitor)."""
    from ray_tpu.core.memory_monitor import system_memory

    used, total = system_memory()
    out = {
        "mem_used_bytes": used,
        "mem_total_bytes": total,
        "cpu_percent": round(100.0 * cpu_sampler.sample(), 2)
        if cpu_sampler is not None
        else 0.0,
    }
    try:
        out["load_1m"] = os.getloadavg()[0]
    except OSError:  # pragma: no cover - non-unix
        out["load_1m"] = 0.0
    return out


def build_node_sample(cpu_sampler, store) -> Dict:
    """The node heartbeat body, shared by the agents' telemetry loop and
    the controller's head-node loop so the two can't drift — only the
    transport differs (agent: notify over its controller connection;
    controller: direct NodeRecord write)."""
    return {
        "host": sample_host(cpu_sampler),
        "object_store": store.stats(),
    }


def sample_devices() -> List[Dict]:
    """Per-device memory stats of THIS process's accelerators.

    Returns [] until user code in this process has initialized a jax
    backend (never the first toucher — see the module docstring), and
    when the backend doesn't expose memory_stats (CPU). Rows:
    {id, platform, kind, bytes_in_use, peak_bytes_in_use, bytes_limit}.
    """
    from ray_tpu.accelerators.tpu import jax_backend_initialized

    if not jax_backend_initialized():
        return []
    rows = []
    for d in sys.modules["jax"].local_devices():
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — backend without memory_stats
            stats = None
        if not stats:
            continue
        rows.append(
            {
                "id": int(getattr(d, "id", len(rows))),
                "platform": getattr(d, "platform", "unknown"),
                "kind": getattr(d, "device_kind", ""),
                "bytes_in_use": int(stats.get("bytes_in_use", 0)),
                "peak_bytes_in_use": int(
                    stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
                ),
                "bytes_limit": int(stats.get("bytes_limit", 0)),
            }
        )
    return rows


class _DeviceGauges:
    """Lazy per-process HBM gauges, flushed by the normal metrics
    pipeline (tags: device id + platform — bounded cardinality; the
    node/process identity rides the controller-side aggregation, not
    Prometheus labels)."""

    def __init__(self):
        from ray_tpu.util.metrics import Gauge

        dk = ("device", "platform")
        self.used = Gauge(
            "tpu_hbm_used_bytes", "Device memory in use (bytes_in_use)", dk
        )
        self.peak = Gauge(
            "tpu_hbm_peak_bytes", "Peak device memory in use", dk
        )
        self.limit = Gauge(
            "tpu_hbm_limit_bytes", "Device memory capacity (bytes_limit)", dk
        )

    def set_from(self, rows: List[Dict]):
        for r in rows:
            tags = {"device": str(r["id"]), "platform": r["platform"]}
            self.used.set(r["bytes_in_use"], tags)
            self.peak.set(r["peak_bytes_in_use"], tags)
            self.limit.set(r["bytes_limit"], tags)


_gauges: Optional[_DeviceGauges] = None


def set_device_gauges(rows: List[Dict]):
    global _gauges
    if not rows:
        return
    if _gauges is None:
        _gauges = _DeviceGauges()
    _gauges.set_from(rows)


def start_process_telemetry(core) -> Optional[threading.Thread]:
    """Worker/driver-side device-telemetry thread: every poll interval,
    sample this process's devices + compile-tracker snapshot and ship
    them to the controller (``device_telemetry``). No-ops cheaply until
    jax is imported; the compile tracker auto-installs at that point so
    workers never need explicit instrumentation."""
    interval = core.config.get("node_telemetry_interval_ms", 2000) / 1000.0
    if interval <= 0:
        return None
    key = f"{core.node_id.hex() if core.node_id else 'head'}/{core.worker_id.hex()[:12]}"

    def loop():
        from ray_tpu.util import compile_tracker

        while True:
            time.sleep(interval)
            if "jax" in sys.modules:
                compile_tracker.maybe_install()
                rows = sample_devices()
                set_device_gauges(rows)
            else:
                rows = []
            # Ship whenever the compile tracker has ANYTHING — jax may be
            # absent while the tracker still carries data (its logging
            # hook fires through jax's pure-Python path, and the health
            # plane's storm actuator needs storms visible either way).
            snap = compile_tracker.snapshot()
            if (
                not rows
                and not snap.get("compiles")
                and not snap.get("functions")
                and not snap.get("active_storms")
            ):
                continue
            payload = {
                "node_id": core.node_id.hex() if core.node_id else None,
                "pid": os.getpid(),
                "mode": core.mode,
                "devices": rows,
                "compile": snap,
            }
            coro = core.peer.call("device_telemetry", key, payload)
            try:
                core.loop_runner.submit(coro)
            except Exception:  # noqa: BLE001 — controller gone; process exits soon
                coro.close()
                return

    t = threading.Thread(target=loop, daemon=True, name="device-telemetry")
    t.start()
    return t
