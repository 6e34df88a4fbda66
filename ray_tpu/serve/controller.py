"""ServeController: the reconciliation control loop, as an actor.

Reference: python/ray/serve/_private/controller.py:86 (ServeController)
+ deployment_state.py (replica FSM) + autoscaling_state.py. One actor owns
desired state (deployments + target replica counts), runs a background
reconcile thread that starts/stops/health-checks replica actors, and serves
queries from handles (replica lists, versioned) and proxies (route table).
"""
from __future__ import annotations

import logging
import threading
import time
from typing import Any, Dict, List, Optional

logger = logging.getLogger("ray_tpu.serve")

CONTROLLER_NAME = "__serve_controller__"


class ServeController:
    def __init__(self):
        import ray_tpu

        self._ray = ray_tpu
        self._lock = threading.RLock()
        self._reconcile_lock = threading.Lock()
        self._deployments: Dict[str, dict] = {}
        # Replica startup tracking: birth time per actor id, and the set
        # that have answered a health check (confirmed). A replica still
        # inside __init__ (model load / jit compile) gets an
        # initialization grace instead of the 5s ping kill (reference:
        # deployment_state initialization timeout).
        self._birth: Dict[Any, float] = {}
        self._confirmed: set = set()
        self._version = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._reconcile_loop, daemon=True)
        self._thread.start()

    # ------------------------------------------------------------------
    # API called by serve.api
    # ------------------------------------------------------------------
    def deploy(
        self,
        name: str,
        cls_blob: bytes,
        init_args: tuple,
        init_kwargs: dict,
        config: dict,
    ):
        with self._lock:
            old = self._deployments.get(name)
            self._deployments[name] = {
                "cls_blob": cls_blob,
                "init_args": init_args,
                "init_kwargs": init_kwargs,
                "config": config,
                "target": config.get("num_replicas") or config.get("min_replicas") or 1,
                "replicas": [],
                "loads": {},  # router_id -> avg ongoing per replica (autoscaling)
                "route_prefix": config.get("route_prefix"),
            }
            self._version += 1
        if old:
            # Redeploy: retire old replicas, start fresh (reference:
            # version-based rolling update, simplified to stop+start).
            for r in old["replicas"]:
                self._kill(r)
        self._reconcile_once()
        return True

    def delete_deployment(self, name: str):
        with self._lock:
            d = self._deployments.pop(name, None)
            self._version += 1
        if d:
            for r in d["replicas"]:
                self._kill(r)
        return True

    def get_replicas(self, name: str):
        """(version, [(ActorHandle, node_id_hex|None, model_ids)]) —
        handles cache this by version; node ids feed locality-preferred
        routing and model ids feed multiplexed (model-affine) routing
        without every router scanning the cluster."""
        with self._lock:
            d = self._deployments.get(name)
            if d is None:
                return self._version, None
            replicas = list(d["replicas"])
            models = dict(d.get("models", {}))
        nodes = self._replica_nodes(replicas)
        return self._version, [
            (r, nodes.get(r._actor_id.hex()), models.get(r._actor_id.hex(), []))
            for r in replicas
        ]

    def report_models(self, name: str, replica_id_hex: str, model_ids: list):
        """A multiplexed replica's resident-model set changed (reference:
        the model-id push that backs model-affine routing)."""
        with self._lock:
            d = self._deployments.get(name)
            if d is not None and replica_id_hex:
                d.setdefault("models", {})[replica_id_hex] = list(model_ids)
                self._version += 1

    def _replica_nodes(self, replicas) -> dict:
        """actor_id hex → node hex for this controller's replicas, cached
        once placement is known (one state query here instead of one per
        router per refresh)."""
        cache = getattr(self, "_node_cache", None)
        if cache is None:
            cache = self._node_cache = {}
        missing = [r for r in replicas if r._actor_id.hex() not in cache]
        if missing:
            try:
                from ray_tpu.util.state import list_actors

                table = {a["actor_id"]: a["node_id"] for a in list_actors()}
                for r in missing:
                    node = table.get(r._actor_id.hex())
                    if node:  # only cache once actually placed
                        cache[r._actor_id.hex()] = node
            except Exception as e:  # noqa: BLE001 — locality is best-effort
                logger.debug("replica locality lookup failed: %s", e)
        return cache

    def get_version(self) -> int:
        return self._version

    def routes(self) -> Dict[str, str]:
        with self._lock:
            return {
                (d["route_prefix"] or f"/{name}"): name
                for name, d in self._deployments.items()
            }

    def report_load(self, name: str, router_id: str, avg_ongoing: float):
        """Routers report in-flight per replica; aggregated per-router so
        several handles don't overwrite each other (reference:
        autoscaling_state.py keeps per-handle request metrics)."""
        with self._lock:
            d = self._deployments.get(name)
            if d is not None:
                d["loads"][router_id] = (avg_ongoing, time.time())

    def status(self) -> dict:
        with self._lock:
            return {
                name: {
                    "target_replicas": d["target"],
                    "running_replicas": len(d["replicas"]),
                    "config": d["config"],
                    "load": self._total_load(d),
                }
                for name, d in self._deployments.items()
            }

    def ready(self, name: str, timeout: float = 30.0) -> bool:
        """Block until the deployment has its target replica count."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                d = self._deployments.get(name)
                if d is not None and len(d["replicas"]) >= d["target"]:
                    return True
            time.sleep(0.05)
        return False

    def shutdown(self):
        self._stop.set()
        with self._lock:
            names = list(self._deployments)
        for n in names:
            self.delete_deployment(n)
        return True

    # ------------------------------------------------------------------
    # Reconciliation
    # ------------------------------------------------------------------
    def _reconcile_loop(self):
        while not self._stop.wait(0.5):
            try:
                self._reconcile_once()
                self._autoscale()
            except Exception as e:  # noqa: BLE001 — the loop must survive
                logger.warning("serve reconcile tick failed: %s", e)

    @staticmethod
    def _total_load(d: dict) -> float:
        """Sum of fresh per-router loads (stale routers age out)."""
        cutoff = time.time() - 10.0
        return sum(v for v, ts in d["loads"].values() if ts > cutoff)

    def _reconcile_once(self):
        # Serialize reconciles: deploy() and the background loop racing here
        # would both spawn replicas and orphan the loser's.
        with self._reconcile_lock:
            self._reconcile_locked()

    def _reconcile_locked(self):
        from ray_tpu.serve.replica import Replica

        with self._lock:
            work = [
                (name, dict(d)) for name, d in self._deployments.items()
            ]
        for name, d in work:
            alive = []
            for r in d["replicas"]:
                if self._healthy(r):
                    alive.append(r)
            missing = d["target"] - len(alive)
            for _ in range(max(0, missing)):
                cfg = d["config"]
                replica = Replica.options(
                    max_concurrency=cfg.get("max_ongoing_requests", 8),
                    num_cpus=cfg.get("num_cpus", 0.1),
                    num_tpus=cfg.get("num_tpus", 0),
                    resources=cfg.get("resources"),
                ).remote(name, d["cls_blob"], d["init_args"], d["init_kwargs"])
                self._birth[replica._actor_id] = time.time()
                alive.append(replica)
            if missing < 0:
                for r in alive[d["target"] :]:
                    self._kill(r)
                alive = alive[: d["target"]]
            with self._lock:
                cur = self._deployments.get(name)
                if cur is not None:
                    if cur["replicas"] != alive:
                        cur["replicas"] = alive
                        self._version += 1

    def _autoscale(self):
        """Request-based scaling (reference: autoscaling_policy.py —
        replicas = ceil(total_ongoing / target_ongoing_requests))."""
        import math

        with self._lock:
            for name, d in self._deployments.items():
                cfg = d["config"]
                lo, hi = cfg.get("min_replicas"), cfg.get("max_replicas")
                if lo is None or hi is None or cfg.get("num_replicas"):
                    continue
                target_ongoing = cfg.get("target_ongoing_requests", 2.0)
                total = self._total_load(d) * max(len(d["replicas"]), 1)
                want = min(hi, max(lo, math.ceil(total / target_ongoing)))
                if want != d["target"]:
                    d["target"] = want
                    self._version += 1

    INIT_GRACE_S = 120.0  # reference: deployment initialization timeout

    def _replica_state(self, key) -> str:
        try:
            from ray_tpu.util import state as state_api

            rec = state_api.get_actor(key.hex())
            return rec["state"] if rec else "DEAD"
        except Exception:  # noqa: BLE001
            return "UNKNOWN"

    def _healthy(self, replica) -> bool:
        key = replica._actor_id
        confirmed = key in self._confirmed
        in_grace = (
            not confirmed
            and time.time() - self._birth.get(key, time.time()) < self.INIT_GRACE_S
        )
        if not confirmed:
            # Don't burn a 5s ping timeout on a replica still inside
            # __init__ — ask the cluster's actor table instead. It, not a
            # clock, says when __init__ is over: a 7B engine builds and
            # compiles for minutes, and a replica killed at INIT_GRACE_S
            # mid-compile is replaced by one that meets the same end.
            state = self._replica_state(key)
            if state == "DEAD":
                self._kill(replica)
                return False
            if state in ("PENDING", "RESTARTING") or (state != "ALIVE" and in_grace):
                return True
        # ALIVE but unconfirmed stays in grace: the first requests may be
        # holding every actor thread through a long jit warmup.
        try:
            ok = self._ray.get(replica.check_health.remote(), timeout=5) == "ok"
            if ok:
                self._confirmed.add(key)
            return ok
        except Exception:  # noqa: BLE001
            if in_grace:
                return True
            self._kill(replica)
            return False

    def _kill(self, replica):
        self._birth.pop(replica._actor_id, None)
        self._confirmed.discard(replica._actor_id)
        try:
            self._ray.kill(replica)
        except Exception:  # noqa: BLE001
            pass
