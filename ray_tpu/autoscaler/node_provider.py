"""Node providers.

Reference: python/ray/autoscaler/node_provider.py (NodeProvider interface:
create_node/terminate_node/non_terminated_nodes/...) and
autoscaler/_private/fake_multi_node/node_provider.py:236
(FakeMultiNodeProvider — simulated provisioning that actually boots
raylets on localhost).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import uuid
from typing import Dict, List, Optional


class NodeProvider:
    """Provisioning backend interface. Implementations for real clouds
    (GKE TPU pools) plug in here; the fake provider covers tests."""

    def create_node(self, node_type: str, resources: Dict[str, float]) -> str:
        raise NotImplementedError

    def terminate_node(self, node_id: str):
        raise NotImplementedError

    def non_terminated_nodes(self) -> List[str]:
        raise NotImplementedError

    def node_type_of(self, node_id: str) -> Optional[str]:
        raise NotImplementedError

    def shutdown(self):
        pass


class FakeMultiNodeProvider(NodeProvider):
    """Boots REAL node agents on localhost — the provisioned capacity
    genuinely joins the cluster and runs tasks."""

    def __init__(self, controller_address: str, session_dir: str):
        self._address = controller_address
        self._session_dir = session_dir
        self._lock = threading.Lock()
        self._nodes: Dict[str, dict] = {}

    def create_node(self, node_type: str, resources: Dict[str, float]) -> str:
        from ray_tpu.core.node_agent import child_env

        provider_id = f"fake-{node_type}-{uuid.uuid4().hex[:8]}"
        log_path = os.path.join(self._session_dir, "logs", f"autoscaled-{provider_id}.log")
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        log = open(log_path, "ab")
        env = child_env()
        # The agent reports this back at register_node, giving the
        # autoscaler the provider↔node identity it needs for per-node
        # idle scale-down (reference: v2 instance_manager cloud ids).
        env["RAY_TPU_PROVIDER_INSTANCE_ID"] = provider_id
        proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "ray_tpu.core.node_agent",
                "--controller",
                self._address,
                "--session-dir",
                self._session_dir,
                "--resources",
                json.dumps(dict(resources)),
            ],
            env=env,
            stdout=log,
            stderr=subprocess.STDOUT,
        )
        with self._lock:
            self._nodes[provider_id] = {
                "proc": proc,
                "node_type": node_type,
                "created_at": time.time(),
            }
        return provider_id

    def terminate_node(self, node_id: str):
        with self._lock:
            info = self._nodes.pop(node_id, None)
        if info is not None:
            info["proc"].terminate()
            try:
                info["proc"].wait(timeout=5)
            except subprocess.TimeoutExpired:
                info["proc"].kill()

    def non_terminated_nodes(self) -> List[str]:
        with self._lock:
            dead = [k for k, v in self._nodes.items() if v["proc"].poll() is not None]
            for k in dead:
                del self._nodes[k]
            return list(self._nodes)

    def node_type_of(self, node_id: str) -> Optional[str]:
        with self._lock:
            info = self._nodes.get(node_id)
            return info["node_type"] if info else None

    def shutdown(self):
        for nid in self.non_terminated_nodes():
            self.terminate_node(nid)
