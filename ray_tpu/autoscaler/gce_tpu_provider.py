"""GCE/GKE TPU pod-slice node provider.

Reference: python/ray/autoscaler/_private/gcp/node_provider.py (GCP
provider; TPU nodes go through tpu.googleapis.com — gcp/node.py
GCPTPUNode) — rebuilt around the one TPU-specific invariant the generic
GCP provider obscures: **a pod slice is one atomic unit**. All hosts of
a `v5e-16` slice are created by one API call, share one gang-scheduling
identity (`TPU-v5e-16-head` on host 0), and die together (maintenance
events / preemption take the whole slice).

Shape:
  GceTpuApi          — the 3-call surface of tpu.googleapis.com v2
                       (nodes.create / nodes.delete / nodes.list)
  RestGceTpuApi      — real impl: GCE metadata-server token + REST
  FakeGceTpuApi      — test impl: same contract; "creating" a slice
                       boots one REAL node agent per host on localhost
                       (the FakeMultiNodeProvider pattern), so
                       autoscaled slices genuinely join the cluster
  GceTpuNodeProvider — NodeProvider adapter: one provider node id ==
                       one SLICE (gang create/terminate/observe)

Node-type config (autoscaler `node_types`):
    "tpu_v5e_16": {
        "resources": {"CPU": 8},        # per HOST, TPU chips implied
        "accelerator_type": "v5e-16",   # slice shape
        "min_workers": 0, "max_workers": 4,
    }
"""
from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import threading
import time
import uuid
from typing import Dict, List, Optional

from ray_tpu.autoscaler.node_provider import NodeProvider

logger = logging.getLogger("ray_tpu.autoscaler.gce_tpu")


def _slice_shape(accelerator_type: str) -> tuple:
    """(num_hosts, chips_per_host) for a pod type like 'v5e-16'."""
    from ray_tpu.accelerators.tpu import TPUAcceleratorManager

    hosts = TPUAcceleratorManager.num_hosts_in_slice(accelerator_type)
    gen, chips = accelerator_type.split("-")
    per_host = min(int(chips), 8 if gen in ("v5litepod", "v5e", "v6e") else 4)
    return max(hosts, 1), per_host


class GceTpuApi:
    """The slice of tpu.googleapis.com v2 the provider needs."""

    def create_node(self, name: str, accelerator_type: str, runtime_version: str,
                    labels: Dict[str, str], startup_script: str = "") -> None:
        raise NotImplementedError

    def delete_node(self, name: str) -> None:
        raise NotImplementedError

    def list_nodes(self) -> List[dict]:
        """[{name, state, accelerator_type, labels}] — state in
        CREATING | READY | DELETING | PREEMPTED | TERMINATED."""
        raise NotImplementedError


class RestGceTpuApi(GceTpuApi):
    """Real API via the GCE metadata server's service-account token
    (reference: gcp/node_provider.py construct_clients_from_provider_config
    — here plain REST, no google-api-python-client dependency)."""

    METADATA_TOKEN_URL = (
        "http://metadata.google.internal/computeMetadata/v1/"
        "instance/service-accounts/default/token"
    )

    def __init__(self, project: str, zone: str):
        self.project = project
        self.zone = zone
        self.base = (
            f"https://tpu.googleapis.com/v2/projects/{project}"
            f"/locations/{zone}/nodes"
        )

    def _token(self) -> str:
        import urllib.request

        req = urllib.request.Request(
            self.METADATA_TOKEN_URL, headers={"Metadata-Flavor": "Google"}
        )
        with urllib.request.urlopen(req, timeout=5) as resp:
            return json.loads(resp.read())["access_token"]

    def _call(self, method: str, url: str, body: Optional[dict] = None) -> dict:
        import urllib.request

        data = json.dumps(body).encode() if body is not None else None
        req = urllib.request.Request(
            url, data=data, method=method,
            headers={
                "Authorization": f"Bearer {self._token()}",
                "Content-Type": "application/json",
            },
        )
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read() or b"{}")

    def create_node(self, name: str, accelerator_type: str, runtime_version: str,
                    labels: Dict[str, str], startup_script: str = "") -> None:
        self._call(
            "POST", f"{self.base}?nodeId={name}",
            {
                "acceleratorType": accelerator_type,
                "runtimeVersion": runtime_version,
                "labels": labels,
                # the boot script starts a node agent per host pointed at
                # the controller; shipped via metadata like the reference
                "metadata": {"startup-script": startup_script},
            },
        )

    def delete_node(self, name: str) -> None:
        self._call("DELETE", f"{self.base}/{name}")

    def list_nodes(self) -> List[dict]:
        from urllib.parse import quote

        out: List[dict] = []
        page_token = ""
        while True:  # nodes.list paginates; dropping pages orphans slices
            url = self.base + (
                f"?pageToken={quote(page_token, safe='')}" if page_token else ""
            )
            resp = self._call("GET", url)
            out.extend(
                {
                    "name": n["name"].rsplit("/", 1)[-1],
                    "state": n.get("state", "READY"),
                    "accelerator_type": n.get("acceleratorType", ""),
                    "labels": n.get("labels", {}),
                }
                for n in resp.get("nodes", [])
            )
            page_token = resp.get("nextPageToken", "")
            if not page_token:
                return out


class FakeGceTpuApi(GceTpuApi):
    """Mocked control plane with REAL data plane: each 'slice' is N node
    agents on localhost, one per host, each advertising its chips and
    the slice's gang resources (TPU-<pod>, TPU-<pod>-head on host 0) —
    exactly what GCE metadata would make real hosts advertise."""

    def __init__(self, controller_address: str, session_dir: str,
                 host_resources: Optional[Dict[str, float]] = None):
        self.controller_address = controller_address
        self.session_dir = session_dir
        self.host_resources = host_resources or {"CPU": 2}
        self._lock = threading.Lock()
        self._slices: Dict[str, dict] = {}

    def create_node(self, name: str, accelerator_type: str, runtime_version: str,
                    labels: Dict[str, str], startup_script: str = "") -> None:
        from ray_tpu.core.node_agent import child_env

        hosts, chips = _slice_shape(accelerator_type)
        procs = []
        logs = []
        for host_idx in range(hosts):
            resources = dict(self.host_resources)
            resources["TPU"] = chips
            resources[f"TPU-{accelerator_type}"] = 1
            if host_idx == 0:
                resources[f"TPU-{accelerator_type}-head"] = 1
            env = child_env()
            env["RAY_TPU_PROVIDER_INSTANCE_ID"] = f"{name}/host{host_idx}"
            log_path = os.path.join(
                self.session_dir, "logs", f"gce-{name}-h{host_idx}.log"
            )
            os.makedirs(os.path.dirname(log_path), exist_ok=True)
            log = open(log_path, "ab")
            logs.append(log)
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable, "-m", "ray_tpu.core.node_agent",
                        "--controller", self.controller_address,
                        "--session-dir", self.session_dir,
                        "--resources", json.dumps(resources),
                    ],
                    env=env, stdout=log,
                    stderr=subprocess.STDOUT,
                )
            )
        with self._lock:
            self._slices[name] = {
                "procs": procs,
                "logs": logs,
                "accelerator_type": accelerator_type,
                "labels": labels,
                "created_at": time.time(),
            }

    def delete_node(self, name: str) -> None:
        with self._lock:
            info = self._slices.pop(name, None)
        if info is None:
            return
        for p in info["procs"]:
            p.terminate()
        for p in info["procs"]:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        for log in info.get("logs", []):
            log.close()

    def preempt(self, name: str) -> None:
        """Test hook: a maintenance event takes the WHOLE slice."""
        with self._lock:
            info = self._slices.get(name)
        if info is None:
            return
        for p in info["procs"]:
            p.kill()

    def list_nodes(self) -> List[dict]:
        out = []
        with self._lock:
            for name, info in list(self._slices.items()):
                dead = sum(1 for p in info["procs"] if p.poll() is not None)
                if dead == len(info["procs"]):
                    state = "TERMINATED"
                elif dead > 0:
                    # gang failure semantics: ANY host down = slice down
                    state = "PREEMPTED"
                else:
                    state = "READY"
                out.append(
                    {
                        "name": name,
                        "state": state,
                        "accelerator_type": info["accelerator_type"],
                        "labels": info["labels"],
                    }
                )
        return out


class GceTpuNodeProvider(NodeProvider):
    """One provider node id == one pod SLICE: create/terminate/observe
    are whole-slice (gang) operations (reference: the GCP provider's TPU
    path, where one tpu.googleapis.com node spans all slice hosts)."""

    #: Per-host boot script for REAL slices (GCE runs it on every host of
    #: the pod): installs the framework, then starts a node agent pointed
    #: at the cluster controller (reference: the GCP provider's
    #: setup_commands + startup script in the cluster yaml). Formatted
    #: with {install} (built from ``package_spec`` — a pip spec or a
    #: gs:// wheel the operator staged — by _install_cmd) and
    #: {controller}; TPU resources are auto-detected on-host via the
    #: accelerator manager.
    STARTUP_TEMPLATE = (
        "#!/bin/bash\n"
        "set -e\n"  # a failed install must not launch a doomed agent
        "{install}\n"
        "python3 -m ray_tpu.core.node_agent --controller {controller} "
        "--session-dir /tmp/ray_tpu/session_gce "
        ">> /var/log/ray_tpu_agent.log 2>&1 &\n"
    )

    @staticmethod
    def _install_cmd(package_spec: str) -> str:
        if package_spec.startswith("gs://"):
            # pip can't fetch gs:// — stage the wheel with gsutil first
            return (
                f"gsutil cp {package_spec} /tmp/ray_tpu_pkg.whl\n"
                "python3 -m pip install --quiet /tmp/ray_tpu_pkg.whl"
            )
        return f"python3 -m pip install --quiet {package_spec}"

    def __init__(self, api: GceTpuApi, cluster_name: str = "rt",
                 runtime_version: str = "tpu-ubuntu2204-base",
                 node_types: Optional[Dict[str, dict]] = None,
                 controller_address: str = "",
                 package_spec: str = "ray-tpu"):
        self.api = api
        self.cluster_name = cluster_name
        self.runtime_version = runtime_version
        self.node_types = node_types or {}
        self.controller_address = controller_address
        self.package_spec = package_spec
        self._types: Dict[str, str] = {}  # slice name -> node_type

    def create_node(self, node_type: str, resources: Dict[str, float]) -> str:
        accelerator_type = (
            (self.node_types.get(node_type) or {}).get("accelerator_type")
            or node_type.replace("tpu_", "").replace("_", "-")
        )
        name = f"{self.cluster_name}-{node_type}-{uuid.uuid4().hex[:8]}"
        startup = (
            self.STARTUP_TEMPLATE.format(
                controller=self.controller_address,
                install=self._install_cmd(self.package_spec),
            )
            if self.controller_address
            else ""
        )
        self.api.create_node(
            name, accelerator_type, self.runtime_version,
            labels={"rt-cluster": self.cluster_name, "rt-node-type": node_type},
            startup_script=startup,
        )
        self._types[name] = node_type
        return name

    def terminate_node(self, node_id: str):
        self.api.delete_node(node_id)
        self._types.pop(node_id, None)

    def non_terminated_nodes(self) -> List[str]:
        out = []
        for n in self.api.list_nodes():
            if n["labels"].get("rt-cluster") != self.cluster_name:
                continue
            # PREEMPTED/TERMINATED slices are gone as a unit — reporting a
            # half-dead slice as alive would strand its gang resources
            if n["state"] in ("READY", "CREATING"):
                self._types.setdefault(
                    n["name"], n["labels"].get("rt-node-type", "")
                )
                out.append(n["name"])
        return out

    def node_type_of(self, node_id: str) -> Optional[str]:
        return self._types.get(node_id)

    def shutdown(self):
        for nid in self.non_terminated_nodes():
            try:
                self.terminate_node(nid)
            except Exception:  # noqa: BLE001 — best-effort teardown
                logger.exception("terminate_node failed for %s", nid)
