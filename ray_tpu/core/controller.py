"""The controller: head-node control plane.

One process combining what the reference splits across the GCS server
(src/ray/gcs/gcs_server/gcs_server.h:219-297 — node/actor/PG/job/KV/pubsub
managers), the raylet's cluster scheduler (src/ray/raylet/scheduling/
cluster_task_manager.cc, GCS-direct mode per gcs_actor_scheduler.cc:60), and
the object directory (src/ray/object_manager/ownership_based_object_directory.cc).

Everything runs on one asyncio loop — state is mutated only from loop
callbacks, which supplies the single-writer discipline the reference gets
from per-component io_contexts (src/ray/common/asio/instrumented_io_context).

Process topology (cf. reference python/ray/_private/node.py:37):
  controller (this)      — control plane + head-node worker pool
  node agents (0..N)     — extra nodes; spawn/kill worker processes
  workers                — connect directly to the controller for dispatch
  drivers                — connect directly to the controller
"""
from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import signal
import socket
import sys
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Set, Tuple

from ray_tpu.config import Config, set_config
from ray_tpu.core.lifecycle import DEATH_CHANNEL, LifecycleRecorder
from ray_tpu.core.object_store import PlasmaStore
from ray_tpu.core.placement_group import PlacementGroupManager
from ray_tpu.core.resources import NodeResources, ResourceSet
from ray_tpu.core.scheduler import ClusterResourceScheduler, ClusterState
from ray_tpu.core.task_spec import SchedulingStrategy, TaskSpec, TaskType
from ray_tpu.exceptions import (
    ActorDiedError,
    ObjectLostError,
    OutOfMemoryError,
    TaskCancelledError,
    WorkerCrashedError,
)
from ray_tpu.runtime_env import env_hash as _env_hash
from ray_tpu.util.guards import OWNER_THREAD, GuardedDict, GuardedSet, snapshot
from ray_tpu.utils import rpc
from ray_tpu.utils.ids import ActorID, NodeID, ObjectID, PlacementGroupID, TaskID, WorkerID

logger = logging.getLogger("ray_tpu.controller")


async def _notify_quiet(peer, method: str, *args, what: str = ""):
    """Best-effort notify to a possibly-dead peer. The expected failure
    mode IS the peer being gone (that is usually why we are notifying), so
    failures are logged at debug instead of swallowed silently."""
    try:
        await peer.notify(method, *args)
    except Exception as e:  # noqa: BLE001 — peer already gone
        logger.debug("notify %s(%s) failed: %s", method, what, e)


# Object meta shapes returned to clients:
#   ("inline", bytes, is_error)
#   ("shm", size, node_id_hex, shm_dir, is_error)


_mem_metrics = None

# Max object records walked per memory-census sweep (round 17): the
# object-table census runs in bounded shards across sweeps instead of
# one O(objects) controller-loop stall per publish.
_CENSUS_CHUNK = 25_000


def _get_mem_metrics():
    """Lazy controller-process memory gauges (Grafana "Memory" row).
    Node tags are node-id prefixes (bounded by cluster size); the
    leak-flag call-site tag is bounded by the detector's trend-table cap
    plus the registry cardinality cap."""
    global _mem_metrics
    if _mem_metrics is None:
        from ray_tpu.util.metrics import Counter, Gauge, Histogram

        _mem_metrics = {
            "store_used": Gauge(
                "object_store_used_bytes",
                "Object store bytes in use per node (file tier + arena)",
                ("node",),
            ),
            "store_pinned": Gauge(
                "object_store_pinned_bytes",
                "Bytes of store objects held by store-side pins per node",
                ("node",),
            ),
            "store_spilled": Gauge(
                "object_store_spilled_bytes",
                "Bytes of store objects spilled to disk per node",
                ("node",),
            ),
            "refs_open": Gauge(
                "object_refs_open",
                "Objects in the controller directory by tier",
                ("kind",),
            ),
            "free_latency": Histogram(
                "object_free_latency_ms",
                "Wall time of one object free (directory pop + replica "
                "delete notifies)",
                boundaries=(0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 25, 50,
                            100, 250),
            ),
            "leak_flags": Counter(
                "memory_leak_flags_total",
                "Call-sites newly flagged by the open-ref growth detector",
                ("callsite",),
            ),
        }
    return _mem_metrics


_batch_m = None


def _batch_metrics():
    """Lazy batched-control-plane histograms (Grafana "Control Plane"
    row): how many leases each rpc_lease_batch round-trip granted. The
    caller-side twin (task_push_batch_size) lives in normal_direct.py
    and ships over the ordinary metric channel."""
    global _batch_m
    if _batch_m is None:
        from ray_tpu.util.metrics import Histogram

        _batch_m = {
            "lease_batch": Histogram(
                "lease_batch_size",
                "Leases granted per lease_batch round-trip",
                boundaries=(1, 2, 4, 8, 16, 32, 64),
            ),
        }
    return _batch_m


@dataclass
class ObjectRecord:
    oid: ObjectID
    state: str = "PENDING"  # PENDING | READY | FAILED
    inline: Optional[bytes] = None
    size: int = 0
    locations: Set[NodeID] = field(default_factory=set)
    is_error: bool = False
    creating_task: Optional[TaskID] = None
    waiters: List[asyncio.Future] = field(default_factory=list)
    # Distributed ref counting (reference: reference_count.cc ownership):
    # processes currently holding >=1 local ref; refs serialized inside
    # this object (containment pins); whether any process ever held it
    # (guards against freeing refs still in flight to a first holder).
    holders: Set[str] = field(default_factory=set)
    children: List[ObjectID] = field(default_factory=list)
    ever_held: bool = False
    # Two-phase GC: a candidate must survive one full sweep interval
    # after being marked before it is freed — covers the window where a
    # borrower's "held" flush (<= ref_flush_interval) is still in flight
    # when the last known holder drops.
    gc_marked: bool = False
    # Memory-census attribution (reference: reference_count.cc call_site
    # per ref): the creating user frame / task label, interned client-side
    # (bounded vocabulary), plus who created it.
    callsite: str = ""
    creator: str = ""

    def meta(self, shm_dirs: Dict[NodeID, str]):
        if self.inline is not None:
            return ("inline", self.inline, self.is_error)
        # Prefer any LIVE replica (locations may briefly hold a node whose
        # death is still being processed); None = no live copy.
        for nid in self.locations:
            if nid in shm_dirs:
                return ("shm", self.size, nid.hex(), shm_dirs[nid], self.is_error)
        return None


@dataclass
class WorkerRecord:
    worker_id: WorkerID
    node_id: NodeID
    peer: rpc.Peer
    pid: int = 0
    state: str = "IDLE"  # STARTING | IDLE | LEASED | ACTOR | DEAD
    running: Set[TaskID] = field(default_factory=set)
    actor_id: Optional[ActorID] = None
    oom_marked: bool = False  # killed by the memory monitor
    # Runtime-env hash this worker is locked to ("" = pristine). Reference:
    # worker_pool keys idle workers by runtime-env hash (worker_pool.h:174).
    env_hash: str = ""
    # Direct-transport listener address ("host:port"; "" = none) —
    # callers push actor tasks straight to this endpoint (reference:
    # the worker's CoreWorkerService address in ActorTableData).
    listen_addr: str = ""


@dataclass
class NodeRecord:
    node_id: NodeID
    shm_dir: str
    peer: Optional[rpc.Peer]  # None for the head node (controller-managed)
    hostname: str = "localhost"
    agent_pid: int = 0  # node agent process (0 for the head)
    state: str = "ALIVE"
    # Agent's object-transfer listener ("host:port"; "" for the head —
    # head objects are fetched over the controller connection).
    fetch_addr: str = ""
    # Provider instance identity (reference: autoscaler v2
    # instance_manager's cloud_instance_id ↔ ray node mapping) — lets the
    # autoscaler reap ONE idle node instead of waiting for full idleness.
    provider_instance_id: str = ""
    workers: Set[WorkerID] = field(default_factory=set)
    num_starting: int = 0
    max_workers: int = 32
    # Latest telemetry heartbeat from this node's agent (host CPU/mem,
    # object-store occupancy; controller-sampled for the head). Stamped
    # with the CONTROLLER's clock on arrival ("ts").
    telemetry: Dict[str, Any] = field(default_factory=dict)
    # Free TPU chip indices on this host; actors holding TPU resources get
    # concrete chips via TPU_VISIBLE_CHIPS (reference: accelerators/tpu.py
    # :155-195 isolation + resource_instance_set.cc per-instance accounting).
    tpu_free: List[int] = field(default_factory=list)


@dataclass
class LeaseRecord:
    """A granted worker lease for direct normal-task submission
    (reference: the raylet's granted leases in local_task_manager.h). The
    controller's part is placement + resource reservation; the worker
    itself is handed out by the node agent (or by the controller for
    head-node leases, where it doubles as the agent)."""

    lease_id: bytes
    demand: ResourceSet  # translated (PG-renamed) resources, reserved
    node_id: NodeID
    owner: rpc.Peer  # caller connection; lease dies with it
    ehash: str = ""
    worker_id: Optional[WorkerID] = None  # head-node leases only


class _LeaseReq:
    __slots__ = ("demand", "translated", "strategy", "ehash", "dep_keys", "peer",
                 "fut", "req_id", "block_reason")

    def __init__(self, demand, translated, strategy, ehash, dep_keys, peer, fut):
        self.demand = demand
        self.translated = translated
        self.strategy = strategy
        self.ehash = ehash
        self.dep_keys = dep_keys
        self.peer = peer
        self.fut = fut
        self.req_id = ""  # flight-recorder lease chain id
        self.block_reason = None  # why the last grant attempt parked


@dataclass
class TaskRecord:
    spec: TaskSpec
    state: str = "PENDING"  # PENDING | DISPATCHED | RUNNING | FINISHED | FAILED
    worker_id: Optional[WorkerID] = None
    node_id: Optional[NodeID] = None
    retries_left: int = 0
    acquired: Optional[ResourceSet] = None
    submitted_at: float = field(default_factory=time.time)
    # Latest why-pending attribution while blocked (flight recorder
    # vocabulary, core/lifecycle.py PENDING_REASONS).
    pending_reason: str = ""
    # Streaming-generator progress (reference: ObjectRefStream,
    # src/ray/core_worker/task_manager.cc streaming-generator returns).
    stream_count: int = 0
    stream_done: bool = False
    stream_waiters: List[asyncio.Future] = field(default_factory=list)
    # Refs nested inside arg values (pinned until the task is terminal —
    # reference: submitted-task references).
    captures: List[ObjectID] = field(default_factory=list)


@dataclass
class ActorRecord:
    actor_id: ActorID
    creation_spec: TaskSpec
    state: str = "PENDING"  # PENDING | ALIVE | RESTARTING | DEAD
    worker_id: Optional[WorkerID] = None
    node_id: Optional[NodeID] = None
    name: str = ""
    restarts_left: int = 0
    num_restarts: int = 0
    death_reason: str = ""
    tpu_chips: List[int] = field(default_factory=list)
    tpu_node: Optional[NodeID] = None
    # Resources held for the actor's lifetime (explicit requests only).
    held_resources: Optional[ResourceSet] = None
    held_node: Optional[NodeID] = None
    # Tasks queued while the actor is not ALIVE.
    pending_tasks: List[TaskSpec] = field(default_factory=list)
    ready_waiters: List[asyncio.Future] = field(default_factory=list)


class Controller:
    def __init__(self, session_dir: str, head_resources: Dict[str, float], config: Config, owned: bool):
        self.session_dir = session_dir
        self.config = config
        self.owned = owned
        self.cluster = ClusterState()
        self.scheduler = ClusterResourceScheduler(self.cluster)
        # Control-plane flight recorder: every task/actor/PG/lease/worker
        # state transition, with per-state dwell times and why-pending
        # attribution (reference: gcs_task_manager's task-events backend).
        self.lifecycle = LifecycleRecorder(
            ring_size=config.lifecycle_ring_size,
            dwell_samples=config.lifecycle_dwell_samples,
            enabled=config.lifecycle_events,
        )
        self.pg_manager = PlacementGroupManager(self.cluster, recorder=self.lifecycle)
        # Single-writer maps (mutated only from the controller's asyncio
        # loop — the module's no-locks discipline). The OWNER_THREAD
        # guard makes that discipline machine-checked under ConcSan.
        self.objects: Dict[ObjectID, ObjectRecord] = GuardedDict(
            OWNER_THREAD, owner=self, name="objects"
        )
        self.workers: Dict[WorkerID, WorkerRecord] = GuardedDict(
            OWNER_THREAD, owner=self, name="workers"
        )
        self.nodes: Dict[NodeID, NodeRecord] = GuardedDict(
            OWNER_THREAD, owner=self, name="nodes"
        )
        self.tasks: Dict[TaskID, TaskRecord] = GuardedDict(
            OWNER_THREAD, owner=self, name="tasks"
        )
        self.actors: Dict[ActorID, ActorRecord] = GuardedDict(
            OWNER_THREAD, owner=self, name="actors"
        )
        self.named_actors: Dict[str, ActorID] = GuardedDict(
            OWNER_THREAD, owner=self, name="named_actors"
        )
        self.kv: Dict[str, Dict[bytes, bytes]] = GuardedDict(
            OWNER_THREAD, owner=self, name="kv"
        )
        # GCS fault tolerance (reference: gcs/store_client/ Redis FT): an
        # append-only journal of {KV, detached actors, PGs}; a restarting
        # controller on the same session dir replays it.
        from ray_tpu.core.persistence import GcsJournal

        self.journal = GcsJournal(session_dir, sync=config.gcs_journal_fsync)
        self._restored = self.journal.replay()
        if not self._restored.empty:
            self.kv = GuardedDict(
                OWNER_THREAD, self._restored.kv, owner=self, name="kv"
            )
            # Compact on every restart: bounds replay cost for long-lived
            # clusters that overwrite the same KV keys repeatedly.
            self.journal.compact(self._restored)
            logger.info(
                "journal replay: %d kv namespaces, %d detached actors, %d PGs",
                len(self._restored.kv), len(self._restored.actors), len(self._restored.pgs),
            )
        self.pending_tasks: List[TaskID] = []
        # Worker leases for direct normal-task submission (reference:
        # normal_task_submitter.cc leasing; controller = placement only).
        import collections as _c
        import itertools as _it

        # Pending work indexed by (scheduling class, env hash): the pump
        # visits CLASSES and skips a blocked one in O(1), so a deep queue
        # of homogeneous tasks costs O(#classes) per pump instead of
        # O(#tasks) (reference: SchedulingClass queues in
        # cluster_task_manager.cc; fixes the measured O(n²) registration
        # collapse at 10k pending actor records).
        self._class_queues: Dict[Tuple, "_c.deque"] = GuardedDict(
            OWNER_THREAD, owner=self, name="class_queues"
        )
        self._dep_parked: Set[TaskID] = set()
        # dep object → pending tasks that consume it: lets an object free
        # fail its dependents in O(dependents) instead of scanning every
        # pending task (objects free routinely via GC sweeps).
        self._dep_index: Dict[ObjectID, Set[TaskID]] = {}

        self.leases: Dict[bytes, LeaseRecord] = GuardedDict(
            OWNER_THREAD, owner=self, name="leases"
        )
        self._lease_reqs: "_c.deque[_LeaseReq]" = _c.deque()
        self._lease_seq = _it.count(1)
        self._lreq_seq = _it.count(1)  # lease-request ids (flight recorder)
        self._head_direct_free: List[WorkerID] = []
        self._head_direct_waiters: "_c.deque[Tuple[str, asyncio.Future]]" = _c.deque()
        # In-flight spawns per PRESET env hash (container workers): a
        # class whose queued depth is already covered by starting workers
        # must not re-request on every pump pass — over-spawn is benign
        # for pooled host workers but each extra here is a container.
        # Entries are [count, last_update_ts]: spawns that die before
        # registering (pull failure, crash) would otherwise suppress
        # respawns for that env forever, so counts go stale after
        # _SPAWN_STALE_S and the class retries.
        self._starting_by_env: Dict[str, list] = {}
        # Synthesized task rows for direct-push tasks (reference: the GCS
        # task manager's event-derived view) — bounded LRU.
        self._direct_task_rows: "_c.OrderedDict[str, dict]" = _c.OrderedDict()
        # Death reasons for recently-dead workers ("oom" | free-text) —
        # direct-push callers query this to turn a connection loss into
        # the right error (reference: NodeDeathInfo / worker exit detail).
        self._dead_worker_info: "_c.OrderedDict[str, str]" = _c.OrderedDict()
        self.drivers: Set[rpc.Peer] = GuardedSet(
            OWNER_THREAD, owner=self, name="drivers"
        )
        self._drain_tasks: Set[asyncio.Task] = set()
        self._pump_scheduled = False
        self._pump_running = False
        self._pump_rerun = False
        self._shutdown = asyncio.Event()
        self._gc_wanted = asyncio.Event()
        self._live_pin_tasks: Set[TaskID] = set()
        # Node ids THIS controller declared dead: re-registration under
        # the same id is refused (see rpc_register_node).
        self._dead_node_ids: Set[str] = set()
        # Recently-freed object ids (bounded): a get/wait/dep-check on a
        # freed object fails fast instead of hanging on a resurrected
        # empty PENDING record.
        import collections as _collections

        self._freed_lru: "_collections.OrderedDict[ObjectID, None]" = (
            _collections.OrderedDict()
        )
        self._holder_index: Dict[str, Set[ObjectID]] = {}
        # In-flight cross-node object pulls, deduped per (oid, dest node).
        from ray_tpu.core.object_transfer import FetchPeerCache

        self._pulls: Dict[Tuple[ObjectID, NodeID], asyncio.Future] = {}
        self._fetch_peers = FetchPeerCache()
        # Topic bus (core/pubsub.py): DEATH_CHANNEL plus the round-17
        # resource/avoid channels ride the same subscriber registry.
        from ray_tpu.core.pubsub import TopicBus

        self.bus = TopicBus()
        # Per-node monotonic sequence numbers for resource-delta pubsub
        # (subscriber mirrors drop stale/out-of-order deltas by seq).
        self._resource_seq: Dict[NodeID, int] = {}
        self._last_resource_broadcast = 0.0
        self._last_resource_reconcile = 0.0
        self.events: List[dict] = []  # task event ring buffer
        self.finished_specs: Dict[TaskID, TaskSpec] = {}  # lineage for reconstruction
        self.metrics: Dict[str, dict] = {}  # aggregated app metrics
        # Serve engine flight-recorder snapshots, pushed by replicas
        # (rpc_serve_report) and served at /api/serve/engine.
        self.serve_state: Dict[str, dict] = {}
        # Per-process device telemetry (HBM gauges + compile-tracker
        # snapshots) pushed by workers/drivers (rpc_device_telemetry),
        # keyed "node_hex/proc". Stale entries pruned on read.
        self.device_state: Dict[str, dict] = {}
        # Memory census (ray-tpu memory): per-callsite open-object trend
        # windows for the leak detector (bounded vocabulary), live leak
        # flags, and per-node spill-op watermarks for the store-pressure
        # churn trigger.
        self._mem_trends: Dict[str, Any] = {}
        self._leak_flags: Dict[str, dict] = {}
        self._spill_ops_prev: Dict[NodeID, int] = {}
        self._census_tick_n = 0  # sweep counter
        # In-progress sharded object-table census cycle (round 17):
        # {"keys", "pos", "kinds", "by_site"} or None between cycles.
        self._census_cycle: Optional[dict] = None
        # Cluster log plane (core/log_plane.py): error-signature index
        # fed by worker/agent/driver ERROR shipping (rpc_log_errors),
        # follow-mode subscribers (``ray-tpu logs --follow``) keyed by
        # their driver connection, and the spike detector's watermark.
        from ray_tpu.core.log_plane import ErrorIndex

        self._error_index = ErrorIndex(cap=config.log_error_index_size)
        self._log_followers: Dict[rpc.Peer, dict] = {}
        self._record_tailer = None
        self._errors_prev_total = 0
        # Health plane (core/health.py): the actuator half of the
        # detectors above — subscribes to leak/pressure/spike/storm
        # signals and drives bounded, audited remediations.
        from ray_tpu.core.health import HealthEngine

        self.health = HealthEngine(self)
        self.dashboard_port: Optional[int] = None

        # Head node: controller doubles as its node agent.
        self.head_node_id = NodeID.from_random()
        cap = config.object_store_memory or _default_store_bytes()
        self.head_store = PlasmaStore(session_dir, cap)
        from ray_tpu.core.object_transfer import ChunkReader

        self._chunk_reader = ChunkReader(self.head_store)
        head_total = ResourceSet.from_dict(head_resources)
        self.cluster.add_node(self.head_node_id, NodeResources(head_total, labels={"node_type": "head"}))
        import socket

        self.nodes[self.head_node_id] = NodeRecord(
            node_id=self.head_node_id,
            shm_dir=self.head_store.shm_dir,
            peer=None,
            hostname=socket.gethostname(),
        )
        ncpu = int(head_resources.get("CPU", 1))
        self.nodes[self.head_node_id].max_workers = max(4 * max(ncpu, 1), 16)
        self.nodes[self.head_node_id].tpu_free = list(
            range(int(head_resources.get("TPU", 0)))
        )
        self._head_prestart = max(ncpu, 1) if config.prestart_workers else 0

    # =================================================================
    # Connection lifecycle
    # =================================================================
    def on_connect(self, peer: rpc.Peer):
        pass

    async def on_disconnect(self, peer: rpc.Peer):
        kind = peer.meta.get("kind")
        holder = peer.meta.get("holder_id")
        if holder:
            self._drop_holder(holder)
        self._drop_subscriber(peer)
        self._log_followers.pop(peer, None)
        # Leases die with their owner's connection (reference: leased
        # workers are returned when the lease-holder worker dies). The
        # workers may be mid-task on orphaned pushes → kill, don't pool.
        owned = [lid for lid, r in self.leases.items() if r.owner is peer]
        for lid in owned:
            await self.rpc_lease_release(peer, lid, kill_worker=True)
        if kind == "worker":
            await self._on_worker_death(peer.meta["worker_id"], "connection lost")
        elif kind == "agent":
            await self._on_node_death(peer.meta["node_id"])
        elif kind == "driver":
            self.drivers.discard(peer)
            if self.owned and not self.drivers:
                # The driver that owns this cluster is gone — tear down.
                self._shutdown.set()

    # =================================================================
    # Registration RPCs
    # =================================================================
    async def rpc_register_driver(self, peer: rpc.Peer):
        peer.meta.update(kind="driver")
        peer.label = "driver"
        self.drivers.add(peer)
        return {
            "session_dir": self.session_dir,
            "head_node_id": self.head_node_id.hex(),
            "shm_dir": self.head_store.shm_dir,
            "config": self.config.to_dict(),
        }

    async def rpc_register_worker(
        self, peer: rpc.Peer, worker_id: WorkerID, node_id: NodeID, pid: int,
        listen_addr: str = "", pool: str = "", env_hash: str = "",
        rejoining: bool = False,
    ):
        if rejoining and worker_id.hex() in self._dead_worker_info:
            # THIS controller already declared the worker dead (its
            # disconnect ran _on_worker_death: actor restarted / gang
            # repaired). Accepting the rejoin would resurrect a zombie
            # twin of an actor that now lives elsewhere. Refuse; the
            # worker exits. A RESTARTED controller has an empty dead
            # table, so the restart ride-through stays intact.
            raise RuntimeError(
                f"worker {worker_id.hex()[:12]} was declared dead; "
                "re-registration refused"
            )
        peer.meta.update(kind="worker", worker_id=worker_id)
        peer.label = f"worker:{worker_id.hex()[:8]}"
        # Pair the agent/head SPAWNED event with REGISTERED — the dwell is
        # the worker-startup latency. Drain locally-spawned head events
        # first so the pair can't arrive out of order.
        self._drain_spawn_events()
        self.lifecycle.record(
            "worker", worker_id.hex(), "REGISTERED", node=node_id.hex()[:12]
        )
        rec = WorkerRecord(
            worker_id=worker_id, node_id=node_id, peer=peer, pid=pid,
            listen_addr=listen_addr,
            # Spawn-time env (container images): the worker is born into
            # its env hash; dispatch exact-matches it (img: hashes never
            # use the pristine-adoption fallback).
            env_hash=env_hash,
        )
        if rejoining:
            # A surviving worker re-registering after a controller
            # restart (or transient partition). Its actual occupancy is
            # unknown to this (fresh) controller — mark it busy so the
            # pump never dispatches onto it or recycles it as idle; it
            # exits with the cluster like any other worker.
            rec.state = "ACTOR"
        self.workers[worker_id] = rec
        node = self.nodes.get(node_id)
        if node is not None:
            node.workers.add(worker_id)
            if not rejoining:
                node.num_starting = max(0, node.num_starting - 1)
        if env_hash:
            entry = self._starting_by_env.get(env_hash)
            if entry is not None:
                entry[0] -= 1
                entry[1] = time.time()
                if entry[0] <= 0:
                    self._starting_by_env.pop(env_hash, None)
        if pool == "direct":
            # Direct-lease pool: never controller-dispatched. Head-node
            # direct workers feed the controller's own free list (it is
            # the head's agent); agent-node ones are tracked by their
            # agent and merely recorded here (death handling, state API).
            rec.state = "DIRECT"
            if node_id == self.head_node_id:
                self._head_direct_put(rec)
        self._schedule_pump()
        return {"session_dir": self.session_dir, "config": self.config.to_dict()}

    async def rpc_register_node(self, peer: rpc.Peer, node_id: NodeID, resources: Dict[str, float], shm_dir: str, hostname: str = "localhost", pid: int = 0, fetch_addr: str = "", provider_instance_id: str = "", labels: Optional[Dict[str, str]] = None):
        if node_id.hex() in self._dead_node_ids:
            # This controller already declared the node DEAD (connection
            # lapse → _on_node_death: workers reaped, PGs rescheduled,
            # gangs repaired). Accepting a re-register would resurrect
            # the node with pristine availability while its orphaned
            # workers still occupy it. Refuse; the agent exits and a
            # fresh agent (new node id) can join cleanly. A RESTARTED
            # controller has an empty dead-set, so the agent
            # reconnect-window ride-through stays intact.
            raise RuntimeError(
                f"node {node_id.hex()[:12]} was declared dead; "
                "re-registration refused — restart the agent"
            )
        peer.meta.update(kind="agent", node_id=node_id)
        peer.label = f"agent:{node_id.hex()[:8]}"
        self.lifecycle.record("node", node_id.hex(), "ALIVE", name=hostname)
        total = ResourceSet.from_dict(resources)
        self.cluster.add_node(node_id, NodeResources(total, labels=labels))
        ncpu = int(resources.get("CPU", 1))
        rec = NodeRecord(
            node_id=node_id, shm_dir=shm_dir, peer=peer, hostname=hostname,
            fetch_addr=fetch_addr, provider_instance_id=provider_instance_id,
        )
        rec.agent_pid = pid
        rec.max_workers = max(4 * max(ncpu, 1), 16)
        rec.tpu_free = list(range(int(resources.get("TPU", 0))))
        self.nodes[node_id] = rec
        self.pg_manager.retry_pending()
        self._schedule_pump()
        if self.config.prestart_workers:
            await self._request_workers(rec, max(ncpu, 1))
        return {"session_dir": self.session_dir, "config": self.config.to_dict()}

    # =================================================================
    # Worker pool
    # =================================================================
    async def _request_workers(self, node: NodeRecord, n: int,
                               container_image: str = None,
                               preset_env_hash: str = ""):
        live = len(node.workers) + node.num_starting
        n = min(n, node.max_workers - live)
        if n <= 0:
            return
        node.num_starting += n
        if preset_env_hash:
            entry = self._starting_by_env.setdefault(preset_env_hash, [0, 0.0])
            entry[0] += n
            entry[1] = time.time()
        if node.peer is None:
            from ray_tpu.core.node_agent import spawn_worker

            extra = (
                {"RAY_TPU_PRESET_ENV_HASH": preset_env_hash}
                if preset_env_hash else None
            )
            for _ in range(n):
                spawn_worker(self.session_dir, f"127.0.0.1:{self.port}",
                             node.node_id, node.shm_dir, extra_env=extra,
                             container_image=container_image)
        else:
            await node.peer.notify(
                "start_workers", n, container_image, preset_env_hash
            )

    async def _recycle_idle_worker(self, node: NodeRecord, wanted_hash: str) -> bool:
        """Retire one idle worker whose env differs from ``wanted_hash`` so
        a replacement (pristine) worker can be spawned. True if a slot is
        being freed."""
        for wid in list(node.workers):
            w = self.workers.get(wid)
            if w is not None and w.state == "IDLE" and w.env_hash != wanted_hash:
                w.state = "DEAD"
                await _notify_quiet(w.peer, "exit", what="recycle idle worker")
                return True
        return False

    def _idle_worker_on(self, node_id: NodeID, env_hash: str = "") -> Optional[WorkerRecord]:
        node = self.nodes.get(node_id)
        if node is None:
            return None
        fallback = None
        for wid in node.workers:
            w = self.workers.get(wid)
            if w is None or w.state != "IDLE":
                continue
            if w.env_hash == env_hash:
                return w  # exact env match (incl. pristine↔pristine)
            if env_hash and w.env_hash == "" and fallback is None:
                fallback = w  # pristine worker can adopt the env
        # Container envs (img:) apply at SPAWN time — a pristine host
        # worker cannot adopt one in-process; exact match only.
        if env_hash.startswith("img:"):
            return None
        return fallback

    # =================================================================
    # Worker leasing (direct normal-task submission)
    # =================================================================
    async def rpc_lease_request(
        self, peer: rpc.Peer, demand_items: list, strategy: SchedulingStrategy,
        ehash: str, dep_keys: list, queued: int = 0,
    ):
        """Grant a worker lease: pick a node (locality-aware for DEFAULT
        strategy), reserve the lease's resources, and tell the caller
        which agent hands out the worker (reference: RequestWorkerLease,
        raylet/node_manager.cc:1795 — here split controller/agent).
        Parks until grantable; the pump re-tries parked requests whenever
        resources or nodes free up."""
        demand = ResourceSet(dict(demand_items))
        translated = self.scheduler.translated_pg_demand(demand, strategy)
        req = _LeaseReq(
            demand, translated, strategy, ehash, dep_keys, peer,
            asyncio.get_running_loop().create_future(),
        )
        req.req_id = "R%d" % next(self._lreq_seq)
        self.lifecycle.record("lease", req.req_id, "REQUESTED")
        grant = self._try_grant_lease(req)
        if grant is not None:
            self.lifecycle.record(
                "lease", req.req_id, "GRANTED", node=grant["node_id"][:12]
            )
            return grant
        self.lifecycle.pending_reason("lease", req.req_id, req.block_reason)
        self._lease_reqs.append(req)
        return await req.fut

    async def rpc_lease_batch(
        self, peer: rpc.Peer, demand_items: list, strategy: SchedulingStrategy,
        ehash: str, dep_keys: list, queued: int = 0, count: int = 1,
    ):
        """Grant up to ``count`` leases for one scheduling key in ONE
        round-trip (round 17 — the per-task lease RPC was the measured
        submission wall). Placement runs per lease against the live
        resource view (the demand-shape index makes each decision O(1)),
        but the lifecycle recording is ONE batched REQUESTED→GRANTED pair
        and the reply is one frame. Partial fills are normal: the caller
        shrinks its window on them (spillback signal). Zero immediate
        grants parks a single request on the legacy path so the
        pending-reason / ABANDONED semantics stay in one place."""
        count = max(1, min(int(count), self.config.lease_batch_max))
        demand = ResourceSet(dict(demand_items))
        translated = self.scheduler.translated_pg_demand(demand, strategy)
        t0 = time.time()
        req = _LeaseReq(
            demand, translated, strategy, ehash, dep_keys, peer,
            asyncio.get_running_loop().create_future(),
        )
        grants = []
        for _ in range(count):
            grant = self._try_grant_lease(req)
            if grant is None:
                break
            grants.append(grant)
        if grants:
            n = len(grants)
            self.lifecycle.record_batch("lease", "REQUESTED", n, ts=t0)
            self.lifecycle.record_batch(
                "lease", "GRANTED", n, prev="REQUESTED",
                dwell_ms=(time.time() - t0) * 1000.0,
                node=grants[0]["node_id"][:12],
            )
            _batch_metrics()["lease_batch"].observe(n)
            return {"grants": grants}
        req.req_id = "R%d" % next(self._lreq_seq)
        self.lifecycle.record("lease", req.req_id, "REQUESTED")
        self.lifecycle.pending_reason("lease", req.req_id, req.block_reason)
        self._lease_reqs.append(req)
        grant = await req.fut
        _batch_metrics()["lease_batch"].observe(1)
        return {"grants": [grant]}

    def _try_grant_lease(self, req: _LeaseReq) -> Optional[dict]:
        nid = self._locality_choice(req)
        if nid is None:
            result = self.scheduler.schedule(req.demand, req.strategy)
            nid = result.node_id
            if nid is None:
                req.block_reason = self._pending_reason(req.strategy, result)
                return None
        node_res = self.cluster.nodes.get(nid)
        if node_res is None or not node_res.acquire(req.translated):
            req.block_reason = "insufficient_resources"
            return None
        lease_id = b"L%d" % next(self._lease_seq)
        self.leases[lease_id] = LeaseRecord(
            lease_id=lease_id, demand=req.translated, node_id=nid,
            owner=req.peer, ehash=req.ehash,
        )
        node = self.nodes[nid]
        agent_addr = "controller" if node.peer is None else node.fetch_addr
        return {"lease_id": lease_id, "node_id": nid.hex(), "agent_addr": agent_addr}

    def _pending_reason(self, strategy: SchedulingStrategy, result) -> str:
        """Refine the scheduler's attribution with control-plane context
        the policy layer can't see: a PLACEMENT_GROUP miss whose group
        hasn't committed yet is gated on the PG, not on capacity."""
        reason = result.reason or (
            "infeasible" if result.infeasible else "insufficient_resources"
        )
        if (
            strategy.kind == "PLACEMENT_GROUP"
            and reason != "infeasible"
            and not self.pg_manager.is_ready(strategy.placement_group_id)
        ):
            return "pg_unready"
        return reason

    def _attribute_block(self, rec: TaskRecord, spec: TaskSpec, result):
        reason = self._pending_reason(spec.scheduling_strategy, result)
        self._mark_pending(rec, spec, reason)
        self.lifecycle.pending_reason(*self._lc_key(spec), reason)

    def _mark_pending(self, rec: TaskRecord, spec: TaskSpec, reason: str):
        """Blocked-with-a-reason is its own lifecycle state (round 17):
        QUEUED measures decision latency (intake → first verdict),
        PENDING the attributed park time — a ghost-actor storm no longer
        charges its deliberate hold to the scheduler. Guarded so
        re-pumping a still-blocked record doesn't fragment the dwell."""
        if not rec.pending_reason:
            self.lifecycle.record(*self._lc_key(spec), "PENDING")
        rec.pending_reason = reason

    def _mark_class_pending(self, q, reason: str):
        """Extend the head's block verdict to its class-mates: a blocked
        class FIFO blocks every member behind the head. Marked members
        form a queue PREFIX (intake clears the mark, so new arrivals are
        unmarked at the tail), so the reverse walk stops at the first
        marked member — O(new arrivals) amortized, not O(queue) per
        block."""
        for tid in reversed(q):
            rec = self.tasks.get(tid)
            if rec is None or rec.state != "PENDING":
                continue
            if rec.pending_reason:
                break
            rec.pending_reason = reason
            self.lifecycle.record(*self._lc_key(rec.spec), "PENDING")

    def _locality_choice(self, req: _LeaseReq) -> Optional[NodeID]:
        """Prefer the feasible node holding the most dependency bytes
        (reference: lease_policy.cc picks the raylet with the task's
        args). DEFAULT strategy only — explicit placement wins."""
        if req.strategy.kind != "DEFAULT" or not req.dep_keys:
            return None
        per_node: Dict[NodeID, int] = {}
        for k in req.dep_keys:
            orec = self.objects.get(ObjectID(k))
            if orec is None or orec.inline is not None or orec.state != "READY":
                continue
            for nid in orec.locations:
                per_node[nid] = per_node.get(nid, 0) + orec.size
        for nid in sorted(per_node, key=per_node.get, reverse=True):  # type: ignore[arg-type]
            node_res = self.cluster.nodes.get(nid)
            if (
                node_res is not None
                and not getattr(node_res, "draining", False)
                and node_res.fits(req.translated)
            ):
                return nid
        return None

    def _pump_leases(self):
        """Re-try parked lease requests (FIFO) — called from the pump."""
        if not self._lease_reqs:
            return
        still = []
        while self._lease_reqs:
            req = self._lease_reqs.popleft()
            if req.fut.done() or req.peer.closed:
                self.lifecycle.record("lease", req.req_id, "ABANDONED")
                continue  # caller gave up / died
            grant = self._try_grant_lease(req)
            if grant is None:
                self.lifecycle.pending_reason("lease", req.req_id, req.block_reason)
                still.append(req)
            else:
                self.lifecycle.record(
                    "lease", req.req_id, "GRANTED", node=grant["node_id"][:12]
                )
                req.fut.set_result(grant)
        self._lease_reqs.extend(still)

    def _spawn_head_direct(self, node):
        """Spawn one direct-pool worker on the head node (the controller
        doubles as the head's node agent)."""
        from ray_tpu.core.node_agent import spawn_worker

        node.num_starting += 1
        spawn_worker(
            self.session_dir, f"127.0.0.1:{self.port}", node.node_id,
            node.shm_dir, extra_env={"RAY_TPU_WORKER_POOL": "direct"},
        )

    async def rpc_lease_worker(self, peer: rpc.Peer, lease_id: bytes, ehash: str):
        """Hand out a head-node worker for a granted lease — the
        controller doubles as the head's node agent (reference: the
        raylet's WorkerPool PopWorker, worker_pool.h:363). Agent nodes
        serve this same RPC themselves (node_agent.rpc_lease_worker)."""
        rec = self.leases.get(lease_id)
        if rec is None:
            raise ValueError(f"unknown lease {lease_id!r}")
        node = self.nodes[rec.node_id]
        w = self._head_direct_pop(ehash)
        while w is None:
            if len(node.workers) + node.num_starting < node.max_workers:
                self._spawn_head_direct(node)
            else:
                # pool at cap: retire one mismatched free direct worker so
                # a pristine replacement can spawn (reference:
                # _recycle_idle_worker / worker_pool idle eviction)
                await self._retire_mismatched_direct(ehash, node)
            fut = asyncio.get_running_loop().create_future()
            self._head_direct_waiters.append((ehash, fut))
            w = await fut
            if w.state == "DEAD":
                w = self._head_direct_pop(ehash)
        # The awaits above race lease_release: the caller may have timed
        # out and released this lease while we waited — the worker must
        # go back to the pool, not leak as LEASED on a dead lease.
        rec = self.leases.get(lease_id)
        if rec is None:
            self._head_direct_put(w)
            raise ValueError(f"lease {lease_id!r} released while waiting for a worker")
        rec.worker_id = w.worker_id
        w.state = "LEASED"
        w.env_hash = ehash or w.env_hash
        return {"worker_addr": w.listen_addr, "worker_id": w.worker_id.hex()}

    async def rpc_lease_worker_batch(self, peer: rpc.Peer, lease_ids: list,
                                     ehash: str):
        """Hand out head-node workers for a BATCH of granted leases in
        one round-trip (round 17). Strictly non-blocking pops — no await
        between pop and bind, so the lease-release race rpc_lease_worker
        guards against cannot happen here. Misses return None in place;
        the caller falls back to the parking single-worker path for
        those (and shrinks its window — the spillback signal). One
        replacement spawn is triggered per miss so capacity catches up."""
        out = []
        misses = 0
        for lease_id in lease_ids:
            rec = self.leases.get(lease_id)
            if rec is None:
                out.append(None)  # released while the batch was in flight
                continue
            w = self._head_direct_pop(ehash)
            if w is None:
                out.append(None)
                misses += 1
                continue
            rec.worker_id = w.worker_id
            w.state = "LEASED"
            w.env_hash = ehash or w.env_hash
            out.append({"worker_addr": w.listen_addr,
                        "worker_id": w.worker_id.hex()})
        if misses:
            node = self.nodes[self.head_node_id]
            for _ in range(misses):
                if len(node.workers) + node.num_starting < node.max_workers:
                    self._spawn_head_direct(node)
                else:
                    await self._retire_mismatched_direct(ehash, node)
        return out

    async def _retire_mismatched_direct(self, ehash: str, node=None):
        for wid in list(self._head_direct_free):
            w = self.workers.get(wid)
            if w is None or w.state == "DEAD":
                self._head_direct_free.remove(wid)
                continue
            if w.env_hash and w.env_hash != ehash:
                self._head_direct_free.remove(wid)
                w.state = "DEAD"
                await _notify_quiet(w.peer, "exit", what="retire mismatched direct")
                # Pair the kill with a replacement spawn (mirrors
                # NodeAgent._retire_mismatched) so the parked caller isn't
                # left waiting on its own 30s lease timeout for capacity
                # that only frees when the retired worker's death is seen.
                if node is not None:
                    self._spawn_head_direct(node)
                return

    def _head_direct_pop(self, ehash: str) -> Optional[WorkerRecord]:
        fallback = None
        for wid in list(self._head_direct_free):
            w = self.workers.get(wid)
            if w is None or w.state != "DIRECT":
                self._head_direct_free.remove(wid)
                continue
            if w.env_hash == ehash:
                self._head_direct_free.remove(wid)
                return w
            if w.env_hash == "" and fallback is None:
                fallback = wid
        if fallback is not None:
            self._head_direct_free.remove(fallback)
            return self.workers[fallback]
        return None

    _SPAWN_STALE_S = 120.0  # silence horizon for in-flight env spawns

    def _env_starting_count(self, ehash: str) -> int:
        """In-flight spawn count for a preset env, expiring stale
        entries (a spawn that died before registering must not suppress
        respawns forever)."""
        entry = self._starting_by_env.get(ehash)
        if entry is None:
            return 0
        if time.time() - entry[1] > self._SPAWN_STALE_S:
            self._starting_by_env.pop(ehash, None)
            return 0
        return max(0, entry[0])

    async def _claim_direct_for_actor(self, node_id: NodeID, ehash: str):
        """Pop a FREE direct-pool worker on ``node_id`` for actor
        creation (reference: worker_pool.h:363-374 — PopWorker serves
        tasks and actors alike; VERDICT r4 weak #4: actor creation must
        not cold-spawn while prestarted workers sit idle)."""
        if ehash.startswith("img:"):
            return None  # container envs need a spawn-time worker
        if node_id == self.head_node_id:
            return self._head_direct_pop(ehash)
        node = self.nodes.get(node_id)
        if node is None or node.peer is None:
            return None
        try:
            wid_hex = await node.peer.call("claim_direct_worker", ehash)
        except Exception:  # noqa: BLE001 — agent gone; fall back to spawn
            return None
        if not wid_hex:
            return None
        w = self.workers.get(WorkerID(bytes.fromhex(wid_hex)))
        if w is None or w.state != "DIRECT":
            # The agent marked it busy; give it back or the pool slot
            # leaks (e.g. claim raced the worker's controller
            # registration).
            await _notify_quiet(
                node.peer, "release_direct_worker", wid_hex, what="agent gone"
            )
            return None
        return w

    async def _unclaim_direct(self, w: WorkerRecord):
        """Return a claimed-but-undispatched direct worker to its pool."""
        if w.node_id == self.head_node_id:
            self._head_direct_put(w)
            return
        w.state = "DIRECT"
        node = self.nodes.get(w.node_id)
        if node is not None and node.peer is not None:
            await _notify_quiet(
                node.peer, "release_direct_worker", w.worker_id.hex(),
                what="agent gone; worker dies with it",
            )

    def _head_direct_put(self, w: WorkerRecord):
        w.state = "DIRECT"
        for i, (ehash, fut) in enumerate(self._head_direct_waiters):
            if not fut.done() and (w.env_hash in ("", ehash)):
                del self._head_direct_waiters[i]
                fut.set_result(w)
                return
        self._head_direct_free.append(w.worker_id)

    async def rpc_lease_release(self, peer: rpc.Peer, lease_id: bytes,
                                kill_worker: bool = False):
        """``kill_worker``: the release came from the lease-holder DYING,
        not from a drained queue — the worker may be mid-task on an
        orphaned push, so it must be exited, never pooled (a pooled
        busy worker would queue the next caller's task behind it)."""
        rec = self.leases.pop(lease_id, None)
        if rec is None:
            return False
        node_res = self.cluster.nodes.get(rec.node_id)
        if node_res is not None:
            node_res.release(rec.demand)
        if rec.worker_id is not None:
            w = self.workers.get(rec.worker_id)
            if w is not None and w.state != "DEAD":
                if kill_worker:
                    w.state = "DEAD"
                    await _notify_quiet(w.peer, "exit", what="lease release kill")
                    # keep parked head lease_worker callers from hanging
                    node = self.nodes[rec.node_id]
                    if self._head_direct_waiters and (
                        len(node.workers) + node.num_starting < node.max_workers
                    ):
                        self._spawn_head_direct(node)
                else:
                    self._head_direct_put(w)
        else:
            # agent lease: the agent bound a worker we never saw — relay
            # the release so a dead lease-holder can't strand it busy
            node = self.nodes.get(rec.node_id)
            if node is not None and node.peer is not None and not node.peer.closed:
                await _notify_quiet(
                    node.peer, "lease_release", lease_id, kill_worker,
                    what="agent dying too",
                )
        self._schedule_pump()
        return True

    async def rpc_worker_death_info(self, peer: rpc.Peer, worker_id_hex: str):
        return self._dead_worker_info.get(worker_id_hex)

    async def rpc_task_lineage(self, peer: rpc.Peer, spec: TaskSpec):
        """Lineage for a direct-push task whose result went to shm: lets
        the existing reconstruction path (_try_reconstruct) resubmit it if
        the storing node dies (reference: owner-side TaskManager lineage;
        inline results never need reconstruction — they live in the
        owner's memory store)."""
        self.finished_specs[spec.task_id] = spec
        for oid in spec.return_ids():
            self._object(oid).creating_task = spec.task_id
        return True

    # =================================================================
    # Task submission / scheduling pump
    # =================================================================
    async def rpc_submit_task(self, peer: rpc.Peer, spec: TaskSpec, captures: Optional[list] = None):
        # Submission is a fire-and-forget notify (pipelined client): an
        # exception here would only be logged, leaving the return objects
        # PENDING forever — so any failure becomes the objects' error.
        try:
            rec = TaskRecord(spec=spec, retries_left=spec.max_retries)
            if captures:
                rec.captures = [
                    c if isinstance(c, ObjectID) else ObjectID(c) for c in captures
                ]
            if spec.dependencies or rec.captures:
                self._live_pin_tasks.add(spec.task_id)
            self.tasks[spec.task_id] = rec
            for oid in spec.return_ids():
                self._object(oid).creating_task = spec.task_id
            if spec.task_type == TaskType.ACTOR_TASK:
                self.lifecycle.record(
                    "task", spec.task_id.hex(), "SUBMITTED", name=spec.name
                )
                await self._submit_actor_task(spec)
            else:
                self.pending_tasks.append(spec.task_id)
                self._event("task", spec, "PENDING_SCHEDULING")
                self._schedule_pump()
        except Exception as e:  # noqa: BLE001 — surfaced through the refs
            logger.exception("submit_task failed for %s", spec.task_id.hex())
            rec = self.tasks.get(spec.task_id)
            if rec is not None:
                rec.state = "FAILED"
            self._fail_task_objects(spec, e)
        return True

    async def rpc_create_actor(
        self, peer: rpc.Peer, spec: TaskSpec, captures: Optional[list] = None, _journal: bool = True
    ):
        actor = ActorRecord(
            actor_id=spec.actor_id,
            creation_spec=spec,
            restarts_left=spec.max_restarts,
        )
        # The name travels in runtime_env["__actor_name__"] to keep TaskSpec lean.
        name = (spec.runtime_env or {}).get("__actor_name__", "")
        actor.name = name
        if name:
            if name in self.named_actors:
                raise ValueError(f"Actor with name {name!r} already exists")
            self.named_actors[name] = spec.actor_id
        self.actors[spec.actor_id] = actor
        if _journal and spec.lifetime == "detached":
            self.journal.actor_register(spec)
        rec = TaskRecord(spec=spec, retries_left=0)
        if captures:
            rec.captures = [
                c if isinstance(c, ObjectID) else ObjectID(c) for c in captures
            ]
        if spec.dependencies or rec.captures:
            # creation args are pinned until the creation task is terminal
            self._live_pin_tasks.add(spec.task_id)
        self.tasks[spec.task_id] = rec
        self.pending_tasks.append(spec.task_id)
        self._event("actor", spec, "PENDING_CREATION")
        self._schedule_pump()
        return True

    async def _submit_actor_task(self, spec: TaskSpec):
        actor = self.actors.get(spec.actor_id)
        if actor is None or actor.state == "DEAD":
            reason = actor.death_reason if actor else "actor not found"
            rec = self.tasks.get(spec.task_id)
            if rec is not None:
                rec.state = "FAILED"  # terminal → arg pins released
            self._fail_task_objects(spec, ActorDiedError(spec.actor_id.hex(), reason))
            return
        if actor.state != "ALIVE":
            actor.pending_tasks.append(spec)
            self.lifecycle.pending_reason(
                "task", spec.task_id.hex(), "waiting_actor"
            )
            return
        await self._dispatch_actor_task(actor, spec)

    async def _dispatch_actor_task(self, actor: ActorRecord, spec: TaskSpec):
        worker = self.workers.get(actor.worker_id)
        if worker is None or worker.peer.closed:
            actor.pending_tasks.append(spec)
            return
        rec = self.tasks.get(spec.task_id)
        if rec is None:
            rec = TaskRecord(spec=spec, retries_left=spec.max_task_retries)
            self.tasks[spec.task_id] = rec
        rec.state = "RUNNING"
        rec.worker_id = worker.worker_id
        rec.node_id = worker.node_id
        worker.running.add(spec.task_id)
        self._event("task", spec, "RUNNING")
        await worker.peer.notify("execute_actor_task", spec)

    def _schedule_pump(self):
        if self._pump_scheduled:
            return
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return  # loop shutting down
        self._pump_scheduled = True
        loop.call_soon(lambda: asyncio.ensure_future(self._pump()))

    async def _pump(self):
        self._pump_scheduled = False
        # Non-reentrant: the loop awaits (notify/spawn) mid-iteration, and a
        # second concurrent pump would race the pending_tasks rebind below
        # and could drop newly submitted tasks.
        if self._pump_running:
            self._pump_rerun = True
            return
        self._pump_running = True
        try:
            while True:
                self._pump_rerun = False
                await self._pump_once()
                if not self._pump_rerun:
                    break
        finally:
            self._pump_running = False

    async def _pump_once(self):
        self._pump_leases()
        import collections

        # Drain the intake list into per-class FIFOs. The pump then visits
        # CLASSES: a blocked class (infeasible / no worker / no resources)
        # is skipped in O(1) with its whole queue intact, so registration
        # of the n-th pending record costs O(#classes), not O(n).
        # Dispatch eligibility is env-affine (idle-worker match keys on
        # the runtime-env hash), so the class key must include it —
        # otherwise an env-B task with an idle env-B worker is starved
        # because an env-A task of the same class blocks first.
        intake, self.pending_tasks = self.pending_tasks, []
        for tid in intake:
            rec = self.tasks.get(tid)
            if rec is None or rec.state != "PENDING":
                continue
            spec = rec.spec
            key = (spec.scheduling_class(), _env_hash(spec.runtime_env))
            q = self._class_queues.get(key)
            if q is None:
                q = self._class_queues[key] = collections.deque()
            q.append(tid)
            lk, leid = self._lc_key(spec)
            self.lifecycle.record(lk, leid, "QUEUED")
            # Back in the queue = awaiting a fresh verdict: clear any
            # stale block mark so the next verdict re-records PENDING
            # (and keeps _mark_class_pending's marked-prefix invariant).
            rec.pending_reason = ""
            for dep in spec.dependencies:
                self._dep_index.setdefault(dep, set()).add(tid)
        # Keyed by (node, container_image, preset_env_hash): container
        # classes need image-wrapped, pre-tagged spawns; host classes
        # spawn pristine (image=None, hash="").
        spawn_requests: Dict[Tuple, int] = {}
        for key in list(self._class_queues.keys()):
            q = self._class_queues.get(key)
            if q:
                await self._pump_class(key, q, spawn_requests)
            if not q:
                self._class_queues.pop(key, None)
        for (nid, image, preset), n in spawn_requests.items():
            node = self.nodes.get(nid)
            if node is not None:
                await self._request_workers(
                    node, n, container_image=image, preset_env_hash=preset
                )

    async def _pump_class(self, key: Tuple, q, spawn_requests: Dict[NodeID, int]):
        """Dispatch from one scheduling-class FIFO until the class blocks
        (head-of-line blocking per class, reference: SchedulingClass
        queues in cluster_task_manager.cc). Returning with the queue
        non-empty means blocked; a completion/attach/registration re-pump
        retries the head."""
        _sclass, ehash = key
        while q:
            tid = q[0]
            rec = self.tasks.get(tid)
            if rec is None or rec.state != "PENDING":
                q.popleft()  # cancelled/failed/dispatched elsewhere
                continue
            spec = rec.spec
            # 1. dependencies local?
            advance = True
            for dep in spec.dependencies:
                if dep not in self.objects and dep in self._freed_lru:
                    self._fail_task_objects(
                        spec, ObjectLostError(dep.hex(), "dependency was freed")
                    )
                    rec.state = "FAILED"
                    self._unindex_deps(spec)
                    break
                orec = self._object(dep)
                if orec.state == "FAILED":
                    self._fail_task_objects(spec, ObjectLostError(dep.hex(), "dependency failed"))
                    rec.state = "FAILED"
                    self._unindex_deps(spec)
                    break
                if orec.state != "READY":
                    # park OUT of the class queue (a dep-waiting head must
                    # not block class-mates whose deps are ready); any dep
                    # state change re-enqueues through the intake list
                    self._park_on_dep(dep, tid)
                    self._mark_pending(rec, spec, "waiting_deps")
                    self.lifecycle.pending_reason(*self._lc_key(spec), "waiting_deps")
                    advance = False
                    break
            if not advance or rec.state != "PENDING":
                q.popleft()
                continue
            # 2. pick node
            demand = self.scheduler.translated_pg_demand(spec.resources, spec.scheduling_strategy)
            result = self.scheduler.schedule(spec.resources, spec.scheduling_strategy)
            if result.node_id is None:
                self._attribute_block(rec, spec, result)
                self._mark_class_pending(q, rec.pending_reason)
                return  # class blocked: infeasible for now
            # 3. idle worker (env-affine)?
            worker = self._idle_worker_on(result.node_id, ehash)
            claimed_direct = False
            if worker is None and spec.task_type == TaskType.ACTOR_CREATION_TASK:
                # Actor fast path: claim a prestarted direct-pool worker
                # instead of cold-spawning — the reference's PopWorker
                # makes no task/actor distinction (worker_pool.h:363-374).
                worker = await self._claim_direct_for_actor(result.node_id, ehash)
                claimed_direct = worker is not None
            if worker is None:
                # A node whose worker pool is EXHAUSTED (full, nothing
                # recyclable) cannot take the task even though resources
                # are free — spill to other feasible nodes instead of
                # wedging on it (reference: lease spillback re-requests
                # with the rejecting raylet excluded).
                excluded: Set[NodeID] = set()
                while worker is None and result.node_id is not None:
                    node = self.nodes[result.node_id]
                    if len(node.workers) + node.num_starting < node.max_workers:
                        break  # room to spawn here
                    if await self._recycle_idle_worker(node, ehash):
                        break  # a slot is freeing up here
                    excluded.add(result.node_id)
                    result = self.scheduler.schedule(
                        spec.resources, spec.scheduling_strategy, exclude=excluded
                    )
                    if result.node_id is None:
                        break
                    demand = self.scheduler.translated_pg_demand(
                        spec.resources, spec.scheduling_strategy
                    )
                    worker = self._idle_worker_on(result.node_id, ehash)
                if worker is None:
                    reason = "spillback" if excluded else "no_idle_worker"
                    self._mark_pending(rec, spec, reason)
                    self.lifecycle.pending_reason(*self._lc_key(spec), reason)
                    if result.node_id is not None:
                        # Worker ramp-up for the queued depth, capped by
                        # the node's SCHEDULABLE concurrency for this
                        # demand — a deep queue of 1-CPU tasks on a 1-CPU
                        # node must not spawn max_workers processes that
                        # can never run concurrently (reference:
                        # worker_pool soft limit ≈ CPU slots).
                        cap = self._class_slots(result.node_id, demand)
                        image = (spec.runtime_env or {}).get("image_uri")
                        depth = len(q)
                        if image:
                            depth -= self._env_starting_count(ehash)
                        n = min(depth, max(cap, 0))
                        if n > 0:
                            skey = (
                                result.node_id, image, ehash if image else ""
                            )
                            spawn_requests[skey] = spawn_requests.get(skey, 0) + n
                    self._mark_class_pending(q, reason)
                    return  # class blocked until a worker attaches/frees
            # 4. acquire resources + dispatch. The recycle loop above
            # awaited: the task may have been cancelled/failed meanwhile —
            # dispatching it would resurrect a FAILED record whose result
            # objects were already failed.
            if rec.state != "PENDING":
                if claimed_direct:
                    await self._unclaim_direct(worker)
                q.popleft()
                continue
            node_res = self.cluster.nodes[result.node_id]
            if not node_res.acquire(demand):
                if claimed_direct:
                    await self._unclaim_direct(worker)
                self._mark_pending(rec, spec, "insufficient_resources")
                self.lifecycle.pending_reason(
                    *self._lc_key(spec), "insufficient_resources"
                )
                self._mark_class_pending(q, "insufficient_resources")
                return  # class blocked on resources
            rec.pending_reason = ""
            rec.acquired = demand
            rec.node_id = result.node_id
            rec.worker_id = worker.worker_id
            rec.state = "DISPATCHED"
            worker.running.add(tid)
            worker.env_hash = ehash or worker.env_hash
            q.popleft()
            self._unindex_deps(spec)
            if spec.task_type == TaskType.ACTOR_CREATION_TASK:
                worker.state = "ACTOR"
                worker.actor_id = spec.actor_id
                actor = self.actors[spec.actor_id]
                actor.worker_id = worker.worker_id
                actor.node_id = result.node_id
                self._assign_tpu_chips(actor, spec, self.nodes[result.node_id])
                self._event("actor", spec, "CREATING")
                await worker.peer.notify("create_actor", spec)
            else:
                worker.state = "LEASED"
                self._event("task", spec, "RUNNING")
                await worker.peer.notify("execute_task", spec)

    def _unindex_deps(self, spec: TaskSpec):
        for dep in spec.dependencies:
            s = self._dep_index.get(dep)
            if s is not None:
                s.discard(spec.task_id)
                if not s:
                    del self._dep_index[dep]

    def _fail_freed_dependents(self, oid: ObjectID):
        for tid in list(self._dep_index.pop(oid, ())):
            rec = self.tasks.get(tid)
            if rec is None or rec.state != "PENDING":
                continue
            rec.state = "FAILED"
            self._fail_task_objects(
                rec.spec, ObjectLostError(oid.hex(), "dependency was freed")
            )
            self._unindex_deps(rec.spec)

    def _park_on_dep(self, dep: ObjectID, tid: TaskID):
        """Hold a dep-waiting task outside the class FIFOs until the dep
        resolves; any state change (_wake on READY or FAILED) re-enqueues
        it through the intake list for a fresh eligibility pass."""
        self._dep_parked.add(tid)
        orec = self._object(dep)
        fut = asyncio.get_running_loop().create_future()

        def _requeue(_):
            self._dep_parked.discard(tid)
            self.pending_tasks.append(tid)
            self._schedule_pump()

        fut.add_done_callback(_requeue)
        orec.waiters.append(fut)

    def _class_slots(self, node_id: NodeID, demand) -> int:
        """How many MORE tasks of ``demand`` the node could start right
        now (available resources, minus workers already spawning) — the
        worker ramp-up cap for one scheduling class. Prevents a deep
        queue of 1-CPU tasks on a 1-CPU node from spawning max_workers
        processes that can never run concurrently (reference:
        worker_pool.h prestart/soft-limit semantics)."""
        node = self.cluster.nodes.get(node_id)
        if node is None:
            return 1
        starting = self.nodes[node_id].num_starting if node_id in self.nodes else 0
        slots = None
        for name, fp in demand.items_fp():
            if fp <= 0:
                continue
            avail = node.available.get(name)
            s = avail // fp
            slots = s if slots is None else min(slots, s)
        if slots is None:
            slots = 4  # zero-resource tasks: modest default ramp
        return max(0, int(slots) - starting)

    # =================================================================
    # Task completion
    # =================================================================
    async def rpc_task_done(
        self,
        peer: rpc.Peer,
        task_id: TaskID,
        results: List[tuple],  # (oid, "inline", data) | (oid, "shm", size)
        error: Optional[Exception],
    ):
        rec = self.tasks.get(task_id)
        if rec is None:
            return False
        spec = rec.spec
        worker = self.workers.get(rec.worker_id) if rec.worker_id else None
        if worker is not None:
            worker.running.discard(task_id)
        # Release resources — EXCEPT a successful creation of an actor with
        # explicit resource requests, whose acquisition transfers to the
        # actor until it dies (reference: actors hold requested resources).
        if (
            error is None
            and spec.task_type == TaskType.ACTOR_CREATION_TASK
            and spec.hold_resources_while_alive
            and rec.acquired is not None
        ):
            actor = self.actors.get(spec.actor_id)
            if actor is not None:
                actor.held_resources = rec.acquired
                actor.held_node = rec.node_id
                rec.acquired = None
            else:
                self._release_task(rec)
        else:
            self._release_task(rec)
        if error is not None:
            retriable = rec.retries_left > 0 and (
                spec.retry_exceptions or isinstance(error, (WorkerCrashedError,))
            )
            if spec.task_type == TaskType.ACTOR_CREATION_TASK:
                # __init__ raised: the actor is dead on arrival (reference:
                # gcs_actor_manager — creation failure is not retried as a
                # restart). Free the half-initialized worker.
                rec.state = "FAILED"
                self._event("actor", spec, "CREATION_FAILED")
                self._fail_task_objects(spec, error)
                actor = self.actors.get(spec.actor_id)
                if actor is not None:
                    actor.restarts_left = 0
                    await self._on_actor_death(spec.actor_id, f"__init__ failed: {error}")
                if worker is not None:
                    worker.actor_id = None
                    await worker.peer.notify("exit")
            elif retriable:
                rec.retries_left -= 1
                rec.state = "PENDING"
                self.pending_tasks.append(task_id)
                self._event("task", spec, "RETRYING")
            else:
                rec.state = "FAILED"
                self._event("task", spec, "FAILED")
                self._fail_task_objects(spec, error)
        else:
            rec.state = "FINISHED"
            self.finished_specs[task_id] = spec
            self._event("task", spec, "FINISHED")
            node_id = worker.node_id if worker else rec.node_id
            census = getattr(self.config, "memory_census", True)
            for item in results:
                oid, kind = item[0], item[1]
                orec = self._object(oid)
                if census and not orec.callsite:
                    # interned: a generator of unique task names must not
                    # grow an unbounded call-site vocabulary here
                    from ray_tpu.core.memory_census import task_site

                    orec.callsite = task_site(spec.name)
                if census and not orec.creator and worker is not None:
                    orec.creator = f"worker:{worker.worker_id.hex()[:12]}"
                if kind == "inline":
                    orec.inline = item[2]
                    orec.size = len(item[2])
                    orec.is_error = bool(item[3]) if len(item) > 3 else False
                    if len(item) > 4 and item[4]:
                        orec.children = list(item[4])
                else:
                    orec.size = item[2]
                    orec.locations.add(node_id)
                    if len(item) > 3 and item[3]:
                        orec.children = list(item[3])
                    await self._account_object(node_id, oid, item[2])
                orec.state = "READY"
                self._wake(orec)
            if spec.task_type == TaskType.ACTOR_CREATION_TASK:
                await self._on_actor_created(spec)
        # Return worker to pool.
        if worker is not None and worker.state == "LEASED":
            worker.state = "IDLE"
        if rec.state in ("FINISHED", "FAILED"):
            # End-of-stream only on terminal states — a retried streaming
            # task must not signal a premature end to its consumers.
            rec.stream_done = True
            self._wake_stream(rec)
        self._schedule_pump()
        return True

    def _release_task(self, rec: TaskRecord):
        if rec.acquired is not None and rec.node_id in self.cluster.nodes:
            self.cluster.nodes[rec.node_id].release(rec.acquired)
        rec.acquired = None

    async def _on_actor_created(self, spec: TaskSpec):
        actor = self.actors.get(spec.actor_id)
        if actor is None:
            return
        actor.state = "ALIVE"
        self.lifecycle.record(
            "actor", spec.actor_id.hex(), "ALIVE", name=spec.name
        )
        for fut in actor.ready_waiters:
            if not fut.done():
                fut.set_result(True)
        actor.ready_waiters.clear()
        pending, actor.pending_tasks = actor.pending_tasks, []
        for t in pending:
            await self._dispatch_actor_task(actor, t)

    def _fail_task_objects(self, spec: TaskSpec, error: Exception):
        from ray_tpu.utils.serialization import serialize

        blob = serialize(error)
        if spec.is_streaming:
            # Streaming failure: the error becomes the stream's final item
            # (reference: streaming generators surface mid-stream errors as
            # the next yielded ref).
            rec = self.tasks.get(spec.task_id)
            if rec is not None:
                oid = ObjectID.for_task_return(spec.task_id, rec.stream_count)
                orec = self._object(oid)
                orec.inline = blob
                orec.is_error = True
                orec.state = "READY"
                self._wake(orec)
                rec.stream_count += 1
                rec.stream_done = True
                self._wake_stream(rec)
            return
        for oid in spec.return_ids():
            orec = self._object(oid)
            orec.inline = blob
            orec.is_error = True
            orec.state = "READY"
            self._wake(orec)

    # =================================================================
    # Failure handling
    # =================================================================
    async def _on_worker_death(self, worker_id: WorkerID, reason: str):
        worker = self.workers.pop(worker_id, None)
        if worker is None:
            return
        worker.state = "DEAD"
        node = self.nodes.get(worker.node_id)
        if node is not None:
            node.workers.discard(worker_id)
        if worker_id in self._head_direct_free:
            self._head_direct_free.remove(worker_id)
        self._dead_worker_info[worker_id.hex()] = (
            "oom" if worker.oom_marked else reason
        )
        self.lifecycle.record(
            "worker", worker_id.hex(), "DEAD",
            reason="oom" if worker.oom_marked else reason,
        )
        await self._publish_death(
            "worker", worker_id.hex(), "DEAD",
            reason="oom" if worker.oom_marked else reason,
            node=worker.node_id.hex(),
            actor=worker.actor_id.hex() if worker.actor_id else "",
        )
        while len(self._dead_worker_info) > 1000:
            self._dead_worker_info.popitem(last=False)
        # Fail or retry running tasks FIRST: _on_actor_death below requeues
        # the creation task under the same deterministic task id, and must
        # not have its fresh record clobbered by this loop.
        will_restart = False
        if worker.actor_id is not None:
            actor = self.actors.get(worker.actor_id)
            will_restart = actor is not None and actor.restarts_left > 0
        for tid in list(worker.running):
            rec = self.tasks.get(tid)
            if rec is None:
                continue
            self._release_task(rec)
            spec = rec.spec
            if spec.task_type == TaskType.ACTOR_CREATION_TASK:
                if will_restart:
                    continue  # restart path requeues this same spec
                rec.state = "FAILED"
                self.lifecycle.record(
                    "actor", spec.actor_id.hex(), "FAILED", name=spec.name
                )
                self._fail_task_objects(
                    spec, ActorDiedError(spec.actor_id.hex(), f"died in __init__ ({reason})")
                )
            elif spec.task_type == TaskType.ACTOR_TASK:
                actor = self.actors.get(spec.actor_id)
                actor_alive = actor is not None and (
                    actor.state != "DEAD" or will_restart
                )
                if rec.retries_left > 0 and actor_alive:
                    rec.retries_left -= 1
                    rec.state = "PENDING"
                    actor.pending_tasks.append(spec)
                    self._event("task", spec, "RETRYING")
                else:
                    rec.state = "FAILED"
                    self.lifecycle.record(
                        "task", spec.task_id.hex(), "FAILED", name=spec.name
                    )
                    self._fail_task_objects(
                        spec,
                        ActorDiedError(spec.actor_id.hex(), f"actor worker died ({reason})"),
                    )
            else:
                if rec.retries_left > 0:
                    rec.retries_left -= 1
                    rec.state = "PENDING"
                    self.pending_tasks.append(tid)
                    self._event("task", spec, "RETRYING")
                else:
                    rec.state = "FAILED"
                    if worker.oom_marked:
                        err = OutOfMemoryError(
                            f"task killed by the memory monitor (node over "
                            f"{self.config.memory_usage_threshold:.0%} memory)"
                        )
                    else:
                        err = WorkerCrashedError(
                            f"worker {worker_id.hex()[:8]} died while running task ({reason})"
                        )
                    self.lifecycle.record(
                        "task", spec.task_id.hex(), "FAILED", name=spec.name
                    )
                    self._fail_task_objects(spec, err)
        if worker.actor_id is not None:
            await self._on_actor_death(worker.actor_id, f"worker died: {reason}")
        self._schedule_pump()

    def _assign_tpu_chips(self, actor: ActorRecord, spec: TaskSpec, node: NodeRecord):
        """Give a TPU actor concrete chips (reference: tpu.py:155-195;
        per-instance accounting, resource_instance_set.cc). This is the
        ONLY place chips are chosen; the variables ride the actor's
        runtime env, which the worker applies before it unpickles the
        actor class — so before user code can import jax."""
        from ray_tpu.accelerators.tpu import TPUAcceleratorManager
        from ray_tpu.core.resources import from_fp

        n = int(from_fp(spec.resources.get("TPU")))
        if n <= 0:
            return
        if len(node.tpu_free) < n:
            logger.warning(
                "TPU accounting drift: actor wants %d chips, node %s has %d free",
                n,
                node.node_id.hex()[:8],
                len(node.tpu_free),
            )
            return
        chips, node.tpu_free = node.tpu_free[:n], node.tpu_free[n:]
        actor.tpu_chips = chips
        actor.tpu_node = node.node_id
        on_host = int(from_fp(self.cluster.nodes[node.node_id].total.get("TPU")))
        renv = dict(spec.runtime_env or {})
        env_vars = dict(renv.get("env_vars") or {})
        env_vars.update(TPUAcceleratorManager.visible_chips_env(chips, on_host))
        if env_vars:
            renv["env_vars"] = env_vars
            spec.runtime_env = renv

    def _release_tpu_chips(self, actor: ActorRecord):
        if actor.tpu_chips and actor.tpu_node is not None:
            node = self.nodes.get(actor.tpu_node)
            if node is not None:
                # lowest-first keeps multi-chip grants adjacent
                node.tpu_free = sorted(node.tpu_free + actor.tpu_chips)
        actor.tpu_chips = []
        actor.tpu_node = None

    async def _on_actor_death(self, actor_id: ActorID, reason: str):
        actor = self.actors.get(actor_id)
        if actor is None or actor.state == "DEAD":
            return
        actor.worker_id = None
        self._release_tpu_chips(actor)
        if actor.held_resources is not None:
            if actor.held_node in self.cluster.nodes:
                self.cluster.nodes[actor.held_node].release(actor.held_resources)
            actor.held_resources = None
            actor.held_node = None
        if actor.restarts_left > 0:
            actor.restarts_left -= 1
            actor.num_restarts += 1
            actor.state = "RESTARTING"
            self._event("actor", actor.creation_spec, "RESTARTING")
            await self._publish_death(
                "actor", actor_id.hex(), "RESTARTING", reason=reason
            )
            # Re-run the creation task.
            spec = actor.creation_spec
            rec = TaskRecord(spec=spec, retries_left=0)
            self.tasks[spec.task_id] = rec
            self.pending_tasks.append(spec.task_id)
            self._schedule_pump()
        else:
            actor.state = "DEAD"
            actor.death_reason = reason
            self._event("actor", actor.creation_spec, "DEAD")
            await self._publish_death(
                "actor", actor_id.hex(), "DEAD", reason=reason,
                name=actor.creation_spec.name,
            )
            if actor.creation_spec.lifetime == "detached":
                self.journal.actor_dead(actor_id.hex())
            if actor.name:
                self.named_actors.pop(actor.name, None)
            err = ActorDiedError(actor_id.hex(), reason)
            for spec in actor.pending_tasks:
                rec = self.tasks.get(spec.task_id)
                if rec is not None:
                    rec.state = "FAILED"
                self._fail_task_objects(spec, err)
            actor.pending_tasks.clear()
            for fut in actor.ready_waiters:
                if not fut.done():
                    fut.set_exception(err)
            actor.ready_waiters.clear()

    async def _on_node_death(self, node_id: NodeID):
        node = self.nodes.pop(node_id, None)
        if node is None:
            return
        self._dead_node_ids.add(node_id.hex())
        node.state = "DEAD"
        self.cluster.remove_node(node_id)
        self.lifecycle.record("node", node_id.hex(), "DEAD")
        await self._publish_death("node", node_id.hex(), "DEAD")
        for wid in list(node.workers):
            w = self.workers.get(wid)
            if w is not None:
                await _notify_quiet(w.peer, "exit", what="node died")
            await self._on_worker_death(wid, "node died")
        # Drop the dead node from EVERY record's location set (objects can
        # have multiple replicas since the network data plane copies them
        # on pull); objects left with no copy attempt lineage
        # reconstruction.
        for orec in self.objects.values():
            if orec.state == "READY" and orec.inline is None and node_id in orec.locations:
                orec.locations.discard(node_id)
                if not orec.locations:
                    await self._try_reconstruct(orec)
        self.pg_manager.on_node_removed(node_id)
        self._schedule_pump()

    async def _try_reconstruct(self, orec: ObjectRecord):
        """Lineage reconstruction: resubmit the creating task (reference:
        src/ray/core_worker/object_recovery_manager.h:70-84)."""
        spec = self.finished_specs.get(orec.creating_task) if orec.creating_task else None
        if spec is None or spec.task_type != TaskType.NORMAL_TASK:
            orec.state = "FAILED"
            orec.inline = None
            self._wake(orec)
            return
        # GC may have freed an input after the task finished — lineage is
        # then evicted and reconstruction must fail fast, not hang on an
        # empty recreated dep record (reference:
        # ReconstructionFailedLineageEvictedError, exceptions.py:663-705).
        for dep in spec.dependencies:
            dep_rec = self.objects.get(dep)
            if dep_rec is None or (
                dep_rec.state != "READY" and dep_rec.creating_task is None
            ):
                orec.state = "FAILED"
                orec.inline = None
                self._wake(orec)
                return
        orec.state = "PENDING"
        rec = TaskRecord(spec=spec, retries_left=0)
        self.tasks[spec.task_id] = rec
        if spec.dependencies:
            self._live_pin_tasks.add(spec.task_id)
        self.pending_tasks.append(spec.task_id)
        self._event("task", spec, "RECONSTRUCTING")
        self._schedule_pump()

    # =================================================================
    # Objects
    # =================================================================
    def _object(self, oid: ObjectID) -> ObjectRecord:
        rec = self.objects.get(oid)
        if rec is None:
            rec = ObjectRecord(oid=oid)
            self.objects[oid] = rec
        return rec

    def _wake(self, orec: ObjectRecord):
        for fut in orec.waiters:
            if not fut.done():
                fut.set_result(True)
        orec.waiters.clear()

    def _shm_dirs(self) -> Dict[NodeID, str]:
        return {nid: n.shm_dir for nid, n in self.nodes.items()}

    @staticmethod
    def _peer_identity(peer: Optional[rpc.Peer]) -> str:
        """Short creator label for object attribution rows."""
        if peer is None:
            return ""
        wid = peer.meta.get("worker_id")
        if wid is not None:
            return f"worker:{wid.hex()[:12]}"
        holder = peer.meta.get("holder_id") or ""
        kind = peer.meta.get("kind") or "proc"
        return f"{kind}:{holder[:12]}" if holder else kind

    def _attribute_object(self, orec: ObjectRecord, peer: Optional[rpc.Peer],
                          callsite: str):
        if callsite and not orec.callsite:
            orec.callsite = callsite
        if not orec.creator:
            orec.creator = self._peer_identity(peer)

    async def rpc_object_put_inline(
        self, peer: rpc.Peer, oid: ObjectID, data: bytes, is_error: bool = False,
        contained: Optional[list] = None, callsite: str = "",
    ):
        orec = self._object(oid)
        orec.inline = data
        orec.size = len(data)
        orec.is_error = is_error
        if contained:
            orec.children = list(contained)
        self._attribute_object(orec, peer, callsite)
        orec.state = "READY"
        self._wake(orec)
        return True

    async def rpc_object_put_shm(
        self, peer: rpc.Peer, oid: ObjectID, size: int, node_id: NodeID, is_error: bool = False,
        contained: Optional[list] = None, callsite: str = "",
    ):
        orec = self._object(oid)
        orec.size = size
        orec.is_error = is_error
        orec.locations.add(node_id)
        if contained:
            orec.children = list(contained)
        self._attribute_object(orec, peer, callsite)
        await self._account_object(node_id, oid, size)
        orec.state = "READY"
        self._wake(orec)
        return True

    async def _account_object(self, node_id: NodeID, oid: ObjectID, size: int):
        """Register a worker-written shm object with its node's store so
        capacity accounting and spill/eviction see it."""
        node = self.nodes.get(node_id)
        if node is None:
            return
        if node.peer is None:
            self.head_store.adopt(oid, size)
        else:
            await node.peer.notify("adopt_object", oid, size)

    async def rpc_object_ensure_local(self, peer: rpc.Peer, oid: ObjectID, node_hex: str):
        """Restore a spilled object into its node's shm dir before a reader
        maps it (reference: spilled-object restore via IO workers,
        raylet/local_object_manager.cc)."""
        node = self.nodes.get(NodeID.from_hex(node_hex))
        if node is None:
            return False
        if node.peer is None:
            return self.head_store.ensure_local(oid)
        return await node.peer.call("ensure_local", oid)

    async def rpc_fetch_chunk(self, peer: rpc.Peer, oid: ObjectID, offset: int, length: int):
        """Serve a chunk of a head-node object to a pulling agent
        (reference: ObjectManagerService on every node — the head's
        'agent' is the controller itself)."""
        return rpc.Raw(self._chunk_reader.read(oid, offset, length))

    async def rpc_object_pull(self, peer: rpc.Peer, oid: ObjectID, dest_node_id: NodeID) -> bool:
        """Ensure ``oid`` is readable on ``dest_node_id``, transferring it
        over the network if needed (reference: PullManager + the
        ownership-based object directory picking the source replica).
        Concurrent pulls of the same (object, node) coalesce."""
        orec = self.objects.get(oid)
        if orec is None or orec.state != "READY" or orec.inline is not None:
            return False
        if dest_node_id in orec.locations:
            return await self.rpc_object_ensure_local(peer, oid, dest_node_id.hex())
        key = (oid, dest_node_id)
        existing = self._pulls.get(key)
        if existing is not None:
            return await asyncio.shield(existing)
        fut = asyncio.get_running_loop().create_future()
        self._pulls[key] = fut
        try:
            ok = await self._do_pull(oid, orec, dest_node_id)
            if not fut.done():
                fut.set_result(ok)
            return ok
        except Exception as e:  # noqa: BLE001 — surface as pull failure
            logger.warning("object pull %s -> %s failed: %s", oid.hex()[:8], dest_node_id.hex()[:8], e)
            if not fut.done():
                fut.set_result(False)
            return False
        finally:
            self._pulls.pop(key, None)

    async def _do_pull(self, oid: ObjectID, orec: ObjectRecord, dest_node_id: NodeID) -> bool:
        dest = self.nodes.get(dest_node_id)
        if dest is None:
            return False
        # pick a LIVE replica (locations may briefly hold a dying node)
        src = next(
            (self.nodes[nid] for nid in orec.locations if nid in self.nodes),
            None,
        )
        if src is None:
            return False
        if src.peer is None:
            src_addr = "controller"  # head objects served by rpc_fetch_chunk
        else:
            src_addr = src.fetch_addr
            if not src_addr:
                return False
        if dest.peer is None:
            # destination is the head: the controller pulls into its own store
            from ray_tpu.core.object_transfer import pull_into_store

            src_peer = await self._fetch_peer_for(src_addr)
            if src_peer is None:
                return False
            ok = await pull_into_store(
                self.head_store, oid, orec.size, src_peer,
                self.config.object_transfer_chunk_bytes,
            )
        else:
            ok = await dest.peer.call("pull_object", oid, orec.size, src_addr)
        if ok:
            orec.locations.add(dest_node_id)
        return bool(ok)

    async def _fetch_peer_for(self, addr: str) -> Optional[rpc.Peer]:
        if addr == "controller":
            return None  # head pulling from itself makes no sense
        return await self._fetch_peers.get(addr)

    async def rpc_object_get(self, peer: rpc.Peer, oids: List[ObjectID], timeout: Optional[float]):
        """Long-poll get: resolves when ALL are ready (or raises on timeout)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        metas = {}
        for oid in oids:
            if oid not in self.objects and oid in self._freed_lru:
                metas[oid.hex()] = ("lost", None, True)
                continue
            orec = self._object(oid)
            while orec.state == "PENDING":
                fut = asyncio.get_running_loop().create_future()
                orec.waiters.append(fut)
                remain = None if deadline is None else deadline - time.monotonic()
                if remain is not None and remain <= 0:
                    return {"timeout": True, "metas": metas}
                try:
                    await asyncio.wait_for(asyncio.shield(fut), remain)
                except asyncio.TimeoutError:
                    return {"timeout": True, "metas": metas}
            if orec.state == "FAILED":
                metas[oid.hex()] = ("lost", None, True)
            else:
                meta = orec.meta(self._shm_dirs())
                if meta is None:
                    # every replica's node died; reconstruction (queued by
                    # _on_node_death) will re-resolve it, or it is lost
                    await self._try_reconstruct(orec)
                    if orec.state == "PENDING":
                        # re-wait on the reconstructed object
                        continue_oids = [o for o in oids if o.hex() not in metas]
                        inner = await self.rpc_object_get(
                            peer, continue_oids,
                            None if deadline is None else max(0.0, deadline - time.monotonic()),
                        )
                        metas.update(inner["metas"])
                        return {"timeout": inner["timeout"], "metas": metas}
                    meta = ("lost", None, True)
                metas[oid.hex()] = meta
        return {"timeout": False, "metas": metas}

    async def rpc_object_wait(self, peer: rpc.Peer, oids: List[ObjectID], num_returns: int, timeout: Optional[float]):
        """ray.wait semantics: return when num_returns of oids are ready."""
        deadline = None if timeout is None else time.monotonic() + timeout

        def _resolved(o: ObjectID) -> bool:
            if o not in self.objects and o in self._freed_lru:
                return True  # freed → resolved (get will fail fast)
            return self._object(o).state != "PENDING"

        while True:
            ready = [o for o in oids if _resolved(o)]
            if len(ready) >= num_returns:
                return [o.hex() for o in ready]
            remain = None if deadline is None else deadline - time.monotonic()
            if remain is not None and remain <= 0:
                return [o.hex() for o in ready]
            futs = []
            for o in oids:
                if o not in self.objects and o in self._freed_lru:
                    continue
                orec = self._object(o)
                if orec.state == "PENDING":
                    fut = asyncio.get_running_loop().create_future()
                    orec.waiters.append(fut)
                    futs.append(fut)
            if not futs:
                # Everything resolved but fewer than num_returns exist —
                # nothing more can become ready.
                return [o.hex() for o in oids if _resolved(o)]
            try:
                await asyncio.wait_for(
                    asyncio.wait(futs, return_when=asyncio.FIRST_COMPLETED), remain
                )
            except asyncio.TimeoutError:
                pass

    async def rpc_object_free(self, peer: rpc.Peer, oids: List[ObjectID]):
        for oid in oids:
            await self._free_object(oid)
        return True

    async def _free_object(self, oid: ObjectID):
        orec = self.objects.pop(oid, None)
        if orec is None:
            return
        t0 = time.monotonic()
        self._freed_lru[oid] = None
        while len(self._freed_lru) > 200_000:
            self._freed_lru.popitem(last=False)
        # Wake any in-flight long-poll gets as a loss, not a hang.
        if orec.waiters:
            orec.state = "FAILED"
            self._wake(orec)
        # Tasks queued behind a blocked class head may depend on the freed
        # object; the per-class pump no longer re-scans every pending task
        # each cycle, so fail them here (frees are rare, pending can be
        # huge — this is the right side of that trade).
        self._fail_freed_dependents(oid)
        for nid in orec.locations:
            node = self.nodes.get(nid)
            if node is None:
                continue
            if node.peer is None:
                self._chunk_reader.invalidate(oid)
                self.head_store.delete(oid)
            else:
                await node.peer.notify("delete_object", oid)
        _get_mem_metrics()["free_latency"].observe(
            (time.monotonic() - t0) * 1000.0
        )

    # -- distributed ref counting (reference: reference_count.cc; the
    # controller is the authority the way owners are in the reference) ----
    async def rpc_ref_update(
        self, peer: rpc.Peer, holder: str, held: List[bytes], dropped: List[bytes]
    ):
        peer.meta.setdefault("holder_id", holder)
        index = self._holder_index.setdefault(holder, set())
        for key in held:
            # A held report for an already-freed object is a dangling
            # borrow — do NOT resurrect a record (a later get would hang
            # on an empty PENDING entry instead of failing fast).
            oid = ObjectID(key)
            orec = self.objects.get(oid)
            if orec is not None:
                orec.holders.add(holder)
                orec.ever_held = True
                orec.gc_marked = False
                index.add(oid)
        for key in dropped:
            oid = ObjectID(key)
            index.discard(oid)
            orec = self.objects.get(oid)
            if orec is not None:
                orec.holders.discard(holder)
                orec.ever_held = True
        self._gc_wanted.set()
        return True

    def _drop_holder(self, holder: str):
        """A process died/disconnected: it no longer holds anything.
        O(objects that process held), via the reverse index."""
        held = self._holder_index.pop(holder, None)
        if not held:
            return
        for oid in held:
            orec = self.objects.get(oid)
            if orec is not None:
                orec.holders.discard(holder)
        self._gc_wanted.set()

    def _pinned_objects(self) -> Set[ObjectID]:
        """Objects that must survive regardless of holders: args of live
        tasks (deps + nested captures) and children contained in any live
        object (the borrowing protocol's containment edges).

        ``_live_pin_tasks`` is pruned lazily here so a sweep costs
        O(live tasks + terminal-since-last-sweep), not O(all tasks ever)
        — self.tasks grows monotonically (1M+ in the queueing bench)."""
        pinned: Set[ObjectID] = set()
        dead: List[TaskID] = []
        for tid in self._live_pin_tasks:
            rec = self.tasks.get(tid)
            if rec is None or rec.state in ("FINISHED", "FAILED"):
                dead.append(tid)
                continue
            pinned.update(rec.spec.dependencies)
            pinned.update(rec.captures)
        self._live_pin_tasks.difference_update(dead)
        # Actor creation args stay pinned while a restart could re-run
        # __init__ (reference: restarts re-execute the creation task).
        for actor in self.actors.values():
            if actor.state == "DEAD":
                continue
            if actor.state == "ALIVE" and actor.restarts_left <= 0:
                continue
            spec = actor.creation_spec
            pinned.update(spec.dependencies)
            rec = self.tasks.get(spec.task_id)
            if rec is not None:
                pinned.update(rec.captures)
        for orec in self.objects.values():
            pinned.update(orec.children)
        return pinned

    async def _gc_sweep_loop(self):
        interval = self.config.gc_sweep_interval_ms / 1000.0
        while not self._shutdown.is_set():
            try:
                await asyncio.wait_for(self._gc_wanted.wait(), timeout=30.0)
            except asyncio.TimeoutError:
                continue
            await asyncio.sleep(interval)  # batch a window of updates
            self._gc_wanted.clear()
            try:
                freed = await self._gc_sweep()
            except Exception:
                logger.exception("gc sweep failed")
                continue
            if freed:
                # Freeing a container unpins its children — cascade until
                # a sweep frees nothing.
                self._gc_wanted.set()

    async def _gc_sweep(self) -> int:
        candidates = [
            orec
            for orec in self.objects.values()
            if orec.ever_held and not orec.holders and orec.state != "PENDING"
        ]
        if not candidates:
            return 0
        pinned = self._pinned_objects()
        freed = marked = 0
        for orec in candidates:
            if orec.oid in pinned:
                orec.gc_marked = False
                continue
            if not orec.gc_marked:
                # phase 1: mark; freed only if still unreferenced at the
                # next sweep (in-flight borrow flushes get a full interval
                # to land and clear the mark)
                orec.gc_marked = True
                marked += 1
                continue
            await self._free_object(orec.oid)
            freed += 1
        if marked:
            self._gc_wanted.set()  # guarantee a follow-up sweep
        if freed:
            logger.debug("gc: freed %d unreferenced objects", freed)
        return freed

    async def rpc_object_sealed(self, peer: rpc.Peer, oid: ObjectID, size: int, node_id: NodeID):
        await self._account_object(node_id, oid, size)
        # a sealed copy IS a replica — record it in the directory (chain
        # broadcast hops report through here)
        orec = self.objects.get(oid)
        if orec is not None and orec.state == "READY" and orec.inline is None:
            orec.locations.add(node_id)
        return True

    async def rpc_object_broadcast(self, peer: rpc.Peer, oid: ObjectID,
                                   dest_node_ids: Optional[list] = None):
        """1→N object distribution over a pipelined agent chain
        (reference: push_manager.h broadcast; release/benchmarks
        README.md:18-21 '1 GiB to 50 nodes'). Every link runs at full
        bandwidth concurrently, so N deliveries cost ~1 transfer time
        instead of N sequential (or N bandwidth-sharing) pulls from one
        source. Returns True when EVERY destination holds a replica."""
        orec = self.objects.get(oid)
        if orec is None or orec.state != "READY" or orec.inline is not None:
            return False
        if dest_node_ids is None:
            dests = [
                nid for nid, n in self.nodes.items()
                if n.state == "ALIVE" and n.peer is not None
                and nid not in orec.locations
            ]
        else:
            dests = [
                NodeID.from_hex(d) if isinstance(d, str) else d
                for d in dest_node_ids
            ]
            dests = [
                d for d in dests
                if d in self.nodes and self.nodes[d].state == "ALIVE"
                and self.nodes[d].peer is not None and d not in orec.locations
            ]
        if not dests:
            return True
        # source: any live replica; the head serves over the controller
        # connection ("controller" pseudo-address)
        src_addr = None
        for nid in orec.locations:
            node = self.nodes.get(nid)
            if node is None or node.state != "ALIVE":
                continue
            src_addr = "controller" if node.peer is None else node.fetch_addr
            if src_addr:
                break
        if src_addr is None:
            return False
        first = self.nodes[dests[0]]
        next_addrs = [self.nodes[d].fetch_addr for d in dests[1:]]
        try:
            ok = await first.peer.call(
                "pull_chain", oid, orec.size, src_addr, next_addrs
            )
        except Exception:  # noqa: BLE001 — a hop died mid-chain
            logger.exception("broadcast chain failed for %s", oid.hex()[:8])
            return False
        return bool(ok)

    # =================================================================
    # Actors: kill / get-by-name / wait-ready
    # =================================================================
    async def rpc_kill_actor(self, peer: rpc.Peer, actor_id: ActorID, no_restart: bool):
        actor = self.actors.get(actor_id)
        if actor is None:
            return False
        if no_restart:
            actor.restarts_left = 0
        worker = self.workers.get(actor.worker_id) if actor.worker_id else None
        if worker is not None:
            await worker.peer.notify("exit")
        else:
            await self._on_actor_death(actor_id, "killed via ray_tpu.kill")
        return True

    async def rpc_wait_actor_ready(self, peer: rpc.Peer, actor_id: ActorID):
        actor = self.actors.get(actor_id)
        if actor is None:
            raise ActorDiedError(actor_id.hex(), "unknown actor")
        if actor.state == "ALIVE":
            return True
        if actor.state == "DEAD":
            raise ActorDiedError(actor_id.hex(), actor.death_reason)
        fut = asyncio.get_running_loop().create_future()
        actor.ready_waiters.append(fut)
        return await fut

    async def rpc_actor_locate(self, peer: rpc.Peer, actor_id: ActorID):
        """Resolve an actor's direct-transport address, long-polling
        through PENDING/RESTARTING (reference: the submitter's resolution
        of ActorTableData updates, actor_task_submitter.cc)."""
        actor = self.actors.get(actor_id)
        if actor is None:
            return {"state": "DEAD", "reason": "actor not found"}
        while actor.state in ("PENDING", "RESTARTING"):
            fut = asyncio.get_running_loop().create_future()
            actor.ready_waiters.append(fut)
            try:
                await asyncio.shield(fut)
            except Exception:  # noqa: BLE001 — death surfaces via state
                break
        if actor.state != "ALIVE":
            return {"state": "DEAD", "reason": actor.death_reason or "actor dead"}
        worker = self.workers.get(actor.worker_id)
        if worker is None or not worker.listen_addr:
            return {"state": "DEAD", "reason": "actor worker has no listener"}
        return {
            "state": "ALIVE",
            "addr": worker.listen_addr,
            "instance": actor.num_restarts,
        }

    # -- general pub/sub (reference: src/ray/pubsub/ — long-poll batched
    # publisher/subscriber; here subscribers ride their existing control
    # connection, so publish is a push notify per subscriber). The
    # subscriber registry and fan-out live in core/pubsub.py's TopicBus;
    # these RPCs are thin delegates. On subscribe to the resource
    # channel, the current full snapshot is pushed first so the mirror
    # starts from a consistent base before deltas stream in.
    async def rpc_subscribe(self, peer: rpc.Peer, channel: str):
        from ray_tpu.core import pubsub as _ps

        self.bus.subscribe(channel, peer)
        if channel == _ps.RESOURCES_CHANNEL:
            await peer.notify("pubsub_msg", channel, self._resource_snapshot())
        elif channel == _ps.AVOID_CHANNEL:
            await peer.notify("pubsub_msg", channel, self._avoid_snapshot())
        return True

    async def rpc_unsubscribe(self, peer: rpc.Peer, channel: str):
        self.bus.unsubscribe(channel, peer)
        return True

    async def rpc_publish(self, peer: rpc.Peer, channel: str, msg) -> int:
        """Fan a message out to the channel's subscribers CONCURRENTLY
        (one wedged subscriber's backpressure must not stall the rest or
        the publisher); returns the number of live subscribers."""
        return await self.bus.publish(channel, msg)

    def _drop_subscriber(self, peer: rpc.Peer):
        self.bus.drop_peer(peer)

    async def _publish_death(self, kind: str, eid: str, state: str, **attrs):
        """Push a lifecycle death/drain event to DEATH_CHANNEL
        subscribers (train executors and other gang supervisors watch
        this instead of waiting for a blocked collective to time out —
        a SIGKILLed host is detected in well under a second). No-op
        without subscribers; failures never propagate into the death
        path itself."""
        if not self.bus.has(DEATH_CHANNEL):
            return
        msg = {"kind": kind, "id": eid, "state": state, "ts": time.time()}
        msg.update({k: v for k, v in attrs.items() if v})
        try:
            await self.rpc_publish(None, DEATH_CHANNEL, msg)
        except Exception as e:  # noqa: BLE001 — observers only
            logger.debug("death-event publish failed: %s", e)

    async def rpc_chaos_install(self, peer: rpc.Peer, node_id_hex: str,
                                plan_json: str):
        """Install (or clear, plan_json="") a fault plan on a running
        node agent — the runtime lever for agent-level slow-node
        throttling (`chaos.install_plan_on_node`). Empty node id targets
        the controller process itself."""
        if not node_id_hex:
            from ray_tpu.util import chaos

            chaos.install_fault_plan(plan_json or None)
            return True
        for nid, node in self.nodes.items():
            if nid.hex() == node_id_hex and node.peer is not None:
                return await node.peer.call("install_fault_plan", plan_json)
        raise ValueError(f"no live agent for node {node_id_hex}")

    async def rpc_stack_dump_all(self, peer: rpc.Peer, timeout_s: float = 10.0):
        """Live stacks of every cluster process (reference: `ray stack` +
        the dashboard reporter's py-spy dumps). Controller itself,
        agents, and workers dump over their existing channels."""
        from ray_tpu.utils.stack_dump import dump_all_threads

        out: Dict[str, str] = {"controller": dump_all_threads()}

        async def ask(name: str, p: rpc.Peer):
            try:
                out[name] = await asyncio.wait_for(p.call("stack_dump"), timeout_s)
            except Exception as e:  # noqa: BLE001 — wedged/gone process
                out[name] = f"<unavailable: {e}>"

        calls = []
        for w in self.workers.values():
            if w.state != "DEAD" and not w.peer.closed:
                calls.append(ask(f"worker:{w.worker_id.hex()[:8]}:pid{w.pid}", w.peer))
        for n in self.nodes.values():
            if n.peer is not None and not n.peer.closed:
                calls.append(ask(f"agent:{n.node_id.hex()[:8]}", n.peer))
        await asyncio.gather(*calls)
        return out

    # =================================================================
    # On-demand distributed profiling (util/profiling.py; reference: the
    # dashboard reporter's per-worker py-spy stack/CPU-profile endpoints)
    # =================================================================
    def _profile_targets(self, node: Optional[str] = None,
                         actor: Optional[str] = None,
                         workers: Optional[List[str]] = None):
        """(name, peer) fan-out targets, filterable to one node's
        processes, one actor's worker, or an explicit worker-id list.
        Unfiltered = every live process: workers, agents, drivers (the
        controller profiles itself in-process, not through a peer)."""
        actor_wids = None
        if actor:
            actor_wids = {
                a.worker_id
                for a in self.actors.values()
                if a.worker_id is not None and a.actor_id.hex().startswith(actor)
            }
        out = []
        for w in self.workers.values():
            if w.state == "DEAD" or w.peer.closed:
                continue
            if node and not w.node_id.hex().startswith(node):
                continue
            if actor_wids is not None and w.worker_id not in actor_wids:
                continue
            if workers and not any(
                w.worker_id.hex().startswith(p) for p in workers
            ):
                continue
            out.append((f"worker:{w.worker_id.hex()[:8]}:pid{w.pid}", w.peer))
        if actor_wids is None and not workers:
            for n in self.nodes.values():
                if n.peer is None or n.peer.closed:
                    continue
                if node and not n.node_id.hex().startswith(node):
                    continue
                out.append((f"agent:{n.node_id.hex()[:8]}", n.peer))
            if not node:
                for i, d in enumerate(sorted(self.drivers, key=id)):
                    if not d.closed:
                        out.append((f"driver:{i}", d))
        return out

    def _include_self(self, node: Optional[str], actor: Optional[str],
                      workers: Optional[List[str]]) -> bool:
        if actor or workers:
            return False
        return not node or self.head_node_id.hex().startswith(node)

    async def rpc_profile_stacks(self, peer: rpc.Peer,
                                 node: Optional[str] = None,
                                 actor: Optional[str] = None,
                                 timeout_s: float = 10.0):
        """Cluster-wide structured stack dump: controller + agents +
        workers + drivers, merged and deduplicated. The controller's own
        leg is a lock-free snapshot (``profiling.dump_stacks`` touches no
        controller state), so dumping mid-scheduling-storm — or mid-
        deadlock — always returns."""
        from ray_tpu.util import profiling

        procs: Dict[str, Any] = {}
        if self._include_self(node, actor, None):
            procs["controller"] = profiling.dump_stacks()

        async def ask(name: str, p: rpc.Peer):
            try:
                procs[name] = await asyncio.wait_for(
                    p.call("dump_stacks"), timeout_s
                )
            except Exception as e:  # noqa: BLE001 — wedged/gone process
                procs[name] = f"<unavailable: {e}>"

        await asyncio.gather(
            *(ask(name, p) for name, p in self._profile_targets(node, actor))
        )
        return {"procs": procs, "merged": profiling.merge_stack_dumps(procs)}

    async def rpc_profile_cpu_all(self, peer: rpc.Peer,
                                  duration_s: float = 10.0,
                                  hz: Optional[float] = None,
                                  node: Optional[str] = None,
                                  workers: Optional[List[str]] = None):
        """Fan out the sampling CPU profiler: every target profiles
        itself concurrently for ``duration_s`` (samplers run on their own
        threads; nobody's control plane blocks), results merge into
        cluster-wide collapsed stacks + per-task CPU attribution."""
        from ray_tpu.util import profiling

        if hz is None:
            hz = float(self.config.profiling_sample_hz)
        duration_s = max(0.05, min(float(duration_s), 600.0))
        results: Dict[str, Any] = {}

        async def ask(name: str, p: rpc.Peer):
            try:
                results[name] = await asyncio.wait_for(
                    p.call("profile_cpu", duration_s, hz), duration_s + 15.0
                )
            except Exception as e:  # noqa: BLE001 — wedged/gone process
                results[name] = f"<unavailable: {e}>"

        legs = [
            ask(name, p)
            for name, p in self._profile_targets(node, None, workers)
        ]
        if self._include_self(node, None, workers):

            async def self_leg():
                results["controller"] = await profiling.sample_async(
                    duration_s, hz
                )

            legs.append(self_leg())
        await asyncio.gather(*legs)
        merged = profiling.merge_cpu_results(results)
        merged["hz"] = hz
        merged["duration_s"] = duration_s
        merged["ms_per_sample"] = 1000.0 / hz
        return merged

    async def rpc_profile_device_all(self, peer: rpc.Peer,
                                     workers: Optional[List[str]] = None,
                                     duration_s: float = 5.0,
                                     capture: Optional[str] = None):
        """Attach jax.profiler traces to already-running workers for
        ``duration_s`` (start → sleep → stop over their live RPC
        channels — no restart). Captures land in each worker's session
        ``profiles/`` root, listed by ``ray-tpu profile captures``."""
        capture = capture or f"ondemand-{int(time.time())}"
        duration_s = max(0.1, min(float(duration_s), 600.0))
        targets = [
            (name, p)
            for name, p in self._profile_targets(None, None, workers)
            if name.startswith("worker:")
        ]
        out: Dict[str, dict] = {}

        async def control(name: str, p: rpc.Peer, action: str):
            try:
                return await asyncio.wait_for(
                    p.call("profile_device", action, capture), 15.0
                )
            except Exception as e:  # noqa: BLE001 — wedged/gone worker
                return {"ok": False, "error": str(e)}

        starts = await asyncio.gather(
            *(control(name, p, "start") for name, p in targets)
        )
        started = []
        for (name, p), res in zip(targets, starts):
            out[name] = res
            if res.get("ok"):
                started.append((name, p))
        if started:
            await asyncio.sleep(duration_s)
            stops = await asyncio.gather(
                *(control(name, p, "stop") for name, p in started)
            )
            for (name, _p), res in zip(started, stops):
                out[name] = res
        return {"capture": capture, "duration_s": duration_s, "workers": out}

    async def rpc_profile_incidents(self, peer: rpc.Peer, limit: int = 100):
        """Incident capture bundles under this session (auto-written by
        the lockwatch/recompile-storm/SLO detectors)."""
        from ray_tpu.util import profiling

        return profiling.list_incidents(self.session_dir)[-max(1, limit):]

    async def rpc_get_incident(self, peer: rpc.Peer, incident_id: str):
        from ray_tpu.util import profiling

        return profiling.get_incident(incident_id, self.session_dir)

    # =================================================================
    # Object & memory observability (`ray-tpu memory`; reference: `ray
    # memory` / dashboard memory view over core-worker ref counting)
    # =================================================================
    async def _dump_memory_fanout(self, node: Optional[str], limit: int,
                                  timeout_s: float) -> Dict[str, Any]:
        """Every process answers ``rpc_dump_memory`` over its existing
        channel (the PR 9 profiling fan-out pattern): workers/drivers
        return their ref census, agents their store's per-object rows."""
        procs: Dict[str, Any] = {}

        async def ask(name: str, p: rpc.Peer):
            try:
                procs[name] = await asyncio.wait_for(
                    p.call("dump_memory", limit=limit), timeout_s
                )
            except Exception as e:  # noqa: BLE001 — wedged/gone process
                procs[name] = f"<unavailable: {e}>"

        await asyncio.gather(
            *(ask(name, p) for name, p in self._profile_targets(node, None))
        )
        return procs

    def _store_stats_by_node(self, procs: Dict[str, Any]) -> Dict[str, dict]:
        """Per-node store stats: the head's live, agents' from their
        fan-out dump (falling back to the last telemetry heartbeat)."""
        stores: Dict[str, dict] = {}
        agent_dumps = {
            name[len("agent:"):]: d
            for name, d in procs.items()
            if name.startswith("agent:") and isinstance(d, dict)
        }
        for nid, nrec in self.nodes.items():
            hexid = nid.hex()
            if nrec.peer is None:
                stores[hexid] = self.head_store.stats()
                continue
            dump = agent_dumps.get(hexid[:8])
            if dump is not None and dump.get("store"):
                stores[hexid] = dump["store"]
            else:
                stores[hexid] = (nrec.telemetry or {}).get("object_store", {})
        return stores

    def _store_object_index(self, procs: Dict[str, Any]) -> Dict[str, dict]:
        """oid hex -> store row (pinned/spilled/in_arena), merged across
        the head store and every agent dump — the spill/pin tier source
        for per-object attribution."""
        index: Dict[str, dict] = {}
        for row in self.head_store.object_rows():
            index[row["object_id"]] = row
        for name, d in procs.items():
            if name.startswith("agent:") and isinstance(d, dict):
                for row in d.get("objects", ()):
                    index.setdefault(row["object_id"], row)
        return index

    def _object_tier(self, orec: ObjectRecord,
                     store_row: Optional[dict]) -> str:
        if orec.state == "PENDING":
            return "pending"
        if orec.state == "FAILED":
            return "failed"
        if orec.inline is not None:
            return "inline"
        if store_row is not None and store_row.get("spilled"):
            return "spilled"
        return "shm"

    async def rpc_summarize_memory(self, peer, limit: int = 50,
                                   node: Optional[str] = None,
                                   timeout_s: float = 5.0):
        """Cluster-wide memory census rollup: controller object directory
        (size/tier/call-site/holders) merged with per-process ref
        censuses and per-node store stats. O(limit) call-site rows on the
        wire; totals are uncapped."""
        procs = await self._dump_memory_fanout(node, 1000, timeout_s)
        stores = self._store_stats_by_node(procs)
        store_index = self._store_object_index(procs)
        by_site: Dict[str, dict] = {}

        def site_row(site: str) -> dict:
            row = by_site.get(site)
            if row is None:
                row = by_site[site] = {
                    "objects": 0, "bytes": 0, "spilled_bytes": 0,
                    "local_refs": 0, "pins": 0,
                    "tiers": {},
                }
            return row

        totals = {
            "objects": len(self.objects),
            "inline_bytes": 0, "shm_bytes": 0, "spilled_bytes": 0,
            "open_refs": 0, "pins": 0, "pin_bytes": 0,
            "memory_store_entries": 0, "memory_store_bytes": 0,
        }
        for oid, orec in self.objects.items():
            srow = store_index.get(oid.hex())
            tier = self._object_tier(orec, srow)
            site = orec.callsite or "(unknown)"
            row = site_row(site)
            row["objects"] += 1
            row["bytes"] += orec.size
            row["tiers"][tier] = row["tiers"].get(tier, 0) + 1
            if tier == "inline":
                totals["inline_bytes"] += orec.size
            elif tier == "shm":
                totals["shm_bytes"] += orec.size
            elif tier == "spilled":
                totals["spilled_bytes"] += orec.size
                row["spilled_bytes"] += orec.size
        proc_rows: Dict[str, dict] = {}
        pin_pids: Set[int] = set()  # the pin registry is per-PROCESS:
        # two connections from one process (a driver + its cluster-admin
        # CoreWorker) must not double-count the same pins
        for name, d in procs.items():
            if name.startswith("agent:") or not isinstance(d, dict):
                if not isinstance(d, dict):
                    proc_rows[name] = {"error": str(d)}
                continue
            refs = d.get("refs", {})
            pins = d.get("pins", {})
            ms = d.get("memory_store", {})
            open_refs = 0
            for site, info in refs.items():
                open_refs += info.get("count", 0)
                row = site_row(site)
                row["local_refs"] += info.get("count", 0)
                row["pins"] += info.get("pinned", 0)
            totals["open_refs"] += open_refs
            pid = d.get("pid")
            if pid not in pin_pids:
                pin_pids.add(pid)
                totals["pins"] += pins.get("count", 0)
                totals["pin_bytes"] += pins.get("bytes", 0)
            totals["memory_store_entries"] += ms.get("entries", 0)
            totals["memory_store_bytes"] += ms.get("ready_bytes", 0)
            proc_rows[name] = {
                "open_refs": open_refs,
                "memory_store": ms,
                "pins": {k: pins.get(k, 0) for k in ("count", "bytes")},
            }
        keep = sorted(
            by_site.items(),
            key=lambda kv: (-kv[1]["bytes"],
                            -(kv[1]["objects"] + kv[1]["local_refs"])),
        )
        return {
            "totals": totals,
            "nodes": stores,
            "by_callsite": dict(keep[: max(1, limit)]),
            "truncated": len(keep) > limit,
            "procs": proc_rows,
            "leaks": sorted(
                self._leak_flags.values(), key=lambda r: -r.get("count", 0)
            ),
        }

    async def rpc_list_object_refs(self, peer, limit: int = 1000,
                                   node: Optional[str] = None,
                                   timeout_s: float = 5.0):
        """Per-object census rows (the `ray memory` table): directory
        objects with owner/call-site/tier/holders (newest ``limit``),
        plus owner-local memory-store objects invisible to the directory,
        attributed by the process fan-out."""
        import collections as _c

        procs = await self._dump_memory_fanout(node, limit, timeout_s)
        store_index = self._store_object_index(procs)
        # borrow/pin attribution per object from the process censuses
        holders_by_oid: Dict[str, List[str]] = {}
        local_rows: List[dict] = []
        for name, d in procs.items():
            if name.startswith("agent:") or not isinstance(d, dict):
                continue
            for row in d.get("objects", ()):
                hexid = row["object_id"]
                if row.get("local_only"):
                    if len(local_rows) < limit:
                        local_rows.append(
                            {
                                "object_id": hexid,
                                "tier": "memory_store",
                                "callsite": row.get("callsite", ""),
                                "creator": name,
                                "holders": [name],
                                "local_refs": row.get("count", 0),
                                "size": None,  # owner-private; size unknown
                                "state": "READY",
                                "pinned": bool(row.get("pinned")),
                            }
                        )
                else:
                    holders_by_oid.setdefault(hexid, []).append(name)
        # Memory-store rows keep their slots: the owner-local tier is the
        # one the directory can never show, so a full directory must not
        # silently squeeze it out of the capped reply.
        limit = max(1, limit)
        dir_limit = max(1, limit - len(local_rows))
        out = []
        for oid, orec in _c.deque(self.objects.items(), maxlen=dir_limit):
            hexid = oid.hex()
            srow = store_index.get(hexid)
            out.append(
                {
                    "object_id": hexid,
                    "state": orec.state,
                    "size": orec.size,
                    "tier": self._object_tier(orec, srow),
                    "callsite": orec.callsite,
                    "creator": orec.creator,
                    "holders": holders_by_oid.get(
                        hexid, sorted(orec.holders)
                    ),
                    "locations": [n.hex() for n in orec.locations],
                    "pinned": bool(srow and srow.get("pinned")),
                    "is_error": orec.is_error,
                }
            )
        return (out + local_rows)[:limit]

    async def rpc_summarize_objects(self, peer, limit: int = 100):
        """Controller-side object rollup (replaces the client pulling
        100k full rows to count them): uncapped totals by state/tier,
        call-site counts capped to the ``limit`` largest."""
        by_state: Dict[str, int] = {}
        by_tier: Dict[str, int] = {}
        sites: Dict[str, dict] = {}
        total_size = 0
        # Same tier rule as summarize_memory (_object_tier), with the
        # head store's spill view (local, no fan-out — agent-node spills
        # show as shm here; full fidelity lives in rpc_summarize_memory).
        spilled_here = self.head_store.spilled_ids()
        _SPILLED_ROW = {"spilled": True}
        for oid, orec in self.objects.items():
            by_state[orec.state] = by_state.get(orec.state, 0) + 1
            tier = self._object_tier(
                orec, _SPILLED_ROW if oid.hex() in spilled_here else None
            )
            by_tier[tier] = by_tier.get(tier, 0) + 1
            total_size += orec.size
            site = orec.callsite or "(unknown)"
            row = sites.setdefault(site, {"count": 0, "bytes": 0})
            row["count"] += 1
            row["bytes"] += orec.size
        keep = sorted(sites.items(), key=lambda kv: -kv[1]["bytes"])
        return {
            "total": len(self.objects),
            "total_size": total_size,
            "by_state": by_state,
            "by_tier": by_tier,
            "callsites": dict(keep[: max(1, limit)]),
            "truncated": len(keep) > limit,
        }

    def _memory_census_tick(self):
        """Per-telemetry-sweep census work: the Grafana "Memory" gauges,
        the open-ref growth (leak) detector, and the store-pressure
        incident trigger. The object-table pass is SHARDED (round 17):
        each sweep walks at most ``_CENSUS_CHUNK`` records against a
        key snapshot taken at cycle start, accumulating kinds/by_site
        across the cycle; gauges and the leak sweep publish once per
        completed cycle. Per-tick controller-loop work is thereby
        bounded regardless of table size — the old stride amortization
        still paid one full O(objects) stall whenever it did fire."""
        if not getattr(self.config, "memory_census", True):
            return
        m = _get_mem_metrics()
        for nid, nrec in self.nodes.items():
            # The head's heartbeat (built one line before this tick in
            # _head_telemetry_loop) already carries a fresh stats() dict —
            # don't pay the O(entries) store scan a second time per sweep.
            store = (nrec.telemetry or {}).get("object_store") or (
                self.head_store.stats() if nrec.peer is None else {}
            )
            tag = {"node": nid.hex()[:12]}
            m["store_used"].set(store.get("used", 0), tag)
            m["store_pinned"].set(store.get("pinned_bytes", 0), tag)
            m["store_spilled"].set(store.get("spilled_bytes", 0), tag)
            self._pressure_check_node(nid, store)
        self._census_tick_n += 1
        if self._census_cycle is None:
            # New cycle: snapshot the key list (a ref copy — milliseconds
            # even at envelope depth) so the shard walk stays stable
            # while the table churns underneath it.
            self._census_cycle = {
                "keys": list(self.objects),
                "pos": 0,
                "kinds": {"inline": 0, "shm": 0, "pending": 0, "failed": 0},
                "by_site": {},
            }
        cyc = self._census_cycle
        keys = cyc["keys"]
        pos = cyc["pos"]
        end = min(len(keys), pos + _CENSUS_CHUNK)
        kinds = cyc["kinds"]
        by_site: Dict[str, int] = cyc["by_site"]
        objects = self.objects
        for key in keys[pos:end]:
            orec = objects.get(key)
            if orec is None:
                continue  # freed since the cycle's snapshot
            if orec.state == "PENDING":
                kinds["pending"] += 1
            elif orec.state == "FAILED":
                kinds["failed"] += 1
            elif orec.inline is not None:
                kinds["inline"] += 1
            else:
                kinds["shm"] += 1
            site = orec.callsite or "(unknown)"
            by_site[site] = by_site.get(site, 0) + 1
        cyc["pos"] = end
        if end >= len(keys):
            for kind, n in kinds.items():
                m["refs_open"].set(n, {"kind": kind})  # ray-tpu: lint-ignore[RTL004] — fixed 4-value tier vocabulary
            self._leak_sweep(by_site)
            self._census_cycle = None

    def _leak_sweep(self, by_site: Dict[str, int]):
        """Flag call-sites whose open-object count rose monotonically
        across ``memory_leak_sweeps`` consecutive sweeps and sits above
        ``memory_leak_min_refs`` — the ref-hoarder signature. Vocabulary
        is bounded: client-side call-sites are interned under
        ``memory_callsite_cap`` and the trend table caps at 512 entries."""
        import collections as _c

        sweeps = max(2, int(getattr(self.config, "memory_leak_sweeps", 5)))
        floor = int(getattr(self.config, "memory_leak_min_refs", 32))
        trends = self._mem_trends
        for site, count in by_site.items():
            dq = trends.get(site)
            if dq is None:
                if len(trends) >= 512:
                    continue  # bounded vocabulary backstop
                dq = trends[site] = _c.deque(maxlen=sweeps)
            dq.append(count)
        for site in [s for s in trends if s not in by_site]:
            trends.pop(site, None)
            self._leak_flags.pop(site, None)
        for site, dq in trends.items():
            window = list(dq)
            cur = window[-1]
            rising = (
                len(window) == sweeps
                and cur >= floor
                and all(b > a for a, b in zip(window, window[1:]))
            )
            if rising:
                flag = self._leak_flags.get(site)
                if flag is None:
                    self._leak_flags[site] = {
                        "callsite": site,
                        "count": cur,
                        "growth": cur - window[0],
                        "first_flagged": time.time(),
                    }
                    _get_mem_metrics()["leak_flags"].inc(
                        1, {"callsite": site}  # ray-tpu: lint-ignore[RTL004] — bounded by the intern cap + trend-table cap
                    )
                    logger.warning(
                        "memory leak suspect: %s — open refs rising "
                        "monotonically over %d sweeps (now %d)",
                        site, sweeps, cur,
                    )
                    from ray_tpu.util.actuators import HealthSignal

                    self.health.observe(HealthSignal(
                        "memory_leak", key=site,
                        detail={"count": cur, "growth": cur - window[0]},
                    ))
                else:
                    flag["count"] = cur
                    flag["growth"] = cur - window[0]
            elif site in self._leak_flags and cur <= window[0]:
                self._leak_flags.pop(site, None)  # recovered

    def _pressure_check_node(self, nid: NodeID, store: dict):
        """Store-pressure incident trigger: occupancy past
        ``memory_incident_occupancy_pct`` or eviction-loop churn past
        ``memory_incident_spill_churn`` spills per sweep fires PR 9's
        incident machinery with a memory autopsy bundle."""
        pct = float(
            getattr(self.config, "memory_incident_occupancy_pct", 0.95)
        )
        churn = int(getattr(self.config, "memory_incident_spill_churn", 200))
        ops = int(store.get("spill_ops", 0) or 0)
        prev = self._spill_ops_prev.get(nid)
        self._spill_ops_prev[nid] = ops
        cap = store.get("capacity") or 0
        used = store.get("used", 0) or 0
        reason = None
        if pct > 0 and cap > 0 and used / cap >= pct:
            reason = "occupancy"
        elif churn > 0 and prev is not None and ops - prev >= churn:
            reason = "spill_churn"
        if reason is None:
            return
        # Health plane BEFORE the incident rate-limit pre-check: the
        # actuator registry has its own cooldown/budget, and a pressure
        # episode suppressed here (a capture fired recently) must still
        # reach the spill actuator.
        from ray_tpu.util.actuators import HealthSignal

        self.health.observe(HealthSignal(
            "memory_pressure", key=nid.hex(), target=nid.hex(),
            detail={
                "reason": reason,
                "occupancy": round(used / cap, 4) if cap else None,
                "spill_ops_delta": (ops - prev) if prev is not None else 0,
            },
        ))
        from ray_tpu.util import profiling

        # Pre-check the rate limit so a sustained-pressure store doesn't
        # spawn a capture thread per sweep (incident() re-checks it
        # atomically) — the slo_breach pattern.
        min_interval = float(
            self.config.profiling_incident_min_interval_s
        )
        if (
            time.time() - profiling._incident_last.get("memory_pressure", 0.0)
            < min_interval
        ):
            return
        autopsy = self._memory_autopsy(nid, reason, store)
        detail = {
            "node": nid.hex()[:12],
            "reason": reason,
            "occupancy": round(used / cap, 4) if cap else None,
            "spill_ops_delta": (ops - prev) if prev is not None else 0,
        }
        import threading as _t

        _t.Thread(
            target=profiling.incident,
            args=("memory_pressure", detail),
            kwargs={"extra_files": {
                "memory.json": json.dumps(autopsy, indent=1, default=str)
            }},
            daemon=True,
            name="memory-incident",
        ).start()

    def _memory_autopsy(self, nid: NodeID, reason: str, store: dict) -> dict:
        """The autopsy bundle body: top call-sites by resident bytes,
        per-node store stats, and the spill/delete queue depths — enough
        to answer "who filled the store" from the incident dir alone."""
        by_site: Dict[str, dict] = {}
        scan = len(self.objects) <= 300_000
        if scan:
            for orec in self.objects.values():
                if orec.state != "READY" or orec.inline is not None:
                    continue
                site = orec.callsite or "(unknown)"
                row = by_site.setdefault(site, {"objects": 0, "bytes": 0})
                row["objects"] += 1
                row["bytes"] += orec.size
        top = sorted(by_site.items(), key=lambda kv: -kv[1]["bytes"])[:20]
        nodes = {}
        for onid, nrec in self.nodes.items():
            if nrec.peer is None:
                nodes[onid.hex()[:12]] = self.head_store.stats()
            else:
                nodes[onid.hex()[:12]] = (
                    (nrec.telemetry or {}).get("object_store") or {}
                )
        return {
            "trigger_node": nid.hex()[:12],
            "reason": reason,
            "store": store,
            "spill_queue": {
                "deferred_deletes": store.get("deferred_deletes", 0),
                "num_spilled": store.get("num_spilled", 0),
                "spilled_bytes": store.get("spilled_bytes", 0),
                "spill_ops": store.get("spill_ops", 0),
            },
            "top_callsites": dict(top),
            "top_callsites_complete": scan,
            "leaks": list(self._leak_flags.values()),
            "nodes": nodes,
        }

    # =================================================================
    # Cluster log plane (core/log_plane.py; reference: the dashboard
    # StateHead logs API + log_monitor + GCS error-event aggregation)
    # =================================================================
    def _log_dir(self) -> str:
        return os.path.join(self.session_dir, "logs")

    def _worker_node_map(self) -> Dict[str, str]:
        """worker-id 8-hex prefix -> node hex (log filenames encode the
        worker; the controller's table supplies the node attribution)."""
        return {
            w.worker_id.hex()[:8]: w.node_id.hex()
            for w in self.workers.values()
        }

    def _attribute_file_node(self, filename: str, wmap: Dict[str, str],
                             fallback: Optional[str] = None) -> Optional[str]:
        stem = os.path.splitext(filename)[0]
        for prefix in ("worker-", "driver-"):
            if stem.startswith(prefix):
                wid = stem[len(prefix):]
                node = wmap.get(wid[:8])
                if node:
                    return node
        if filename.startswith(("controller", "driver-")):
            return self.head_node_id.hex()
        return fallback

    def _log_agent_targets(self, node: Optional[str]):
        out = []
        for n in self.nodes.values():
            if n.peer is None or n.peer.closed:
                continue
            if node and not n.node_id.hex().startswith(node):
                continue
            out.append(n)
        return out

    async def rpc_list_logs(self, peer, node: Optional[str] = None,
                            timeout_s: float = 10.0):
        """Cluster-wide log listing: the head's session log dir plus
        every agent's, merged and deduplicated by filename (single-host
        simulations share one dir; true multi-host nodes each contribute
        their own), each row attributed to the node whose worker wrote
        it."""
        from ray_tpu.core import log_plane

        per_node: Dict[str, list] = {}
        if not node or self.head_node_id.hex().startswith(node):
            # off-loop like the agents: listing stats every log file
            per_node[self.head_node_id.hex()] = await asyncio.to_thread(
                log_plane.list_local, self._log_dir()
            )

        async def ask(n: NodeRecord):
            try:
                res = await asyncio.wait_for(n.peer.call("list_logs"), timeout_s)
                per_node[n.node_id.hex()] = res.get("files", [])
            except Exception as e:  # noqa: BLE001 — wedged/gone agent
                logger.debug("list_logs on %s failed: %s",
                             n.node_id.hex()[:8], e)

        await asyncio.gather(*(ask(n) for n in self._log_agent_targets(node)))
        wmap = self._worker_node_map()
        rows: Dict[str, dict] = {}
        for node_hex, files in per_node.items():
            for f in files:
                name = f["filename"]
                if name in rows:
                    continue
                f = dict(f)
                f["node"] = self._attribute_file_node(name, wmap, node_hex)
                rows[name] = f
        out = sorted(rows.values(), key=lambda r: r["filename"])
        if node:
            out = [r for r in out
                   if r.get("node") and r["node"].startswith(node)]
        return out

    async def rpc_get_log(self, peer, filename: str, tail: int = 1000,
                          node: Optional[str] = None,
                          timeout_s: float = 10.0):
        """One log file's tail, wherever it lives: the head's dir first,
        then the agents (path-traversal guarded on every leg)."""
        from ray_tpu.core import log_plane

        if not node or self.head_node_id.hex().startswith(node):
            try:
                # off-loop: reading a rotation-capped file is up to
                # ~2x log_rotate_bytes of I/O
                return await asyncio.to_thread(
                    log_plane.read_local, self._log_dir(), filename, tail
                )
            except FileNotFoundError:
                pass
        last_err: Exception = FileNotFoundError(filename)
        for n in self._log_agent_targets(node):
            try:
                return await asyncio.wait_for(
                    n.peer.call("get_log", filename, tail), timeout_s
                )
            except ValueError:
                raise  # traversal attempt — do not keep probing
            except Exception as e:  # noqa: BLE001 — missing there / agent gone
                last_err = e
        raise last_err

    async def rpc_search_logs(self, peer, pattern: Optional[str] = None,
                              severity: Optional[str] = None,
                              task: Optional[str] = None,
                              actor: Optional[str] = None,
                              node: Optional[str] = None,
                              since: Optional[float] = None,
                              until: Optional[float] = None,
                              limit: int = 1000,
                              timeout_s: float = 10.0):
        """Cluster-wide structured log search (the `ray-tpu logs --grep/
        --task/--err` backend): regex + severity floor + time range +
        entity filters fan out to every node's sidecars over the
        existing channels (the PR 9/10 pattern), results merge bounded
        and time-ordered, deduplicated by (file, line) for shared-dir
        single-host nodes."""
        from ray_tpu.core import log_plane

        limit = max(1, min(int(limit), 10000))
        filters = dict(pattern=pattern, severity=severity, task=task,
                       actor=actor, node=node, since=since, until=until,
                       limit=limit)
        merged: Dict[tuple, dict] = {}

        def fold(records):
            for rec in records:
                merged.setdefault(
                    (rec.get("file", ""), rec.get("line", 0)), rec
                )

        if not node or self.head_node_id.hex().startswith(node):
            # off-loop like the agents: a regex scan over sidecars near
            # the rotation cap must not stall the scheduler loop
            fold(await asyncio.to_thread(
                log_plane.search_local, self._log_dir(), **filters
            ))

        async def ask(n: NodeRecord):
            try:
                fold(await asyncio.wait_for(
                    n.peer.call("search_logs", **filters), timeout_s
                ))
            except Exception as e:  # noqa: BLE001 — wedged/gone agent
                logger.debug("search_logs on %s failed: %s",
                             n.node_id.hex()[:8], e)

        await asyncio.gather(*(ask(n) for n in self._log_agent_targets(node)))
        wmap = self._worker_node_map()
        out = []
        for rec in merged.values():
            if rec.get("node") is None and rec.get("file"):
                rec["node"] = self._attribute_file_node(rec["file"], wmap)
                if node and not str(rec["node"] or "").startswith(node):
                    continue
            out.append(rec)
        out.sort(key=lambda r: (r.get("ts") or 0.0, r.get("file", ""),
                                r.get("line", 0)))
        return out[:limit]

    async def rpc_log_errors(self, peer, batch: List[dict]):
        """ERROR/exception records shipped by workers, agents, and
        drivers — folded into the bounded error-signature index."""
        for rec in batch:
            self._error_index.ingest(rec)
        return True

    async def rpc_summarize_errors(self, peer, limit: int = 50):
        """The error index: repeated failures collapsed by signature
        (exception type + interned top user frames) with counts, first/
        last seen, a sample traceback, and the lifecycle entity link."""
        return self._error_index.summarize(limit)

    async def rpc_log_follow(self, peer, filters: Optional[dict] = None):
        """Register this connection for live structured log delivery
        (``ray-tpu logs --follow``): matching records push as
        ``log_records`` notifies over the LogTailer→driver channel."""
        f = dict(filters or {})
        if f.pop("err", None):
            f.setdefault("severity", "ERROR")
        f = {k: v for k, v in f.items() if k in (
            "pattern", "severity", "task", "actor", "node") and v}
        self._log_followers[peer] = f
        self._ensure_record_tailer()
        return True

    async def rpc_log_unfollow(self, peer):
        self._log_followers.pop(peer, None)
        return True

    def _ensure_record_tailer(self):
        """Lazy structured tailer: worker sidecars only start being
        tailed once somebody follows (span sinks and raw logs are
        excluded by the pattern). Like the raw log-to-driver tailer
        above, this covers every worker logging into the session dir —
        all nodes on the single-host simulation; a true multi-host
        deployment would relay per-agent tailers (search/list DO fan
        out; follow is head-dir scoped)."""
        if self._record_tailer is not None:
            return
        from ray_tpu.core.log_monitor import LogTailer

        self._record_tailer = LogTailer(
            self._log_dir(), self._broadcast_records,
            pattern="worker-*.jsonl", start_at_end=True,
        )
        self._record_tailer.start()

    def _broadcast_records(self, batch):
        """Thread→loop bridge: parse tailed sidecar lines once, then fan
        matching records out to each follower by ITS filters."""
        if not self._log_followers or self._loop is None:
            return
        recs = []
        for source, line in batch:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            rec["file"] = source
            recs.append(rec)
        if not recs:
            return
        from ray_tpu.core import log_plane

        async def send():
            for peer, filters in list(self._log_followers.items()):
                try:
                    matched = [
                        r for r in recs
                        if log_plane.match_record(r, **filters)
                    ]
                except Exception as e:  # noqa: BLE001 — bad follower regex
                    logger.debug("follow filter failed: %s", e)
                    continue
                if matched:
                    await _notify_quiet(
                        peer, "log_records", matched, what="follower gone"
                    )

        asyncio.run_coroutine_threadsafe(send(), self._loop)

    def _error_spike_check(self):
        """Error-rate-spike trigger: >= log_error_spike_threshold ERROR
        records ingested within one telemetry sweep fires the PR 9
        incident machinery with the offending log tail attached."""
        threshold = int(getattr(self.config, "log_error_spike_threshold", 50))
        total = self._error_index.total
        delta = total - self._errors_prev_total
        self._errors_prev_total = total
        if threshold <= 0 or delta < threshold:
            return
        # Health plane before the incident rate-limit pre-check (same
        # rationale as memory_pressure): resolve the loudest signature and
        # the node it blames so the quarantine actuator has a target.
        try:
            top = self._error_index.summarize(limit=1)["signatures"]
            sig, row = next(iter(top.items())) if top else ("", {})
            nodes = row.get("nodes") or []
            from ray_tpu.util.actuators import HealthSignal

            self.health.observe(HealthSignal(
                "error_spike",
                key=nodes[0] if nodes else sig[:64],
                target=nodes[0] if nodes else "",
                detail={"signature": sig[:160], "errors_this_sweep": delta,
                        "count": row.get("count", 0)},
            ))
        except Exception as e:  # noqa: BLE001 — health must not break detection
            logger.debug("error-spike health observe failed: %s", e)
        from ray_tpu.core.log_plane import format_record
        from ray_tpu.util import profiling

        # Pre-check the rate limit so sustained error storms don't spawn
        # a capture thread per sweep (incident() re-checks atomically) —
        # the slo_breach/memory_pressure pattern.
        min_interval = float(self.config.profiling_incident_min_interval_s)
        if (
            time.time() - profiling._incident_last.get("error_spike", 0.0)
            < min_interval
        ):
            return
        tail = "\n".join(
            format_record(r) for r in self._error_index.recent_tail(100)
        )
        summary = self._error_index.summarize(limit=10)
        detail = {
            "errors_this_sweep": delta,
            "threshold": threshold,
            "top_signatures": {
                sig: row["count"]
                for sig, row in summary["signatures"].items()
            },
        }
        import threading as _t

        _t.Thread(
            target=profiling.incident,
            args=("error_spike", detail),
            kwargs={"extra_files": {"log_tail.txt": tail}},
            daemon=True,
            name="error-spike-incident",
        ).start()

    def _drain_spawn_events(self):
        """Fold worker SPAWNED events recorded by in-process spawns (the
        controller doubles as the head's agent) into the flight recorder.
        Agent-side spawns arrive through rpc_task_events instead."""
        from ray_tpu.core import node_agent as _na

        while True:
            try:
                ev = _na._lifecycle_events.popleft()
            except IndexError:
                return
            self.lifecycle.ingest(ev)

    async def rpc_task_events(self, peer: rpc.Peer, batch: List[dict]):
        """Batched task events from workers executing direct-push tasks
        (reference: TaskEventBuffer flushes to the GCS task manager) —
        plus driver-side SUBMITTED/WORKER_ASSIGNED and agent-side worker
        SPAWNED events, all folded into the flight recorder.

        Ingest is chunked: a 100k-task drain can land tens of thousands
        of events in one flush, and a single synchronous walk that size
        stalls the controller loop (and every lease/push RPC behind it)
        for ~100 ms. Yielding between chunks keeps loop_p50 flat while
        the recorder absorbs the same volume."""
        for i, ev in enumerate(batch):
            if i and i % 2000 == 0:
                await asyncio.sleep(0)
            self.lifecycle.ingest(ev)
        # The legacy ring keeps its pre-recorder semantics — worker
        # EXECUTION events only. Driver SUBMITTED/WORKER_ASSIGNED and
        # agent SPAWNED halves live in the flight recorder; letting them
        # into this buffer would halve the timeline's RUNNING→FINISHED
        # pairing window at the same task_event_buffer_size.
        self.events.extend(
            e for e in batch
            if e.get("kind") == "task"
            and e.get("state") in ("RUNNING", "FINISHED", "FAILED")
        )
        if len(self.events) > self.config.task_event_buffer_size:
            del self.events[: len(self.events) // 2]
        # Keep the state API's task view covering direct-push tasks the
        # controller never dispatched (reference: GcsTaskManager's
        # event-derived task table).
        wid = peer.meta.get("worker_id")
        w = self.workers.get(wid) if wid else None
        node_hex = w.node_id.hex() if w is not None else None
        for ev in batch:
            if ev.get("kind") != "task" or "task_id" not in ev:
                continue
            state = ev.get("state", "")
            if state not in ("RUNNING", "FINISHED", "FAILED"):
                # The task-row view stays EXECUTION-derived (worker
                # events only), as before the flight recorder: driver-
                # side SUBMITTED/WORKER_ASSIGNED halves ride a separate
                # flush channel and would race terminal rows backwards;
                # pre-execution states live in the lifecycle ring.
                continue
            cur = self._direct_task_rows.get(ev["task_id"])
            if (
                cur is not None
                and cur["state"] in ("FINISHED", "FAILED")
                and state == "RUNNING"
            ):
                continue  # late RUNNING flush must not regress a terminal row
            self._direct_task_rows[ev["task_id"]] = {
                "task_id": ev["task_id"],
                "name": ev.get("name", ""),
                "state": state,
                "type": ev.get("type", "NORMAL_TASK"),
                "node_id": node_hex,
            }
            self._direct_task_rows.move_to_end(ev["task_id"])
        while len(self._direct_task_rows) > 10000:
            self._direct_task_rows.popitem(last=False)
        return True

    async def rpc_get_actor_by_name(self, peer: rpc.Peer, name: str):
        actor_id = self.named_actors.get(name)
        if actor_id is None:
            return None
        actor = self.actors[actor_id]
        return {
            "actor_id": actor_id,
            "creation_spec": actor.creation_spec,
        }

    async def rpc_cancel_by_object(self, peer: rpc.Peer, oid: ObjectID, force: bool):
        orec = self.objects.get(oid)
        if orec is None or orec.creating_task is None:
            return False
        return await self.rpc_cancel_task(peer, orec.creating_task, force)

    async def rpc_cancel_task(self, peer: rpc.Peer, task_id: TaskID, force: bool):
        rec = self.tasks.get(task_id)
        if rec is None:
            return False
        if rec.state == "PENDING":
            rec.state = "FAILED"
            rec.retries_left = 0
            self.pending_tasks = [t for t in self.pending_tasks if t != task_id]
            self.lifecycle.record(
                *self._lc_key(rec.spec), "FAILED", reason="cancelled"
            )
            self._fail_task_objects(rec.spec, TaskCancelledError(task_id.hex()))
            self._unindex_deps(rec.spec)
            return True
        if rec.state in ("DISPATCHED", "RUNNING") and rec.worker_id:
            worker = self.workers.get(rec.worker_id)
            if worker is not None:
                rec.retries_left = 0
                if force:
                    await worker.peer.notify("exit")
                else:
                    await worker.peer.notify("cancel", task_id)
            return True
        return False

    # =================================================================
    # KV store (reference: gcs/gcs_server/gcs_kv_manager.cc)
    # =================================================================
    async def rpc_kv_put(self, peer, ns: str, key: bytes, value: bytes, overwrite: bool = True):
        table = self.kv.setdefault(ns, {})
        if not overwrite and key in table:
            return False
        table[key] = value
        self.journal.kv_put(ns, key, value)
        return True

    async def rpc_kv_get(self, peer, ns: str, key: bytes):
        return self.kv.get(ns, {}).get(key)

    async def rpc_kv_del(self, peer, ns: str, key: bytes):
        existed = self.kv.get(ns, {}).pop(key, None) is not None
        if existed:
            self.journal.kv_del(ns, key)
        return existed

    async def rpc_kv_keys(self, peer, ns: str, prefix: bytes):
        return [k for k in self.kv.get(ns, {}) if k.startswith(prefix)]

    # =================================================================
    # Placement groups
    # =================================================================
    async def rpc_pg_create(self, peer, bundles: List[Dict[str, float]], strategy: str, name: str):
        pg_id = PlacementGroupID.from_random()
        rs = [ResourceSet.from_dict(b) for b in bundles]
        self.pg_manager.create(pg_id, rs, strategy, name)
        self.journal.pg_create(pg_id.hex(), bundles, strategy, name)
        self._schedule_pump()
        return pg_id

    async def rpc_pg_wait_ready(self, peer, pg_id: PlacementGroupID, timeout: Optional[float]):
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self.pg_manager.is_ready(pg_id):
            if pg_id not in self.pg_manager.groups:
                raise ValueError(f"placement group {pg_id.hex()} not found")
            if deadline is not None and time.monotonic() > deadline:
                return False
            self.pg_manager.retry_pending()
            await asyncio.sleep(0.02)
        return True

    async def rpc_pg_shrink(self, peer, pg_id: PlacementGroupID,
                            indices: List[int]):
        ok = self.pg_manager.shrink(pg_id, indices)
        if ok:
            # Journaled: a restarted controller must not resurrect the
            # retired bundles from the pg_create record.
            self.journal.pg_shrink(pg_id.hex(), indices)
        self._schedule_pump()
        return ok

    async def rpc_pg_remove(self, peer, pg_id: PlacementGroupID):
        self.pg_manager.remove(pg_id)
        self.journal.pg_remove(pg_id.hex())
        self._schedule_pump()
        return True

    async def rpc_pg_table(self, peer):
        return self.pg_manager.table()

    async def rpc_pg_bundle_nodes(self, peer, pg_id: PlacementGroupID):
        rec = self.pg_manager.groups.get(pg_id)
        if rec is None:
            return None
        return [n.hex() if n else None for n in rec.bundle_nodes]

    # =================================================================
    # Introspection / state API (reference: python/ray/util/state/api.py)
    # =================================================================
    async def rpc_cluster_resources(self, peer):
        total = ResourceSet()
        for n in self.cluster.nodes.values():
            total = total + n.total
        return total.to_dict()

    async def rpc_available_resources(self, peer):
        total = ResourceSet()
        for n in self.cluster.nodes.values():
            total = total + n.available
        return total.to_dict()

    def _node_row(self, nid: NodeID, node: NodeRecord, devstate: Dict[str, dict]) -> dict:
        res = self.cluster.nodes.get(nid)
        devices = []
        for payload in devstate.values():
            if (payload.get("node_id") or "") == nid.hex():
                pid = payload.get("pid")
                devices.extend({**d, "pid": pid} for d in payload.get("devices", ()))
        return {
            "node_id": nid.hex(),
            "state": node.state,
            "is_head": node.peer is None,
            "num_workers": len(node.workers),
            "agent_pid": node.agent_pid,
            "hostname": node.hostname,
            "provider_instance_id": node.provider_instance_id,
            "resources": res.to_dict() if res else {},
            "telemetry": node.telemetry,
            "devices": devices,
        }

    async def rpc_list_nodes(self, peer):
        devstate = self._live_device_state()
        return [
            self._node_row(nid, node, devstate)
            for nid, node in self.nodes.items()
        ]

    @staticmethod
    def _worker_row(w: WorkerRecord, hostname: str) -> dict:
        return {
            "worker_id": w.worker_id.hex(),
            "node_id": w.node_id.hex(),
            "state": w.state,
            "pid": w.pid,
            "hostname": hostname,
            "actor_id": w.actor_id.hex() if w.actor_id else None,
        }

    def _hostname_of(self, node_id: NodeID) -> str:
        node = self.nodes.get(node_id)
        return node.hostname if node is not None else "localhost"

    async def rpc_list_workers(self, peer):
        return [
            self._worker_row(w, self._hostname_of(w.node_id))
            for w in self.workers.values()
        ]

    # -- targeted gets (reference: the state API's get_* endpoints; a
    # point lookup must not pull a 100k-row list_* dump over the wire) --
    async def rpc_get_node(self, peer, node_id: str):
        try:
            nid = NodeID.from_hex(node_id)
        except (ValueError, TypeError):
            return None
        node = self.nodes.get(nid)
        if node is None:
            return None
        return self._node_row(nid, node, self._live_device_state())

    async def rpc_get_worker(self, peer, worker_id: str):
        try:
            wid = WorkerID.from_hex(worker_id)
        except (ValueError, TypeError):
            return None
        w = self.workers.get(wid)
        if w is None:
            return None
        return self._worker_row(w, self._hostname_of(w.node_id))

    async def rpc_get_task(self, peer, task_id: str):
        try:
            tid = TaskID.from_hex(task_id)
        except (ValueError, TypeError):
            return None
        rec = self.tasks.get(tid)
        if rec is not None:
            return {
                "task_id": tid.hex(),
                "name": rec.spec.name,
                "state": rec.state,
                "type": rec.spec.task_type.name,
                "node_id": rec.node_id.hex() if rec.node_id else None,
            }
        # direct-push tasks live only in the event-derived rows
        return self._direct_task_rows.get(task_id)

    async def rpc_get_actor(self, peer, actor_id: str):
        try:
            aid = ActorID.from_hex(actor_id)
        except (ValueError, TypeError):
            return None
        a = self.actors.get(aid)
        if a is None:
            return None
        return {
            "actor_id": a.actor_id.hex(),
            "state": a.state,
            "name": a.name,
            "num_restarts": a.num_restarts,
            "node_id": a.node_id.hex() if a.node_id else None,
            "death_reason": a.death_reason,
        }

    async def rpc_list_tasks(self, peer, limit: int = 1000):
        import collections as _c

        out = []
        seen = set()
        # deque(maxlen) keeps peak memory O(limit) even at 1M+ task
        # records — the status RPC must not materialize the full table.
        for tid, rec in _c.deque(self.tasks.items(), maxlen=limit):
            seen.add(tid.hex())
            out.append(
                {
                    "task_id": tid.hex(),
                    "name": rec.spec.name,
                    "state": rec.state,
                    "type": rec.spec.task_type.name,
                    "node_id": rec.node_id.hex() if rec.node_id else None,
                }
            )
        # direct-push tasks (event-derived rows; no TaskRecord exists)
        for tid_hex, row in _c.deque(self._direct_task_rows.items(), maxlen=limit):
            if tid_hex not in seen:
                out.append(row)
        return out[-limit:]

    async def rpc_summarize_tasks(self, peer, limit: int = 1000):
        """O(limit)-payload task rollup (reference: the state API's
        summarize_tasks backed by GcsTaskManager counters): counts by
        (name, state) capped to the ``limit`` busiest names, plus
        UNCAPPED totals by state — at 40k+ tasks the status RPC must not
        serialize the table."""
        by_name_state: Dict[Tuple[str, str], int] = {}
        by_state: Dict[str, int] = {}
        by_reason: Dict[str, int] = {}
        total = 0
        for rec in self.tasks.values():
            key = (rec.spec.name, rec.state)
            by_name_state[key] = by_name_state.get(key, 0) + 1
            by_state[rec.state] = by_state.get(rec.state, 0) + 1
            if rec.state == "PENDING" and rec.pending_reason:
                by_reason[rec.pending_reason] = (
                    by_reason.get(rec.pending_reason, 0) + 1
                )
            total += 1
        for row in self._direct_task_rows.values():
            key = (row.get("name", ""), row.get("state", ""))
            by_name_state[key] = by_name_state.get(key, 0) + 1
            by_state[key[1]] = by_state.get(key[1], 0) + 1
            total += 1
        # Cap to the busiest `limit` names (name count is user-bounded in
        # practice, but one misbehaving generator of unique names must
        # not blow up the reply).
        per_name: Dict[str, int] = {}
        for (name, _state), n in by_name_state.items():
            per_name[name] = per_name.get(name, 0) + n
        keep = set(sorted(per_name, key=per_name.get, reverse=True)[: max(0, limit)])
        names: Dict[str, Dict[str, int]] = {}
        for (name, state), n in sorted(by_name_state.items()):
            if name in keep:
                names.setdefault(name, {})[state] = n
        return {
            "tasks": names,
            "counts_by_state": by_state,
            "pending_reasons": by_reason,
            "total": total,
            "truncated": len(per_name) > len(keep),
        }

    def _control_plane_summary(self) -> dict:
        """Round-17 control-plane rollup for ``ray-tpu state``: batch-size
        histograms (how well batching amortizes the lease/push RPCs) and
        the scheduler's fast-path vs full-scan split (how often placement
        was a dict lookup + heap peek vs an O(nodes) walk)."""

        def counter(name: str) -> Dict[str, float]:
            e = self.metrics.get(name)
            if not e:
                return {}
            out: Dict[str, float] = {}
            for tags, v in e["series"].items():
                label = ",".join(f"{k}={val}" for k, val in tags) or "(all)"
                out[label] = out.get(label, 0) + v
            return out

        def hist(name: str):
            e = self.metrics.get(name)
            if not e:
                return None
            merged = bounds = None
            for _tags, payload in e["series"].items():
                st = payload["state"]
                bounds = payload.get("boundaries") or bounds
                merged = (
                    list(st) if merged is None
                    else [a + b for a, b in zip(merged, st)]
                )
            if merged is None or not bounds:
                return None
            count = int(merged[-1])
            total = merged[-2]
            def _lbl(b):
                return int(b) if float(b).is_integer() else b

            buckets = {}
            for i, b in enumerate(bounds):
                buckets[f"<={_lbl(b)}"] = merged[i]
            buckets[f">{_lbl(bounds[-1])}"] = merged[len(bounds)]
            return {
                "count": count,
                "sum": total,
                "avg": round(total / count, 2) if count else 0.0,
                "buckets": buckets,
            }

        return {
            "scheduler_fast_path_total": counter("scheduler_fast_path_total"),
            "scheduler_full_scan_total": sum(
                counter("scheduler_full_scan_total").values()
            ),
            "lease_batch_size": hist("lease_batch_size"),
            "task_push_batch_size": hist("task_push_batch_size"),
            "pubsub_channels": self.bus.channels(),
            "resource_deltas_published": sum(self._resource_seq.values()),
        }

    async def rpc_summarize_lifecycle(self, peer):
        """Flight-recorder rollup: per-(kind, state) transition counts +
        dwell p50/p95/p99, why-pending attribution counters, live
        pending attribution (see core/lifecycle.py), and the round-17
        control-plane section (batch sizes, scheduler fast-path split)."""
        from ray_tpu.util import metrics as _metrics

        self._drain_spawn_events()
        snap = self.lifecycle.snapshot()
        # Fold any counters/histograms still sitting in this process's
        # metric registry so the summary reflects work up to now, not up
        # to the last telemetry sweep.
        self.scheduler.drain_counters()
        records = _metrics.drain_records()
        if records:
            await self.rpc_metrics_report(None, records)
        snap["control_plane"] = self._control_plane_summary()
        return snap

    async def rpc_list_lifecycle_events(self, peer, limit: int = 10000):
        self._drain_spawn_events()
        return self.lifecycle.tail(limit)

    async def rpc_summarize_health(self, peer, limit: int = 50):
        """Self-healing plane summary: registered actuators, recent
        actions with outcomes, per-trigger signal counts, and live
        avoids (quarantined / throttled nodes)."""
        return self.health.snapshot(limit=limit)

    async def rpc_list_actors(self, peer):
        return [
            {
                "actor_id": a.actor_id.hex(),
                "state": a.state,
                "name": a.name,
                "num_restarts": a.num_restarts,
                "node_id": a.node_id.hex() if a.node_id else None,
                "death_reason": a.death_reason,
            }
            for a in self.actors.values()
        ]

    async def rpc_list_objects(self, peer, limit: int = 1000):
        import collections as _c

        out = []
        for oid, rec in _c.deque(self.objects.items(), maxlen=limit):
            out.append(
                {
                    "object_id": oid.hex(),
                    "state": rec.state,
                    "size": rec.size,
                    "is_error": rec.is_error,
                    "locations": [n.hex() for n in rec.locations],
                    "callsite": rec.callsite,
                    "creator": rec.creator,
                    "holders": len(rec.holders),
                }
            )
        return out

    async def rpc_list_events(self, peer, limit: int = 10000):
        return self.events[-limit:]

    # =================================================================
    # App metrics (reference: metrics agent, _private/metrics_agent.py:119;
    # workers flush deltas, the controller aggregates)
    # =================================================================
    async def rpc_metrics_report(self, peer, records: list):
        for name, mtype, desc, tags, payload in records:
            entry = self.metrics.setdefault(
                name, {"type": mtype, "description": desc, "series": {}}
            )
            series = entry["series"]
            if mtype == "counter":
                series[tags] = series.get(tags, 0.0) + payload
            elif mtype == "gauge":
                series[tags] = payload
            elif mtype == "histogram":
                cur = series.get(tags)
                if cur is None:
                    series[tags] = payload
                else:
                    cur["state"] = [a + b for a, b in zip(cur["state"], payload["state"])]

    async def rpc_metrics_snapshot(self, peer):
        snap = {
            name: {
                "type": e["type"],
                "description": e["description"],
                "series": [(list(k), v) for k, v in e["series"].items()],
            }
            for name, e in self.metrics.items()
        }
        # Derived cross-rank straggler gauge: max-min of the ranks' last
        # op latency per collective key. Computed at snapshot time (the
        # controller is the only place all ranks' series meet), so
        # Prometheus/Grafana see it like any reported gauge.
        skew = self._collective_skew()
        if skew:
            snap["collective_skew_ms"] = {
                "type": "gauge",
                "description": "Cross-rank skew (max-min last op latency) per collective",
                "series": [
                    ([["group", r["group"]], ["op", r["op"]]], r["skew_ms"])
                    for r in skew
                ],
            }
        return snap

    async def rpc_serve_report(self, peer, key: str, snapshot: Optional[dict]):
        """An LLM engine's periodic flight-recorder snapshot (reference
        shape: serve replicas pushing autoscaling/queue metrics to the
        serve controller). Keyed deployment/replica/engine; stale entries
        (dead replicas) are pruned on the next report. ``snapshot=None``
        is an idle heartbeat: nothing changed engine-side, just keep the
        stored snapshot alive."""
        if snapshot is None:
            cur = self.serve_state.get(key)
            if cur is not None:
                cur["ts"] = time.time()
            return
        # Stamp arrival with THIS clock: staleness pruning must not trust
        # the engine host's wall time (a skewed worker node would have
        # its live snapshots pruned as stale on arrival).
        snapshot["ts"] = time.time()
        self.serve_state[key] = snapshot
        cutoff = time.time() - 120.0
        for k in [k for k, v in self.serve_state.items()
                  if v.get("ts", 0) < cutoff]:
            del self.serve_state[k]

    async def rpc_serve_state(self, peer):
        # Filter on read too: after the last engine stops reporting
        # (deployment deleted, replica dead) nothing triggers the
        # report-side prune, and a dead engine's occupancy must not be
        # served as live state forever.
        cutoff = time.time() - 120.0
        return {k: v for k, v in self.serve_state.items()
                if v.get("ts", 0) >= cutoff}

    # =================================================================
    # Node/device telemetry (reference: raylet resource-usage heartbeats
    # + the dashboard reporter agent's host/GPU stats)
    # =================================================================
    async def rpc_node_telemetry(self, peer, node_id: NodeID, sample: dict):
        node = self.nodes.get(node_id)
        if node is None:
            return
        # Controller clock, same reason as rpc_serve_report: staleness
        # checks must not trust a skewed worker host's wall time.
        sample["ts"] = time.time()
        node.telemetry = sample

    async def rpc_device_telemetry(self, peer, key: str, payload: dict):
        """A worker/driver process's per-device HBM sample + compile
        snapshot. Keyed node/proc; dead processes stop reporting and age
        out (pruned on the next report and on read)."""
        payload["ts"] = time.time()
        self.device_state[key] = payload
        cutoff = time.time() - 60.0
        for k in [k for k, v in self.device_state.items()
                  if v.get("ts", 0) < cutoff]:
            del self.device_state[k]

    def _live_device_state(self) -> Dict[str, dict]:
        cutoff = time.time() - 60.0
        return {k: v for k, v in self.device_state.items()
                if v.get("ts", 0) >= cutoff}

    async def rpc_collective_skew(self, peer):
        return self._collective_skew()

    async def rpc_compile_state(self, peer):
        """Per-process compile-tracker snapshots (from device telemetry):
        {node_hex/proc: compile snapshot}."""
        return {
            k: v.get("compile", {})
            for k, v in self._live_device_state().items()
            if v.get("compile")
        }

    async def rpc_summarize_resources(self, peer):
        """Cluster resource rollup (reference: `ray status` /
        summarize_* in util/state/api.py): per-node host CPU/mem +
        object-store occupancy from the telemetry heartbeats, per-device
        HBM used/limit and compile activity from worker device reports,
        plus cluster-wide totals."""
        now = time.time()
        devstate = self._live_device_state()
        by_node: Dict[str, list] = {}
        for key, payload in devstate.items():
            node_hex = payload.get("node_id") or key.split("/")[0]
            by_node.setdefault(node_hex, []).append(payload)
        nodes_out = {}
        totals = {
            "mem_used_bytes": 0, "mem_total_bytes": 0,
            "hbm_used_bytes": 0, "hbm_limit_bytes": 0, "hbm_peak_bytes": 0,
            "object_store_used": 0, "object_store_capacity": 0,
            "num_devices": 0, "compiles": 0, "compile_seconds": 0.0,
            "active_storms": [],
        }
        for nid, node in self.nodes.items():
            res = self.cluster.nodes.get(nid)
            tel = node.telemetry or {}
            host = tel.get("host", {})
            store = tel.get("object_store", {})
            row = {
                "hostname": node.hostname,
                "is_head": node.peer is None,
                "state": node.state,
                "num_workers": len(node.workers),
                "host": host,
                "object_store": {
                    "used": store.get("used", 0),
                    "capacity": store.get("capacity", 0),
                    "num_objects": store.get("num_objects", 0),
                    "num_spilled": store.get("num_spilled", 0),
                    # memory-census columns: spill-dir disk usage, store-
                    # side pins, and the deferred-delete queue depth
                    "spilled_bytes": store.get("spilled_bytes", 0),
                    "pinned_slots": store.get("pinned_slots", 0),
                    "pinned_bytes": store.get("pinned_bytes", 0),
                    "deferred_deletes": store.get("deferred_deletes", 0),
                    "spill_ops": store.get("spill_ops", 0),
                },
                "resources": {
                    "total": res.total.to_dict() if res else {},
                    "available": res.available.to_dict() if res else {},
                },
                "telemetry_age_s": round(now - tel["ts"], 2) if "ts" in tel else None,
                "devices": [],
                "compile": {
                    "compiles": 0, "compile_seconds": 0.0,
                    "compiles_per_min": 0.0,
                    "storms_total": 0, "active_storms": [],
                },
            }
            for payload in by_node.get(nid.hex(), ()):
                pid = payload.get("pid")
                for d in payload.get("devices", ()):
                    row["devices"].append({**d, "pid": pid})
                comp = payload.get("compile") or {}
                row["compile"]["compiles"] += comp.get("compiles", 0)
                row["compile"]["compile_seconds"] += comp.get("compile_seconds", 0.0)
                row["compile"]["storms_total"] += comp.get("storms_total", 0)
                # compiles in the tracker's rolling window, normalized to
                # per-minute — the live "compiles/min" column of `status`
                window = comp.get("storm_window_s") or 60.0
                in_window = sum(
                    f.get("window_count", 0)
                    for f in (comp.get("functions") or {}).values()
                )
                row["compile"]["compiles_per_min"] = round(
                    row["compile"].get("compiles_per_min", 0.0)
                    + in_window * 60.0 / window, 1,
                )
                for name in (comp.get("active_storms") or {}):
                    row["compile"]["active_storms"].append(name)
            row["devices"].sort(key=lambda d: (d.get("pid") or 0, d["id"]))
            totals["mem_used_bytes"] += host.get("mem_used_bytes", 0)
            totals["mem_total_bytes"] += host.get("mem_total_bytes", 0)
            totals["object_store_used"] += row["object_store"]["used"]
            totals["object_store_capacity"] += row["object_store"]["capacity"]
            totals["hbm_used_bytes"] += sum(d["bytes_in_use"] for d in row["devices"])
            totals["hbm_limit_bytes"] += sum(d["bytes_limit"] for d in row["devices"])
            totals["hbm_peak_bytes"] += sum(
                d["peak_bytes_in_use"] for d in row["devices"]
            )
            totals["num_devices"] += len(row["devices"])
            totals["compiles"] += row["compile"]["compiles"]
            totals["compile_seconds"] += round(row["compile"]["compile_seconds"], 4)
            totals["active_storms"].extend(row["compile"]["active_storms"])
            nodes_out[nid.hex()] = row
        totals["collective_skew_ms"] = self._collective_skew()
        return {"nodes": nodes_out, "totals": totals}

    def _collective_skew(self) -> List[dict]:
        """Cross-rank skew (max - min of the last per-rank op latency)
        per collective key, derived from the ``collective_last_op_ms``
        gauge series every rank reports — the straggler view per
        ring/mesh. Sorted worst-first."""
        entry = self.metrics.get("collective_last_op_ms")
        if not entry:
            return []
        per_key: Dict[Tuple[str, str], Dict[str, float]] = {}
        for tags, value in entry["series"].items():
            t = dict(tags)
            key = (t.get("group", "?"), t.get("op", "?"))
            per_key.setdefault(key, {})[t.get("rank", "?")] = value
        out = []
        for (group, op), ranks in per_key.items():
            if len(ranks) < 2:
                continue
            mx, mn = max(ranks.values()), min(ranks.values())
            out.append(
                {
                    "group": group, "op": op,
                    "skew_ms": round(mx - mn, 3),
                    "max_ms": round(mx, 3), "min_ms": round(mn, 3),
                    "slowest_rank": max(ranks, key=ranks.get),
                    "ranks": len(ranks),
                }
            )
        out.sort(key=lambda r: -r["skew_ms"])
        return out

    # -- resource-view pubsub (round 17: push-on-change replaces
    # per-sweep polling; core/pubsub.py documents the delivery model) --
    def _resource_row(self, nid: NodeID, avoids) -> dict:
        res = self.cluster.nodes[nid]
        av = avoids.get(nid)
        return {
            "available": res.available.to_dict(),
            "total": res.total.to_dict(),
            "draining": res.draining,
            "avoid": ("hard" if av[1] else "soft") if av else None,
        }

    def _resource_snapshot(self) -> dict:
        avoids = self.cluster.avoids()
        nodes = {}
        for nid in self.cluster.nodes:
            row = self._resource_row(nid, avoids)
            row["seq"] = self._resource_seq.setdefault(nid, 0)
            nodes[nid.hex()] = row
        return {"snapshot": True, "nodes": nodes}

    def _avoid_snapshot(self) -> dict:
        avoids = self.cluster.avoids()
        return {
            "snapshot": True,
            "avoid": {
                nid.hex(): {"hard": hard, "deadline": dl}
                for nid, (dl, hard) in avoids.items()
            },
            "draining": [
                nid.hex() for nid, res in self.cluster.nodes.items() if res.draining
            ],
        }

    async def _broadcast_resource_deltas(self):
        """Drain the scheduler's dirty-node set into per-node seq'd
        deltas on RESOURCES_CHANNEL, coalesced to at most one publish
        per resource_broadcast_min_interval_ms; a full snapshot rides
        the same channel every resource_reconcile_interval_s so mirrors
        converge past any dropped/reordered deltas. Avoid/drain state
        pushes to AVOID_CHANNEL on the reconcile cadence (it also rides
        every resource delta, so agents gating on the resource mirror
        see it immediately)."""
        from ray_tpu.core import pubsub as _ps

        dirty = self.cluster.dirty_nodes
        if not (self.bus.has(_ps.RESOURCES_CHANNEL) or self.bus.has(_ps.AVOID_CHANNEL)):
            dirty.clear()  # nobody listening — don't let the set grow
            return
        now = time.monotonic()
        min_iv = self.config.resource_broadcast_min_interval_ms / 1000.0
        if dirty and now - self._last_resource_broadcast >= min_iv:
            self._last_resource_broadcast = now
            avoids = self.cluster.avoids()
            batch = list(dirty)
            dirty.clear()
            for nid in batch:
                seq = self._resource_seq.get(nid, 0) + 1
                self._resource_seq[nid] = seq
                if nid not in self.cluster.nodes:
                    # Seq floor is kept: a re-registered node continues
                    # the sequence so mirrors never mistake its first
                    # post-rejoin delta for a stale pre-removal one.
                    msg = {"node": nid.hex(), "seq": seq, "removed": True}
                else:
                    msg = self._resource_row(nid, avoids)
                    msg["node"] = nid.hex()
                    msg["seq"] = seq
                await self.bus.publish(_ps.RESOURCES_CHANNEL, msg)
        if now - self._last_resource_reconcile >= self.config.resource_reconcile_interval_s:
            self._last_resource_reconcile = now
            if self.bus.has(_ps.RESOURCES_CHANNEL):
                await self.bus.publish(_ps.RESOURCES_CHANNEL, self._resource_snapshot())
            if self.bus.has(_ps.AVOID_CHANNEL):
                await self.bus.publish(_ps.AVOID_CHANNEL, self._avoid_snapshot())

    async def _head_telemetry_loop(self):
        """The controller doubles as the head node's agent — sample the
        head host + its store on the same cadence the agents report."""
        interval = self.config.node_telemetry_interval_ms / 1000.0
        if interval <= 0:
            return
        from ray_tpu.core import node_telemetry
        from ray_tpu.core.memory_monitor import HostCpuSampler
        from ray_tpu.util import metrics as _metrics

        cpu = HostCpuSampler()
        cpu.sample()  # prime the delta
        while not self._shutdown.is_set():
            await asyncio.sleep(interval)
            self._drain_spawn_events()
            # Recorder metrics are throttle-flushed from record(); a
            # quiet cluster still syncs its last batch here.
            self.lifecycle.flush_metrics()
            node = self.nodes.get(self.head_node_id)
            if node is None:
                continue
            sample = node_telemetry.build_node_sample(cpu, self.head_store)
            sample["ts"] = time.time()
            node.telemetry = sample
            # Memory census sweep: Grafana gauges, the open-ref growth
            # (leak) detector, and the store-pressure incident trigger.
            try:
                self._memory_census_tick()
            except Exception:  # noqa: BLE001 — census must not kill telemetry
                logger.exception("memory census tick failed")
            # Log plane sweep: the controller's own captured ERROR
            # records feed the index in-process (it has no ship loop),
            # then the error-rate-spike detector runs over the sweep.
            try:
                from ray_tpu.core import log_plane as _lp

                for rec in _lp.drain_ship():
                    self._error_index.ingest(rec)
                self._error_spike_check()
            except Exception:  # noqa: BLE001 — log plane must not kill telemetry
                logger.exception("log plane sweep failed")
            # Health plane tick: expire avoids, refresh gauges, and scan
            # shipped compile snapshots for new recompile storms.
            try:
                self.health.tick()
            except Exception:  # noqa: BLE001 — health must not kill telemetry
                logger.exception("health tick failed")
            # Resource-view pubsub: coalesced dirty-node deltas plus the
            # periodic reconcile snapshot (round 17).
            try:
                await self._broadcast_resource_deltas()
            except Exception:  # noqa: BLE001 — pubsub must not kill telemetry
                logger.exception("resource delta broadcast failed")
            # Scheduler fast-path/full-scan counters accumulate as plain
            # ints on the decision path (a metrics inc per placement
            # would cost more than the fast path saves) — flush here.
            self.scheduler.drain_counters()
            # Metrics recorded IN the controller process (head-side
            # object transfers, chunk serving) have no CoreWorker flusher
            # — fold them straight into the aggregation.
            records = _metrics.drain_records()
            if records:
                await self.rpc_metrics_report(None, records)

    async def rpc_resource_demand(self, peer):
        """Unmet demand for the autoscaler: resource sets of tasks that are
        waiting for placement plus bundles of pending placement groups
        (reference: SchedulerResourceReporter feeding the autoscaler via
        GcsAutoscalerStateManager)."""
        import itertools

        demand = []
        # pending work lives in the intake list, the per-class FIFOs, and
        # the dep-parked set — all of it is unmet demand
        pending_views = itertools.chain(
            self.pending_tasks,
            *self._class_queues.values(),
            self._dep_parked,
        )
        def _with_labels(item: dict, strategy) -> dict:
            # label-constrained demand carries its hard expressions so the
            # autoscaler can pick a node TYPE whose labels satisfy them
            hard = (strategy.node_labels or {}).get("hard") if strategy else None
            if hard:
                item["_labels"] = hard
            return item

        for tid in pending_views:
            rec = self.tasks.get(tid)
            if rec is not None and rec.state == "PENDING":
                demand.append(_with_labels(
                    rec.spec.resources.to_dict(), rec.spec.scheduling_strategy
                ))
        for req in self._lease_reqs:
            # parked worker-lease requests are unmet task demand too
            demand.append(_with_labels(req.demand.to_dict(), req.strategy))
        pg_demand = []
        for pg in self.pg_manager.pending_records():
            pg_demand.append(
                {"strategy": pg.strategy, "bundles": [b.to_dict() for b in pg.bundles]}
            )
        return {"tasks": demand, "placement_groups": pg_demand}

    # =================================================================
    # Streaming generators
    # =================================================================
    @staticmethod
    def _wake_stream(rec: TaskRecord):
        """Wake whoever waits for the stream's next item or its end."""
        for fut in rec.stream_waiters:
            if not fut.done():
                fut.set_result(True)
        rec.stream_waiters.clear()

    async def rpc_stream_items(self, peer, node_id: NodeID, runs: list):
        """One shipment of a worker's streaming generators (``worker_main.
        _StreamShipper``): ``runs`` is ``[task_id, first, callsite, entries]``
        a stream, ``entries`` its consecutive items from index ``first`` on,
        each ``(kind, payload, is_error, contained)``: ``inline`` with the
        item's bytes, or ``shm`` with the size of what the worker wrote to
        ``node_id``'s store. Every item is filed under its own id, so a ref an
        item resolves as it always did."""
        for task_id, first, callsite, entries in runs:
            for k, (kind, payload, is_error, contained) in enumerate(entries):
                oid = ObjectID.for_task_return(task_id, first + k)
                if kind == "inline":
                    await self.rpc_object_put_inline(
                        peer, oid, payload, is_error, contained, callsite)
                else:
                    await self.rpc_object_put_shm(
                        peer, oid, payload, node_id, is_error, contained, callsite)
            rec = self.tasks.get(task_id)
            if rec is None:
                continue
            rec.stream_count = max(rec.stream_count, first + len(entries))
            self._wake_stream(rec)
        return True

    async def rpc_stream_take(self, peer, task_id: TaskID, index: int):
        """Block until item ``index`` exists, then hand out every item from
        it on that has arrived, BY VALUE, in this one reply: ``(bytes,
        is_error)`` each, or None for one the consumer has to get by its ref
        (not inline, or it holds refs of its own, which the object pins).
        None at end-of-stream. An item handed out by value that no ref was
        ever taken to is freed here: nobody else will ask for it."""
        if await self.rpc_stream_next(peer, task_id, index) is None:
            return None
        out = []
        for i in range(index, self.tasks[task_id].stream_count):
            oid = ObjectID.for_task_return(task_id, i)
            orec = self.objects.get(oid)
            if orec is None or orec.state != "READY" or orec.inline is None or orec.children:
                out.append(None)
                continue
            out.append((orec.inline, orec.is_error))
            if not orec.ever_held and not orec.holders and not orec.waiters:
                await self._free_object(oid)
        return out

    async def rpc_stream_next(self, peer, task_id: TaskID, index: int):
        """Block until item `index` exists; "item" when available, None at
        end-of-stream."""
        while True:
            rec = self.tasks.get(task_id)
            if rec is None:
                return None
            if index < rec.stream_count:
                return "item"
            if rec.stream_done or rec.state in ("FAILED", "FINISHED"):
                return "item" if index < rec.stream_count else None
            fut = asyncio.get_running_loop().create_future()
            rec.stream_waiters.append(fut)
            await fut

    async def rpc_drain_node(self, peer, node_id: NodeID, timeout_s: float = 300.0):
        """Graceful drain (reference: NodeManager drain / rpc::DrainNode +
        `ray drain-node`): stop placing work on the node, let running work
        finish (up to ``timeout_s``), then retire it. Actors with
        max_restarts left restart elsewhere through the normal death path.
        Returns immediately; drain progresses in the background."""
        node = self.nodes.get(node_id)
        if node is None or node.state != "ALIVE":
            raise ValueError(f"node {node_id.hex()} not alive")
        if node.peer is None:
            raise ValueError("cannot drain the head node")
        node.state = "DRAINING"
        self.cluster.set_draining(node_id, True)
        self.lifecycle.record("node", node_id.hex(), "DRAINING")
        await self._publish_death("node", node_id.hex(), "DRAINING")

        # Preempt restartable actors right away (reference: preemption
        # flagging, actor_task_submitter.h:67): their death path restarts
        # them on schedulable nodes and max_task_retries resubmits
        # in-flight methods. Non-restartable actors ride out the drain.
        for wid in list(node.workers):
            w = self.workers.get(wid)
            if w is not None and w.state == "ACTOR" and w.actor_id is not None:
                actor = self.actors.get(w.actor_id)
                if actor is not None and actor.restarts_left > 0:
                    try:
                        await w.peer.notify("exit")
                    except Exception:
                        pass

        async def finish_drain():
            # Wait for in-flight plain-task work to finish (actor-method
            # streams can arrive indefinitely and must not starve the
            # drain; their actors were preempted above or accept the cut).
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                busy = [
                    w
                    for wid in node.workers
                    if (w := self.workers.get(wid)) is not None
                    and w.state == "LEASED"
                    and w.running
                ]
                if not busy:
                    break
                await asyncio.sleep(0.2)
            rec = self.nodes.get(node_id)
            if rec is not None and rec.state == "DRAINING":
                try:
                    await rec.peer.notify("exit")
                except Exception:
                    pass

        # Keep a strong ref: the loop holds tasks weakly (same pitfall the
        # memory-monitor task documents below).
        task = asyncio.get_running_loop().create_task(finish_drain())
        self._drain_tasks.add(task)
        task.add_done_callback(self._drain_tasks.discard)
        return True

    async def rpc_ping(self, peer):
        return "pong"

    async def rpc_shutdown_cluster(self, peer):
        """Start the teardown and name what it will end: (pid, start time)
        of this process and of every worker and node agent on this host,
        for ``cluster_utils.end_cluster`` to wait on. Pids of another host
        mean nothing in the caller's /proc and are left out."""
        from ray_tpu.core.cluster_utils import process_start
        from ray_tpu.core.node_agent import _children

        here = {n.node_id for n in self.nodes.values()
                if n.hostname in ("localhost", socket.gethostname())}
        pids = {os.getpid(), *_children}  # head workers, registered yet or not
        pids.update(n.agent_pid for n in self.nodes.values() if n.node_id in here)
        pids.update(w.pid for w in self.workers.values() if w.node_id in here)
        self._shutdown.set()
        return [(pid, process_start(pid)) for pid in sorted(pids) if pid]

    # =================================================================
    def _lc_key(self, spec: TaskSpec) -> Tuple[str, str]:
        """Flight-recorder entity for a spec: actor-creation tasks chart
        the ACTOR's chain (SUBMITTED → ... → ALIVE), everything else the
        task's."""
        if spec.task_type == TaskType.ACTOR_CREATION_TASK and spec.actor_id:
            return "actor", spec.actor_id.hex()
        return "task", spec.task_id.hex()

    def _event(self, kind: str, spec: TaskSpec, state: str):
        self.events.append(
            {
                "ts": time.time(),
                "kind": kind,
                "task_id": spec.task_id.hex(),
                "name": spec.name,
                "state": state,
            }
        )
        if len(self.events) > self.config.task_event_buffer_size:
            del self.events[: len(self.events) // 2]
        if kind == "task" and spec.task_type == TaskType.ACTOR_CREATION_TASK:
            # A creation task's chain is charted under the ACTOR entity
            # (_lc_key: SUBMITTED/QUEUED/CREATING → ALIVE closes nothing);
            # a lone task.FINISHED here would inflate task counts with no
            # matching task.SUBMITTED. Legacy self.events keeps the row.
            return
        eid = (
            spec.actor_id.hex()
            if kind == "actor" and spec.actor_id
            else spec.task_id.hex()
        )
        self.lifecycle.record(kind, eid, state, name=spec.name)

    # =================================================================
    def _oom_candidates(self, head_only: bool, node_id: Optional[NodeID] = None):
        """KillCandidates among a node's workers (reference:
        worker_killing_policy candidate assembly)."""
        from ray_tpu.core.memory_monitor import KillCandidate

        candidates = []
        for w in self.workers.values():
            node = self.nodes.get(w.node_id)
            if node is None:
                continue
            if head_only and node.peer is not None:
                continue
            if node_id is not None and w.node_id != node_id:
                continue
            if w.state == "LEASED" and w.running:
                tid = next(iter(w.running))
                rec = self.tasks.get(tid)
                if rec is None:
                    continue
                candidates.append(
                    KillCandidate(
                        worker_id=w.worker_id.hex(),
                        pid=w.pid,
                        is_retriable=rec.retries_left > 0,
                        start_time=rec.submitted_at,
                        owner_id=rec.spec.owner_id.hex() if rec.spec.owner_id else "",
                    )
                )
            elif w.state == "ACTOR" and w.actor_id is not None:
                actor = self.actors.get(w.actor_id)
                if actor is None:
                    continue
                candidates.append(
                    KillCandidate(
                        worker_id=w.worker_id.hex(),
                        pid=w.pid,
                        is_retriable=actor.restarts_left > 0,
                        # Actors rank as oldest: tasks die before actors.
                        start_time=0.0,
                        owner_id=actor.creation_spec.owner_id.hex()
                        if actor.creation_spec.owner_id
                        else "",
                    )
                )
        return candidates

    async def _direct_oom_candidates(self, head_only: bool, node_id: Optional[NodeID] = None):
        """Candidates among DIRECT-pool workers, whose running tasks the
        controller never sees — ask each worker what it's executing
        (rpc_current_task). OOM is rare; a per-incident fan-out beats
        per-task tracking traffic."""
        from ray_tpu.core.memory_monitor import KillCandidate

        targets = []
        for w in self.workers.values():
            node = self.nodes.get(w.node_id)
            if node is None:
                continue
            if head_only and node.peer is not None:
                continue
            if node_id is not None and w.node_id != node_id:
                continue
            if w.state == "DIRECT" or (
                w.state == "LEASED" and not w.running and w.actor_id is None
            ):
                targets.append(w)

        async def ask(w):
            try:
                info = await asyncio.wait_for(w.peer.call("current_task"), 0.5)
            except Exception:  # noqa: BLE001 — dying worker
                return None
            if not info:
                return None
            return KillCandidate(
                worker_id=w.worker_id.hex(),
                pid=w.pid,
                is_retriable=bool(info.get("retriable")),
                start_time=float(info.get("start", time.time())),
                owner_id=info.get("owner", ""),
            )

        results = await asyncio.gather(*(ask(w) for w in targets))
        return [c for c in results if c is not None]

    def _oom_policy(self):
        from ray_tpu.core.memory_monitor import POLICIES

        policy = POLICIES.get(self.config.worker_killing_policy)
        if policy is None:
            logger.error(
                "unknown worker_killing_policy %r; using retriable_fifo",
                self.config.worker_killing_policy,
            )
            policy = POLICIES["retriable_fifo"]
        return policy

    async def rpc_node_over_memory(self, peer: rpc.Peer, node_id: NodeID):
        """A node agent's memory monitor crossed the threshold: pick a
        victim among THAT node's workers (the policies need task/actor
        context only the controller has) and return its pid for the
        agent to SIGKILL locally (reference: each raylet runs its own
        MemoryMonitor; victim choice is worker_killing_policy)."""
        candidates = self._oom_candidates(False, node_id)
        candidates += await self._direct_oom_candidates(False, node_id)
        victim = self._oom_policy()(candidates)
        if victim is None:
            return None
        w = self.workers.get(WorkerID.from_hex(victim.worker_id))
        if w is None:
            return None
        logger.warning(
            "node %s over memory: killing worker %s (pid %s, policy %s)",
            node_id.hex()[:8], victim.worker_id[:8], victim.pid,
            self.config.worker_killing_policy,
        )
        w.oom_marked = True
        # Belt-and-braces: also ask the worker to exit — if the agent's
        # SIGKILL fails (permission, races), the worker still dies and
        # the oom_marked flag stays truthful about the death cause.
        await _notify_quiet(w.peer, "exit", what="OOM kill fallback")
        return victim.pid

    async def _memory_monitor_loop(self):
        """Kill workers when the HEAD host's memory crosses the threshold
        (reference: memory_monitor.h polling + worker_killing_policy
        victim choice). Non-head nodes run the same monitor in their
        agent, reporting through rpc_node_over_memory — on single-host
        simulations the agents' monitors see the same memory, so the
        head-only filter here avoids double-killing."""
        from ray_tpu.core.memory_monitor import MemoryMonitor

        monitor = MemoryMonitor(threshold=self.config.memory_usage_threshold)
        policy = self._oom_policy()
        interval = self.config.memory_monitor_refresh_ms / 1000.0
        while not self._shutdown.is_set():
            await asyncio.sleep(interval)
            if not monitor.should_kill():
                continue
            candidates = self._oom_candidates(head_only=True)
            candidates += await self._direct_oom_candidates(head_only=True)
            victim = policy(candidates)
            if victim is None:
                continue
            wid = WorkerID.from_hex(victim.worker_id)
            w = self.workers.get(wid)
            if w is None:
                continue
            logger.warning(
                "memory monitor killing worker %s (pid %s, policy %s)",
                victim.worker_id[:8],
                victim.pid,
                self.config.worker_killing_policy,
            )
            w.oom_marked = True
            try:
                os.kill(victim.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                await _notify_quiet(w.peer, "exit", what="OOM SIGKILL fallback")

    async def _restore_persisted(self):
        """Re-create journaled PGs and detached actors after a restart
        (reference: GCS restart restores actor/PG tables, then the actor
        manager reschedules; gcs_actor_manager.cc restart path)."""
        for pg_hex, pg in self._restored.pgs.items():
            pg_id = PlacementGroupID.from_hex(pg_hex)
            rs = [ResourceSet.from_dict(b) for b in pg["bundles"]]
            self.pg_manager.create(pg_id, rs, pg["strategy"], pg["name"])
            if pg.get("retired"):
                self.pg_manager.shrink(pg_id, pg["retired"])
        for actor_hex, spec in self._restored.actors.items():
            if spec.dependencies:
                # Arg objects died with the old cluster; without lineage for
                # them the actor cannot be re-created faithfully.
                logger.warning("cannot restore detached actor %s: has object deps", actor_hex)
                self.journal.actor_dead(actor_hex)
                continue
            await self.rpc_create_actor(None, spec, _journal=False)
        if self._restored.pgs or self._restored.actors:
            self._schedule_pump()

    def _broadcast_logs(self, batch):
        """Thread→loop bridge: fan worker-log lines out to drivers
        (reference: log_monitor publish + driver print_to_stdstream)."""
        # Runs on the log-tailer THREAD; ``drivers`` is loop-owned. The
        # emptiness peek here is only an optimization (skip scheduling a
        # coroutine when nobody listens), so take it as an atomic
        # snapshot — the authoritative read happens in send() on the
        # loop. ConcSan flagged the bare read (owner_thread finding).
        if not snapshot(self.drivers) or self._loop is None:
            return

        async def send():
            for peer in list(self.drivers):
                await _notify_quiet(peer, "log_batch", batch, what="driver gone")

        asyncio.run_coroutine_threadsafe(send(), self._loop)

    async def run(self, port: int = 0):
        from ray_tpu.utils.net import bind_host

        # Loopback unless RAY_TPU_NODE_IP opts into multi-host (agents on
        # other hosts must reach the control plane).
        server, self.port = await rpc.serve(self, host=bind_host(), port=port)
        self._loop = asyncio.get_running_loop()
        # The controller's own incident captures (store pressure, lock
        # watchdog) resolve the session via this env hint — the spawned
        # controller process otherwise has no session marker (workers get
        # it from spawn_worker).
        os.environ.setdefault("RAY_TPU_SESSION_DIR", self.session_dir)
        # Profiling: continuous incident sampler (off unless configured)
        # + flight-recorder tail so controller incident bundles carry the
        # scheduler context alongside stacks/samples.
        from ray_tpu.util import profiling

        profiling.ensure_continuous(
            hz=self.config.profiling_continuous_hz,
            ring_s=self.config.profiling_ring_s,
        )
        profiling.set_recorder_tail_provider(lambda: self.lifecycle.tail(500))
        if self.config.log_structured:
            # Controller leg of the log plane: scheduler warnings/errors
            # become structured records (handler-only; streams already
            # land in controller.log) and feed the error index via the
            # telemetry sweep.
            from ray_tpu.core import log_plane

            log_plane.install(
                self.session_dir,
                node_id=self.head_node_id.hex(),
                proc="controller",
                capture_streams=False,
                rotate_bytes=self.config.log_rotate_bytes,
            )
        self._log_tailer = None
        if self.config.log_to_driver:
            from ray_tpu.core.log_monitor import LogTailer

            # One tailer on the session log dir covers every worker that
            # logs into this session (all nodes are host-local processes;
            # a true multi-host deployment runs a tailer per node agent).
            self._log_tailer = LogTailer(
                os.path.join(self.session_dir, "logs"), self._broadcast_logs
            )
            self._log_tailer.start()
        await self._restore_persisted()
        if self.config.memory_monitor_refresh_ms > 0:
            # Keep a strong ref: the loop holds tasks weakly and an
            # unreferenced monitor could be garbage-collected mid-run.
            self._monitor_task = asyncio.get_running_loop().create_task(
                self._memory_monitor_loop()
            )
        if self.config.object_auto_gc:
            self._gc_task = asyncio.get_running_loop().create_task(
                self._gc_sweep_loop()
            )
        if self.config.node_telemetry_interval_ms > 0:
            # Strong ref (loop holds tasks weakly, same as the monitor).
            self._telemetry_task = asyncio.get_running_loop().create_task(
                self._head_telemetry_loop()
            )
        if self.config.dashboard_port >= 0:
            from ray_tpu.core.http_gateway import start_http_gateway

            self.dashboard_port = start_http_gateway(
                self, asyncio.get_running_loop(), self.config.dashboard_port
            )
            with open(os.path.join(self.session_dir, "dashboard_port"), "w") as f:
                f.write(str(self.dashboard_port))
        with open(os.path.join(self.session_dir, "controller_port"), "w") as f:
            f.write(str(self.port))
        if self._head_prestart:
            await self._request_workers(self.nodes[self.head_node_id], self._head_prestart)
        await self._shutdown.wait()
        if self._log_tailer is not None:
            self._log_tailer.stop()
        if self._record_tailer is not None:
            self._record_tailer.stop()
        # Teardown: tell everyone to exit.
        for w in list(self.workers.values()):
            await _notify_quiet(w.peer, "exit", what="cluster teardown")
        for n in self.nodes.values():
            if n.peer is not None:
                await _notify_quiet(n.peer, "exit", what="cluster teardown")
        await asyncio.sleep(0.1)
        # a head worker that was still dialing in never heard "exit" (the
        # node agents end theirs the same way)
        from ray_tpu.core.node_agent import kill_children

        kill_children()
        server.close()
        self.head_store.destroy()


def _default_store_bytes() -> int:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    kb = int(line.split()[1])
                    return min(int(kb * 1024 * 0.3), 16 * 1024**3)
    except (OSError, ValueError, IndexError):
        pass  # no /proc/meminfo (macOS) or unparseable — use the default
    return 2 * 1024**3


def main():
    from ray_tpu.util import chaos, lockwatch

    lockwatch.maybe_install()  # RAY_TPU_LOCKWATCH=1: watch locks created from here on
    chaos.install_fault_plan_from_env()  # RAY_TPU_FAULT_PLAN: deterministic chaos
    parser = argparse.ArgumentParser()
    parser.add_argument("--session-dir", required=True)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--resources", default="{}")
    parser.add_argument("--config", default="{}")
    parser.add_argument("--owned", action="store_true")
    args = parser.parse_args()
    logging.basicConfig(
        level=logging.INFO,
        format="[controller] %(levelname)s %(message)s",
    )
    cfg = Config.from_env().apply_overrides(json.loads(args.config))
    set_config(cfg)
    os.makedirs(args.session_dir, exist_ok=True)
    ctrl = Controller(args.session_dir, json.loads(args.resources), cfg, owned=args.owned)

    loop = asyncio.new_event_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, ctrl._shutdown.set)
    try:
        loop.run_until_complete(ctrl.run(args.port))
    finally:
        loop.close()


if __name__ == "__main__":
    main()
