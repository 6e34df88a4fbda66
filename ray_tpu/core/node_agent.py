"""Node agent: per-node daemon for non-head nodes.

Reference: the raylet (src/ray/raylet/main.cc, node_manager.cc) minus
scheduling (which is GCS-direct in this design — see controller.py): it
registers the node's resources, hosts the node's shared-memory store, and
spawns/kills worker processes on request (reference: worker_pool.cc:438
``StartWorkerProcess``).
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import json
import logging
import os
import signal
import subprocess
import sys
import time
from typing import Dict

from ray_tpu.core.object_store import PlasmaStore
from ray_tpu.util.guards import OWNER_THREAD, GuardedDict, GuardedSet
from ray_tpu.utils import rpc
from ray_tpu.utils.ids import NodeID, ObjectID, WorkerID

logger = logging.getLogger("ray_tpu.node_agent")

_children: Dict[int, subprocess.Popen] = {}

# Worker-lifecycle events recorded at spawn time (flight recorder,
# core/lifecycle.py): SPAWNED here pairs with REGISTERED at the
# controller, making the dwell the worker-startup latency. Agents ship
# the deque over their telemetry channel; the controller (spawning head
# workers through this same function) drains it in-process. Bounded —
# an undrained deque (telemetry disabled) must not grow forever.
_lifecycle_events: "collections.deque" = collections.deque(maxlen=10000)


_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def place_compile_cache(env) -> str:
    """Where XLA's persistent compile cache lives, decided in ``env`` (a
    child's environment, or ``os.environ`` for the driver) and returned.

    A directory given from outside wins and jax reads it from the variable
    itself. Otherwise the cache is ``<checkout>/.jax_cache``: a cache
    only hits when every process of every run looks in the same place, so
    the default is never a temp, pid or session directory."""
    if not env.get(COMPILE_CACHE_ENV):
        env[COMPILE_CACHE_ENV] = os.path.join(_PKG_ROOT, ".jax_cache")
    return env[COMPILE_CACHE_ENV]


def child_env() -> dict:
    """Environment for every process the cluster spawns (controller, node
    agents, workers, monitors): the parent's, plus the package on
    PYTHONPATH and the compile-cache directory (place_compile_cache)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _PKG_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    place_compile_cache(env)
    return env


def spawn_worker(session_dir: str, controller_addr: str, node_id: NodeID, shm_dir: str,
                 extra_env: Dict[str, str] = None,
                 container_image: str = None) -> subprocess.Popen:
    """Start a worker process (reference: python/ray/_private/workers/
    default_worker.py is the reference's equivalent entrypoint).

    ``container_image``: launch the worker INSIDE this OCI image via the
    node's container runtime (reference: runtime_env/image_uri.py; here
    ray_tpu/runtime_env/container.py builds the podman/docker argv)."""
    worker_id = WorkerID.from_random()
    _lifecycle_events.append(
        {
            "ts": time.time(),
            "kind": "worker",
            "id": worker_id.hex(),
            "state": "SPAWNED",
            "node": node_id.hex()[:12],
        }
    )
    env = child_env()
    env.update(
        RAY_TPU_CONTROLLER=controller_addr,
        RAY_TPU_NODE_ID=node_id.hex(),
        RAY_TPU_WORKER_ID=worker_id.hex(),
        RAY_TPU_SHM_DIR=shm_dir,
        RAY_TPU_SESSION_DIR=session_dir,
        # Log-to-driver streaming tails the redirected stdout file; block
        # buffering would hold prints back until process exit.
        PYTHONUNBUFFERED="1",
    )
    if extra_env:
        env.update(extra_env)
    cmd = [sys.executable, "-m", "ray_tpu.core.worker_main"]
    if container_image:
        # wrap_command embeds the (cached) image pull in the spawned
        # shell — spawn_worker itself never blocks on a registry (it is
        # called from the controller/agent event loop).
        from ray_tpu.runtime_env import container as _container

        cmd = _container.wrap_command(container_image, cmd, env, session_dir, shm_dir)
    log_dir = os.path.join(session_dir, "logs")
    os.makedirs(log_dir, exist_ok=True)
    # O_APPEND ("ab") is load-bearing: the worker size-caps this file
    # in-process by copy-truncate rotation (core/log_plane.py — rename
    # would chase this inherited fd), and append-mode writes land at the
    # new EOF after a truncate instead of leaving a sparse hole.
    out = open(os.path.join(log_dir, f"worker-{worker_id.hex()[:8]}.log"), "ab")
    proc = subprocess.Popen(
        cmd,
        env=env,
        stdout=out,
        stderr=subprocess.STDOUT,
        start_new_session=False,
    )
    _children[proc.pid] = proc
    return proc


def reap_children():
    for pid, proc in list(_children.items()):
        if proc.poll() is not None:
            _children.pop(pid, None)


def kill_children():
    for proc in _children.values():
        try:
            proc.terminate()
        except Exception:
            pass


class _DirectWorker:
    """One spawned direct-pool worker in the agent's free-worker view."""

    __slots__ = ("wid", "addr", "env_hash", "busy", "peer")

    def __init__(self, wid: str, addr: str, peer=None):
        self.wid = wid
        self.addr = addr
        self.env_hash = ""
        self.busy = False
        self.peer = peer  # the worker's attach connection (exit channel)


class NodeAgent:
    def __init__(self, controller_addr: str, session_dir: str, resources: Dict[str, float], capacity: int):
        self.controller_addr = controller_addr
        self.session_dir = session_dir
        self.resources = resources
        self.node_id = NodeID.from_random()
        self.store = PlasmaStore(session_dir, capacity, name=self.node_id.hex()[:8])
        self._exit = asyncio.Event()
        self._controller_peer = None
        from ray_tpu.core.object_transfer import ChunkReader, FetchPeerCache

        self._fetch_peers = FetchPeerCache()
        self._chunk_reader = ChunkReader(self.store)
        self._chunk_bytes = 8 * 1024 * 1024
        # Single-writer agent state (asyncio-loop discipline, same as the
        # controller's maps): OWNER_THREAD guards make it ConcSan-checked.
        self._inflight_pulls: Dict = GuardedDict(
            OWNER_THREAD, owner=self, name="inflight_pulls"
        )  # oid -> InflightPull (broadcast hops)
        # Direct-lease worker pool: THE AGENT owns this node's free-worker
        # view (reference: the raylet's WorkerPool, worker_pool.h:174); the
        # controller only places leases onto the node.
        import collections

        self._direct: Dict[str, _DirectWorker] = GuardedDict(
            OWNER_THREAD, owner=self, name="direct"
        )
        self._direct_waiters: "collections.deque" = collections.deque()
        self._direct_starting = 0
        self._direct_spawns: list = []  # Popen handles not yet attached
        self._lease_workers: Dict[bytes, str] = GuardedDict(
            OWNER_THREAD, owner=self, name="lease_workers"
        )  # lease_id -> worker id
        # rpc_lease_worker grants in flight, and leases released while
        # their grant was still in flight (bounded: only grants currently
        # executing can enter _released_leases; the grant's finally
        # clears both).
        self._granting: set = GuardedSet(
            OWNER_THREAD, owner=self, name="granting"
        )
        self._released_leases: set = GuardedSet(
            OWNER_THREAD, owner=self, name="released_leases"
        )
        ncpu = int(resources.get("CPU", 1))
        self._max_direct = max(4 * max(ncpu, 1), 16)
        self._listen_addr = ""  # set in run()
        # Push-fed local cluster view (round 17, core/pubsub.py): the
        # controller streams per-node availability deltas and avoid/
        # drain state instead of the agent polling per decision. Mirror
        # stats ride the telemetry heartbeat; self-avoid transitions are
        # logged for operators.
        from ray_tpu.core.pubsub import ResourceViewMirror

        self.resource_mirror = ResourceViewMirror()
        self._avoid_view: Dict = {"avoid": {}, "draining": []}
        self._self_avoided = False

    # -- notifications from the controller ------------------------------
    def rpc_pubsub_msg(self, peer, channel: str, message):
        """Topic-bus push (round 17): resource deltas/snapshots feed the
        local mirror; avoid/drain snapshots update the avoid view. Both
        are at-most-once pushes — the periodic reconcile snapshot is
        what guarantees convergence (see core/pubsub.py)."""
        from ray_tpu.core import pubsub as _ps

        if channel == _ps.RESOURCES_CHANNEL:
            self.resource_mirror.ingest(message)
            self._note_self_avoid()
        elif channel == _ps.AVOID_CHANNEL:
            if isinstance(message, dict) and message.get("snapshot"):
                self._avoid_view = {
                    "avoid": message.get("avoid", {}),
                    "draining": message.get("draining", []),
                }
                self._note_self_avoid()

    def _note_self_avoid(self):
        """Log transitions of THIS node's avoid/drain standing (pushed,
        not polled — the operator sees quarantine land in the agent log
        within one broadcast interval)."""
        me = self.node_id.hex()
        view = self.resource_mirror.nodes.get(me) or {}
        avoided = bool(
            view.get("avoid")
            or view.get("draining")
            or me in self._avoid_view.get("avoid", {})
            or me in self._avoid_view.get("draining", [])
        )
        if avoided != self._self_avoided:
            self._self_avoided = avoided
            if avoided:
                logger.warning(
                    "this node is now avoided/draining (pushed via topic "
                    "bus) — existing leases keep running; no new placements"
                )
            else:
                logger.warning("this node's avoid/drain standing cleared")

    def rpc_resource_view(self, peer):
        """The agent's push-fed mirror, for tests and `ray-tpu` debug
        tooling (equivalence vs. the controller's authoritative view)."""
        return {
            "nodes": self.resource_mirror.nodes,
            "applied": self.resource_mirror.applied,
            "stale": self.resource_mirror.stale,
            "reconciles": self.resource_mirror.reconciles,
            "avoid_view": self._avoid_view,
        }

    def rpc_start_workers(self, peer, n: int, container_image: str = None,
                          preset_env_hash: str = ""):
        extra = {"RAY_TPU_PRESET_ENV_HASH": preset_env_hash} if preset_env_hash else None
        for _ in range(n):
            spawn_worker(self.session_dir, self.controller_addr, self.node_id,
                         self.store.shm_dir, extra_env=extra,
                         container_image=container_image)
        # Ship SPAWNED promptly: the worker's REGISTERED hits the
        # controller directly, and the spawn half must arrive first for
        # the startup dwell to pair (the telemetry loop is the backstop).
        asyncio.ensure_future(self._flush_lifecycle_events())

    async def _flush_lifecycle_events(self):
        peer = self._controller_peer
        if peer is None or peer.closed:
            return  # not connected yet: leave events queued for the backstop
        batch = []
        while _lifecycle_events:
            batch.append(_lifecycle_events.popleft())
        if not batch:
            return
        try:
            await peer.notify("task_events", batch)
        except Exception as e:  # noqa: BLE001 — transient controller hiccup
            # Re-queue for the telemetry backstop if there's room (the
            # deque is bounded; a full queue drops this batch rather than
            # displacing newer spawn events).
            if (_lifecycle_events.maxlen or 0) - len(_lifecycle_events) >= len(batch):
                _lifecycle_events.extendleft(reversed(batch))
            logger.debug("lifecycle event ship failed: %s", e)

    def rpc_delete_object(self, peer, oid: ObjectID):
        self._chunk_reader.invalidate(oid)
        self.store.delete(oid)

    def rpc_adopt_object(self, peer, oid: ObjectID, size: int):
        self.store.adopt(oid, size)

    def rpc_ensure_local(self, peer, oid: ObjectID) -> bool:
        return self.store.ensure_local(oid)

    # -- object data plane (reference: object_manager.cc Push/Pull) -----
    async def rpc_fetch_chunk(self, peer, oid: ObjectID, offset: int, length: int):
        delay = getattr(self, "_config", {}).get("chaos_fetch_delay_ms", 0)
        if delay:
            await asyncio.sleep(delay / 1000.0)  # fault injection (tests)
        # Raw: the chunk crosses as an out-of-band frame (no pickle copy)
        ip = self._inflight_pulls.get(oid)
        if ip is not None:
            # mid-broadcast hop: serve from the in-progress buffer once
            # the contiguous watermark covers the range
            await ip.wait_for(offset + length)
            ip = self._inflight_pulls.get(oid)
            if ip is not None and ip.view is not None:
                return rpc.Raw(ip.read(offset, length))
        return rpc.Raw(self._chunk_reader.read(oid, offset, length))

    async def rpc_pull_object(self, peer, oid: ObjectID, size: int, src_addr: str) -> bool:
        """Pull a remote object into this node's store, chunked over the
        network (reference: PullManager → ObjectBufferPool chunk
        reassembly). ``src_addr`` is another agent's listener, or
        "controller" for head-node objects (fetched over the existing
        controller connection)."""
        from ray_tpu.core.object_transfer import pull_into_store

        src_peer = await self._peer_for(src_addr)
        return await pull_into_store(self.store, oid, size, src_peer, self._chunk_bytes)

    async def _peer_for(self, addr: str) -> rpc.Peer:
        if addr == "controller":
            return self._controller_peer
        p = await self._fetch_peers.get(addr)
        if p is None:
            raise ConnectionError(f"cannot reach source agent at {addr}")
        return p

    async def rpc_pull_chain(self, peer, oid: ObjectID, size: int, src_addr: str,
                             next_addrs: list) -> bool:
        """One hop of a 1→N broadcast chain (reference: push_manager.h —
        the reference rate-limits a fan-out push; a pipelined CHAIN moves
        1 GiB to N nodes in ~1 transfer time because every link runs at
        full bandwidth concurrently, each hop forwarding chunks as its
        contiguous watermark grows). Kicks the downstream hop FIRST so it
        pulls from this node's in-progress buffer, then pulls from
        upstream; resolves when this hop AND everything downstream hold
        the object."""
        from ray_tpu.core.object_transfer import InflightPull, fetch_into, pull_into_store

        already = self.store.contains(oid) and self.store.ensure_local(oid)
        # Register the inflight entry BEFORE the downstream hop is kicked:
        # the downstream's first fetch_chunk can arrive before our own
        # upstream pull has created the buffer, and must park on the
        # watermark instead of hitting a store miss in ChunkReader.
        entry = None
        if next_addrs and not already:
            entry = InflightPull(None, size)
            self._inflight_pulls[oid] = entry
        down_fut = None
        ok = True
        try:
            if next_addrs:
                nxt = await self._fetch_peers.get(next_addrs[0])
                if nxt is None:
                    raise ConnectionError(f"cannot reach next hop {next_addrs[0]}")
                down_fut = asyncio.ensure_future(
                    nxt.call("pull_chain", oid, size, self._listen_addr, next_addrs[1:])
                )
            if already:
                pass  # already local: just relay
            else:
                src_peer = await self._peer_for(src_addr)
                try:
                    buf = self.store.create(oid, size)
                except FileExistsError:
                    # concurrent regular pull owns the slot — wait for it
                    ok = await pull_into_store(
                        self.store, oid, size, src_peer, self._chunk_bytes
                    )
                    buf = None
                    # unpark downstream readers: the object is now stored
                    # (or the pull failed) — they re-check the store.
                    # Always settle OUR entry (a concurrent chain for the
                    # same oid may have overwritten the dict slot; its
                    # readers are parked on a different entry), and pop
                    # the slot only if it is still ours.
                    if entry is not None:
                        if self._inflight_pulls.get(oid) is entry:
                            self._inflight_pulls.pop(oid, None)
                        if ok:
                            entry.advance(size)
                        else:
                            entry.fail()
                        entry = None
                if buf is not None:
                    view = buf.view()
                    if entry is None:
                        entry = InflightPull(view, size)
                        if oid not in self._inflight_pulls:
                            self._inflight_pulls[oid] = entry
                    else:
                        entry.view = view
                    err = await fetch_into(
                        src_peer, oid, size, view, self._chunk_bytes,
                        progress=entry.advance,
                    )
                    # No awaits between here and seal/cleanup: readers on
                    # this loop can't observe the intermediate states.
                    entry.view = None
                    del view
                    buf.close()
                    if self._inflight_pulls.get(oid) is entry:
                        self._inflight_pulls.pop(oid, None)
                    if err is not None:
                        entry.fail()
                        self.store.delete(oid)
                        raise err
                    self.store.seal(oid)
                    entry.advance(size)
                if ok:
                    # register the new replica so the controller's object
                    # directory (and broadcast completion) sees it
                    await self._controller_peer.notify(
                        "object_sealed", oid, size, self.node_id
                    )
        except Exception:
            if entry is not None:
                if self._inflight_pulls.get(oid) is entry:
                    self._inflight_pulls.pop(oid, None)
                entry.fail()
            if down_fut is not None:
                down_fut.cancel()
            raise
        if down_fut is not None:
            ok_down = await down_fut
            return bool(ok) and bool(ok_down)
        return bool(ok)

    # -- direct-lease worker pool (reference: WorkerPool::PopWorker) ----
    def rpc_worker_attach(self, peer, worker_id_hex: str, listen_addr: str):
        """A direct-pool worker this agent spawned announces itself."""
        self._direct_starting = max(0, self._direct_starting - 1)
        if self._direct_spawns:
            self._direct_spawns.pop(0)  # count-based pairing with spawns
        w = _DirectWorker(worker_id_hex, listen_addr, peer)
        self._direct[worker_id_hex] = w
        peer.meta["direct_wid"] = worker_id_hex
        self._hand_to_waiter(w)

    def _hand_to_waiter(self, w: _DirectWorker) -> bool:
        for i, (ehash, _lid, fut) in enumerate(self._direct_waiters):
            if not fut.done() and w.env_hash in ("", ehash):
                del self._direct_waiters[i]
                w.busy = True
                w.env_hash = ehash or w.env_hash
                fut.set_result(w)
                return True
        return False

    def _pop_free(self, ehash: str):
        fallback = None
        for w in self._direct.values():
            if w.busy:
                continue
            if w.env_hash == ehash:
                return w
            if w.env_hash == "" and fallback is None:
                fallback = w
        return fallback

    def rpc_claim_direct_worker(self, peer, ehash: str):
        """Controller claims a free pooled worker for ACTOR CREATION
        (reference: PopWorker serves actors too, worker_pool.h:363-374).
        Non-blocking: None when the pool has nothing compatible — the
        controller falls back to its spawn path."""
        w = self._pop_free(ehash)
        if w is None:
            return None
        w.busy = True
        w.env_hash = ehash or w.env_hash
        return w.wid

    def rpc_release_direct_worker(self, peer, wid: str):
        """Undo an actor claim that never dispatched (scheduling race)."""
        w = self._direct.get(wid)
        if w is not None:
            w.busy = False
            self._hand_to_waiter(w)

    async def rpc_lease_worker(self, peer, lease_id: bytes, ehash: str):
        """Hand out (or spawn) a worker for a controller-granted lease.
        The controller reserved the lease's resources; this side only
        manages processes (reference: LocalTaskManager dispatch popping
        from the WorkerPool, local_task_manager.cc:122)."""
        lid = bytes(lease_id)
        self._granting.add(lid)
        try:
            w = self._pop_free(ehash)
            if w is None:
                if len(self._direct) + self._direct_starting < self._max_direct:
                    self._spawn_direct()
                else:
                    self._retire_mismatched(ehash)
                fut = asyncio.get_running_loop().create_future()
                self._direct_waiters.append((ehash, lid, fut))
                w = await fut
            else:
                w.busy = True
                w.env_hash = ehash or w.env_hash
            # The await races lease_release: the caller's 30s lease RPC may
            # have timed out (controller relayed the release before any
            # binding existed). Binding the worker to the dead lease would
            # strand it busy forever — pool it instead.
            if lid in self._released_leases:
                w.busy = False
                self._hand_to_waiter(w)
                raise ConnectionError(
                    f"lease {lid!r} released while waiting for a worker"
                )
            # lease→worker binding lets the CONTROLLER free this worker when
            # the lease-holder dies without ever sending lease_return (its
            # disconnect cleanup relays rpc_lease_release here)
            self._lease_workers[lid] = w.wid
            return {"worker_addr": w.addr, "worker_id": w.wid}
        finally:
            self._granting.discard(lid)
            self._released_leases.discard(lid)

    def rpc_lease_worker_batch(self, peer, lease_ids: list, ehash: str):
        """Hand out workers for a BATCH of controller-granted leases in
        one round-trip (round 17). Strictly non-blocking: no await
        between pop and bind, so the lease-release race rpc_lease_worker
        parks against cannot happen here. Misses return None in place —
        the caller falls back to the parking single-worker path for
        those — and each miss triggers one spawn/retire so pool capacity
        catches up with the window."""
        out = []
        misses = 0
        for lease_id in lease_ids:
            lid = bytes(lease_id)
            if lid in self._released_leases:
                self._released_leases.discard(lid)
                out.append(None)
                continue
            w = self._pop_free(ehash)
            if w is None:
                out.append(None)
                misses += 1
                continue
            w.busy = True
            w.env_hash = ehash or w.env_hash
            self._lease_workers[lid] = w.wid
            out.append({"worker_addr": w.addr, "worker_id": w.wid})
        for _ in range(misses):
            if len(self._direct) + self._direct_starting < self._max_direct:
                self._spawn_direct()
            else:
                self._retire_mismatched(ehash)
        return out

    def _spawn_direct(self):
        self._direct_starting += 1
        proc = spawn_worker(
            self.session_dir, self.controller_addr, self.node_id,
            self.store.shm_dir,
            extra_env={
                "RAY_TPU_WORKER_POOL": "direct",
                "RAY_TPU_AGENT_ADDR": self._listen_addr,
            },
        )
        self._direct_spawns.append(proc)
        asyncio.ensure_future(self._flush_lifecycle_events())

    def _reap_direct_spawns(self):
        """A direct worker that died BEFORE attaching (import error, OOM)
        must not inflate _direct_starting forever — that would wedge the
        pool at a phantom cap with every waiter parked. Count-based: the
        spawn list length mirrors _direct_starting; attach pops one."""
        dead = [p for p in self._direct_spawns if p.poll() is not None]
        for p in dead:
            self._direct_spawns.remove(p)
            self._direct_starting = max(0, self._direct_starting - 1)
        if dead and self._direct_waiters:
            # retry the spawn the dead process was supposed to satisfy
            if len(self._direct) + self._direct_starting < self._max_direct:
                self._spawn_direct()

    def _retire_mismatched(self, ehash: str):
        """Pool at cap with no usable free worker: retire one free worker
        locked to a different env so a pristine replacement can spawn."""
        for wid, w in list(self._direct.items()):
            if not w.busy and w.env_hash and w.env_hash != ehash:
                self._direct.pop(wid, None)
                if w.peer is not None and not w.peer.closed:
                    asyncio.ensure_future(w.peer.notify("exit"))
                self._spawn_direct()
                return

    def rpc_lease_return(self, peer, worker_id_hex: str, lease_id: bytes = None):
        if lease_id is not None:
            self._lease_workers.pop(bytes(lease_id), None)
        w = self._direct.get(worker_id_hex)
        if w is None:
            return
        w.busy = False
        self._hand_to_waiter(w)

    def rpc_lease_release(self, peer, lease_id: bytes, kill_worker: bool = False):
        """Controller relay on lease-holder death: reclaim the bound
        worker (idempotent vs. a caller's own lease_return, which pops
        the binding first). With ``kill_worker`` the worker may be
        mid-task on an orphaned push — exit it rather than pooling a
        busy worker."""
        lid = bytes(lease_id)
        wid = self._lease_workers.pop(lid, None)
        if wid is None:
            # The caller may still be parked in rpc_lease_worker (its
            # lease RPC timed out): fail the waiter so a later worker
            # never binds to the dead lease, or — if the hand-off already
            # happened but the binding hasn't been written — flag the
            # lease so the grant path pools the worker instead.
            for i, (_ehash, wlid, fut) in enumerate(self._direct_waiters):
                if wlid == lid:
                    del self._direct_waiters[i]
                    if not fut.done():
                        fut.set_exception(
                            ConnectionError("lease released while parked")
                        )
                    return
            if lid in self._granting:
                self._released_leases.add(lid)
            return
        w = self._direct.get(wid)
        if w is None:
            return
        if kill_worker:
            self._direct.pop(wid, None)
            if w.peer is not None and not w.peer.closed:
                asyncio.ensure_future(w.peer.notify("exit"))
            # parked lease_worker callers must not hang on the shrunken
            # pool — pair the pop with a replacement spawn (same contract
            # as _retire_mismatched)
            if self._direct_waiters and (
                len(self._direct) + self._direct_starting < self._max_direct
            ):
                self._spawn_direct()
            return
        w.busy = False
        self._hand_to_waiter(w)

    def rpc_exit(self, peer):
        self._exit.set()

    def rpc_ping(self, peer):
        return "pong"

    def rpc_stack_dump(self, peer):
        from ray_tpu.utils.stack_dump import dump_all_threads

        return dump_all_threads()

    def rpc_dump_stacks(self, peer):
        from ray_tpu.util import profiling

        return profiling.dump_stacks()

    def rpc_profile_cpu(self, peer, duration_s: float = 10.0, hz: float = 100.0):
        from ray_tpu.util import profiling

        return profiling.sample_async(duration_s, hz)

    def rpc_spill_store(self, peer, fraction: float = 0.6):
        """Health-plane pressure actuator: proactively spill this node's
        store down to ``fraction`` of capacity (both tiers). Runs off-loop
        — a large arena drain copies bytes and must not stall heartbeats."""
        return asyncio.to_thread(self.store.spill_to_fraction, fraction)

    def rpc_dump_memory(self, peer, limit: int = 1000):
        """This node's store leg of the memory census fan-out: live
        store stats (occupancy, spill-dir bytes, pins, deferred deletes)
        plus per-object rows for tier attribution."""
        return {
            "kind": "store",
            "node_id": self.node_id.hex(),
            "store": self.store.stats(),
            "objects": self.store.object_rows(limit),
        }

    # -- log plane fan-out legs (core/log_plane.py; reference: the
    # dashboard agent's per-node logs grpc service) ---------------------
    def _log_dir(self) -> str:
        return os.path.join(self.session_dir, "logs")

    # File I/O runs off-loop (to_thread): a grep over sidecars near the
    # 64 MB rotation cap must not stall the agent's control channel —
    # heartbeats, worker RPCs, and spawns share this event loop.
    async def rpc_list_logs(self, peer):
        from ray_tpu.core import log_plane

        files = await asyncio.to_thread(log_plane.list_local, self._log_dir())
        return {"node_id": self.node_id.hex(), "files": files}

    async def rpc_get_log(self, peer, filename: str, tail: int = 1000):
        from ray_tpu.core import log_plane

        return await asyncio.to_thread(
            log_plane.read_local, self._log_dir(), filename, tail
        )

    async def rpc_search_logs(self, peer, **filters):
        from ray_tpu.core import log_plane

        return await asyncio.to_thread(
            log_plane.search_local, self._log_dir(), **filters
        )

    def rpc_install_fault_plan(self, peer, plan_json: str):
        """Install (or clear, empty string) a deterministic fault plan in
        THIS agent process at runtime — the slow-node throttle lever
        (`chaos.install_plan_on_node` via the controller fan-out)."""
        from ray_tpu.util import chaos

        chaos.install_fault_plan(plan_json or None)
        return True

    def on_disconnect(self, peer):
        wid = peer.meta.get("direct_wid")
        if wid is not None:
            self._direct.pop(wid, None)  # direct-pool worker died
            return
        # Only the controller connection is load-bearing; fetch peers
        # (other agents pulling from us) come and go.
        if peer is self._controller_peer or self._controller_peer is None:
            window = float(
                getattr(self, "_config", {}).get("controller_reconnect_window_s", 0.0)
            )
            if window <= 0:
                self._exit.set()
            else:
                asyncio.ensure_future(self._reconnect_controller(window))

    async def _reconnect_controller(self, window: float):
        """Bounded jittered-backoff reconnect + re-register after the
        controller connection dropped (rides through a controller
        restart; a controller that is truly gone still exits this agent,
        one window later). Workers this agent spawned reconnect on their
        own — their records re-form controller-side as they re-register."""
        import random as _random

        host, port = self.controller_addr.rsplit(":", 1)
        # monotonic: a wall-clock step (NTP) must not stretch or collapse
        # the reconnect window
        deadline = time.monotonic() + window
        wait = 0.1
        while time.monotonic() < deadline and not self._exit.is_set():
            try:
                peer = await rpc.connect(host, int(port), self, retries=1)
                await self._register(peer)
                self._controller_peer = peer
                logger.warning("reconnected to controller at %s", self.controller_addr)
                return
            except Exception as e:  # noqa: BLE001 — retry within the window
                if "re-registration refused" in str(e):
                    # Permanent: the live controller declared this node
                    # dead while we were away — burning the rest of the
                    # window on identical refusals helps nobody.
                    logger.error("controller refused re-registration: %s", e)
                    break
                logger.debug("controller reconnect attempt failed: %s", e)
                await asyncio.sleep(min(wait * (0.5 + _random.random()),
                                        max(0.0, deadline - time.monotonic())))
                wait = min(wait * 1.7, 2.0)
        logger.error("controller gone for %.0fs — agent exiting", window)
        self._exit.set()

    async def _register(self, peer: rpc.Peer):
        """Register (or RE-register after a controller restart) this node
        on ``peer`` and absorb the returned cluster config."""
        import socket

        from ray_tpu.utils.net import host_ip

        chunk_fallback = self._chunk_bytes
        labels = {}
        raw_labels = os.environ.get("RAY_TPU_NODE_LABELS", "")
        if raw_labels:
            try:
                parsed = json.loads(raw_labels)
            except ValueError:
                parsed = None
            # must be a str→str dict: dict(['ab']) would silently fabricate
            # phantom labels and non-dict JSON would fail registration
            if isinstance(parsed, dict) and all(
                isinstance(k, str) and isinstance(v, str)
                for k, v in parsed.items()
            ):
                labels = parsed
            else:
                logger.warning(
                    "RAY_TPU_NODE_LABELS must be a JSON object of string "
                    "values, got %r — ignoring", raw_labels,
                )
        info = await peer.call(
            "register_node", self.node_id, self.resources, self.store.shm_dir,
            hostname=socket.gethostname(), pid=os.getpid(),
            fetch_addr=self._listen_addr,
            provider_instance_id=os.environ.get("RAY_TPU_PROVIDER_INSTANCE_ID", ""),
            labels=labels,
        )
        cfg = (info or {}).get("config") or {}
        self._chunk_bytes = int(cfg.get("object_transfer_chunk_bytes", chunk_fallback))
        self._config = cfg
        # Join the push-fed resource/avoid channels (round 17). Runs on
        # every (re-)register, so a controller restart re-subscribes and
        # the first snapshot re-seeds the mirror. Best-effort: an old
        # controller without the bus just leaves the mirror empty.
        try:
            from ray_tpu.core import pubsub as _ps

            await peer.call("subscribe", _ps.RESOURCES_CHANNEL)
            await peer.call("subscribe", _ps.AVOID_CHANNEL)
        except Exception as e:  # noqa: BLE001 — mirror is observability
            logger.debug("resource pubsub subscribe failed: %s", e)

    async def run(self):
        from ray_tpu.utils.net import bind_host, host_ip

        host, port = self.controller_addr.rsplit(":", 1)
        # Listener for sibling agents pulling object chunks (reference:
        # the ObjectManagerService gRPC server every node runs).
        # Loopback unless RAY_TPU_NODE_IP opts this host into multi-host.
        _server, fetch_port = await rpc.serve(self, bind_host(), 0)
        self._listen_addr = f"{host_ip()}:{fetch_port}"
        peer = await rpc.connect(host, int(port), self)
        await self._register(peer)
        self._controller_peer = peer
        cfg = self._config
        from ray_tpu.util import profiling

        profiling.ensure_continuous(
            hz=float(cfg.get("profiling_continuous_hz", 0.0)),
            ring_s=float(cfg.get("profiling_ring_s", 60.0)),
        )
        if cfg.get("log_structured", True):
            # Agent leg of the log plane: its own logging records become
            # a structured sidecar (handler-only — the agent's streams
            # are the session's agent-*.log already); ERROR records ship
            # with the telemetry heartbeat.
            from ray_tpu.core import log_plane

            log_plane.install(
                self.session_dir,
                node_id=self.node_id.hex(),
                proc=f"agent-{self.node_id.hex()[:8]}",
                capture_streams=False,
                rotate_bytes=int(cfg.get("log_rotate_bytes", 64 << 20)),
            )
        monitor_task = asyncio.get_running_loop().create_task(
            self._memory_monitor_loop()
        )
        telemetry_task = asyncio.get_running_loop().create_task(
            self._telemetry_loop()
        )
        try:
            while not self._exit.is_set():
                reap_children()
                self._reap_direct_spawns()
                try:
                    await asyncio.wait_for(self._exit.wait(), timeout=1.0)
                except asyncio.TimeoutError:
                    pass
        finally:
            monitor_task.cancel()
            telemetry_task.cancel()
            kill_children()
            self._chunk_reader.close()
            self.store.destroy()

    async def _telemetry_loop(self):
        """Periodic node telemetry heartbeat: host CPU/mem (cgroup-aware),
        object-store occupancy, and worker counts, shipped to the
        controller (reference: the raylet's ReportResourceUsage heartbeat
        + the dashboard reporter agent's host stats). Also drains this
        process's metric registry — the agent has no CoreWorker, so the
        normal metrics flusher can't reach the controller for it (the
        object-transfer histograms recorded here ride this loop)."""
        interval_ms = int(self._config.get("node_telemetry_interval_ms", 2000))
        if interval_ms <= 0:
            return
        from ray_tpu.core import node_telemetry
        from ray_tpu.core.memory_monitor import HostCpuSampler
        from ray_tpu.util import metrics as _metrics

        cpu = HostCpuSampler()
        cpu.sample()  # prime the delta
        while not self._exit.is_set():
            await asyncio.sleep(interval_ms / 1000.0)
            await self._flush_lifecycle_events()
            sample = node_telemetry.build_node_sample(cpu, self.store)
            sample["num_direct_workers"] = len(self._direct)
            sample["num_children"] = len(_children)
            sample["resource_mirror"] = {
                "nodes": len(self.resource_mirror.nodes),
                "applied": self.resource_mirror.applied,
                "stale": self.resource_mirror.stale,
                "reconciles": self.resource_mirror.reconciles,
            }
            records = _metrics.drain_records()
            from ray_tpu.core import log_plane as _lp

            errors = _lp.drain_ship()
            try:
                await self._controller_peer.notify(
                    "node_telemetry", self.node_id, sample
                )
                if records:
                    await self._controller_peer.notify("metrics_report", records)
                if errors:
                    await self._controller_peer.notify("log_errors", errors)
            except Exception as e:  # noqa: BLE001 — transient controller hiccup
                if self._exit.is_set():
                    return
                _metrics.requeue_records(records)
                _lp.requeue_ship(errors)
                if self._controller_peer.closed:
                    # reconnect in progress (on_disconnect) — keep ticking
                    # so heartbeats resume on the fresh peer; _exit ends
                    # us if the reconnect window runs out.
                    continue
                logger.warning("telemetry report failed: %s", e)

    async def _memory_monitor_loop(self):
        """Per-node OOM monitoring (reference: every raylet runs its own
        MemoryMonitor). Multi-host only — on single-host simulations all
        'nodes' see the same host memory and the head's monitor covers
        it; per-agent monitors there would mass-fire on one host spike."""
        from ray_tpu.utils.net import multihost_enabled

        if not multihost_enabled():
            return
        refresh_ms = int(self._config.get("memory_monitor_refresh_ms", 250))
        if refresh_ms <= 0:
            return
        from ray_tpu.core.memory_monitor import MemoryMonitor

        monitor = MemoryMonitor(
            threshold=float(self._config.get("memory_usage_threshold", 0.95))
        )
        while not self._exit.is_set():
            await asyncio.sleep(refresh_ms / 1000.0)
            if not monitor.should_kill():
                continue
            try:
                # victim choice needs task/actor context → the controller
                pid = await self._controller_peer.call(
                    "node_over_memory", self.node_id
                )
            except Exception as e:  # noqa: BLE001
                if self._controller_peer.closed or self._exit.is_set():
                    return  # controller gone; agent is exiting anyway
                # transient/remote error: OOM protection must SURVIVE it
                logger.warning("node_over_memory report failed: %s", e)
                continue
            if pid:
                try:
                    os.kill(pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass


def main():
    from ray_tpu.util import chaos, lockwatch

    lockwatch.maybe_install()  # RAY_TPU_LOCKWATCH=1: watch locks created from here on
    chaos.install_fault_plan_from_env()  # RAY_TPU_FAULT_PLAN: deterministic chaos
    parser = argparse.ArgumentParser()
    parser.add_argument("--controller", required=True)
    parser.add_argument("--session-dir", required=True)
    parser.add_argument("--resources", default="{}")
    parser.add_argument("--store-capacity", type=int, default=1 << 30)
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO, format="[node_agent] %(levelname)s %(message)s")
    agent = NodeAgent(args.controller, args.session_dir, json.loads(args.resources), args.store_capacity)

    loop = asyncio.new_event_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        loop.add_signal_handler(sig, agent._exit.set)
    try:
        loop.run_until_complete(agent.run())
    finally:
        loop.close()


if __name__ == "__main__":
    main()
