"""CoreWorker: the in-process runtime embedded by drivers and workers.

Reference: src/ray/core_worker/core_worker.h:295 (SubmitTask / CreateActor /
SubmitActorTask / Get / Put / Wait) and its Cython surface
python/ray/_raylet.pyx:3282. Blocking public methods bridge onto the
process's asyncio loop; object payloads are read zero-copy out of the node's
shared-memory store.
"""
from __future__ import annotations

import itertools
import os
import threading
from concurrent.futures import Future
from typing import Any, List, Optional, Sequence

from concurrent.futures import TimeoutError as _CfTimeout

from ray_tpu.core.object_ref import ObjectRef, _RefMarker, _capture, set_ref_tracker
from ray_tpu.core.object_store import PlasmaClient
from ray_tpu.core.task_spec import SchedulingStrategy, TaskSpec, TaskType
from ray_tpu.exceptions import GetTimeoutError, ObjectLostError
from ray_tpu.utils import rpc
from ray_tpu.utils.ids import NodeID, ObjectID, TaskID, WorkerID
from ray_tpu.utils.serialization import deserialize, serialize

INLINE_LIMIT_FALLBACK = 100 * 1024

# Control-plane methods that block by DESIGN (waiting for objects,
# streams, placement, drains — their duration is the workload's, not the
# control plane's). Everything else gets the bounded default timeout
# (``control_call_timeout_s``) when the caller passes none, so a wedged
# or partitioned controller surfaces as an error instead of a hang.
_UNBOUNDED_METHODS = frozenset(
    {
        "object_get",
        "object_wait",
        "object_pull",
        "object_ensure_local",
        "object_broadcast",
        "stream_next",
        "stream_take",
        "pg_wait_ready",
        "wait_actor_ready",
        "drain_node",
        "task_done",  # carries result upload; sized by payload, not control
    }
)


class RefTracker:
    """Per-process local ref table (reference: ReferenceCounter's local
    refs, src/ray/core_worker/reference_count.h:142). Zero-crossings are
    collected and batch-flushed; ids touched-and-dropped within one flush
    window still flush as drops so the controller learns the object was
    once held (transient refs must not leak).

    Also carries the memory census's creation-site attribution: puts and
    task submissions :meth:`attribute` their refs with the interned user
    call-site (reference: reference_count.cc keeps a per-ref call_site
    string for ``ray memory``); sites drop with their last ref."""

    def __init__(self):
        import collections

        self._lock = threading.Lock()
        self._counts: dict[bytes, int] = {}
        self._touched: set[bytes] = set()
        # oid key -> interned creation call-site (memory_census); absent
        # for borrowed/deserialized refs.
        self._sites: dict[bytes, str] = {}
        # dec() is called from ObjectRef.__del__, which the cyclic GC may
        # run on ANY thread — including one currently inside inc()/drain()
        # holding the (non-reentrant) lock. dec therefore never locks: it
        # appends to a thread-safe deque that drain/inc fold in later.
        self._pending_decs = collections.deque()

    def inc(self, oid):
        key = oid.binary()
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + 1
            self._touched.add(key)

    def dec(self, oid):
        self._pending_decs.append(oid.binary())  # lock-free (see __init__)

    def attribute(self, key: bytes, site: str):
        """Record the creation call-site for a ref this process created
        (no-op for empty sites — census disabled)."""
        if not site:
            return
        with self._lock:
            if key not in self._sites:
                self._sites[key] = site

    def site_of(self, key: bytes) -> str:
        return self._sites.get(key, "")

    def census_snapshot(self) -> "tuple[dict[bytes, int], dict[bytes, str]]":
        """(open counts, sites) copies for the memory census dump —
        pending decs folded first so the snapshot reflects GC'd refs."""
        with self._lock:
            self._fold_decs_locked()
            return dict(self._counts), dict(self._sites)

    def _fold_decs_locked(self):
        while True:
            try:
                key = self._pending_decs.popleft()
            except IndexError:
                return
            n = self._counts.get(key, 0) - 1
            if n <= 0:
                self._counts.pop(key, None)
                self._sites.pop(key, None)
            else:
                self._counts[key] = n
            self._touched.add(key)

    def pending_drops(self) -> int:
        """Decs queued by ObjectRef.__del__ but not yet folded into the
        flush — the health plane's gc_nudge reports this as evidence a
        forced collection actually freed refs."""
        return len(self._pending_decs)

    def drain(self) -> tuple[list[bytes], list[bytes]]:
        """(held, dropped) among ids touched since the last drain."""
        with self._lock:
            self._fold_decs_locked()
            touched, self._touched = self._touched, set()
            held = [k for k in touched if self._counts.get(k, 0) > 0]
            dropped = [k for k in touched if self._counts.get(k, 0) <= 0]
        return held, dropped

def _serialize_parts_capturing(value: Any):
    """serialize_parts() + captured nested refs — the zero-extra-copy path
    for large puts/returns (nested refs → containment pins)."""
    from ray_tpu.utils.serialization import serialize_parts

    token = _capture.set([])
    try:
        meta, raws, total = serialize_parts(value)
        contained = _capture.get()  # ray-tpu: lint-ignore[RTL008] — ContextVar.get(), not a queue: returns immediately
    finally:
        _capture.reset(token)
    if contained:
        # serialize_parts may pickle twice (fast-path fallback) — dedupe
        # the captured refs so pins aren't double-counted
        seen, out = set(), []
        for c in contained:
            k = c.binary() if hasattr(c, "binary") else bytes(c)
            if k not in seen:
                seen.add(k)
                out.append(c)
        contained = out
    return meta, raws, total, contained


def _serialize_capturing(value: Any) -> tuple[bytes, list]:
    """Contiguous-blob variant of :func:`_serialize_parts_capturing`."""
    from ray_tpu.utils.serialization import assemble_parts

    meta, raws, _, contained = _serialize_parts_capturing(value)
    return assemble_parts(meta, raws), contained


class CoreWorker:
    """One per process. ``mode`` is "driver" or "worker"."""

    def __init__(
        self,
        address: str,
        mode: str,
        loop_runner: rpc.EventLoopThread,
        handler: Any = None,
        worker_id: Optional[WorkerID] = None,
        node_id: Optional[NodeID] = None,
        local_shm_dir: Optional[str] = None,
        listen_addr: str = "",
    ):
        from ray_tpu.core.memory_store import LocalMemoryStore

        self.mode = mode
        self.address = address
        self.loop_runner = loop_runner
        self.worker_id = worker_id or WorkerID.from_random()
        self.node_id = node_id
        self._put_counter = itertools.count()
        self._task_counter = itertools.count()
        # What ships this process's streamed items (``worker_main.
        # _StreamShipper``, with its counts): made by the first stream.
        self.stream_shipper = None
        self._lock = threading.Lock()
        self._handler = handler or _NullHandler()
        self._listen_addr = listen_addr
        self._reconnect_lock = threading.Lock()
        self._reconnect_cbs: list = []  # called with the fresh peer
        # Once a full reconnect window fails (controller truly gone) or
        # this process initiated the disconnect, later ConnectionLost
        # errors fail fast instead of burning another window each.
        self._reconnect_dead = False
        self._control_timeout: Optional[float] = 300.0  # pre-config fallback
        host, port = address.rsplit(":", 1)
        self.peer: rpc.Peer = loop_runner.run(rpc.connect(host, int(port), self._handler))
        self.peer.label = "controller"
        if mode == "driver":
            info = self._call("register_driver")
            self.node_id = NodeID.from_hex(info["head_node_id"])
            self.local_shm_dir = info["shm_dir"]
        else:
            info = self._call(
                "register_worker", self.worker_id, node_id, os.getpid(),
                listen_addr=listen_addr,
                pool=os.environ.get("RAY_TPU_WORKER_POOL", ""),
                # Spawn-time env identity (container images): the worker
                # was born into this env hash (runtime_env/container.py).
                env_hash=os.environ.get("RAY_TPU_PRESET_ENV_HASH", ""),
            )
            self.local_shm_dir = local_shm_dir
        self.session_dir = info["session_dir"]
        self.config = info["config"]
        self.inline_limit = self.config.get("max_inline_object_size", INLINE_LIMIT_FALLBACK)
        self._control_timeout = (
            float(self.config.get("control_call_timeout_s", 300.0)) or None
        )
        self.plasma = PlasmaClient(self.local_shm_dir)
        self._plasma_clients: dict[str, PlasmaClient] = {}
        # Owner-local memory store + direct actor transport (reference:
        # memory_store.cc; actor_task_submitter.h caller→actor push).
        self.memory_store = LocalMemoryStore()
        self.direct_enabled = bool(self.config.get("direct_actor_calls", True))
        self.direct_normal_enabled = bool(self.config.get("direct_normal_tasks", True))
        self._submitters: dict = {}  # ActorID -> ActorSubmitter
        self._direct_tasks: dict = {}  # TaskID -> ActorSubmitter (cancel routing)
        self._direct_returns: dict = {}  # return ObjectID -> TaskID
        self._normal_sub = None  # lazily-created NormalSubmitter
        # Batched caller-thread → loop handoff for direct submissions.
        self._direct_handoff = rpc.BatchedHandoff(
            self.loop_runner.loop, lambda item: item[0]._enqueue(item[1])
        )
        # Distributed ref counting: local ref table + periodic flush of
        # held/dropped transitions to the controller.
        self.refs = RefTracker()
        self._refs_closed = threading.Event()
        self._ref_flush_task = None
        self._async_errors: list = []
        set_ref_tracker(self.refs)
        # Memory census: call-site attribution at put/submit (the
        # ``memory_census`` config is the envelope A/B knob).
        from ray_tpu.core import memory_census

        memory_census.set_enabled(
            bool(self.config.get("memory_census", True))
        )
        if self.config.get("object_auto_gc", True):
            self._ref_flush_task = self.loop_runner.submit(self._ref_flush_loop())

    async def _ref_flush_loop(self):
        import asyncio

        interval = self.config.get("ref_flush_interval_ms", 200) / 1000.0
        me = self.worker_id.hex()
        while not self._refs_closed.is_set():
            await asyncio.sleep(interval)
            held, dropped = self.refs.drain()
            # Owner-local (never-promoted) objects don't exist in the
            # controller's directory: their GC is a local eviction, and
            # mentioning them to the controller would create leaked empty
            # records (reference: memory-store objects are owner-private).
            ms = self.memory_store
            g_held = [k for k in held if not ms.is_local_only(k)]
            g_dropped = []
            for k in dropped:
                local_only = ms.is_local_only(k)
                ms.evict(k)
                if not local_only:
                    g_dropped.append(k)
            if g_held or g_dropped:
                try:
                    await self.peer.notify("ref_update", me, g_held, g_dropped)
                except Exception:
                    return  # connection gone; controller reaps us on disconnect

    # ------------------------------------------------------------------
    def _call(self, method: str, *args, timeout: Optional[float] = None, **kwargs):
        """Sync controller RPC. Callers that pass no timeout get the
        bounded ``control_call_timeout_s`` default unless the method
        blocks by design (:data:`_UNBOUNDED_METHODS`). A connection loss
        triggers ONE bounded reconnect + re-register attempt (rides
        through a controller restart) before the error surfaces.

        The post-reconnect retry makes control calls AT-LEAST-ONCE: a
        request the controller executed whose response died with the
        connection is re-issued. Controller-restart rides are safe (the
        journal replay is the state), but a transient drop to a LIVE
        controller can duplicate a non-idempotent call — exactly-once
        needs per-request ids + controller-side dedup (roadmap)."""
        if timeout is None and method not in _UNBOUNDED_METHODS:
            timeout = self._control_timeout
        try:
            return self.loop_runner.run(self.peer.call(method, *args, **kwargs), timeout)
        except rpc.ConnectionLost:
            if not self.try_reconnect():
                raise
            return self.loop_runner.run(self.peer.call(method, *args, **kwargs), timeout)

    def on_reconnect(self, cb):
        """Register a callback invoked (from the reconnecting thread)
        with the fresh controller peer after a successful re-register."""
        self._reconnect_cbs.append(cb)

    def try_reconnect(self) -> bool:
        """Bounded reconnect + re-register after controller connection
        loss (jittered backoff within ``controller_reconnect_window_s``).
        Safe from any thread; concurrent callers coalesce on the lock.
        Returns True when ``self.peer`` is live again."""
        import random as _random
        import time as _time

        window = 0.0
        if isinstance(getattr(self, "config", None), dict):
            window = float(self.config.get("controller_reconnect_window_s", 0.0))
        if window <= 0 or self._reconnect_dead:
            return False
        resumed_peer = None
        with self._reconnect_lock:
            if not self.peer.closed:
                return True  # someone else already reconnected
            host, port = self.address.rsplit(":", 1)
            deadline = _time.monotonic() + window
            wait = 0.1
            last: Optional[BaseException] = None
            # Holding _reconnect_lock across the bounded dial/register
            # is the design: concurrent callers MUST coalesce on one
            # reconnect attempt  # ray-tpu: lint-ignore-file[RTL001]
            while _time.monotonic() < deadline:
                try:
                    peer = self.loop_runner.run(
                        rpc.connect(host, int(port), self._handler, retries=1),
                        timeout=10,
                    )
                    peer.label = "controller"
                    if self.mode == "driver":
                        self.loop_runner.run(peer.call("register_driver"), 10)
                    else:
                        self.loop_runner.run(
                            peer.call(
                                "register_worker", self.worker_id, self.node_id,
                                os.getpid(), listen_addr=self._listen_addr,
                                # Never re-advertise into a worker pool and
                                # mark busy: the restarted controller must
                                # not dispatch onto a possibly-mid-actor
                                # process it knows nothing about.
                                pool="",
                                env_hash=os.environ.get("RAY_TPU_PRESET_ENV_HASH", ""),
                                rejoining=True,
                            ),
                            10,
                        )
                    self.peer = peer
                    resumed_peer = peer
                    break
                except Exception as e:  # noqa: BLE001 — retry within window
                    if "re-registration refused" in str(e):
                        # Permanent: the live controller declared this
                        # process dead while it was away — further
                        # attempts get the identical refusal.
                        last = e
                        break
                    _time.sleep(min(wait * (0.5 + _random.random()),
                                    max(0.0, deadline - _time.monotonic())))
                    wait = min(wait * 1.7, 2.0)
                    last = e
            if resumed_peer is None:
                import logging

                logging.getLogger("ray_tpu.client").warning(
                    "controller reconnect failed after %.0fs: %s", window, last
                )
                self._reconnect_dead = True
                return False
        # Resume work (pubsub resubscribe, callbacks) issues RPCs of its
        # own — run it OUTSIDE the lock: a second connection loss here
        # re-enters try_reconnect on this same thread, which would
        # self-deadlock on the non-reentrant lock.
        self._resume_after_reconnect(resumed_peer)
        return True

    def _resume_after_reconnect(self, peer):
        import logging

        logging.getLogger("ray_tpu.client").warning(
            "reconnected to controller at %s (%s)", self.address, self.mode
        )
        # Ref-flush loop exits on connection loss — restart it.
        if self._ref_flush_task is not None and self._ref_flush_task.done():
            self._ref_flush_task = self.loop_runner.submit(self._ref_flush_loop())
        # Re-establish pubsub subscriptions (death watchers, etc.).
        try:
            from ray_tpu.experimental import pubsub

            pubsub._resubscribe(self)
        except Exception as e:  # noqa: BLE001 — subscriptions are best-effort
            logging.getLogger("ray_tpu.client").warning(
                "pubsub resubscribe failed: %s", e
            )
        for cb in list(self._reconnect_cbs):
            try:
                cb(peer)
            except Exception:  # noqa: BLE001 — one bad callback must not block others
                logging.getLogger("ray_tpu.client").exception(
                    "reconnect callback failed"
                )

    def _submit(self, method: str, *args, **kwargs) -> Future:
        return self.loop_runner.submit(self.peer.call(method, *args, **kwargs))

    # ------------------------------------------------------------------
    # Objects
    # ------------------------------------------------------------------
    def put(self, value: Any) -> ObjectRef:
        from ray_tpu.core import memory_census
        from ray_tpu.utils.serialization import assemble_parts

        # Creation-site attribution (reference: reference counting records
        # a call_site per ref for `ray memory`): captured before the
        # serialize so deep value graphs can't push the user frame out of
        # the bounded walk.
        site = memory_census.capture_callsite()
        oid = ObjectID.for_put(self.worker_id, next(self._put_counter))
        meta, raws, total, contained = _serialize_parts_capturing(value)
        if contained:
            self.promote_refs(contained)  # nested refs escape via the put
        if total <= self.inline_limit:
            self._call(
                "object_put_inline", oid, assemble_parts(meta, raws), False,
                contained or [], callsite=site,
            )
        else:
            # Single copy: parts go straight into the shm mapping.
            self.plasma.put_parts(oid, meta, raws, total)
            self._call(
                "object_put_shm", oid, total, self.node_id, False,
                contained or [], callsite=site,
            )
        ref = ObjectRef(oid)
        self.refs.attribute(oid.binary(), site)
        return ref

    def put_serialized(
        self, oid: ObjectID, data: bytes, is_error: bool = False,
        contained: Optional[list] = None, callsite: str = "",
    ):
        if contained:
            self.promote_refs(contained)
        if len(data) <= self.inline_limit:
            self._call(
                "object_put_inline", oid, data, is_error, contained or [],
                callsite=callsite,
            )
        else:
            self.plasma.put_bytes(oid, data)
            self._call(
                "object_put_shm", oid, len(data), self.node_id, is_error,
                contained or [], callsite=callsite,
            )

    def get(self, refs: Sequence[ObjectRef] | ObjectRef, timeout: Optional[float] = None):
        single = isinstance(refs, ObjectRef)
        ref_list: List[ObjectRef] = [refs] if single else list(refs)
        values = self._get_values([r.id for r in ref_list], timeout)
        return values[0] if single else values

    def get_async(self, refs: Sequence[ObjectRef]) -> Future:
        """Future-returning get (used by ObjectRef.future())."""
        fut: Future = Future()

        def _run():
            try:
                fut.set_result(self._get_values([r.id for r in refs]))
            except Exception as e:  # noqa: BLE001
                fut.set_exception(e)

        threading.Thread(target=_run, daemon=True).start()
        return fut

    def _get_values(self, oids: List[ObjectID], timeout: Optional[float] = None) -> List[Any]:
        self._check_async_errors()
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        # Partition: owner-local entries resolve in-process with ZERO
        # controller round-trips (reference: memory_store.cc Get); the
        # rest go through the controller directory.
        local: dict[bytes, Any] = {}
        remote: List[ObjectID] = []
        for oid in oids:
            e = self.memory_store.lookup(oid.binary())
            if e is not None and e.kind == "inline":
                local[oid.binary()] = e
            else:
                remote.append(oid)
        resp_fut = self._submit("object_get", remote, timeout) if remote else None
        local_values: dict[bytes, tuple] = {}
        shm_fallback: List[ObjectID] = []
        for oid in oids:
            e = local.get(oid.binary())
            if e is None:
                continue
            remain = None if deadline is None else max(0.0, deadline - _time.monotonic())
            try:
                payload, is_err = e.value(remain)
            except (TimeoutError, _CfTimeout):  # _CfTimeout: pre-3.11 alias
                if resp_fut is not None:
                    resp_fut.cancel()
                raise GetTimeoutError(f"get() timed out after {timeout}s")
            if e.kind == "shm":
                # resolved to a large result living in the global store
                shm_fallback.append(oid)
            else:
                local_values[oid.binary()] = (payload, is_err)
        metas = {}
        if resp_fut is not None:
            # bounded by the caller's get() deadline: the controller leg
            # resolves this future within the requested timeout (resp
            # carries the timed-out flag); unbounded only when the USER
            # asked get(timeout=None)  # ray-tpu: lint-ignore[RTL008]
            resp = resp_fut.result()
            if resp["timeout"]:
                raise GetTimeoutError(f"get() timed out after {timeout}s")
            metas = resp["metas"]
        if shm_fallback:
            remain = None if deadline is None else max(0.0, deadline - _time.monotonic())
            resp = self._call("object_get", shm_fallback, remain)
            if resp["timeout"]:
                raise GetTimeoutError(f"get() timed out after {timeout}s")
            metas.update(resp["metas"])
        out = []
        for oid in oids:
            entry = local_values.get(oid.binary())
            if entry is not None:
                payload, is_error = entry
                if isinstance(payload, Exception):
                    raise payload
                value = deserialize(payload)
            else:
                meta = metas[oid.hex()]
                kind = meta[0]
                if kind == "lost":
                    raise ObjectLostError(oid.hex(), "object lost and could not be reconstructed")
                if kind == "inline":
                    _, data, is_error = meta
                    # Objects are immutable: cache the fetched value so
                    # repeated gets are process-local (reference:
                    # memory_store.cc caches gotten small objects).
                    # promoted=True keeps ref flushes going to the
                    # controller; the entry evicts when local refs drop.
                    key = oid.binary()
                    self.memory_store.put(key, data, is_error)
                    self.memory_store.mark_promoted(key)
                    value = deserialize(data)
                else:
                    _, size, node_hex, shm_dir, is_error = meta
                    if deadline is None:
                        remain = None
                    else:
                        remain = deadline - _time.monotonic()
                        if remain <= 0:
                            raise GetTimeoutError(f"get() timed out after {timeout}s")
                    value = deserialize(
                        self._read_object(oid, size, node_hex, shm_dir, timeout=remain)
                    )
            if is_error:
                raise value
            out.append(value)
        return out

    def _plasma_for(self, shm_dir: str) -> PlasmaClient:
        if shm_dir == self.local_shm_dir:
            return self.plasma
        with self._lock:
            client = self._plasma_clients.get(shm_dir)
            if client is None:
                client = self._plasma_clients[shm_dir] = PlasmaClient(shm_dir)
            return client

    def _resolve_mapping(self, local: bool, shm_dir: str) -> "tuple[PlasmaClient, bool]":
        """(plasma client whose mapping serves this object on THIS node,
        whether a missing mapping means a cross-node pull is needed first).
        The one locality rule shared by the copying (`_read_object`) and
        pinned (`get_pinned_view`) read paths: remote objects map through
        the owner's shm_dir only when cross_node_shm says path-opens work
        (nodes sharing one host's filesystem, the co-located-cluster
        shortcut); otherwise they are pulled into this node's store."""
        if local:
            return self.plasma, False
        if not self.config.get("cross_node_shm", False):
            return self.plasma, True
        return self._plasma_for(shm_dir), False

    def _read_object(self, oid: ObjectID, size: int, node_hex: str, shm_dir: str,
                     timeout: Optional[float] = None) -> memoryview:
        local = self.node_id is not None and node_hex == self.node_id.hex()
        plasma, needs_pull = self._resolve_mapping(local, shm_dir)
        view = plasma.try_view(oid, size)
        if view is not None:
            return view
        if needs_pull:
            # Network data plane (reference: object_manager.cc Push/Pull):
            # the object lives on another node — pull it into THIS node's
            # store over the network, then map it locally.
            try:
                ok = self._call("object_pull", oid, self.node_id, timeout=timeout)
            except (TimeoutError, _CfTimeout):
                raise GetTimeoutError(
                    f"get() timed out pulling {oid.hex()[:8]} cross-node"
                )
            if not ok:
                raise ObjectLostError(oid.hex(), "cross-node object pull failed")
            missing = "object missing after pull"
        else:
            # Possibly spilled to disk — ask the owning node to restore it.
            if not self._call("object_ensure_local", oid, node_hex):
                raise ObjectLostError(oid.hex(), "object missing from store")
            missing = "object missing from store"
        view = plasma.try_view(oid, size)
        if view is None:
            raise ObjectLostError(oid.hex(), missing)
        return view

    def get_pinned_view(self, oid: ObjectID, timeout: Optional[float] = None):
        """Zero-copy read: resolve ``oid`` to a ``(memoryview, release)``
        pair over the node's shared-memory mapping, pinned against arena
        eviction until ``release()`` is called (the data layer's zero-copy
        block decode; reference: plasma client Get returning store buffers
        that the raylet pins while mapped). Returns None when the object is
        inline-tier, an error marker, or not mappable — callers fall back
        to a copying ``get``. Blocks until the object is ready."""
        e = self.memory_store.lookup(oid.binary())
        if e is not None:
            # Owner-local entry: wait for resolution (kind may flip from
            # inline to shm when a large result lands in the store).
            try:
                _, is_err = e.value(timeout)
            except (TimeoutError, _CfTimeout):
                raise GetTimeoutError(f"get() timed out after {timeout}s")
            if is_err or e.kind != "shm":
                return None
        resp = self._call("object_get", [oid], timeout)
        if resp["timeout"]:
            raise GetTimeoutError(f"get() timed out after {timeout}s")
        meta = resp["metas"][oid.hex()]
        if meta[0] != "shm":
            return None
        _, size, node_hex, shm_dir, is_error = meta
        if is_error:
            return None
        local = self.node_id is not None and node_hex == self.node_id.hex()
        plasma, _ = self._resolve_mapping(local, shm_dir)
        pv = plasma.view_pinned(oid, size)
        if pv is None:
            # Spilled, or living on another node: materialize locally
            # (pull / restore), then map again.
            try:
                self._read_object(oid, size, node_hex, shm_dir, timeout=timeout)
            except ObjectLostError:
                return None
            pv = plasma.view_pinned(oid, size)
        return pv

    def get_raw(self, oid: ObjectID) -> tuple[Any, bool]:
        """(value, is_error) without raising — used by arg resolution."""
        e = self.memory_store.lookup(oid.binary())
        if e is not None and e.kind == "inline":
            payload, is_err = e.value()
            if e.kind == "inline":  # may flip to shm while pending
                if isinstance(payload, Exception):
                    return payload, True
                return deserialize(payload), is_err
        resp = self._call("object_get", [oid], None)
        meta = resp["metas"][oid.hex()]
        if meta[0] == "lost":
            return ObjectLostError(oid.hex(), "lost"), True
        if meta[0] == "inline":
            return deserialize(meta[1]), meta[2]
        _, size, node_hex, shm_dir, is_error = meta
        return deserialize(self._read_object(oid, size, node_hex, shm_dir)), is_error

    def wait(self, refs: Sequence[ObjectRef], num_returns: int = 1, timeout: Optional[float] = None):
        self._check_async_errors()
        import time as _time

        local_futs = {}  # ref -> Entry future (resolution == readiness)
        remote = []
        for r in refs:
            e = self.memory_store.lookup(r.id.binary())
            if e is not None:
                local_futs[r] = e.ensure_future()
            else:
                remote.append(r)
        if not local_futs:
            ready_hex = set(self._call("object_wait", [r.id for r in refs], num_returns, timeout))
            return self._split_wait(refs, ready_hex, num_returns)
        deadline = None if timeout is None else _time.monotonic() + timeout
        if not remote:
            import concurrent.futures as _cf

            pending = {f for f in local_futs.values() if not f.done()}
            while True:
                ready_hex = {r.id.hex() for r, f in local_futs.items() if f.done()}
                if len(ready_hex) >= num_returns or not pending:
                    return self._split_wait(refs, ready_hex, num_returns)
                remain = None if deadline is None else deadline - _time.monotonic()
                if remain is not None and remain <= 0:
                    return self._split_wait(refs, ready_hex, num_returns)
                done, pending = _cf.wait(
                    pending, timeout=remain, return_when=_cf.FIRST_COMPLETED
                )
                if not done and remain is not None:
                    return self._split_wait(
                        refs,
                        {r.id.hex() for r, f in local_futs.items() if f.done()},
                        num_returns,
                    )
        # Mixed local/remote: poll the controller in short slices while
        # local futures resolve independently (rare path — a wait over
        # both direct-call results and globally-owned objects).
        remote_ready: set = set()
        while True:
            ready_hex = {r.id.hex() for r, f in local_futs.items() if f.done()} | remote_ready
            remain = None if deadline is None else deadline - _time.monotonic()
            need = num_returns - len(ready_hex)
            if need <= 0 or (remain is not None and remain <= 0):
                return self._split_wait(refs, ready_hex, num_returns)
            slice_t = 0.05 if remain is None else max(0.0, min(0.05, remain))
            remote_ready |= set(
                self._call("object_wait", [r.id for r in remote], max(need, 1), slice_t)
            )

    @staticmethod
    def _split_wait(refs, ready_hex, num_returns):
        ready, not_ready = [], []
        for r in refs:
            (ready if r.id.hex() in ready_hex and len(ready) < num_returns else not_ready).append(r)
        return ready, not_ready

    def free(self, refs: Sequence[ObjectRef]):
        remote = []
        for r in refs:
            key = r.id.binary()
            local_only = self.memory_store.is_local_only(key)
            self.memory_store.evict(key)  # drop local copy either way
            if not local_only:
                remote.append(r.id)
        if remote:
            self._call("object_free", remote)

    # ------------------------------------------------------------------
    # Tasks
    # ------------------------------------------------------------------
    def build_args(self, args: tuple, kwargs: dict) -> "tuple[bytes, List[ObjectID], list]":
        """Returns (blob, deps). Top-level refs become _RefMarker deps
        (resolved before dispatch); refs *nested inside* arg values are
        captured during serialization and pinned for the task's lifetime
        via ``last_captures`` (the reference's submitted-task references,
        reference_count.h UpdateSubmittedTaskReferences)."""
        deps: List[ObjectID] = []

        def mark(v):
            if isinstance(v, ObjectRef):
                deps.append(v.id)
                return _RefMarker(v.id)
            return v

        margs = tuple(mark(a) for a in args)
        mkwargs = {k: mark(v) for k, v in kwargs.items()}
        blob, contained = _serialize_capturing((margs, mkwargs))
        return blob, deps, contained

    # Submission is pipelined: fire-and-forget notify, return refs
    # immediately (reference: NormalTaskSubmitter queues without blocking
    # the caller; return ids are deterministic). Submission-side failures
    # surface on the next sync point via _check_async_errors; task-side
    # failures surface through the returned refs as usual.
    def _note_async_error(self, fut):
        exc = fut.exception() if not fut.cancelled() else None
        if exc is not None:
            self._async_errors.append(exc)

    def _check_async_errors(self):
        if self._async_errors:
            raise self._async_errors.pop(0)

    def _attribute_returns(self, refs: List[ObjectRef]):
        """Attribute a submission's return refs to the user call-site
        (the ``.remote()`` line). One bounded stack walk per submit; the
        per-code-object intern cache makes steady-state cost a dict hit."""
        from ray_tpu.core import memory_census

        site = memory_census.capture_callsite()
        if site:
            for r in refs:
                self.refs.attribute(r.id.binary(), site)

    def _submit_pipelined(self, spec: TaskSpec, captures: Optional[list]) -> List[ObjectRef]:
        self._check_async_errors()
        fut = self.loop_runner.submit(
            self.peer.notify("submit_task", spec, captures or [])
        )
        fut.add_done_callback(self._note_async_error)
        refs = [ObjectRef(oid) for oid in spec.return_ids()]
        self._attribute_returns(refs)
        return refs

    def submit_task(self, spec: TaskSpec, captures: Optional[list] = None) -> List[ObjectRef]:
        if (
            self.direct_normal_enabled
            and spec.task_type == TaskType.NORMAL_TASK
            and not spec.is_streaming
            # Container envs need spawn-time (image-wrapped) workers,
            # which only the controller's dispatch path provisions; the
            # direct-lease pool hands out host workers.
            and not (spec.runtime_env or {}).get("image_uri")
        ):
            return self._submit_normal_direct(spec, captures)
        self.promote_refs(list(spec.dependencies) + list(captures or []))
        return self._submit_pipelined(spec, captures)

    def _submit_normal_direct(self, spec: TaskSpec, captures: Optional[list]) -> List[ObjectRef]:
        """Lease-based direct submission (reference:
        normal_task_submitter.cc). Top-level owner-local deps travel
        inline with the push — no promotion; captured (nested) refs must
        be globally resolvable by the executing worker → promote."""
        self._check_async_errors()
        if captures:
            self.promote_refs(captures)
        rids = spec.return_ids()
        self.memory_store.register_pending([oid.binary() for oid in rids])
        refs = [ObjectRef(oid) for oid in rids]
        self._attribute_returns(refs)
        if spec.dependencies or captures:
            pins = [ObjectRef(d) for d in spec.dependencies]
            pins += [
                ObjectRef(c if isinstance(c, ObjectID) else ObjectID(c))
                for c in (captures or [])
            ]
        else:
            pins = None
        self._normal_submitter().submit(spec, pins)
        return refs

    def _normal_submitter(self):
        sub = self._normal_sub
        if sub is None:
            with self._lock:
                if self._normal_sub is None:
                    from ray_tpu.core.normal_direct import NormalSubmitter

                    self._normal_sub = NormalSubmitter(self)
                sub = self._normal_sub
        return sub

    def create_actor(self, spec: TaskSpec, captures: Optional[list] = None):
        self.promote_refs(list(spec.dependencies) + list(captures or []))
        self._call("create_actor", spec, captures or [])

    def submit_actor_task(self, spec: TaskSpec, captures: Optional[list] = None) -> List[ObjectRef]:
        if not self.direct_enabled or spec.is_streaming:
            self.promote_refs(list(spec.dependencies) + list(captures or []))
            return self._submit_pipelined(spec, captures)
        # Direct caller→actor push (reference: actor_task_submitter.h).
        # Top-level ref deps the caller owns locally travel inline with
        # the push; nested (captured) refs must be globally resolvable by
        # the executing worker → promote.
        self._check_async_errors()
        if captures:
            self.promote_refs(captures)
        rids = spec.return_ids()
        self.memory_store.register_pending([oid.binary() for oid in rids])
        refs = [ObjectRef(oid) for oid in rids]
        self._attribute_returns(refs)
        # Pin args (deps + captures) until the reply lands — the owner-side
        # equivalent of the reference's submitted-task references.
        if spec.dependencies or captures:
            pins = [ObjectRef(d) for d in spec.dependencies]
            pins += [ObjectRef(c if isinstance(c, ObjectID) else ObjectID(c)) for c in (captures or [])]
        else:
            pins = None
        sub = self._submitter_for(spec.actor_id)
        self._direct_tasks[spec.task_id] = sub
        for oid in rids:
            self._direct_returns[oid] = spec.task_id
        sub.submit(spec, pins)
        return refs

    def _queue_direct(self, submitter, call):
        self._direct_handoff.push((submitter, call))

    def _submitter_for(self, actor_id):
        with self._lock:
            sub = self._submitters.get(actor_id)
            if sub is None:
                from ray_tpu.core.direct import ActorSubmitter

                sub = self._submitters[actor_id] = ActorSubmitter(self, actor_id)
            return sub

    def _direct_task_done(self, spec: TaskSpec):
        self._direct_tasks.pop(spec.task_id, None)
        for oid in spec.return_ids():
            self._direct_returns.pop(oid, None)

    def promote_refs(self, oids: Sequence, timeout: Optional[float] = None):
        """Publish owner-local objects whose refs are escaping this
        process to the controller directory (promotion-on-escape — the
        reference instead resolves owners from the ref; see
        memory_store.py module docstring). NON-BLOCKING: ready values are
        published via a notify on the controller connection (ordered
        before any subsequent submit on the same connection); pending
        entries are flagged and publish when their reply resolves them —
        the controller's dependency wait covers the gap."""
        from ray_tpu.utils.serialization import serialize

        for oid in oids:
            oid = oid if isinstance(oid, ObjectID) else ObjectID(oid)
            key = oid.binary()
            status = self.memory_store.request_promotion(key)
            if status != "ready":
                continue  # done / gone / deferred-to-resolve
            e = self.memory_store.lookup(key)
            if e is None:
                continue
            payload, is_err = e.value(0)
            if e.kind == "shm":
                continue  # resolved to a global shm object
            if isinstance(payload, Exception):
                payload, is_err = serialize(payload), True
            self.loop_runner.submit(
                self.peer.notify("object_put_inline", oid, bytes(payload), is_err, [])
            )
            self.memory_store.mark_promoted(key)

    def next_task_id(self) -> TaskID:
        return TaskID.for_index(self.worker_id, next(self._task_counter))

    # ------------------------------------------------------------------
    # Control
    # ------------------------------------------------------------------
    def kill_actor(self, actor_id, no_restart: bool):
        self._call("kill_actor", actor_id, no_restart)

    def wait_actor_ready(self, actor_id, timeout: Optional[float] = None):
        return self._call("wait_actor_ready", actor_id, timeout=timeout)

    def get_actor_by_name(self, name: str):
        return self._call("get_actor_by_name", name)

    def cancel_task(self, task_id: TaskID, force: bool):
        sub = self._direct_tasks.get(task_id)
        if sub is not None:
            sub.cancel_threadsafe(task_id)
            return
        if self._normal_sub is not None and self._normal_sub.owns_task(task_id):
            self._normal_sub.cancel_threadsafe(task_id)
            return
        self._call("cancel_task", task_id, force)

    def cancel_by_object(self, oid: ObjectID, force: bool):
        tid = self._direct_returns.get(oid)
        if tid is None and self._normal_sub is not None:
            tid = self._normal_sub.task_for_return(oid)
        if tid is not None:
            self.cancel_task(tid, force)
            return
        self._call("cancel_by_object", oid, force)

    # KV
    def kv_put(self, ns: str, key: bytes, value: bytes, overwrite: bool = True) -> bool:
        return self._call("kv_put", ns, key, value, overwrite)

    def kv_get(self, ns: str, key: bytes) -> Optional[bytes]:
        return self._call("kv_get", ns, key)

    def kv_del(self, ns: str, key: bytes) -> bool:
        return self._call("kv_del", ns, key)

    def kv_keys(self, ns: str, prefix: bytes) -> List[bytes]:
        return self._call("kv_keys", ns, prefix)

    def drain_node(self, node_id: NodeID, timeout_s: float = 300.0) -> bool:
        return self._call("drain_node", node_id, timeout_s)

    # PGs
    def pg_create(self, bundles, strategy: str, name: str):
        return self._call("pg_create", bundles, strategy, name)

    def pg_wait_ready(self, pg_id, timeout):
        return self._call("pg_wait_ready", pg_id, timeout)

    def pg_remove(self, pg_id):
        return self._call("pg_remove", pg_id)

    def pg_shrink(self, pg_id, bundle_indices):
        return self._call("pg_shrink", pg_id, list(bundle_indices))

    def pg_table(self):
        return self._call("pg_table")

    def pg_bundle_nodes(self, pg_id):
        return self._call("pg_bundle_nodes", pg_id)

    # Introspection
    def cluster_resources(self):
        return self._call("cluster_resources")

    def available_resources(self):
        return self._call("available_resources")

    def list_state(self, what: str, **kwargs):
        return self._call(f"list_{what}", **kwargs)

    def disconnect(self):
        self._reconnect_dead = True  # deliberate: never dial back out
        self._refs_closed.set()
        if self._ref_flush_task is not None:
            self._ref_flush_task.cancel()
        try:
            self.loop_runner.run(self.peer.close(), timeout=2)
        except Exception:
            pass


class _NullHandler:
    def on_disconnect(self, peer):
        pass

    # Every CoreWorker-embedded process answers the profiling fan-out —
    # drivers AND handler-less admin connections (cluster_utils,
    # autoscaler monitor): a wedged driver (deadlocked ray_tpu.get,
    # stuck user loop) is exactly what `ray-tpu profile stacks` exists
    # to see.
    def rpc_stack_dump(self, peer):
        from ray_tpu.utils.stack_dump import dump_all_threads

        return dump_all_threads()

    def rpc_dump_stacks(self, peer):
        from ray_tpu.util import profiling

        return profiling.dump_stacks()

    def rpc_profile_cpu(self, peer, duration_s: float = 10.0, hz: float = 100.0):
        from ray_tpu.util import profiling

        return profiling.sample_async(duration_s, hz)

    def rpc_dump_memory(self, peer, limit: int = 1000):
        """This process's object/memory census (`ray-tpu memory` fan-out
        leg): open local refs by creation call-site, owner-local memory
        store occupancy, live zero-copy pins. Drivers hold refs too — a
        leak is as often the driver's list as an actor's."""
        from ray_tpu.core import memory_census

        return memory_census.dump(limit)

    # The controller broadcasts worker log lines / follow-mode records to
    # every driver connection; admin connections (cluster_utils, monitor)
    # have no console to print them to. Drop the pushes silently — a
    # missing handler would log an ERROR per batch, which the log plane
    # then ships back as a head-attributed error signature (self-inflicted
    # spike noise).
    def rpc_log_batch(self, peer, batch):
        pass

    def rpc_log_records(self, peer, batch):
        pass


class DriverHandler(_NullHandler):
    """Driver-side handlers for controller pushes (reference: the driver
    prints worker log lines — worker.py print_to_stdstream)."""

    def rpc_log_batch(self, peer, batch):
        from ray_tpu.core.log_monitor import print_to_driver

        print_to_driver(batch)

    def rpc_log_records(self, peer, batch):
        """Structured follow-mode records (``ray-tpu logs --follow``):
        the controller pushes filtered sidecar records; the registered
        sink (or a default stderr renderer) consumes them."""
        from ray_tpu.core.log_monitor import deliver_records

        deliver_records(batch)

    def rpc_pubsub_msg(self, peer, channel: str, message):
        from ray_tpu.experimental.pubsub import _deliver

        _deliver(channel, message)
