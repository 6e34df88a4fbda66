"""Worker process: executes tasks and hosts actors.

Reference: python/ray/_private/workers/default_worker.py (entrypoint) +
the Cython execution path python/ray/_raylet.pyx:2222
``task_execution_handler`` and the receiver-side scheduling queues
(src/ray/core_worker/transport/task_receiver.cc, concurrency groups in
transport/concurrency_group_manager.h).

Structure: the asyncio loop (in a background thread via EventLoopThread)
handles RPC; execution happens on a ThreadPoolExecutor so blocking user code
never stalls the control plane. Actor tasks run on a per-actor pool of
``max_concurrency`` threads — FIFO when 1 (ordered actors), concurrent
otherwise. ``async def`` methods are driven to completion on the executing
thread (the reference uses boost fibers — transport/fiber.h).
"""
from __future__ import annotations

import asyncio
import logging
import os
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional

from ray_tpu.core.client import CoreWorker
from ray_tpu.core.object_ref import _RefMarker
from ray_tpu.core.task_spec import TaskSpec, TaskType
from ray_tpu.exceptions import TaskError
from ray_tpu.utils import rpc
from ray_tpu.utils.ids import NodeID, TaskID, WorkerID
from ray_tpu.utils.serialization import (
    deserialize,
    deserialize_function,
    serialize,
)

logger = logging.getLogger("ray_tpu.worker")


class WorkerHandler:
    """RPC handler for controller→worker messages AND the worker's direct
    listener (caller→actor pushes arrive on separate connections —
    reference: the worker's CoreWorkerService gRPC server).

    Dispatches may arrive between worker registration and executor attach
    (registration happens inside CoreWorker.__init__) — buffer until ready.
    """

    def __init__(self):
        self.executor: Optional[TaskExecutor] = None
        self._buffer: list = []
        self._controller_peer = None
        self._agent_peer = None

    def attach_executor(self, executor: "TaskExecutor"):
        self.executor = executor
        buffered, self._buffer = self._buffer, []
        for spec, kind in buffered:
            executor.submit(spec, kind)

    def _dispatch(self, spec: TaskSpec, kind: str):
        if self.executor is None:
            self._buffer.append((spec, kind))
        else:
            self.executor.submit(spec, kind)

    def rpc_execute_task(self, peer, spec: TaskSpec):
        self._dispatch(spec, "task")

    def rpc_create_actor(self, peer, spec: TaskSpec):
        self._dispatch(spec, "actor_create")

    def rpc_execute_actor_task(self, peer, spec: TaskSpec):
        self._dispatch(spec, "actor_task")

    def rpc_push_actor_task(self, peer, packed: tuple, inline_deps=None):
        """Direct caller→actor push; the returned Future resolves to the
        reply carrying the results (reference:
        CoreWorkerService::PushTask). Returning a Future (not awaiting)
        keeps the hot path free of per-request task creation."""
        from ray_tpu.core.task_spec import unpack_actor_task

        spec = unpack_actor_task(packed)
        if self.executor is None:
            return self._push_when_ready(spec, "actor_task", inline_deps)
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self.executor.submit(spec, "actor_task", reply=(loop, fut), inline_deps=inline_deps)
        return fut

    def rpc_push_task(self, peer, packed: tuple, inline_deps=None):
        """Direct lease-holder→worker push of a NORMAL task (reference:
        NormalTaskSubmitter PushNormalTask → CoreWorkerService::PushTask);
        results travel back in the reply to the caller's memory store."""
        from ray_tpu.core.task_spec import unpack_normal_task

        spec = unpack_normal_task(packed)
        if self.executor is None:
            return self._push_when_ready(spec, "task", inline_deps)
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self.executor.submit(spec, "task", reply=(loop, fut), inline_deps=inline_deps)
        return fut

    def rpc_push_task_batch(self, peer, packed_list: list, inline_deps=None):
        """Push a BATCH of normal tasks in one frame with ONE gathered
        reply (round 17): the reply frame is half the per-task RPC cost,
        and the execution pool is serial anyway, so per-task replies buy
        nothing. ``inline_deps`` is the merged dep dict for the whole
        batch. Resolves to a list of per-task (results, error) tuples in
        submission order."""
        from ray_tpu.core.task_spec import unpack_normal_task

        specs = [unpack_normal_task(p) for p in packed_list]
        if self.executor is None:
            return self._push_batch_when_ready(specs, inline_deps)
        loop = asyncio.get_running_loop()
        futs = []
        for spec in specs:
            fut = loop.create_future()
            self.executor.submit(spec, "task", reply=(loop, fut),
                                 inline_deps=inline_deps)
            futs.append(fut)
        return asyncio.gather(*futs)

    async def _push_when_ready(self, spec: TaskSpec, kind: str, inline_deps):
        while self.executor is None:  # registration race (first push only)
            await asyncio.sleep(0.002)
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        self.executor.submit(spec, kind, reply=(loop, fut), inline_deps=inline_deps)
        return fut

    async def _push_batch_when_ready(self, specs: list, inline_deps):
        while self.executor is None:  # registration race (first push only)
            await asyncio.sleep(0.002)
        loop = asyncio.get_running_loop()
        futs = []
        for spec in specs:
            fut = loop.create_future()
            self.executor.submit(spec, "task", reply=(loop, fut),
                                 inline_deps=inline_deps)
            futs.append(fut)
        return await asyncio.gather(*futs)

    def rpc_cancel(self, peer, task_id: TaskID):
        if self.executor is not None:
            self.executor.cancelled.add(task_id)

    def rpc_current_task(self, peer):
        """What this worker is executing right now — queried by the
        controller's OOM victim policies for direct-push tasks it never
        dispatched (reference: the raylet knows its leased workers'
        tasks; here the worker itself is the source of truth)."""
        if self.executor is None:
            return None
        return self.executor.current_task_info

    def rpc_exit(self, peer):
        ex = self.executor
        events = ex._events if ex is not None else None
        if not events or self._controller_peer is None or self._controller_peer.closed:
            os._exit(0)
        # Best-effort final event flush: an exiting worker (actor kill,
        # pool retire, teardown) must not eat the tail of its tasks'
        # lifecycle chains — up to one flush period of RUNNING/FINISHED
        # events can still be buffered. A timer hard-exits if the
        # controller connection is wedged.
        threading.Timer(1.0, lambda: os._exit(0)).start()
        batch = []
        while events and len(batch) < 10000:
            batch.append(events.popleft())

        async def _flush_then_exit():
            try:
                await self._controller_peer.notify("task_events", batch)
            except Exception as e:  # noqa: BLE001 — exiting regardless
                logger.debug("final event flush failed: %s", e)
            os._exit(0)

        asyncio.ensure_future(_flush_then_exit())

    def rpc_ping(self, peer):
        return "pong"

    def rpc_stack_dump(self, peer):
        """Live stacks of every thread (reference: py-spy dump via the
        dashboard reporter / `ray stack`)."""
        from ray_tpu.utils.stack_dump import dump_all_threads

        return dump_all_threads()

    def rpc_dump_stacks(self, peer):
        """Structured stack dump: thread names + frames + current-task
        attribution + lockwatch held-lock annotations (the `ray-tpu
        profile stacks` fan-out leg)."""
        from ray_tpu.util import profiling

        return profiling.dump_stacks()

    def rpc_profile_cpu(self, peer, duration_s: float = 10.0, hz: float = 100.0):
        """Sampling CPU profile of this worker for ``duration_s``. The
        sampler runs on its own thread; the returned coroutine just
        sleeps, so the worker's control plane stays live."""
        from ray_tpu.util import profiling

        return profiling.sample_async(duration_s, hz)

    def rpc_profile_device(self, peer, action: str, capture: str = "",
                           base_dir=None):
        """Attach/detach a jax.profiler trace on this live worker (no
        restart). Returns {ok, dir?, error?}; gracefully degrades when
        jax or the backend profiler is unavailable."""
        from ray_tpu.util import profiling

        return profiling.device_trace_control(action, capture, base_dir)

    def rpc_dump_memory(self, peer, limit: int = 1000):
        """This worker's object/memory census (`ray-tpu memory` fan-out
        leg): open local refs by creation call-site, owner-local memory
        store occupancy, and live zero-copy arena pins."""
        from ray_tpu.core import memory_census

        return memory_census.dump(limit)

    def rpc_pubsub_msg(self, peer, channel: str, message):
        from ray_tpu.experimental.pubsub import _deliver

        _deliver(channel, message)

    def rpc_gc_nudge(self, peer):
        """Health-plane leak actuator: force a collection in this worker
        so unreachable reference cycles holding ObjectRefs break NOW
        (the refs' __del__ marks them dropped; the ref-flush loop ships
        the drops within one flush period). Returns collection stats."""
        import gc

        unreachable = gc.collect()
        pending = 0
        core = self.executor.core if self.executor is not None else None
        if core is not None:
            pending = core.refs.pending_drops()
        return {"unreachable": unreachable, "pending_drops": pending}

    def rpc_pin_shapes(self, peer, functions):
        """Health-plane storm actuator: pin shape-bucketing for the named
        functions in this worker's compile tracker (util/compile_tracker)
        so recompile-storm workloads round dynamic dims up to power-of-2
        buckets instead of recompiling per shape."""
        from ray_tpu.util import compile_tracker

        return compile_tracker.pin_functions(functions)

    def on_disconnect(self, peer):
        if peer is self._agent_peer:
            # The spawning agent died (host death, SIGKILL): this worker
            # is an orphan — nothing will ever retire it, and a rejoined
            # agent spawns a fresh pool. Self-reap immediately instead of
            # lingering as a stray process (the PR 13 orphan fix).
            logger.warning("node agent connection lost; exiting")
            os._exit(1)
        # Direct-caller connections come and go; only the controller
        # connection is load-bearing.
        if peer is not self._controller_peer:
            return
        core = self.executor.core if self.executor is not None else None
        window = 0.0
        if core is not None and isinstance(getattr(core, "config", None), dict):
            window = float(core.config.get("controller_reconnect_window_s", 0.0))
        # Only a BUSY worker (hosting an actor / running a task) has
        # state worth riding a controller restart for. An idle pool
        # worker that reconnects just re-idles — exiting now instead of
        # lingering a full window loses nothing (the agent respawns on
        # demand) and keeps teardown/chaos tests free of straggler
        # processes.
        busy = self.executor is not None and (
            self.executor.actor_instance is not None
            or self.executor.current_task_info is not None
        )
        if window <= 0 or core is None or not busy:
            os._exit(1)

        # Bounded reconnect (jittered backoff inside try_reconnect):
        # rides through a controller restart on the same address; a
        # controller that is truly gone still ends with exit(1), just
        # one window later. Runs on its own thread — this callback is
        # on the IO loop the reconnect itself needs.
        def _rejoin():
            if core.try_reconnect():
                self._controller_peer = core.peer
            else:
                os._exit(1)

        threading.Thread(target=_rejoin, daemon=True,
                         name="controller-rejoin").start()


class TaskExecutor:
    def __init__(self, core: CoreWorker):
        import collections

        self.core = core
        self.pool = ThreadPoolExecutor(max_workers=1, thread_name_prefix="task-exec")
        self.actor_pool: Optional[ThreadPoolExecutor] = None
        # Named concurrency groups (reference: concurrency_group_manager.h
        # :34 — per-group executors so a slow group can't starve another;
        # ordering is preserved within each group's queue). Actor tasks
        # that arrive before __init__ completes park in _pending_actor so
        # group routing (which needs the constructed class) happens after
        # creation, in submission order.
        self.actor_groups: Dict[str, ThreadPoolExecutor] = {}
        self._actor_ready = False
        self._pending_actor: list = []
        self._actor_gate = threading.Lock()
        self.actor_instance: Any = None
        self.cancelled: set = set()
        self._shipper_gate = threading.Lock()
        self.current_task_info: Optional[dict] = None  # read by rpc_current_task
        self._func_cache: Dict[bytes, Any] = {}
        self._reply_handoff = None  # created lazily (needs the loop)
        # Direct-push tasks bypass the controller, so their observability
        # events flush in periodic batches (reference: TaskEventBuffer →
        # GCS task manager, task_event_buffer.cc).
        self._events = collections.deque()
        self.core.loop_runner.submit(self._event_flush_loop())

    async def _event_flush_loop(self):
        interval = self.core.config.get("event_flush_period_s", 0.25)
        while True:
            await asyncio.sleep(interval)
            if not self._events:
                continue
            batch = []
            while self._events and len(batch) < 10000:
                batch.append(self._events.popleft())
            try:
                await self.core.peer.notify("task_events", batch)
            except Exception:  # noqa: BLE001 — controller gone
                return

    def _group_for(self, spec: TaskSpec) -> Optional[str]:
        """Resolve an actor task's concurrency group: per-call override
        (.options(concurrency_group=...)) wins over the method's declared
        group (@ray_tpu.method(concurrency_group=...))."""
        if spec.concurrency_group:
            return spec.concurrency_group
        if self.actor_instance is not None and spec.actor_method_name:
            m = getattr(type(self.actor_instance), spec.actor_method_name, None)
            if m is not None:
                return getattr(m, "__ray_tpu_method_options__", {}).get(
                    "concurrency_group"
                )
        return None

    def submit(self, spec: TaskSpec, kind: str, reply=None, inline_deps=None):
        if kind == "actor_task":
            with self._actor_gate:
                if not self._actor_ready:
                    # __init__ still running (or queued): park; flushed in
                    # order by _flush_pending_actor_tasks after creation.
                    self._pending_actor.append((spec, reply, inline_deps))
                    return
            # Unknown group names fall through to the default pool; _run
            # rejects them with a clean TaskError before executing.
            pool = (
                self.actor_groups.get(self._group_for(spec))
                or self.actor_pool
                or self.pool
            )
        else:
            pool = self.pool
        pool.submit(self._guarded_run, spec, kind, reply, inline_deps)

    def _flush_pending_actor_tasks(self):
        """Called once creation finished (or failed): open the gate and
        route everything parked behind it, preserving submission order."""
        with self._actor_gate:
            self._actor_ready = True
            pending, self._pending_actor = self._pending_actor, []
            for spec, reply, inline_deps in pending:
                pool = (
                    self.actor_groups.get(self._group_for(spec))
                    or self.actor_pool
                    or self.pool
                )
                pool.submit(self._guarded_run, spec, "actor_task", reply, inline_deps)

    def _guarded_run(self, spec: TaskSpec, kind: str, reply=None, inline_deps=None):
        try:
            self._run(spec, kind, reply, inline_deps)
        except Exception:
            logger.exception("internal error running task %s", spec.name)
            if reply is not None:
                self._reply(reply, ([], TaskError(spec.name, traceback.format_exc(), None)))
        finally:
            # Creation done (success OR failure): release parked actor
            # tasks — on failure they run against actor_instance=None and
            # report clean TaskErrors, same as before the gate existed.
            if kind == "actor_create" and not self._actor_ready:
                self._flush_pending_actor_tasks()
            from ray_tpu import runtime_context
            from ray_tpu.util import profiling

            runtime_context._set_task(None, None)
            profiling.set_thread_task(None)

    def _reply(self, reply, payload):
        """Batched exec-thread → loop handoff for completed replies."""
        loop, fut = reply
        if self._reply_handoff is None:
            self._reply_handoff = rpc.BatchedHandoff(loop, _resolve_reply)
        self._reply_handoff.push((fut, payload))

    # ------------------------------------------------------------------
    def _load_func(self, spec: TaskSpec):
        fn = self._func_cache.get(spec.func_digest)
        if fn is None:
            fn = deserialize_function(spec.func_blob)
            self._func_cache[spec.func_digest] = fn
        return fn

    def _resolve_args(self, spec: TaskSpec, inline_deps=None):
        args, kwargs = deserialize(spec.args_blob)

        def res(v):
            if isinstance(v, _RefMarker):
                if inline_deps is not None:
                    data = inline_deps.get(v.oid.binary())
                    if data is not None:
                        # caller-owned value shipped with the push
                        # (reference: LocalDependencyResolver inlining)
                        return deserialize(data)
                value, is_error = self.core.get_raw(v.oid)
                if is_error:
                    # dependency failures propagate AS the original error
                    # (ObjectLostError, the producer's exception, …) — not
                    # wrapped in this task's TaskError (reference: dep
                    # errors pass through ray.get unchanged)
                    raise _DepError(value)
                return value
            return v

        return tuple(res(a) for a in args), {k: res(v) for k, v in kwargs.items()}

    def _wait_for_granted_chips(self, granted: float):
        """Before a TPU task's code: the ``granted`` chips of this process
        (its runtime env, applied by now, says which) may still be held by
        their last owner's dying process; wait until they can be opened."""
        from ray_tpu.accelerators.tpu import TPUAcceleratorManager as tpu
        from ray_tpu.accelerators.tpu import jax_backend_initialized

        if jax_backend_initialized():
            return  # they are this process's already
        ids = tpu.get_current_process_visible_accelerator_ids()
        paths = tpu.chip_devices(ids)
        if not paths or (ids is None and len(paths) != granted):
            # no VFIO host; or a share of its chips and none by name (a plain
            # task: only actors are given ids), and a neighbour may hold the rest
            return
        # Opens no backend and takes seconds, and the task's code imports it
        # anyway: spend them before the wait, not after it.
        import jax  # noqa: F401

        waited = tpu.wait_for_chips(paths)
        self._events.append(
            {
                "ts": time.time(),
                "kind": "worker",
                "id": self.core.worker_id.hex(),
                "state": "CHIPS_READY",
                "chip_wait_ms": round(waited * 1000.0, 1),
            }
        )

    def _run(self, spec: TaskSpec, kind: str, reply=None, inline_deps=None):
        if spec.task_id in self.cancelled:
            from ray_tpu.exceptions import TaskCancelledError

            err = TaskCancelledError(spec.task_id.hex())
            if reply is not None:
                self._reply(reply, ([], err))
            else:
                self._report(spec, None, err)
            return
        from ray_tpu import runtime_context
        from ray_tpu.util import profiling

        runtime_context._set_task(
            spec.task_id.hex(), spec.actor_id.hex() if spec.actor_id else None
        )
        # CPU-sample attribution: the profiler tags this thread's samples
        # with the executing task/actor-method name (cleared in finally;
        # spec.name already carries "actor.<method>" for actor tasks).
        profiling.set_thread_task(spec.name)
        if reply is not None:
            # Direct pushes bypass the controller, so the worker emits the
            # RUNNING half of the task's timeline span itself (FINISHED
            # comes from _report_direct); the event flush batches both.
            self._events.append(
                {
                    "ts": time.time(),
                    "kind": "task",
                    "type": spec.task_type.name,
                    "task_id": spec.task_id.hex(),
                    "name": spec.name,
                    "state": "RUNNING",
                }
            )
        if kind == "task" and reply is not None:
            # direct-push normal task: controller doesn't track it, so the
            # worker itself answers OOM-victim queries (rpc_current_task)
            self.current_task_info = {
                "task_id": spec.task_id.hex(),
                "name": spec.name,
                "owner": spec.owner_id.hex() if spec.owner_id else "",
                "retriable": spec.max_retries > 0,
                "start": time.time(),
            }
        trace_span_cm = None
        profiler_cm = None
        try:
            if spec.runtime_env:
                from ray_tpu import runtime_env as _renv

                _renv.ensure_applied(spec.runtime_env)
                ctx = spec.runtime_env.get("__trace_ctx__")
                if ctx:
                    # Caller traced this call: record the execution span
                    # under its context (reference: tracing_helper's
                    # _inject_tracing_into_function execution wrapper).
                    from ray_tpu.util import tracing as _tracing

                    if not _tracing.tracing_enabled():
                        _tracing.enable_tracing(
                            os.environ.get("RAY_TPU_SESSION_DIR", "/tmp/ray_tpu")
                        )
                    _tracing.attach_context(ctx)
                    trace_span_cm = _tracing.start_span(
                        f"execute:{spec.name}", {"task_id": spec.task_id.hex()}
                    )
                    trace_span_cm.__enter__()
            if spec.resources.get("TPU"):
                from ray_tpu.core.resources import from_fp

                self._wait_for_granted_chips(from_fp(spec.resources.get("TPU")))
            if spec.runtime_env and spec.runtime_env.get("jax_profiler"):
                # per-task jax.profiler capture (reference: the nsight
                # runtime-env plugin wraps the worker with the profiler)
                from ray_tpu.runtime_env.jax_profiler import task_trace

                profiler_cm = task_trace(spec, spec.runtime_env["jax_profiler"])
                profiler_cm.__enter__()
            args, kwargs = self._resolve_args(spec, inline_deps)
            if kind == "task":
                fn = self._load_func(spec)
                result = _maybe_async(fn(*args, **kwargs))
            elif kind == "actor_create":
                cls = self._load_func(spec)
                self.actor_instance = cls(*args, **kwargs)
                n = max(1, spec.max_concurrency)
                self.actor_pool = ThreadPoolExecutor(n, thread_name_prefix="actor-exec")
                for gname, gsize in (spec.concurrency_groups or {}).items():
                    self.actor_groups[gname] = ThreadPoolExecutor(
                        max(1, int(gsize)), thread_name_prefix=f"actor-cg-{gname}"
                    )
                result = None
            elif spec.func_blob is not None:
                # Function-on-actor (reference: __ray_call__): compiled-DAG
                # loops and worker-group utilities execute arbitrary fns
                # against the actor instance.
                fn = self._load_func(spec)
                result = _maybe_async(fn(self.actor_instance, *args, **kwargs))
            else:  # actor_task
                group = self._group_for(spec)  # per-call override OR declared
                if group and group not in self.actor_groups:
                    raise ValueError(
                        f"unknown concurrency group {group!r}; "
                        f"declared groups: {sorted(self.actor_groups)}"
                    )
                method = getattr(self.actor_instance, spec.actor_method_name)
                result = _maybe_async(method(*args, **kwargs))
            # Close the profiler capture BEFORE reporting: the caller's
            # ray.get returns at report time and must be able to list
            # the finished capture (streaming generator bodies run during
            # _report and are not captured — a documented edge).
            if profiler_cm is not None:
                cmx, profiler_cm = profiler_cm, None
                try:
                    cmx.__exit__(None, None, None)
                except Exception as e:  # noqa: BLE001 — capture teardown only
                    logger.debug("profiler capture teardown failed: %s", e)
            # Report inside the span: for streaming tasks the generator
            # body runs during _report, which must be attributed.
            if reply is not None:
                self._report_direct(spec, result, None, reply)
            else:
                self._report(spec, result, None)
        except _DepError as e:
            if reply is not None:
                self._report_direct(spec, None, e.inner, reply)
            else:
                self._report(spec, None, e.inner)
        except Exception as e:  # noqa: BLE001 — user errors cross the wire
            tb = traceback.format_exc()
            err = e if isinstance(e, TaskError) else TaskError(spec.name, tb, None)
            # Structured log plane: the failure traceback is recorded —
            # attributed to this task — BEFORE the error crosses the
            # wire, so `state.summarize_errors()` sees every failure even
            # when the caller never gets the ref (core/log_plane.py).
            from ray_tpu.core import log_plane

            log_plane.record_task_error(spec.name, spec.task_id.hex(), e, tb)
            if reply is not None:
                self._report_direct(spec, None, err, reply)
            else:
                self._report(spec, None, err)
        finally:
            self.current_task_info = None
            if profiler_cm is not None:
                try:
                    profiler_cm.__exit__(None, None, None)
                except Exception as e:  # noqa: BLE001 — capture teardown only
                    logger.debug("profiler capture teardown failed: %s", e)
            if trace_span_cm is not None:
                from ray_tpu.util import tracing as _tracing

                trace_span_cm.__exit__(None, None, None)
                _tracing.detach_context()

    def _report_direct(self, spec: TaskSpec, result, error, reply):
        """Direct-push completion: results travel back IN the push reply
        to the caller's memory store (reference: PushTask reply carries
        return objects). Large results go to the local shm store and are
        registered with the controller directory; inline results with
        nested refs are also registered so containment pins exist."""
        results = []
        if error is None:
            try:
                if spec.num_returns == 1:
                    values = [result]
                else:
                    values = list(result)
                    if len(values) != spec.num_returns:
                        raise ValueError(
                            f"task {spec.name} returned {len(values)} values, "
                            f"expected num_returns={spec.num_returns}"
                        )
                from ray_tpu.core.client import _serialize_parts_capturing
                from ray_tpu.core.memory_census import task_site
                from ray_tpu.utils.serialization import assemble_parts

                # census attribution label — "" (no-op) when the census
                # is disabled; interned, so unique task names stay bounded
                site = task_site(spec.name)
                for oid, value in zip(spec.return_ids(), values):
                    meta, raws, total, contained = _serialize_parts_capturing(value)
                    if contained:
                        # nested refs escape to the caller → must be
                        # globally resolvable + containment-pinned
                        self.core.promote_refs(contained)
                    if total <= self.core.inline_limit:
                        data = assemble_parts(meta, raws)
                        if contained:
                            self.core._call(
                                "object_put_inline", oid, data, False, contained,
                                callsite=site,
                            )
                        # 5th element: globally registered — the caller
                        # must mark its entry promoted so ref flushes
                        # reach the controller (else the record + its
                        # containment pins leak forever)
                        results.append((oid, "inline", data, False, bool(contained)))
                    else:
                        self.core.plasma.put_parts(oid, meta, raws, total)
                        self.core._call(
                            "object_put_shm", oid, total, self.core.node_id,
                            False, contained or [],
                            callsite=site,
                        )
                        results.append((oid, "shm"))
            except Exception:  # noqa: BLE001 — unpicklable results
                results = []
                error = TaskError(spec.name, traceback.format_exc(), None)
        self._events.append(
            {
                "ts": time.time(),
                "kind": "task",
                "type": spec.task_type.name,
                "task_id": spec.task_id.hex(),
                "name": spec.name,
                "state": "FINISHED" if error is None else "FAILED",
            }
        )
        if (
            spec.task_type == TaskType.NORMAL_TASK
            and any(r[1] == "shm" for r in results)
        ):
            # shm results are reconstructible — give the controller the
            # lineage the legacy path would have recorded (reference:
            # owner-side TaskManager lineage feeding ObjectRecoveryManager)
            self.core._submit("task_lineage", spec)
        self._reply(reply, (results, error))

    def _report(self, spec: TaskSpec, result, error):
        if spec.is_streaming and error is None:
            self._report_stream(spec, result)
            return
        results = []
        if error is None:
            try:
                if spec.num_returns == 1:
                    values = [result]
                else:
                    values = list(result)
                    if len(values) != spec.num_returns:
                        raise ValueError(
                            f"task {spec.name} returned {len(values)} values, "
                            f"expected num_returns={spec.num_returns}"
                        )
                from ray_tpu.core.client import _serialize_parts_capturing
                from ray_tpu.utils.serialization import assemble_parts

                for oid, value in zip(spec.return_ids(), values):
                    # Refs nested in a return value are pinned by the
                    # return object (containment) until it is freed —
                    # otherwise the worker's own ref drop could GC a
                    # ray_tpu.put() object before the caller ever sees it.
                    meta, raws, total, contained = _serialize_parts_capturing(value)
                    if contained:
                        self.core.promote_refs(contained)
                    if total <= self.core.inline_limit:
                        results.append(
                            (oid, "inline", assemble_parts(meta, raws), False, contained)
                        )
                    else:
                        self.core.plasma.put_parts(oid, meta, raws, total)
                        results.append((oid, "shm", total, contained))
            except Exception:  # noqa: BLE001 — unpicklable results must not hang the caller
                results = []
                error = TaskError(spec.name, traceback.format_exc(), None)
        try:
            self.core._call("task_done", spec.task_id, results, error)
        except rpc.ConnectionLost:
            os._exit(1)

    def _report_stream(self, spec: TaskSpec, result):
        """Stream generator items as they are produced: each yield becomes
        its own object (reference: streaming generator execution,
        _raylet.pyx:1077). This thread runs the generator and never waits
        for a shipment; what it has yielded leaves by ``_StreamShipper``."""
        from ray_tpu.core.client import _serialize_capturing
        from ray_tpu.core.memory_census import task_site as _task_site

        shipper = self._stream_shipper()
        site = _task_site(spec.name)
        index = 0
        error = None
        try:
            for item in result:
                if spec.task_id in self.cancelled:
                    # Consumer cancelled mid-stream (abandoned LLM stream):
                    # stop producing; close() runs the generator's finally
                    # blocks so replica-side resources are released.
                    try:
                        result.close()
                    except Exception:  # noqa: BLE001 — user close errors
                        logger.exception("stream close failed for %s", spec.name)
                    break
                data, contained = _serialize_capturing(item)
                shipper.add(spec.task_id, index, data, False, contained, site)
                index += 1
        except Exception as e:  # noqa: BLE001 — mid-stream error → final item
            tb = traceback.format_exc()
            err_item = e if isinstance(e, TaskError) else TaskError(spec.name, tb, None)
            from ray_tpu.core import log_plane

            log_plane.record_task_error(spec.name, spec.task_id.hex(), e, tb)
            shipper.add(spec.task_id, index, serialize(err_item), True, None, "")
        # Every item reaches the controller before the task's end does.
        shipper.flush(spec.task_id)
        try:
            self.core._call("task_done", spec.task_id, [], error)
        except rpc.ConnectionLost:
            os._exit(1)

    def _stream_shipper(self) -> "_StreamShipper":
        with self._shipper_gate:
            if self.core.stream_shipper is None:
                self.core.stream_shipper = _StreamShipper(self.core)
            return self.core.stream_shipper


class _StreamShipper:
    """Group commit of what this process's streaming generators have yielded.

    ``add`` (a generator's thread) files one serialized item and returns at
    once. ONE thread ships: whatever has accumulated, of every stream, leaves
    as one ``stream_items`` call to the controller, each stream's consecutive
    items as one run; while that call is on its way the next run gathers. An
    idle stream's lone item leaves at once: nothing waits for company, there is
    no timer and no size. An item over the inline limit is written to this
    node's store by the thread that yielded it, and only its size rides the
    call. Counts: ``stream_items_total`` and ``stream_shipments_total`` (their
    ratio is items a shipment) in this process's metric registry, and the same
    two as plain numbers here."""

    def __init__(self, core: CoreWorker):
        from ray_tpu.util.metrics import Counter

        self.core = core
        self._cv = threading.Condition()
        self._pending: list = []  # (task_id, index, entry, callsite), in yield order
        self._unshipped: Dict[TaskID, int] = {}
        self.items = self.shipments = 0
        self._items = Counter(
            "stream_items_total", "Items streaming generators yielded in this process")
        self._shipments = Counter(
            "stream_shipments_total",
            "Controller calls that carried them (items a shipment = stream_items_total / this)")
        threading.Thread(target=self._ship, daemon=True, name="stream-shipper").start()

    def add(self, task_id: TaskID, index: int, data: bytes, is_error: bool,
            contained: Optional[list], callsite: str):
        from ray_tpu.utils.ids import ObjectID

        if contained:
            self.core.promote_refs(contained)
        if len(data) <= self.core.inline_limit:
            entry = ("inline", data, is_error, contained or [])
        else:
            self.core.plasma.put_bytes(ObjectID.for_task_return(task_id, index), data)
            entry = ("shm", len(data), is_error, contained or [])
        with self._cv:
            self._pending.append((task_id, index, entry, callsite))
            self._unshipped[task_id] = self._unshipped.get(task_id, 0) + 1
            self._cv.notify_all()

    def flush(self, task_id: TaskID):
        """Return when every item of ``task_id`` added so far has shipped."""
        with self._cv:
            while self._unshipped.get(task_id):
                # bounded by the shipment's own call (the control timeout): ``_ship``
                # counts a run down whether its call returned or raised
                self._cv.wait()  # ray-tpu: lint-ignore[RTL008]

    def _ship(self):
        while True:
            with self._cv:
                while not self._pending:
                    self._cv.wait()  # ray-tpu: lint-ignore[RTL008] — the shipper idles until a generator yields
                pending, self._pending = self._pending, []
            runs: Dict[TaskID, list] = {}
            for task_id, index, entry, callsite in pending:
                run = runs.get(task_id)
                if run is None:
                    run = runs[task_id] = [task_id, index, callsite, []]
                run[3].append(entry)
            try:
                self.core._call("stream_items", self.core.node_id, list(runs.values()))
            except rpc.ConnectionLost:
                os._exit(1)
            except Exception:  # noqa: BLE001 — the consumer times out; keep shipping
                logger.exception("stream shipment of %d items failed", len(pending))
            self.items += len(pending)
            self.shipments += 1
            self._items.inc(len(pending))
            self._shipments.inc(1)
            with self._cv:
                for task_id, _first, _site, entries in runs.values():
                    left = self._unshipped[task_id] - len(entries)
                    if left:
                        self._unshipped[task_id] = left
                    else:
                        del self._unshipped[task_id]
                self._cv.notify_all()


class _DepError(Exception):
    """Carrier for a failed dependency's ORIGINAL error."""

    def __init__(self, inner):
        self.inner = inner


def _resolve_reply(item):
    fut, payload = item
    if not fut.done():
        fut.set_result(payload)


def _maybe_async(result):
    # inspect.iscoroutine, NOT asyncio.iscoroutine: the latter also
    # matches plain generator objects (legacy generator-based coroutine
    # support, Python ≤3.10), which would asyncio.run() streaming task
    # generators instead of handing them to _report_stream.
    import inspect

    if inspect.iscoroutine(result):
        return asyncio.run(result)
    return result


def main():
    logging.basicConfig(level=logging.INFO, format="[worker] %(levelname)s %(message)s")
    from ray_tpu.util import lockwatch

    lockwatch.maybe_install()  # RAY_TPU_LOCKWATCH=1: watch locks created from here on
    from ray_tpu.util import chaos

    chaos.install_fault_plan_from_env()  # RAY_TPU_FAULT_PLAN: deterministic chaos
    addr = os.environ["RAY_TPU_CONTROLLER"]
    node_id = NodeID.from_hex(os.environ["RAY_TPU_NODE_ID"])
    worker_id = WorkerID.from_hex(os.environ["RAY_TPU_WORKER_ID"])
    shm_dir = os.environ["RAY_TPU_SHM_DIR"]

    from ray_tpu.utils.net import bind_host, host_ip

    handler = WorkerHandler()
    loop_runner = rpc.EventLoopThread("worker-io")
    # Direct-transport listener: callers push actor tasks straight here
    # (reference: each worker hosts a CoreWorkerService gRPC server).
    # Loopback unless RAY_TPU_NODE_IP opts this host into multi-host.
    _server, listen_port = loop_runner.run(rpc.serve(handler, bind_host(), 0))
    core = CoreWorker(
        addr,
        mode="worker",
        loop_runner=loop_runner,
        handler=handler,
        worker_id=worker_id,
        node_id=node_id,
        local_shm_dir=shm_dir,
        listen_addr=f"{host_ip()}:{listen_port}",
    )
    handler._controller_peer = core.peer
    # Structured log plane (core/log_plane.py): stamp every logging
    # record / print() line / task traceback with {node, worker, task,
    # severity, ts} into the JSONL sidecar next to this worker's raw log,
    # rotate both at log_rotate_bytes, and ship ERROR records to the
    # controller's error index. Installed BEFORE the executor attaches so
    # buffered tasks' output is captured too.
    if core.config.get("log_structured", True):
        from ray_tpu.core import log_plane

        log_plane.install(
            core.session_dir,
            node_id=node_id.hex(),
            worker_id=worker_id.hex(),
            capture_streams=True,
            rotate_bytes=int(core.config.get("log_rotate_bytes", 64 << 20)),
        )
        log_plane.start_ship_loop(core)
    # Make the full public API usable from inside tasks (nested tasks,
    # ray_tpu.get/put in user code) BEFORE any buffered task can run.
    from ray_tpu.core import api
    from ray_tpu import runtime_context

    runtime_context._set_process(node_id.hex(), worker_id.hex())
    api._attach_worker(core)
    handler.attach_executor(TaskExecutor(core))
    # Device telemetry (per-device HBM + compile tracking): no-ops until
    # user code imports jax in this worker, then reports ~every poll.
    from ray_tpu.core.node_telemetry import start_process_telemetry

    start_process_telemetry(core)
    # Continuous low-rate CPU sampling for incident auto-capture (off
    # unless profiling_continuous_hz is configured).
    from ray_tpu.util import profiling

    profiling.ensure_continuous()
    agent_addr = os.environ.get("RAY_TPU_AGENT_ADDR", "")
    if agent_addr:
        # Direct-pool worker spawned by a node agent: announce to the
        # agent's free-worker view (reference: worker registration with
        # its raylet). The connection stays open; the agent uses it to
        # retire the worker and to observe its death.
        async def _attach():
            host, port = agent_addr.rsplit(":", 1)
            peer = await rpc.connect(host, int(port), handler)
            await peer.notify(
                "worker_attach", worker_id.hex(), f"{host_ip()}:{listen_port}"
            )
            handler._agent_peer = peer  # keep alive

        loop_runner.run(_attach())

    # serve-forever park by design; exit via rpc_exit / os._exit  # ray-tpu: lint-ignore[RTL008]
    threading.Event().wait()


if __name__ == "__main__":
    main()
