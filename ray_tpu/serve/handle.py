"""DeploymentHandle: the client-side router.

Reference: python/ray/serve/handle.py (DeploymentHandle/DeploymentResponse)
and the power-of-two-choices replica scheduler
(serve/_private/replica_scheduler/pow_2_scheduler.py:51). Routing state is
client-side: the handle caches the replica list by controller version and
tracks its own in-flight counts; each call samples two replicas and picks
the less loaded (p2c), the same algorithm the reference router runs.
"""
from __future__ import annotations

import collections
import logging
import random
import threading
import time
from typing import Any, Dict, Optional

logger = logging.getLogger("ray_tpu.serve")


class DeploymentResponse:
    """Future-like result of handle.remote() (reference: handle.py
    DeploymentResponse). Passable as an argument to further handle calls —
    it degrades to its underlying ObjectRef so the value flows worker-to-
    worker without driver roundtrips (reference: response passing)."""

    def __init__(self, ref, on_done):
        self._ref = ref
        fut = ref.future()
        fut.add_done_callback(lambda _f: on_done())
        self._fut = fut

    def result(self, timeout: Optional[float] = None):
        values = self._fut.result(timeout)
        return values[0]

    def _to_object_ref(self):
        return self._ref

    def __reduce__(self):
        # Crossing a process boundary: ship the plain ref.
        from ray_tpu.core.object_ref import ObjectRef

        return (ObjectRef, (self._ref.id,))


class DeploymentStreamingResponse:
    """Iterator over a streaming deployment call's items (reference:
    handle.py DeploymentResponseGenerator). Each ``next()`` blocks until
    the replica yields the next item (bounded by ``item_timeout_s``).

    Items travel by value, a run at a time: whatever the replica had
    shipped when this side asked comes in one controller call
    (``ObjectRefGenerator.take``) and is handed out one ``next()`` at a
    time; ``in_hand()`` gives a writer the rest of the run without a wait.

    The router's in-flight count is released on exhaustion, on ANY
    error, on close(), and as a last resort on GC — an abandoned stream
    (client disconnect, the normal LLM cancel path) must not leave a
    phantom in-flight count biasing p2c routing and autoscaling forever.
    """

    def __init__(self, ref_gen, on_done, item_timeout_s: Optional[float] = 60.0):
        self._gen = ref_gen
        self._gen.timeout = item_timeout_s
        self._on_done = on_done
        self._finished = False
        self._exhausted = False
        self._run: collections.deque = collections.deque()  # (value, is_error) in hand

    def _finish(self):
        if not self._finished:
            self._finished = True
            if not self._exhausted:
                # Abandoned before exhaustion (client disconnect — the
                # normal LLM cancel path): cancel the replica-side
                # generator task so it stops producing and pinning stream
                # objects (reference: serve request cancellation →
                # ray.cancel on the replica task).
                try:
                    from ray_tpu.core.api import _require_worker

                    _require_worker().cancel_task(self._gen.task_id, False)
                except Exception as e:  # noqa: BLE001 — best-effort on teardown
                    logger.debug("stream cancel on teardown failed: %s", e)
            try:
                self._on_done()
            except Exception as e:  # noqa: BLE001 — release must never raise
                logger.debug("stream release callback failed: %s", e)

    def close(self):
        self._finish()

    def __del__(self):
        self._finish()

    def __iter__(self):
        return self

    def __next__(self):
        if not self._run:
            try:
                self._run.extend(self._gen.take())
            except StopIteration:
                self._exhausted = True
                self._finish()
                raise
            except BaseException:
                self._finish()
                raise
        value, is_error = self._run.popleft()
        if is_error:
            self._finish()
            raise value
        return value

    def in_hand(self) -> list:
        """The items of the run in hand that ``next()`` has not given out
        yet, without a wait (none: the next ``next()`` asks the controller).
        An error among them stays in hand, for the ``next()`` after."""
        out = []
        while self._run and not self._run[0][1]:
            out.append(self._run.popleft()[0])
        return out


class _Router:
    def __init__(self, deployment_name: str, controller):
        import uuid

        self._name = deployment_name
        self._id = uuid.uuid4().hex[:12]
        self._controller = controller
        self._lock = threading.Lock()
        self._replicas: list = []
        self._local: list = []
        self._by_model: Dict[str, list] = {}
        self._version = -1
        self._inflight: Dict[Any, int] = {}
        self._last_report = 0.0
        self._last_refresh = 0.0

    def _refresh(self, force: bool = False):
        import ray_tpu

        now = time.monotonic()
        if not force and self._replicas and now - self._last_refresh < 0.5:
            return
        self._last_refresh = now
        version = ray_tpu.get(self._controller.get_version.remote())
        if version != self._version:
            v, rows = ray_tpu.get(self._controller.get_replicas.remote(self._name))
            if rows is None:
                raise RuntimeError(f"deployment {self._name} does not exist")
            replicas = [r for r, _node, _models in rows]
            local = self._local_subset([(r, node) for r, node, _m in rows])
            by_model: Dict[str, list] = {}
            for r, _node, models in rows:
                for mid in models or ():
                    by_model.setdefault(mid, []).append(r)
            with self._lock:
                self._version = v
                self._replicas = replicas
                self._local = local
                self._by_model = by_model
                self._inflight = {r: self._inflight.get(r, 0) for r in replicas}

    @staticmethod
    def _local_subset(pairs) -> list:
        """Replicas co-located on this node — routed to preferentially
        (reference: pow_2_scheduler's prefer_local_node routing; the
        basis of the per-node proxy pattern). Node ids come from the
        serve controller with the replica list."""
        try:
            from ray_tpu.runtime_context import get_runtime_context

            my_node = get_runtime_context().get_node_id()
            if my_node is None:
                return []  # driver process — no node identity, no locality
            return [r for r, node in pairs if node is not None and node == my_node]
        except Exception:  # noqa: BLE001 — locality is best-effort
            return []

    def pick(self, multiplexed_model_id: str = ""):
        """p2c: sample two, take the one with fewer in-flight requests.
        With a model id, replicas that already hold the model win (the
        reference's model-affine pow-2 routing); if none holds it yet,
        fall back to the general pool — the chosen replica loads it and
        the next refresh makes the route sticky."""
        deadline = time.monotonic() + 30
        force = False
        while True:
            self._refresh(force)
            force = True  # empty replica list → poll the controller directly
            with self._lock:
                # Local-PREFERRED: co-located replicas win while they have
                # headroom comparable to the global pool; a saturated
                # local replica falls back to remote ones (reference:
                # prefer-local routing only when the local replica has
                # capacity).
                pool = self._replicas
                holders = self._by_model.get(multiplexed_model_id) if multiplexed_model_id else None
                if holders:
                    live = [r for r in holders if r in self._inflight]
                    if live:
                        pool = live
                elif self._local:
                    local_min = min(self._inflight.get(r, 0) for r in self._local)
                    global_min = min(
                        (self._inflight.get(r, 0) for r in self._replicas),
                        default=0,
                    )
                    if local_min <= global_min + 2:
                        pool = self._local
                if pool:
                    if len(pool) == 1:
                        chosen = pool[0]
                    else:
                        a, b = random.sample(pool, 2)
                        chosen = a if self._inflight.get(a, 0) <= self._inflight.get(b, 0) else b
                    self._inflight[chosen] = self._inflight.get(chosen, 0) + 1
                    return chosen
            if time.monotonic() > deadline:
                raise TimeoutError(f"no replicas for {self._name}")
            time.sleep(0.05)

    def done(self, replica):
        with self._lock:
            if replica in self._inflight and self._inflight[replica] > 0:
                self._inflight[replica] -= 1
        self._maybe_report()

    def _maybe_report(self):
        now = time.monotonic()
        if now - self._last_report < 1.0:
            return
        self._last_report = now
        with self._lock:
            n = max(len(self._replicas), 1)
            avg = sum(self._inflight.values()) / n
        try:
            self._controller.report_load.remote(self._name, self._id, avg)
        except Exception as e:  # noqa: BLE001 — controller restarting
            logger.debug("router load report failed: %s", e)


class DeploymentHandle:
    def __init__(self, deployment_name: str, controller, method_name: str = "__call__"):
        self.deployment_name = deployment_name
        self._controller = controller
        self._method = method_name
        self._mux_id = ""
        self._router = _Router(deployment_name, controller)

    def __getattr__(self, name: str) -> "DeploymentHandle":
        if name.startswith("_"):
            raise AttributeError(name)
        return self._clone(method=name)

    def _clone(self, method=None, mux_id=None) -> "DeploymentHandle":
        h = DeploymentHandle.__new__(DeploymentHandle)
        h.deployment_name = self.deployment_name
        h._controller = self._controller
        h._method = method if method is not None else self._method
        h._mux_id = mux_id if mux_id is not None else self._mux_id
        h._router = self._router  # share routing state across method handles
        return h

    def options(self, method_name: Optional[str] = None,
                multiplexed_model_id: Optional[str] = None) -> "DeploymentHandle":
        """``multiplexed_model_id``: route to a replica already holding
        the model (reference: handle.options(multiplexed_model_id=...))."""
        return self._clone(method=method_name, mux_id=multiplexed_model_id)

    def _request_meta(self) -> dict:
        """Per-request metadata riding with the call: the submit
        timestamp lets the replica compute queue wait (submit→execution
        start) and e2e latency without clock plumbing of its own."""
        return {
            "submit_ts": time.time(),
            "deployment": self.deployment_name,
            "method": self._method,
        }

    def remote(self, *args, **kwargs) -> DeploymentResponse:
        from ray_tpu.util import tracing

        args = tuple(_unwrap(a) for a in args)
        kwargs = {k: _unwrap(v) for k, v in kwargs.items()}
        meta = self._request_meta()
        # The submit span parents the replica-side execution span (the
        # trace context is injected into the actor task at .remote()).
        with tracing.start_span(
            f"handle:{self.deployment_name}.{self._method}"
        ):
            replica = self._router.pick(self._mux_id)
            ref = replica.handle_request.remote(
                self._method, args, kwargs, self._mux_id, meta
            )
        return DeploymentResponse(ref, on_done=lambda r=replica: self._router.done(r))

    def stream(self, *args, **kwargs) -> DeploymentStreamingResponse:
        """Streaming call: the deployment method is a generator; items
        arrive as they are yielded (reference: handle.options(stream=True)
        → DeploymentResponseGenerator; the LLM token-streaming path)."""
        from ray_tpu.util import tracing

        args = tuple(_unwrap(a) for a in args)
        kwargs = {k: _unwrap(v) for k, v in kwargs.items()}
        meta = self._request_meta()
        with tracing.start_span(
            f"handle:{self.deployment_name}.{self._method}", {"stream": True}
        ):
            replica = self._router.pick(self._mux_id)
            gen = replica.handle_request_stream.options(num_returns="streaming").remote(
                self._method, args, kwargs, self._mux_id, meta
            )
        return DeploymentStreamingResponse(
            gen, on_done=lambda r=replica: self._router.done(r)
        )

    def __reduce__(self):
        return (_rebuild_handle, (self.deployment_name, self._method, self._mux_id))


def _rebuild_handle(name: str, method: str, mux_id: str = ""):
    from ray_tpu.serve.api import get_deployment_handle

    h = get_deployment_handle(name)
    return h._clone(method=method, mux_id=mux_id)


def _unwrap(v):
    return v._to_object_ref() if isinstance(v, DeploymentResponse) else v
