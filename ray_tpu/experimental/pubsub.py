"""General pub/sub over the controller (reference: src/ray/pubsub/ —
Publisher/Subscriber used for object locations, errors, logs; the
reference batches long-polls, here messages push over each subscriber's
existing control connection).

    sub = pubsub.subscribe("events")
    pubsub.publish("events", {"x": 1})
    msg = sub.get(timeout=5)       # {"x": 1}
    sub.close()

Works from drivers and workers alike.
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Dict, List, Optional

# Reentrant: ``subscribe`` and ``close`` call the controller UNDER it, and
# the call that meets a lost connection reconnects on its own thread, which
# re-issues the process's subscriptions (``_resubscribe``) under it again.
# A plain lock there waited on itself for ever, and every later
# ``subscribe`` / ``close`` of the process behind it.
_lock = threading.RLock()
_subscribers: Dict[str, List["Subscriber"]] = {}


class Subscriber:
    def __init__(self, channel: str):
        self.channel = channel
        self._q: "queue.Queue" = queue.Queue()
        self._closed = False

    def get(self, timeout: Optional[float] = None) -> Any:
        """Next message (blocking). Raises queue.Empty on timeout."""
        return self._q.get(timeout=timeout)

    def get_nowait(self) -> Any:
        return self._q.get_nowait()

    def close(self):
        if self._closed:
            return
        self._closed = True
        # Controller RPC happens UNDER the lock: subscribe/unsubscribe
        # reach the controller in registry order, so a racing subscribe
        # on the same channel can never be cancelled out by this close.
        with _lock:
            subs = _subscribers.get(self.channel, [])
            if self in subs:
                subs.remove(self)
            if not subs:
                _subscribers.pop(self.channel, None)
                from ray_tpu.core.api import _require_worker

                try:
                    _require_worker()._call("unsubscribe", self.channel)
                except Exception:  # noqa: BLE001 — teardown
                    pass


def subscribe(channel: str) -> Subscriber:
    from ray_tpu.core.api import _require_worker

    sub = Subscriber(channel)
    with _lock:
        first = channel not in _subscribers
        _subscribers.setdefault(channel, []).append(sub)
        if first:
            try:
                _require_worker()._call("subscribe", channel)
            except BaseException:
                # roll back so a later subscribe() re-issues the RPC
                # instead of assuming the channel is live
                _subscribers[channel].remove(sub)
                if not _subscribers[channel]:
                    del _subscribers[channel]
                raise
    return sub


def publish(channel: str, message: Any) -> int:
    """Publish; returns the number of remote subscriber PROCESSES
    reached (local subscribers in other processes each count once)."""
    from ray_tpu.core.api import _require_worker

    return _require_worker()._call("publish", channel, message)


def _deliver(channel: str, message: Any):
    """Called by the process's RPC handler on pubsub_msg pushes."""
    with _lock:
        subs = list(_subscribers.get(channel, ()))
    for s in subs:
        s._q.put(message)


def _resubscribe(core):
    """Re-issue subscriptions on a fresh controller connection (called
    by CoreWorker after a reconnect — the restarted controller has no
    memory of this process's channels)."""
    with _lock:
        channels = [ch for ch, subs in _subscribers.items() if subs]
    for ch in channels:
        core._call("subscribe", ch)
