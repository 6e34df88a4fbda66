"""Control-plane flight recorder: lifecycle events for tasks, actors,
placement groups, worker leases, and worker processes.

Reference: src/ray/gcs/gcs_server/gcs_task_manager.{h,cc} — the GCS task
manager ingests batched ``TaskEvents`` from every worker into a bounded
store and serves the state API / ``ray timeline`` from it. Same shape
here, generalized past tasks: every control-plane entity records
state-TRANSITION events (``submitted → queued → lease_granted →
worker_assigned → running → finished/failed``, actor restarts, PG
reserve/commit) into a bounded ring, and each transition's **dwell time**
(how long the entity sat in the previous state) feeds per-(kind, state)
sample rings and cluster metrics.

Writers:
  controller   — authoritative for controller-dispatched tasks, actors,
                 PGs, leases, and worker registration (records in-process)
  workers      — direct-push task RUNNING/FINISHED events ride the
                 existing ``task_events`` batch channel (worker_main)
  drivers      — direct-path SUBMITTED/WORKER_ASSIGNED events ship over
                 the same channel (normal_direct)
  node agents  — worker SPAWNED events ship with their telemetry loop

The controller's recorder is the single aggregation point: cross-process
events are folded in by :meth:`LifecycleRecorder.ingest`, which tolerates
out-of-order arrival across flush channels (a late-arriving older event
is ring-recorded but never corrupts dwell accounting).

Everything is bounded: the event ring (``lifecycle_ring_size``), the
per-state dwell sample rings (``lifecycle_dwell_samples``), the open-
entity map (LRU), and the metric tag space (kind/state/reason only —
never task ids).
"""
from __future__ import annotations

import collections
import time
from typing import Any, Dict, List, Optional, Tuple

# Pubsub channel carrying worker/actor/node DEATH (and node DRAIN)
# events as they are recorded — subscribers (e.g. the train
# BackendExecutor's gang watcher) learn about a failure push-style in
# ~the connection-loss latency instead of waiting out an RPC timeout.
DEATH_CHANNEL = "lifecycle:deaths"

# Terminal states pop the entity's open entry: the transition chain is
# complete and the entity must not pin LRU space.
TERMINAL_STATES = frozenset(
    {
        "FINISHED",
        "FAILED",
        "DEAD",
        "REMOVED",
        "REGISTERED",  # worker spawn chain: SPAWNED -> REGISTERED
        "GRANTED",  # lease chain: REQUESTED -> GRANTED
        "ABANDONED",  # lease requester died/timed out while parked
    }
)

# "Why pending" attribution vocabulary (bounded — these are metric tags).
PENDING_REASONS = (
    "insufficient_resources",  # feasible nodes exist, none has capacity now
    "no_idle_worker",  # resources free but the node's worker pool is busy
    "pg_unready",  # task targets a placement group not yet CREATED
    "spillback",  # every candidate node's pool rejected the task
    "infeasible",  # no node could EVER satisfy the demand
    "waiting_deps",  # parked on an unresolved object dependency
    "waiting_actor",  # actor task queued while the actor is not ALIVE
)

# Controller-internal state names -> the canonical lifecycle vocabulary
# (the legacy ``self.events`` ring keeps the old names for back-compat).
_CANONICAL = {
    "PENDING_SCHEDULING": "SUBMITTED",
    "PENDING_CREATION": "SUBMITTED",
    "CREATING": "WORKER_ASSIGNED",
    "CREATION_FAILED": "FAILED",
    "RECONSTRUCTING": "RETRYING",
}

_INGEST_KINDS = frozenset(
    {"task", "actor", "pg", "lease", "worker", "node", "action"}
)

# Extra attrs forwarded from shipped events into the ring (never metric
# tags): the self-healing "action" events carry their audit fields here.
_INGEST_ATTRS = ("name", "node", "worker", "actuator", "trigger", "target",
                 "outcome", "dry_run", "remote", "chip_wait_ms")

_DWELL_BOUNDARIES_MS = (
    1, 5, 10, 25, 50, 100, 250, 500, 1000, 2500, 5000, 15000, 60000,
)

_metrics: Optional[Dict[str, Any]] = None


def _get_metrics() -> Dict[str, Any]:
    """Process-wide metric singletons (Metric registers globally; a
    recorder re-created in tests must not duplicate series)."""
    global _metrics
    if _metrics is None:
        from ray_tpu.util.metrics import Counter, Histogram

        _metrics = {
            "dwell": Histogram(
                "task_state_dwell_ms",
                "Time spent in each lifecycle state before transitioning out",
                boundaries=_DWELL_BOUNDARIES_MS,
                tag_keys=("kind", "state"),
            ),
            "transitions": Counter(
                "task_state_transitions_total",
                "Lifecycle state transitions by entity kind and new state",
                ("kind", "state"),
            ),
            "reasons": Counter(
                "task_pending_reason_total",
                "Why-pending attribution: why a task/lease could not be placed",
                ("reason",),
            ),
            "lease": Histogram(
                "lease_latency_ms",
                "Worker-lease scheduling latency (lease request to grant)",
                boundaries=_DWELL_BOUNDARIES_MS,
            ),
        }
    return _metrics


class LifecycleRecorder:
    """Bounded flight recorder for control-plane state transitions.

    Single-writer by design: the controller mutates it only from its
    asyncio loop (the same discipline as every other controller
    structure), so no lock is needed.
    """

    def __init__(self, ring_size: int = 20000, dwell_samples: int = 4096,
                 enabled: bool = True):
        self.enabled = enabled
        self.events: "collections.deque[dict]" = collections.deque(maxlen=ring_size)
        # (kind, id) -> [state, ts, pending_reason] for entities mid-chain.
        self._open: "collections.OrderedDict[Tuple[str, str], list]" = (
            collections.OrderedDict()
        )
        self._max_open = max(4 * ring_size, 50000)
        # (kind, id) -> terminal ts for recently-closed chains (LRU): a
        # late-arriving non-terminal half (cross-channel flush race, e.g.
        # a fast task's driver SUBMITTED after the worker's FINISHED)
        # must not re-open a finished entity — but a GENUINE re-open with
        # a newer ts (lineage reconstruction) still may.
        self._closed: "collections.OrderedDict[Tuple[str, str], float]" = (
            collections.OrderedDict()
        )
        self._dwell: Dict[Tuple[str, str], collections.deque] = {}
        self._dwell_samples = dwell_samples
        self._counts: Dict[Tuple[str, str], int] = {}
        self._reasons: Dict[str, int] = {}
        self._recorded = 0
        # Cluster-metric sync is THROTTLED: per-event Counter/Histogram
        # calls cost ~10us each (tags-key + cap resolution + lock) which
        # measurably taxes the controller loop at envelope depths, so
        # record() only accumulates locally and a bulk flush
        # (Histogram.observe_many / Counter.inc(n)) runs at most every
        # _METRIC_FLUSH_S — and on snapshot(), so readers never see a
        # stale rollup.
        self._pending_dwell: Dict[Tuple[str, str], list] = {}
        self._pending_lease: list = []
        self._pending_transitions: Dict[Tuple[str, str], int] = {}
        self._last_metric_flush = time.monotonic()

    _METRIC_FLUSH_S = 0.5

    # ------------------------------------------------------------------
    def record(self, kind: str, eid: str, state: str,
               ts: Optional[float] = None, **attrs) -> Optional[dict]:
        """Record one transition. ``attrs`` go into the ring event only
        (free-form context: name/node/reason) — never into metric tags."""
        if not self.enabled:
            return None
        state = _CANONICAL.get(state, state)
        if ts is None:
            ts = time.time()
        key = (kind, eid)
        entry = self._open.get(key)
        prev = None
        dwell_ms = None
        stale = False
        if entry is not None:
            if ts >= entry[1]:
                prev = entry[0]
                dwell_ms = (ts - entry[1]) * 1000.0
            else:
                # Out-of-order cross-channel arrival (e.g. a driver's
                # SUBMITTED flushing after the worker's RUNNING): keep the
                # newer open state, record the event without dwell.
                stale = True
        terminal = state in TERMINAL_STATES
        if terminal:
            # Close the chain even when the terminal event arrived
            # out-of-order (cross-host clock skew can stamp a worker's
            # FINISHED behind the driver's WORKER_ASSIGNED): leaving the
            # entry open would leak a ghost into `open`/pending counts.
            self._open.pop(key, None)
            while len(self._closed) >= self._max_open:
                self._closed.popitem(last=False)
            self._closed[key] = ts if entry is None else max(ts, entry[1])
        elif not stale:
            if entry is None:
                closed_ts = self._closed.get(key)
                if closed_ts is not None:
                    if ts <= closed_ts:
                        # late half of an already-finished chain: record
                        # the event, never re-open (a ghost open entry
                        # would inflate `open`/pending counts forever)
                        stale = True
                    else:
                        self._closed.pop(key, None)  # genuine re-open
            if not stale:
                if entry is None:
                    if len(self._open) >= self._max_open:
                        self._open.popitem(last=False)
                    self._open[key] = [state, ts, None]
                else:
                    entry[0], entry[1], entry[2] = state, ts, None
                    self._open.move_to_end(key)
        if dwell_ms is not None and prev is not None:
            pkey = (kind, prev)
            dq = self._dwell.get(pkey)
            if dq is None:
                dq = self._dwell[pkey] = collections.deque(
                    maxlen=self._dwell_samples
                )
            dq.append(dwell_ms)
            pend = self._pending_dwell.get(pkey)
            if pend is None:
                pend = self._pending_dwell[pkey] = []
            pend.append(dwell_ms)
            if kind == "lease" and state == "GRANTED":
                self._pending_lease.append(dwell_ms)
        skey = (kind, state)
        self._counts[skey] = self._counts.get(skey, 0) + 1
        self._pending_transitions[skey] = self._pending_transitions.get(skey, 0) + 1
        now_m = time.monotonic()
        if now_m - self._last_metric_flush >= self._METRIC_FLUSH_S:
            self.flush_metrics(now_m)
        ev = {"ts": ts, "kind": kind, "id": eid, "state": state}
        if prev is not None:
            ev["prev"] = prev
        if dwell_ms is not None:
            ev["dwell_ms"] = round(dwell_ms, 3)
        for k, v in attrs.items():
            if v is not None and v != "":
                ev[k] = v
        self.events.append(ev)
        self._recorded += 1
        return ev

    def record_batch(self, kind: str, state: str, n: int,
                     ts: Optional[float] = None, prev: Optional[str] = None,
                     dwell_ms: Optional[float] = None, **attrs) -> Optional[dict]:
        """Record ``n`` identical transitions as ONE ring event.

        The batched lease path grants N leases in one controller
        round-trip; recording them one-by-one would re-serialize exactly
        what the batching won (N record() calls, N ring appends, N
        entries churning the _open LRU). This folds the whole grant
        batch into one ring event carrying ``count``, one count bump of
        n, and one bulk dwell extension.

        Only for chains that OPEN AND CLOSE within the same call site
        (e.g. lease REQUESTED→GRANTED inside rpc_lease_batch): it never
        touches the ``_open``/``_closed`` maps, so out-of-order merging
        against per-event record() calls for the same entities is the
        caller's responsibility.
        """
        if not self.enabled or n <= 0:
            return None
        state = _CANONICAL.get(state, state)
        if ts is None:
            ts = time.time()
        if dwell_ms is not None and prev is not None:
            pkey = (kind, prev)
            dq = self._dwell.get(pkey)
            if dq is None:
                dq = self._dwell[pkey] = collections.deque(
                    maxlen=self._dwell_samples
                )
            dq.extend([dwell_ms] * n)
            pend = self._pending_dwell.get(pkey)
            if pend is None:
                pend = self._pending_dwell[pkey] = []
            pend.extend([dwell_ms] * n)
            if kind == "lease" and state == "GRANTED":
                self._pending_lease.extend([dwell_ms] * n)
        skey = (kind, state)
        self._counts[skey] = self._counts.get(skey, 0) + n
        self._pending_transitions[skey] = (
            self._pending_transitions.get(skey, 0) + n
        )
        now_m = time.monotonic()
        if now_m - self._last_metric_flush >= self._METRIC_FLUSH_S:
            self.flush_metrics(now_m)
        ev = {"ts": ts, "kind": kind, "id": "(batch)", "state": state,
              "count": n}
        if prev is not None:
            ev["prev"] = prev
        if dwell_ms is not None:
            ev["dwell_ms"] = round(dwell_ms, 3)
        for k, v in attrs.items():
            if v is not None and v != "":
                ev[k] = v
        self.events.append(ev)
        self._recorded += n
        return ev

    def pending_reason(self, kind: str, eid: str, reason: Optional[str]):
        """Attribute WHY an entity is stuck pending. Counted once per
        reason CHANGE (a blocked class re-visited every pump must not
        inflate the counter); the current reason is kept on the open
        entry so summaries can show live pending attribution."""
        if not self.enabled or not reason:
            return
        entry = self._open.get((kind, eid))
        if entry is None:
            # Unknown/LRU-evicted entity: without the entry there is no
            # dedup state, and counting every pump re-visit would inflate
            # the counter with pump frequency — skip instead (every call
            # site records a transition before attributing).
            return
        if entry[2] == reason:
            return
        entry[2] = reason
        self._reasons[reason] = self._reasons.get(reason, 0) + 1
        _get_metrics()["reasons"].inc(1, {"reason": reason})

    def ingest(self, ev: dict):
        """Fold one cross-process event (worker/driver/agent batches)."""
        if not self.enabled or not isinstance(ev, dict):
            return
        kind = ev.get("kind")
        if kind not in _INGEST_KINDS:
            return
        eid = ev.get("task_id") or ev.get("id")
        state = ev.get("state")
        if not eid or not state:
            return
        attrs = {k: ev.get(k) for k in _INGEST_ATTRS if ev.get(k) is not None}
        self.record(kind, eid, state, ts=ev.get("ts"), **attrs)

    def flush_metrics(self, now_m: Optional[float] = None):
        """Sync accumulated transitions/dwell into the cluster metrics
        (bulk: one tags-key + lock per (kind, state), not per event)."""
        self._last_metric_flush = now_m if now_m is not None else time.monotonic()
        if not (
            self._pending_transitions or self._pending_dwell or self._pending_lease
        ):
            return
        m = _get_metrics()
        trans, self._pending_transitions = self._pending_transitions, {}
        for (kind, state), n in trans.items():
            # bounded vocabulary: kinds are the 5 _INGEST_KINDS and states
            # the canonical lifecycle set — never entity ids
            m["transitions"].inc(n, {"kind": kind, "state": state})  # ray-tpu: lint-ignore[RTL004]
        dwell, self._pending_dwell = self._pending_dwell, {}
        for (kind, state), vals in dwell.items():
            m["dwell"].observe_many(vals, {"kind": kind, "state": state})
        lease, self._pending_lease = self._pending_lease, []
        if lease:
            m["lease"].observe_many(lease)

    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        """Aggregate view: per-(kind, state) transition counts and dwell
        percentiles, why-pending counters, currently-open entities by
        state, and ring accounting."""
        from ray_tpu.util.metrics import summarize_samples

        self.flush_metrics()

        states: Dict[str, Dict[str, dict]] = {}
        for (kind, state), n in sorted(self._counts.items()):
            states.setdefault(kind, {})[state] = {"count": n}
        for (kind, state), dq in sorted(self._dwell.items()):
            row = states.setdefault(kind, {}).setdefault(state, {"count": 0})
            row["dwell_ms"] = summarize_samples(dq)
        open_by: Dict[str, Dict[str, int]] = {}
        pending_now: Dict[str, int] = {}
        for (kind, _eid), entry in self._open.items():
            by = open_by.setdefault(kind, {})
            by[entry[0]] = by.get(entry[0], 0) + 1
            if entry[2]:
                pending_now[entry[2]] = pending_now.get(entry[2], 0) + 1
        return {
            "enabled": self.enabled,
            "states": states,
            "pending_reasons": dict(self._reasons),
            "pending_now": pending_now,
            "open": open_by,
            "events": {
                "recorded": self._recorded,
                "in_ring": len(self.events),
                "ring_size": self.events.maxlen,
            },
        }

    def tail(self, limit: int = 10000) -> List[dict]:
        n = len(self.events)
        if limit <= 0 or n == 0:
            return []
        if limit >= n:
            return list(self.events)
        import itertools

        # islice instead of list(...)[-limit:]: no full-ring copy on the
        # controller loop for a partial read.
        return list(itertools.islice(self.events, n - limit, n))


# ---------------------------------------------------------------------------
def to_chrome(events: List[dict]) -> List[dict]:
    """Lifecycle events -> Chrome-trace slices: per entity, consecutive
    transitions become complete ("X") events named by the state dwelled
    in, plus an instant for the final state. Loadable alongside span
    JSONL files in one chrome://tracing view (``ray-tpu timeline``)."""
    by_entity: Dict[Tuple[str, str], List[dict]] = {}
    for ev in events:
        if "kind" in ev and "id" in ev and "ts" in ev:
            by_entity.setdefault((ev["kind"], ev["id"]), []).append(ev)
    trace: List[dict] = []
    for (kind, eid), evs in by_entity.items():
        evs.sort(key=lambda e: e["ts"])
        pid = f"lifecycle:{kind}"
        tid = eid[:12]
        for a, b in zip(evs, evs[1:]):
            args = {"kind": kind, "id": eid, "next": b["state"]}
            if a.get("name"):
                args["name"] = a["name"]
            trace.append(
                {
                    "name": a["state"],
                    "cat": "lifecycle",
                    "ph": "X",
                    "ts": a["ts"] * 1e6,
                    "dur": max(0.0, (b["ts"] - a["ts"])) * 1e6,
                    "pid": pid,
                    "tid": tid,
                    "args": args,
                }
            )
        last = evs[-1]
        args = {"kind": kind, "id": eid}
        if last.get("name"):
            args["name"] = last["name"]
        if last.get("reason"):
            args["reason"] = last["reason"]
        trace.append(
            {
                "name": last["state"],
                "cat": "lifecycle",
                "ph": "i",
                "s": "t",
                "ts": last["ts"] * 1e6,
                "pid": pid,
                "tid": tid,
                "args": args,
            }
        )
    return trace
