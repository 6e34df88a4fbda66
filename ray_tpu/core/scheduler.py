"""Cluster scheduling: policies + resource bookkeeping.

Reference: src/ray/raylet/scheduling/ — ``ClusterResourceScheduler``
(cluster_resource_scheduler.cc) picks nodes with pluggable policies
(policy/hybrid_scheduling_policy.h:50, scheduling_policy.h), and placement
groups reserve bundle resources through a 2-phase prepare/commit
(placement_group_resource_manager.h:44-84).

Architectural difference from the reference: scheduling here is
GCS-direct — the controller holds the authoritative resource view and
assigns leases itself (the reference supports this mode too:
gcs_actor_scheduler.cc:60 ``ScheduleByGcs``). Raylet-side spillover
scheduling can be reintroduced when nodes own their local view.
"""
from __future__ import annotations

import collections
import heapq
import itertools
import logging
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from ray_tpu.config import get_config
from ray_tpu.core.resources import NodeResources, ResourceSet
from ray_tpu.core.task_spec import SchedulingStrategy
from ray_tpu.util.guards import OWNER_THREAD, GuardedDict, GuardedSet
from ray_tpu.utils.ids import NodeID, PlacementGroupID

logger = logging.getLogger(__name__)

_sched_metrics: Optional[Dict[str, object]] = None


def _get_sched_metrics() -> Dict[str, object]:
    """Process-wide metric singletons (a scheduler re-created in tests
    must not duplicate series)."""
    global _sched_metrics
    if _sched_metrics is None:
        from ray_tpu.util.metrics import Counter

        _sched_metrics = {
            "fast": Counter(
                "scheduler_fast_path_total",
                "Placement decisions served by an O(1) path "
                "(native core or the demand-shape index)",
                ("strategy",),
            ),
            "full": Counter(
                "scheduler_full_scan_total",
                "Placement decisions that rescanned every node "
                "(label/affinity/PG strategies, exclude filters, cold shapes)",
            ),
        }
    return _sched_metrics


@dataclass
class ScheduleResult:
    node_id: Optional[NodeID]
    infeasible: bool = False  # no node could EVER run this → autoscaler hint
    # Why-pending attribution for a None placement (bounded vocabulary,
    # core/lifecycle.py PENDING_REASONS): "infeasible" vs
    # "insufficient_resources"; the pump layers pool/PG context on top.
    reason: Optional[str] = None


def _none_reason(node_id, infeasible: bool) -> Optional[str]:
    """Attribution for a native-core placement miss (the C++ core reports
    only the infeasible bit)."""
    if node_id is not None:
        return None
    return "infeasible" if infeasible else "insufficient_resources"


def match_label_expressions(exprs: Optional[Dict], labels: Dict[str, str]) -> bool:
    """Evaluate wire-form label expressions ({key: (op, values)}) against
    a node's labels (reference: util/scheduling_strategies.py:94-115
    In/NotIn/Exists/DoesNotExist)."""
    for key, (op, values) in (exprs or {}).items():
        present = key in labels
        val = labels.get(key)
        if op == "in":
            if not present or val not in values:
                return False
        elif op == "not_in":
            if present and val in values:
                return False
        elif op == "exists":
            if not present:
                return False
        elif op == "does_not_exist":
            if present:
                return False
        else:
            raise ValueError(f"unknown label operator {op!r}")
    return True


@dataclass
class _ShapeEntry:
    """Feasibility bucket for one demand shape (round 17, O(1) hot path).

    ``fits`` is the live set of nodes whose availability satisfies the
    shape RIGHT NOW, maintained incrementally by capacity-change
    callbacks; ``heap`` is a lazy-deleted min-heap over (pack-order
    position, node) of (a superset of) that set, so the hybrid policy's
    pack-first pick is a heap peek instead of a cluster rescan.
    Duplicate heap entries after a node leaves and re-enters ``fits``
    are harmless — membership in ``fits`` is the truth, stale tops are
    popped on peek. Any topology/drain/avoid change invalidates the
    whole cache (rare); only capacity changes are tracked per node.
    """

    demand: ResourceSet
    pos: Dict[NodeID, int] = field(default_factory=dict)
    fits: Set[NodeID] = field(default_factory=set)
    feasible: Set[NodeID] = field(default_factory=set)
    heap: List[Tuple[int, NodeID]] = field(default_factory=list)


class ClusterState:
    """Authoritative view of node resources (reference:
    ClusterResourceManager, cluster_resource_data.h).

    When the native toolchain is available the C++ scheduling core
    (ray_tpu/native/src/sched.cc) holds a write-through mirror and makes
    the hybrid/spread placement decisions over dense fixed-point arrays —
    the reference keeps this exact layer in C++ for the same reason.
    """

    def __init__(self):
        # Controller-loop single-writer state (no locks by design):
        # GuardedDict/GuardedSet give the ConcSan witness thread-affinity
        # checks when RAY_TPU_CONCSAN=1 and cost nothing otherwise.
        self.nodes: Dict[NodeID, NodeResources] = GuardedDict(
            OWNER_THREAD, owner=self, name="nodes"
        )
        # Stable ordering for deterministic pack behavior.
        self._order: List[NodeID] = []
        self._spread_rr = itertools.count()
        # Health-plane avoid set: node -> [monotonic deadline, hard].
        # hard = quarantine (drain semantics: no new placements at all),
        # soft = admission throttle (node moves to the back of the
        # placement order so other nodes absorb new work first). Expiry
        # is pruned lazily on read and by the health tick.
        self._avoid: Dict[NodeID, list] = GuardedDict(
            OWNER_THREAD, owner=self, name="avoid"
        )
        # Demand-shape feasibility index (round 17): shape key -> live
        # fits/feasible sets + pack-order heap, LRU-bounded. See
        # _ShapeEntry. Kept coherent by NodeResources watcher callbacks
        # (capacity) and wholesale invalidation (topology/drain/avoid).
        self._shape_cache: "collections.OrderedDict[tuple, _ShapeEntry]" = (
            collections.OrderedDict()
        )
        self._shape_cache_cap = 128
        # Nodes whose availability changed since the last resource-delta
        # broadcast (core/pubsub.py RESOURCES_CHANNEL) — the controller's
        # coalesced publisher drains this.
        self.dirty_nodes: Set[NodeID] = GuardedSet(
            OWNER_THREAD, owner=self, name="dirty_nodes"
        )
        self.native = None
        if not get_config().disable_native_sched:
            try:
                from ray_tpu.native import sched as _nsched

                if _nsched.available():
                    self.native = _nsched.NativeSched()
            except Exception:
                # available() already covers the no-toolchain case, so an
                # exception here is a real regression — say so instead of
                # silently dropping to the Python policy path.
                logger.warning("native scheduling core failed to load", exc_info=True)
                self.native = None

    def add_node(self, node_id: NodeID, resources: NodeResources):
        self.nodes[node_id] = resources
        if node_id not in self._order:  # re-registration keeps pack order
            self._order.append(node_id)
        if self.native is not None:
            self.native.add_node(node_id, resources.total.items_fp())
            resources.bind_native(self.native, node_id)
        resources.bind_watcher(self, node_id)
        self._invalidate_shapes()
        self.dirty_nodes.add(node_id)

    def remove_node(self, node_id: NodeID):
        res = self.nodes.pop(node_id, None)
        if res is not None:
            res.bind_native(None, None)
            res.bind_watcher(None, None)
        self._order = [n for n in self._order if n != node_id]
        self._avoid.pop(node_id, None)
        if self.native is not None:
            self.native.remove_node(node_id)
        self._invalidate_shapes()
        self.dirty_nodes.add(node_id)

    def set_draining(self, node_id: NodeID, draining: bool = True):
        """Graceful drain (reference: NodeManager drain / rpc::DrainNode):
        a draining node keeps its accounting (running work still releases
        correctly) but receives no new placements."""
        res = self.nodes.get(node_id)
        if res is not None:
            res.draining = draining
        if self.native is not None:
            self.native.set_draining(node_id, draining)
        self._invalidate_shapes()
        self.dirty_nodes.add(node_id)

    # -- health-plane avoids (core/health.py actuators) -----------------
    def set_avoid(self, node_id: NodeID, duration_s: float,
                  hard: bool = False) -> bool:
        """Quarantine (hard) or admission-throttle (soft) a node for
        ``duration_s``. Hard avoids mirror into the native core as
        draining so the C++ fast path honors them; the node's OWN
        ``draining`` flag (user drains) is never touched — an expiring
        quarantine must not un-drain a node the operator drained."""
        import time as _time

        res = self.nodes.get(node_id)
        if res is None:
            return False
        prev = self._avoid.get(node_id)
        self._avoid[node_id] = [_time.monotonic() + float(duration_s), bool(hard)]
        self._invalidate_shapes()
        if hard and self.native is not None and not res.draining:
            self.native.set_draining(node_id, True)
        elif not hard and prev is not None and prev[1]:
            # Downgrade hard -> soft: release the native drain mirror.
            if self.native is not None and not res.draining:
                self.native.set_draining(node_id, False)
        return True

    def clear_avoid(self, node_id: NodeID):
        entry = self._avoid.pop(node_id, None)
        if entry is None:
            return
        self._invalidate_shapes()
        res = self.nodes.get(node_id)
        if (
            entry[1]
            and self.native is not None
            and res is not None
            and not res.draining
        ):
            self.native.set_draining(node_id, False)

    def prune_avoids(self):
        import time as _time

        now = _time.monotonic()
        for nid in [n for n, (dl, _h) in self._avoid.items() if dl <= now]:
            self.clear_avoid(nid)

    def avoids(self) -> Dict[NodeID, tuple]:
        self.prune_avoids()
        return {n: (dl, h) for n, (dl, h) in self._avoid.items()}

    def soft_avoid_active(self) -> bool:
        if not self._avoid:
            return False
        self.prune_avoids()
        return any(not h for _dl, h in self._avoid.values())

    def ordered_nodes(self) -> List[NodeID]:
        if self._avoid:
            self.prune_avoids()
        front: List[NodeID] = []
        back: List[NodeID] = []
        for n in self._order:
            if n not in self.nodes or getattr(self.nodes[n], "draining", False):
                continue
            entry = self._avoid.get(n)
            if entry is None:
                front.append(n)
            elif entry[1]:
                continue  # quarantined: no new placements at all
            else:
                back.append(n)  # throttled: last resort only
        return front + back

    # -- demand-shape feasibility index (round 17) ----------------------
    def _invalidate_shapes(self):
        if self._shape_cache:
            self._shape_cache.clear()

    def note_capacity_changed(self, node_id: NodeID):
        """NodeResources watcher callback: availability (or capacity —
        PG commits add renamed group resources via add_total) changed on
        ``node_id``. O(#cached shapes) set/heap maintenance, never a
        cluster scan."""
        self.dirty_nodes.add(node_id)
        if not self._shape_cache:
            return
        nr = self.nodes.get(node_id)
        if nr is None:
            return
        for e in self._shape_cache.values():
            pos = e.pos.get(node_id)
            if pos is None:
                continue
            if nr.is_feasible(e.demand):
                e.feasible.add(node_id)
            else:
                e.feasible.discard(node_id)
            if nr.available.fits(e.demand):
                if node_id not in e.fits:
                    e.fits.add(node_id)
                    heapq.heappush(e.heap, (pos, node_id))
            else:
                e.fits.discard(node_id)

    def shape_entry(self, demand: ResourceSet) -> _ShapeEntry:
        """The feasibility bucket for ``demand``'s shape, building it
        with ONE full scan on first sight (amortized away across every
        later decision for the same shape)."""
        key = tuple(sorted(demand.items_fp()))
        e = self._shape_cache.get(key)
        if e is not None:
            self._shape_cache.move_to_end(key)
            return e
        e = _ShapeEntry(demand=ResourceSet(dict(demand.items_fp())))
        for i, nid in enumerate(self.ordered_nodes()):
            e.pos[nid] = i
            nr = self.nodes[nid]
            if nr.is_feasible(demand):
                e.feasible.add(nid)
                if nr.available.fits(demand):
                    e.fits.add(nid)
                    heapq.heappush(e.heap, (i, nid))
        while len(self._shape_cache) >= self._shape_cache_cap:
            self._shape_cache.popitem(last=False)
        self._shape_cache[key] = e
        return e


class ClusterResourceScheduler:
    def __init__(self, state: ClusterState):
        self.state = state
        self._spread_idx = 0
        # Fast-path vs full-scan decision accounting. Plain ints on the
        # decision path (a Counter.inc costs ~10us — the very overhead
        # the fast path removes); drain_counters() bulk-flushes into the
        # cluster metrics from the telemetry sweep.
        self._fast_counts: Dict[str, int] = {}
        self._full_scans = 0

    def _count_fast(self, strategy: str):
        self._fast_counts[strategy] = self._fast_counts.get(strategy, 0) + 1

    def drain_counters(self):
        """Flush accumulated decision counts into
        ``scheduler_fast_path_total{strategy}`` /
        ``scheduler_full_scan_total`` (called from the controller's
        telemetry sweep, and by summarize_lifecycle)."""
        fast, self._fast_counts = self._fast_counts, {}
        full, self._full_scans = self._full_scans, 0
        if not fast and not full:
            return
        m = _get_sched_metrics()
        for strategy, n in fast.items():
            # bounded vocabulary: hybrid_native/hybrid_shape/spread_native
            m["fast"].inc(n, {"strategy": strategy})  # ray-tpu: lint-ignore[RTL004] — bounded strategy vocabulary (fast-path kinds only)
        if full:
            m["full"].inc(full)

    # ------------------------------------------------------------------
    def schedule(self, demand: ResourceSet, strategy: SchedulingStrategy,
                 exclude: "Optional[set]" = None) -> ScheduleResult:
        """``exclude``: nodes the caller cannot use right now (worker pool
        exhausted) — the spillback filter (reference: raylet lease
        spillback re-requests with the rejecting node excluded)."""
        if strategy.kind == "NODE_AFFINITY":
            return self._node_affinity(demand, strategy, exclude)
        if strategy.kind == "SPREAD":
            return self._spread(demand, exclude)
        if strategy.kind == "PLACEMENT_GROUP":
            return self._placement_group(demand, strategy, exclude)
        if strategy.kind == "NODE_LABEL":
            return self._node_label(demand, strategy, exclude)
        return self._hybrid(demand, exclude)

    # ------------------------------------------------------------------
    def _feasible_nodes(self, demand: ResourceSet, exclude=None) -> List[NodeID]:
        return [
            nid
            for nid in self.state.ordered_nodes()
            if self.state.nodes[nid].is_feasible(demand)
            and not (exclude and nid in exclude)
        ]

    def _hybrid(self, demand: ResourceSet, exclude=None) -> ScheduleResult:
        """Pack onto the first nodes (stable order) while their utilization is
        below ``scheduler_spread_threshold``; otherwise pick the
        least-utilized available node (reference:
        hybrid_scheduling_policy.cc HybridPolicyWithFilter)."""
        threshold = get_config().scheduler_spread_threshold
        # The native fast path knows about quarantines (mirrored as
        # draining) but not soft throttles (an ORDER preference) — while
        # any throttle is live, placement takes the Python policy path.
        if (
            self.state.native is not None
            and not exclude
            and not self.state.soft_avoid_active()
        ):
            self._count_fast("hybrid_native")
            node_id, infeasible = self.state.native.schedule_hybrid(
                demand.items_fp(), threshold
            )
            return ScheduleResult(node_id, infeasible=infeasible,
                                  reason=_none_reason(node_id, infeasible))
        if not exclude:
            # Demand-shape index: the common no-filter decision is a
            # heap peek + one utilization check instead of a cluster
            # rescan. ``exclude`` (spillback) takes the scan path — the
            # filter is per-request and must not pollute shared buckets.
            e = self.state.shape_entry(demand)
            self._count_fast("hybrid_shape")
            if not e.fits:
                if e.feasible:
                    return ScheduleResult(None, infeasible=False,
                                          reason="insufficient_resources")
                return ScheduleResult(None, infeasible=True,
                                      reason="infeasible")
            heap = e.heap
            while heap and heap[0][1] not in e.fits:
                heapq.heappop(heap)  # lazy-deleted / duplicate entries
            first = heap[0][1]
            if self.state.nodes[first].utilization() < threshold:
                return ScheduleResult(first)
            # Past-threshold tail (rare): same semantics as the scan
            # path, but over the fits set only.
            for _p, nid in sorted((e.pos[n], n) for n in e.fits):
                if self.state.nodes[nid].utilization() < threshold:
                    return ScheduleResult(nid)
            # ``fits`` is a set of random ids: a tie goes to the earlier
            # node, as in the scan path below and the native scheduler.
            best = min(
                e.fits,
                key=lambda n: (self.state.nodes[n].utilization(), e.pos[n]),
            )
            return ScheduleResult(best)
        self._full_scans += 1
        feasible = self._feasible_nodes(demand, exclude)
        if not feasible:
            return ScheduleResult(None, infeasible=True, reason="infeasible")
        available = [n for n in feasible if self.state.nodes[n].fits(demand)]
        if not available:
            return ScheduleResult(None, infeasible=False,
                                  reason="insufficient_resources")
        for nid in available:
            if self.state.nodes[nid].utilization() < threshold:
                return ScheduleResult(nid)
        best = min(available, key=lambda n: self.state.nodes[n].utilization())
        return ScheduleResult(best)

    def _spread(self, demand: ResourceSet, exclude=None) -> ScheduleResult:
        if self.state.native is not None and not exclude:
            self._count_fast("spread_native")
            node_id, infeasible = self.state.native.schedule_spread(demand.items_fp())
            return ScheduleResult(node_id, infeasible=infeasible,
                                  reason=_none_reason(node_id, infeasible))
        self._full_scans += 1
        feasible = self._feasible_nodes(demand, exclude)
        if not feasible:
            return ScheduleResult(None, infeasible=True, reason="infeasible")
        available = [n for n in feasible if self.state.nodes[n].fits(demand)]
        if not available:
            return ScheduleResult(None, reason="insufficient_resources")
        pick = available[self._spread_idx % len(available)]
        self._spread_idx += 1
        return ScheduleResult(pick)

    def _node_affinity(self, demand: ResourceSet, strategy: SchedulingStrategy, exclude=None) -> ScheduleResult:
        nid = NodeID.from_hex(strategy.node_id) if isinstance(strategy.node_id, str) else strategy.node_id
        if exclude and nid in exclude:
            if strategy.soft:
                # soft affinity is a preference — spill elsewhere
                return self._hybrid(demand, exclude)
            # hard pin: the node cannot take the task right now — wait
            return ScheduleResult(None, infeasible=False, reason="no_idle_worker")
        node = self.state.nodes.get(nid)
        if node is not None and not node.draining and node.fits(demand):
            return ScheduleResult(nid)
        if strategy.soft:
            return self._hybrid(demand, exclude)
        if node is None:
            return ScheduleResult(None, infeasible=True, reason="infeasible")
        return ScheduleResult(None, reason="insufficient_resources")

    def _node_label(self, demand: ResourceSet, strategy: SchedulingStrategy,
                    exclude=None) -> ScheduleResult:
        """Hard label expressions filter candidates (no match anywhere →
        infeasible, surfaced to the autoscaler with the label demand);
        soft expressions rank the survivors."""
        labels = strategy.node_labels or {}
        hard, soft = labels.get("hard"), labels.get("soft")
        self._full_scans += 1
        candidates = [
            nid for nid in self.state.ordered_nodes()
            if match_label_expressions(hard, self.state.nodes[nid].labels)
            and not (exclude and nid in exclude)
        ]
        if not candidates:
            return ScheduleResult(None, infeasible=True, reason="infeasible")
        feasible = [n for n in candidates if self.state.nodes[n].is_feasible(demand)]
        if not feasible:
            return ScheduleResult(None, infeasible=True, reason="infeasible")
        available = [n for n in feasible if self.state.nodes[n].fits(demand)]
        if not available:
            return ScheduleResult(None, reason="insufficient_resources")
        if soft:
            preferred = [
                n for n in available
                if match_label_expressions(soft, self.state.nodes[n].labels)
            ]
            if preferred:
                available = preferred
        best = min(available, key=lambda n: self.state.nodes[n].utilization())
        return ScheduleResult(best)

    def _placement_group(self, demand: ResourceSet, strategy: SchedulingStrategy, exclude=None) -> ScheduleResult:
        """Translate demand into the PG's renamed group resources
        (reference: placement_group_resource_manager.h — ``CPU`` →
        ``CPU_group_<pgid>`` / ``CPU_group_<i>_<pgid>``)."""
        pgid = strategy.placement_group_id
        suffix = (
            f"_group_{strategy.bundle_index}_{pgid.hex()}"
            if strategy.bundle_index >= 0
            else f"_group_{pgid.hex()}"
        )
        translated = ResourceSet({k + suffix: v for k, v in demand.items_fp()})
        # Also consume the wildcard pool when a specific bundle was requested,
        # so pg-wide accounting stays consistent with the reference.
        if strategy.bundle_index >= 0:
            wildcard = ResourceSet({f"{k}_group_{pgid.hex()}": v for k, v in demand.items_fp()})
            translated = translated + wildcard
        self._full_scans += 1
        for nid in self.state.ordered_nodes():
            if exclude and nid in exclude:
                continue
            if self.state.nodes[nid].fits(translated):
                return ScheduleResult(nid)
        # The renamed group resources exist only once the PG committed —
        # the pump refines this to "pg_unready" when the PG isn't CREATED.
        return ScheduleResult(None, reason="insufficient_resources")

    def translated_pg_demand(self, demand: ResourceSet, strategy: SchedulingStrategy) -> ResourceSet:
        if strategy.kind != "PLACEMENT_GROUP":
            return demand
        pgid = strategy.placement_group_id
        parts = {}
        for k, v in demand.items_fp():
            if strategy.bundle_index >= 0:
                parts[f"{k}_group_{strategy.bundle_index}_{pgid.hex()}"] = v
                parts[f"{k}_group_{pgid.hex()}"] = parts.get(f"{k}_group_{pgid.hex()}", 0) + v
            else:
                parts[f"{k}_group_{pgid.hex()}"] = v
        return ResourceSet(parts)


def schedule_bundles(
    state: ClusterState,
    bundles: List[ResourceSet],
    strategy: str,
    occupied: Optional[set] = None,
) -> Optional[List[NodeID]]:
    """Place PG bundles per PACK/SPREAD/STRICT_PACK/STRICT_SPREAD
    (reference: raylet/scheduling/policy/bundle_scheduling_policy.h:82-106).

    Returns one node per bundle or None if infeasible. Trial placement is
    done against a scratch copy of availability so multi-bundle-per-node
    accounting is correct.

    ``occupied`` is the node set already holding this group's SURVIVING
    bundles during a partial re-place (host-death rescheduling): for
    STRICT_PACK the missing bundles MUST land there (one node), for
    STRICT_SPREAD they must NOT, and SPREAD prefers fresh nodes first —
    mirroring how those nodes would look to a full placement.
    """
    # Scratch availability.
    avail: Dict[NodeID, ResourceSet] = {
        nid: ResourceSet(dict(state.nodes[nid].available.items_fp()))
        for nid in state.ordered_nodes()
    }
    order = state.ordered_nodes()
    occupied = occupied or set()
    if occupied:
        if strategy == "STRICT_PACK":
            order = [n for n in order if n in occupied]
        elif strategy == "STRICT_SPREAD":
            order = [n for n in order if n not in occupied]

    def try_place(nid: NodeID, demand: ResourceSet) -> bool:
        if avail[nid].fits(demand):
            avail[nid] = avail[nid] - demand
            return True
        return False

    placement: List[Optional[NodeID]] = [None] * len(bundles)

    if strategy in ("STRICT_PACK", "PACK"):
        # STRICT_PACK: all bundles on one node (one ICI slice on TPU).
        for nid in order:
            ok = all(avail[nid].fits(b) for b in _stack(bundles))
            if ok and _fits_all(avail[nid], bundles):
                return [nid] * len(bundles)
        if strategy == "STRICT_PACK":
            return None
        # PACK fallback: greedy fill nodes in order.
        for i, b in enumerate(bundles):
            placed = False
            for nid in order:
                if try_place(nid, b):
                    placement[i] = nid
                    placed = True
                    break
            if not placed:
                return None
        return placement  # type: ignore[return-value]

    if strategy in ("SPREAD", "STRICT_SPREAD"):
        used_nodes: set = set(occupied) if strategy == "SPREAD" else set()
        for i, b in enumerate(bundles):
            candidates = [n for n in order if n not in used_nodes] + (
                [] if strategy == "STRICT_SPREAD" else [n for n in order if n in used_nodes]
            )
            placed = False
            for nid in candidates:
                if try_place(nid, b):
                    placement[i] = nid
                    used_nodes.add(nid)
                    placed = True
                    break
            if not placed:
                return None
        return placement  # type: ignore[return-value]

    raise ValueError(f"unknown bundle strategy {strategy}")


def _stack(bundles: List[ResourceSet]) -> List[ResourceSet]:
    total = ResourceSet()
    for b in bundles:
        total = total + b
    return [total]


def _fits_all(avail: ResourceSet, bundles: List[ResourceSet]) -> bool:
    total = ResourceSet()
    for b in bundles:
        total = total + b
    return avail.fits(total)
