"""Global config table.

The reference defines 217 ``RAY_CONFIG(type, name, default)`` entries
overridable via ``RAY_<name>`` env vars (reference:
src/ray/common/ray_config_def.h). Same pattern here: a declarative table,
env-var override ``RAY_TPU_<NAME>``, plus per-``init`` ``_system_config``
dict overrides.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field, fields
from typing import Any


def _env(name: str, default):
    raw = os.environ.get(f"RAY_TPU_{name.upper()}")
    if raw is None:
        return default
    t = type(default)
    if t is bool:
        return raw.lower() in ("1", "true", "yes")
    if t in (int, float, str):
        return t(raw)
    return json.loads(raw)


@dataclass
class Config:
    # --- object store ---
    # Objects at or below this size are carried inline through the control
    # plane instead of the shared-memory store (reference default 100KB:
    # ray_config_def.h ``max_direct_call_object_size``).
    max_inline_object_size: int = 100 * 1024
    # Per-node shared-memory store capacity (bytes). 0 = auto (30% of RAM).
    object_store_memory: int = 0
    # Chunk size for node-to-node object transfer (reference 64MB chunks:
    # object_manager.cc).
    object_transfer_chunk_bytes: int = 8 * 1024 * 1024
    # Allow readers to mmap ANOTHER node's shared-memory store directly —
    # only valid when all "nodes" share one host's filesystem (the
    # single-host simulation shortcut). Off (default) = cross-node reads
    # go through the chunked network data plane like the reference.
    cross_node_shm: bool = False
    # Spill to disk when store is above this fraction.
    object_spilling_threshold: float = 0.8
    spill_directory: str = ""

    # --- scheduler ---
    # Hybrid policy: pack onto lower-index nodes until utilization crosses
    # this threshold, then spread (reference:
    # raylet/scheduling/policy/hybrid_scheduling_policy.h:50).
    scheduler_spread_threshold: float = 0.5
    # Max tasks a single lease dispatch round hands to one worker.
    max_tasks_in_flight_per_worker: int = 10
    worker_lease_timeout_s: float = 30.0
    # Kill switch for the native C++ scheduling core (falls back to the
    # pure-Python policy path). Env override: RAY_TPU_DISABLE_NATIVE_SCHED.
    disable_native_sched: bool = False

    # --- workers ---
    # Prestarted workers per node (reference prestarts 1/CPU:
    # raylet/worker_pool.h:365).
    prestart_workers: bool = True
    worker_register_timeout_s: float = 60.0
    idle_worker_killing_time_s: float = 300.0
    maximum_startup_concurrency: int = 8

    # --- memory / OOM (reference: memory_monitor.h, ray_config_def.h
    # memory_usage_threshold / memory_monitor_refresh_ms) ---
    memory_usage_threshold: float = 0.95
    memory_monitor_refresh_ms: int = 250  # 0 disables the monitor
    worker_killing_policy: str = "retriable_fifo"  # or "group_by_owner"

    # --- fault tolerance ---
    health_check_period_s: float = 1.0
    health_check_failure_threshold: int = 5
    task_retry_delay_s: float = 0.05
    actor_restart_delay_s: float = 0.1
    # Default bound for control-plane RPCs issued without an explicit
    # timeout (register/kv/pg-admin/lease bookkeeping/...). Methods that
    # block by DESIGN (object get/wait, streams, pg readiness, drains)
    # are exempt — see client._UNBOUNDED_METHODS. A wedged controller
    # then surfaces as a timeout error instead of a process hung forever.
    control_call_timeout_s: float = 300.0
    # Controller-connection loss: workers, agents, and drivers attempt to
    # reconnect + re-register with jittered backoff for this long before
    # treating the controller as gone (worker/agent exit; driver raises).
    # Rides through a controller restart on the same address when the
    # persistence journal is intact. 0 = legacy exit-on-first-disconnect.
    controller_reconnect_window_s: float = 10.0
    # fsync the GCS journal on every append (reference analogue: Redis
    # persistence guarantees for GCS FT). Off by default: a torn tail is
    # detected and dropped on replay, and the journal is for whole-process
    # crashes, not host power loss.
    gcs_journal_fsync: bool = False

    # --- direct transport ---
    # Push actor tasks straight from the caller to the actor's worker
    # (reference: actor_task_submitter.h caller→actor gRPC); results land
    # in the caller's owner-local memory store. Off → every call routes
    # through the controller (the pre-round-2 path).
    direct_actor_calls: bool = True
    # Lease-based direct submission for NORMAL tasks (reference:
    # normal_task_submitter.cc worker leasing + PushNormalTask): the
    # caller leases a worker (controller does placement only; the node
    # agent owns the local free-worker view) and pushes tasks straight to
    # it, reusing the lease across a scheduling key's queue. Off → tasks
    # dispatch through the controller loop (the round-2 path).
    direct_normal_tasks: bool = True
    # Pushes in flight per leased worker (reference:
    # max_tasks_in_flight_per_worker pipelining) — 2 keeps the worker's
    # execution thread fed while the previous reply is on the wire.
    max_tasks_in_flight_per_lease: int = 2
    # Outstanding lease requests + held leases per scheduling key
    # (reference: max_pending_lease_requests_per_scheduling_category).
    max_leases_per_scheduling_key: int = 10
    # Batched control plane (round 17): one rpc_lease_batch round-trip
    # grants up to N leases per scheduling key, and pushes to an
    # already-leased worker coalesce into one framed push_task_batch RPC
    # with ONE gathered reply. Dynamic windows (grow on full grants /
    # clean batch completion, shrink on spillback / failure) replace the
    # static per-lease and per-key caps above, which then only serve the
    # legacy path. Off = the round-13 per-task path (the bench A/B knob).
    lease_batching: bool = True
    # Cap on leases granted per batch request — also the ceiling of the
    # per-key dynamic lease window.
    lease_batch_max: int = 16
    # Cap on tasks per push_task_batch frame — also the ceiling of the
    # per-lease dynamic in-flight window.
    task_push_batch_max: int = 64

    # --- control plane ---
    raylet_heartbeat_period_s: float = 0.5
    pubsub_batch_size: int = 1000
    # Topic-bus resource sync (round 17): capacity changes publish
    # coalesced per-node availability deltas on RESOURCES_CHANNEL no
    # more often than this; subscribers mirror push-on-change instead of
    # polling per sweep. 0 = publish every change uncoalesced.
    resource_broadcast_min_interval_ms: int = 100
    # Periodic full-snapshot reconciliation for topic-bus mirrors
    # (out-of-order / dropped deltas self-heal within one period).
    resource_reconcile_interval_s: float = 10.0
    task_event_buffer_size: int = 100000
    # Worker-side task-event flush cadence. The state API is eventually
    # consistent for direct-push tasks (reference: GCS task events are
    # buffered the same way); short period = snappy `list_tasks`.
    event_flush_period_s: float = 0.25

    # --- distributed ref counting / object GC ---
    # Free objects no process references (reference: reference_count.cc
    # ownership GC). Off → objects live for the session (freed only by
    # ray_tpu.internal.free or store eviction).
    object_auto_gc: bool = True
    # Worker-side batch flush cadence for local-ref zero crossings.
    # COUPLING: two-phase GC safety requires gc_sweep_interval_ms >=
    # 2 * ref_flush_interval_ms — a GC-marked object must survive one full
    # sweep so a borrower's in-flight "held" flush can land before the
    # free. _validate() clamps the sweep interval to keep the invariant.
    ref_flush_interval_ms: int = 200
    # Controller GC sweep debounce after a ref update arrives (see the
    # coupling note on ref_flush_interval_ms).
    gc_sweep_interval_ms: int = 1000

    # --- observability ---
    # App-metric flush cadence (reference: metrics_report_interval_ms).
    metrics_report_interval_ms: int = 2000
    # 0 = pick a free port for the controller's HTTP observability endpoint
    # (/metrics Prometheus text + /api/v0/* state JSON); -1 disables it.
    dashboard_port: int = 0
    # Node/device telemetry poll cadence (host CPU/mem + object store in
    # the agents' controller heartbeat; per-device HBM + compile stats in
    # workers' device_telemetry reports). 0 disables both loops.
    node_telemetry_interval_ms: int = 2000
    # Recompilation-storm detector: >= threshold compiles of the SAME
    # function name inside the window flags a storm (warning log + state
    # API + jax_recompile_storms_total).
    compile_storm_threshold: int = 5
    compile_storm_window_s: float = 60.0
    # Per-metric cap on distinct label sets: series past the cap are
    # dropped (counted in metrics_series_dropped_total) so per-request or
    # per-task tags can't blow up the registry/controller/Prometheus.
    metrics_max_series_per_metric: int = 200
    # Control-plane flight recorder (core/lifecycle.py): task/actor/PG/
    # lease/worker state-transition events with per-state dwell times and
    # why-pending attribution, aggregated controller-side and exposed via
    # state.summarize_lifecycle() / `ray-tpu timeline`. Off = near-zero
    # overhead (the envelope A/B knob).
    lifecycle_events: bool = True
    # Controller-side event ring bound (newest N transitions kept).
    lifecycle_ring_size: int = 20000
    # Per-(kind, state) dwell sample ring bound (percentile source).
    lifecycle_dwell_samples: int = 4096

    # --- object & memory observability (core/memory_census.py) ---
    # Master switch for creation call-site attribution + the per-process
    # ref census + the controller's leak/pressure detectors (the
    # envelope A/B knob: benchmarks/envelope.py --no-memory-census).
    memory_census: bool = True
    # Bounded call-site intern table: past the cap every new site
    # collapses into "(other)" so census groups / leak-trend entries /
    # metric tags built from call-sites stay bounded.
    memory_callsite_cap: int = 512
    # Leak detector: flag a call-site whose open-object count rises
    # monotonically across this many consecutive census sweeps (one
    # sweep per node_telemetry_interval_ms) ...
    memory_leak_sweeps: int = 5
    # ... and sits at or above this floor (small transients don't flag).
    memory_leak_min_refs: int = 32
    # Store-pressure incident trigger: object-store occupancy at/above
    # this fraction fires PR 9's incident machinery with a memory
    # autopsy bundle (0 disables the occupancy trigger).
    memory_incident_occupancy_pct: float = 0.95
    # ... or this many spill operations within one census sweep
    # (eviction-loop churn; 0 disables the churn trigger).
    memory_incident_spill_churn: int = 200

    # --- log plane (core/log_plane.py) ---
    # Master switch for structured log capture: every worker (and driver)
    # stamps logging records + stdout/stderr lines + task tracebacks with
    # {node, worker, task, severity, ts} into a bounded JSONL sidecar
    # next to the raw log, ships ERROR records to the controller's error
    # index, and answers the cluster-wide log search fan-out. The
    # envelope A/B knob (benchmarks/envelope.py log-churn arm).
    log_structured: bool = True
    # Size cap for worker log files — BOTH the raw worker-*.log (rotated
    # copy-truncate, the redirected-stdout fd keeps appending) and the
    # structured .jsonl sidecar (rotated by rename). One rotated ``.1``
    # half is kept, like the PR 6 span sinks — disk is bounded at ~2x
    # the cap per file.
    log_rotate_bytes: int = 64 * 1024 * 1024
    # Worker→controller shipping cadence for ERROR/exception records
    # (only those ship; the full firehose stays in node-local sidecars
    # reached by the search fan-out).
    log_ship_interval_ms: int = 1000
    # Bounded error-signature index on the controller (same bounded-
    # intern pattern as the memory census CallsiteTable): past the cap
    # new signatures collapse into "(other)".
    log_error_index_size: int = 256
    # Error-rate-spike incident trigger: this many ERROR records ingested
    # within one telemetry sweep fires the PR 9 incident machinery with
    # the offending log tail attached (0 disables).
    log_error_spike_threshold: int = 50

    # --- self-healing health plane (core/health.py + util/actuators.py) ---
    # Master switch for the observe→act loop: detector signals (leak /
    # pressure / storm / error-spike) drive bounded, audited actuators.
    # Off = detectors keep writing autopsies only (the pre-PR-16 world;
    # also the envelope A/B knob).
    health_actuators: bool = True
    # Comma-separated actuator names forced into dry-run (decision made
    # + audited + lifecycle event, side effect suppressed). "*" = all.
    health_dry_run: str = ""
    # Per-(actuator, target) cooldown: the same remedy never re-fires at
    # the same target inside this window.
    health_action_cooldown_s: float = 30.0
    # Global budget across all actuators (a detector storm must not turn
    # the health plane into its own denial of service).
    health_max_actions_per_min: int = 6
    # error-spike quarantine: hard scheduler avoid of the offending node
    # (drain semantics) for this long.
    health_quarantine_s: float = 60.0
    # store-pressure admission throttle: soft scheduler avoid (node moves
    # to the back of placement order) for this long.
    health_throttle_s: float = 30.0
    # store-pressure proactive spill target: spill LRU entries until the
    # store's file-tier occupancy is at or below this fraction.
    health_spill_target_pct: float = 0.6
    # memory-leak nudge: at most this many holder processes get the
    # gc/ref-reclamation RPC per action.
    health_nudge_max_procs: int = 8
    # Bounded action audit ring in the controller.
    health_audit_ring: int = 256

    # --- profiling (util/profiling.py) ---
    # Default sample rate for on-demand `ray-tpu profile cpu` runs.
    profiling_sample_hz: int = 100
    # Continuous low-rate background sampler feeding the incident ring
    # (0 = off, the default; ~5-20 Hz keeps overhead well under the 3%
    # budget; `python bench.py --cpu` reports it as profiling_overhead_pct).
    profiling_continuous_hz: float = 0.0
    # How many seconds of recent samples the incident ring retains.
    profiling_ring_s: float = 60.0
    # Incident auto-capture master switch: detector hooks (lockwatch
    # long-hold/cycle, recompile storms, SLO breaches) flush capture
    # bundles under <session>/incidents/.
    profiling_incidents: bool = True
    # Newest N incident bundles kept on disk (oldest pruned at write).
    profiling_incident_keep: int = 20
    # Per-trigger rate limit between captures in one process.
    profiling_incident_min_interval_s: float = 30.0
    # Serve TTFT SLO-breach capture threshold in ms (0 = disabled).
    profiling_slo_ttft_ms: float = 0.0

    # --- fault injection (tests only; reference:
    # python/ray/tests/chaos/chaos_network_delay.yaml injects network
    # latency with k8s traffic shaping — here the agents' chunk server
    # sleeps per chunk, stretching transfers so chaos can land mid-pull) ---
    chaos_fetch_delay_ms: int = 0

    # --- misc ---
    temp_dir: str = field(default_factory=lambda: os.environ.get("RAY_TPU_TMPDIR", "/tmp/ray_tpu"))
    log_to_driver: bool = True

    def __post_init__(self):
        self._validate()

    def _validate(self):
        # Two-phase GC safety (see ref_flush_interval_ms): clamp rather
        # than raise so a user tuning one knob can't silently break
        # borrowed-object liveness.
        floor = 2 * self.ref_flush_interval_ms
        if self.gc_sweep_interval_ms < floor:
            import logging

            logging.getLogger("ray_tpu.config").warning(
                "gc_sweep_interval_ms=%d raised to %d (must be >= 2x "
                "ref_flush_interval_ms for two-phase GC safety)",
                self.gc_sweep_interval_ms, floor,
            )
            self.gc_sweep_interval_ms = floor
        return self

    def apply_overrides(self, overrides: dict[str, Any] | None):
        if not overrides:
            return self
        for k, v in overrides.items():
            if not hasattr(self, k):
                raise ValueError(f"Unknown config key: {k}")
            setattr(self, k, v)
        return self._validate()

    @classmethod
    def from_env(cls) -> "Config":
        cfg = cls()
        for f in fields(cls):
            setattr(cfg, f.name, _env(f.name, getattr(cfg, f.name)))
        return cfg._validate()

    def to_dict(self) -> dict:
        return {f.name: getattr(self, f.name) for f in fields(self)}


_global_config: Config | None = None


def get_config() -> Config:
    global _global_config
    if _global_config is None:
        _global_config = Config.from_env()
    return _global_config


def set_config(cfg: Config):
    global _global_config
    _global_config = cfg
