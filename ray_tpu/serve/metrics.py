"""Serve-path SLO metrics: per-request histograms and engine gauges.

Reference: python/ray/serve/_private/metrics_utils.py and the serve
request metrics the reference records from proxies and replicas
(serve_num_http_requests, serve_deployment_processing_latency_ms, ...).
Here the hot-path components (proxy → handle → replica → batcher →
LLMEngine) record into the process-local metric registry
(``ray_tpu/util/metrics.py``) and the normal flush pipeline carries the
series to the controller → Prometheus → Grafana.

All metrics are lazy per-process singletons: the registry keeps every
constructed Metric alive, so components must share one instance per name
(``serve_metrics()``) instead of constructing their own.

TTFT/TPOT semantics (LLM serving SLOs): for a streaming request, TTFT is
submit→first streamed item and TPOT is the mean inter-item gap; for the
engine's own accounting the flight recorder (llm_engine.py) keeps exact
per-request breakdowns.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional


def percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile over a pre-sorted list (shared by the
    engine flight recorder and ``state.summarize_serve``)."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[i]


def summarize_latencies(
    values_by_field: Dict[str, List[float]],
) -> Dict[str, Dict[str, float]]:
    """{field: {p50, p95, p99, count}} over raw (unsorted) samples — the
    one summary shape used by the flight recorder and summarize_serve."""
    out: Dict[str, Dict[str, float]] = {}
    for field, raw in values_by_field.items():
        vals = sorted(raw)
        out[field] = {
            "p50": percentile(vals, 0.50),
            "p95": percentile(vals, 0.95),
            "p99": percentile(vals, 0.99),
            "count": len(vals),
        }
    return out

# Latency bucket boundaries (ms): sub-ms token cadence up to multi-minute
# batch jobs — shared by every serve latency histogram so Grafana
# histogram_quantile panels are comparable across metrics.
MS_BOUNDARIES = (
    0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500,
    1000, 2500, 5000, 10000, 30000, 60000,
)
BATCH_BOUNDARIES = (1, 2, 4, 8, 16, 32, 64, 128)

_lock = threading.Lock()
_metrics: Optional["_ServeMetrics"] = None

# Ambient replica identity: set by the Replica actor before it constructs
# the user instance, so anything the instance creates (LLMEngine, batch
# queues) tags its series with the owning deployment/replica without
# explicit plumbing.
_replica_ctx: Dict[str, str] = {}


def set_replica_context(deployment: str, replica: str) -> None:
    _replica_ctx.clear()
    _replica_ctx.update({"deployment": deployment, "replica": replica})


def replica_context() -> Dict[str, str]:
    return dict(_replica_ctx)


class _ServeMetrics:
    def __init__(self):
        from ray_tpu.util.metrics import Counter, Gauge, Histogram

        dr = ("deployment", "replica")
        # -- per-request SLO histograms (recorded by the replica) -------
        self.queue_ms = Histogram(
            "serve_request_queue_ms",
            "Time from handle submit to replica execution start",
            MS_BOUNDARIES, dr,
        )
        self.ttft_ms = Histogram(
            "serve_ttft_ms",
            "Time from handle submit to first streamed item (time to first token)",
            MS_BOUNDARIES, dr,
        )
        self.tpot_ms = Histogram(
            "serve_tpot_ms",
            "Mean inter-item latency of a streaming response (time per output token)",
            MS_BOUNDARIES, dr,
        )
        self.e2e_ms = Histogram(
            "serve_e2e_ms",
            "End-to-end request latency (handle submit to completion)",
            MS_BOUNDARIES, dr,
        )
        self.tokens_out = Counter(
            "serve_tokens_out_total",
            "Items streamed back to clients (tokens for LLM deployments)",
            dr,
        )
        self.requests = Counter(
            "serve_requests_total",
            "Requests handled by replicas, by outcome",
            ("deployment", "replica", "outcome"),
        )
        # -- ingress -----------------------------------------------------
        self.proxy_requests = Counter(
            "serve_proxy_requests_total",
            "HTTP requests through the serve proxy, by route and status",
            ("route", "code"),
        )
        self.proxy_ms = Histogram(
            "serve_proxy_request_ms",
            "Proxy-observed request latency (streaming: full stream duration)",
            MS_BOUNDARIES, ("route",),
        )
        # -- @serve.batch -----------------------------------------------
        self.batch_size = Histogram(
            "serve_batch_size",
            "Items per @serve.batch flush",
            BATCH_BOUNDARIES, ("fn",),
        )
        self.batch_wait_ms = Histogram(
            "serve_batch_wait_ms",
            "Oldest item's wait in the batch queue at flush time",
            MS_BOUNDARIES, ("fn",),
        )
        # -- engine (set/inc by the LLMEngine at step cadence, throttled)
        self.engine_active = Gauge(
            "serve_engine_active_slots", "Decode slots occupied", dr
        )
        self.engine_waiting = Gauge(
            "serve_engine_waiting", "Requests queued for admission", dr
        )
        self.engine_kv_free = Gauge(
            "serve_engine_kv_blocks_free", "Free KV cache blocks", dr
        )
        self.engine_kv_util = Gauge(
            "serve_engine_kv_utilization", "Fraction of KV blocks in use", dr
        )
        self.engine_steps = Counter(
            "serve_engine_steps_total", "Engine scheduler iterations", dr
        )
        self.engine_tokens = Counter(
            "serve_engine_tokens_total", "Tokens emitted by the engine", dr
        )
        self.engine_prompt_tokens = Counter(
            "serve_engine_prompt_tokens_total", "Prompt tokens prefilled", dr
        )
        self.engine_prefills = Counter(
            "serve_engine_prefills_total", "Prefill program invocations", dr
        )
        self.engine_preemptions = Counter(
            "serve_engine_preemptions_total", "Recompute preemptions", dr
        )
        # -- engine perf suite (prefix cache / chunked prefill / overlap)
        self.engine_prefix_hit_tokens = Counter(
            "serve_engine_prefix_hit_tokens_total",
            "Prompt tokens served from the prefix KV cache (not recomputed)",
            dr,
        )
        self.engine_prefix_lookup_tokens = Counter(
            "serve_engine_prefix_lookup_tokens_total",
            "Prompt tokens looked up in the prefix KV cache (hit-rate denominator)",
            dr,
        )
        self.engine_prefix_evictions = Counter(
            "serve_engine_prefix_evictions_total",
            "Prefix-cache blocks evicted (refcount-0 only), by how the victim "
            "was chosen: lru (the coldest, no waiting request matched it), "
            "spared (in place of a colder chain a waiting request matched), "
            "wanted (every evictable block was matched: a leaf from the "
            "queue's tail)",
            dr + ("choice",),
        )
        self.engine_cached_blocks = Gauge(
            "serve_engine_prefix_cached_blocks",
            "KV blocks resident in the prefix cache (pinned + evictable)",
            dr,
        )
        self.engine_prefix_published_blocks = Counter(
            "serve_engine_prefix_published_blocks_total",
            "Blocks that entered the prefix cache as their slot was given back "
            "(an answer's, a preempted request's), beside a prefill's own",
            dr,
        )
        self.engine_prefill_chunks = Counter(
            "serve_engine_prefill_chunks_total",
            "Chunk-program calls (chunked/suffix prefill); one holds the "
            "suffixes an iteration admitted",
            dr,
        )
        self.engine_prefill_segments = Counter(
            "serve_engine_prefill_segments_total",
            "Slots' suffixes or chunks prefilled by chunk-program calls "
            "(segments a call = this / serve_engine_prefill_chunks_total)",
            dr,
        )
        self.engine_prefill_tile_queries = Counter(
            "serve_engine_prefill_tile_queries_total",
            "Queries of the tiles that chunk-program calls' segments took (a "
            "segment is padded to whole tiles)",
            dr,
        )
        self.engine_prefill_live_queries = Counter(
            "serve_engine_prefill_live_queries_total",
            "Real queries among serve_engine_prefill_tile_queries_total (the "
            "rest is padding, which the latent prefill kernel skips)",
            dr,
        )
        self.engine_moe_pairs_here = Counter(
            "serve_engine_moe_pairs_here_total",
            "Token-expert pairs computed by the experts this replica holds "
            "(an expert model's counted program calls)",
            dr,
        )
        self.engine_moe_experts_touched = Counter(
            "serve_engine_moe_experts_touched_total",
            "Held experts with at least one pair, summed over expert layers and "
            "counted steps or calls",
            dr,
        )
        self.engine_moe_layer_steps = Counter(
            "serve_engine_moe_layer_steps_total",
            "Expert layers x steps or calls counted (touched experts a layer = "
            "serve_engine_moe_experts_touched_total / this)",
            dr,
        )
        self.engine_moe_fused_layer_steps = Counter(
            "serve_engine_moe_fused_layer_steps_total",
            "Of serve_engine_moe_layer_steps_total, those whose held experts one "
            "kernel multiplied (moe_decode_experts: a call under the chip's ridge)",
            dr,
        )
        self.engine_moe_grouped_layer_steps = Counter(
            "serve_engine_moe_grouped_layer_steps_total",
            "Of serve_engine_moe_layer_steps_total, those whose sorted pairs one "
            "kernel multiplied (moe_grouped_experts: a call over the chip's ridge)",
            dr,
        )
        self.engine_emit_batches = Counter(
            "serve_engine_emit_batches_total",
            "Queue entries the engine handed its requests (a window's tokens of a "
            "request are one; tokens an entry = serve_engine_tokens_total / this)",
            dr,
        )
        self.engine_state_slots_live = Counter(
            "serve_engine_state_slots_live_total",
            "Rows of state kept by slot (a state-space layer's) that dispatched decode "
            "windows read and wrote: occupied slots, summed over windows",
            dr,
        )
        self.engine_state_slots_table = Counter(
            "serve_engine_state_slots_table_total",
            "Rows of state kept by slot that the engine held, summed over dispatched "
            "windows (max_batch a window; live share = ..._live_total / this)",
            dr,
        )
        self.engine_state_segments_carried = Counter(
            "serve_engine_state_segments_carried_total",
            "Chunk-call segments that began from their slot's stored state (a later "
            "chunk of a long prompt)",
            dr,
        )
        self.engine_state_segments_fresh = Counter(
            "serve_engine_state_segments_fresh_total",
            "Chunk-call segments that began at position 0, from no state",
            dr,
        )
        self.engine_overlap_windows = Counter(
            "serve_engine_overlap_windows_total",
            "Decode windows dispatched before the previous window was read "
            "(host/device overlap)",
            dr,
        )
        self.engine_windows_behind_prefill = Counter(
            "serve_engine_windows_behind_prefill_total",
            "Decode windows dispatched behind their iteration's prefill programs, "
            "before the host had read the prefills' first tokens",
            dr,
        )
        self.engine_prefill_flushed_first = Counter(
            "serve_engine_prefill_flushed_first_total",
            "Decode windows that waited for the host to read the prefills' first "
            "tokens: the dispatch had to preempt, and the victim may own one of them",
            dr,
        )
        self.engine_overlap_blocked = Counter(
            "serve_engine_overlap_blocked_total",
            "Decode windows found in flight and NOT overlapped, by reason "
            "(idle, admission, finishing)",
            dr + ("reason",),
        )
        self.engine_device_starved = Counter(
            "serve_engine_device_starved_seconds_total",
            "Seconds the device had nothing queued while the engine had work "
            "for it, by where the scheduler thread was (admit_plan, admit_build, "
            "admit_key, admit_launch, dispatch_blocks, dispatch_key, "
            "dispatch_ship, dispatch_launch, emit, record, between)",
            dr + ("where",),
        )


def serve_metrics() -> _ServeMetrics:
    global _metrics
    if _metrics is None:
        with _lock:
            if _metrics is None:
                _metrics = _ServeMetrics()
    return _metrics
