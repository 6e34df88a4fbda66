"""Tier-1 envelope regression smoke (round 17).

A tiny-depth version of benchmarks/envelope.py's queued arm pinned
against a committed baseline: if the batched control plane regresses
``task.SUBMITTED`` dwell (submission handling + dep resolution) or the
end-to-end drain by more than 3x, tier-1 fails — the full 100k-depth
envelope only runs per-round, so this is the tripwire in between. No
pacing-sensitive sleeps: both budgets are ratios against the committed
JSON, not wall-clock constants tuned to one box.
"""
import json
import os
import time

import ray_tpu

_BASELINE = os.path.join(
    os.path.dirname(__file__), "..", "benchmarks", "ENVELOPE_SMOKE_BASELINE.json"
)


def test_envelope_smoke_submitted_dwell_within_budget():
    with open(_BASELINE) as f:
        base = json.load(f)
    n = int(base["queued"])
    budget_ms = 3.0 * float(base["task_submitted_p50_ms"])
    budget_drain_s = 3.0 * float(base["drain_s"])

    ray_tpu.init(num_cpus=int(base["num_cpus"]))
    try:
        @ray_tpu.remote(num_cpus=1)
        def noop():
            return 0

        t0 = time.perf_counter()
        refs = [noop.remote() for _ in range(n)]
        out = ray_tpu.get(refs, timeout=240)
        drain_s = time.perf_counter() - t0
        assert out == [0] * n

        from ray_tpu.util import state as state_api

        snap = state_api.summarize_lifecycle()
        assert snap.get("enabled"), "flight recorder off — smoke can't anchor"
        sub = snap["states"]["task"]["SUBMITTED"]
        assert sub["count"] >= n
        p50 = sub["dwell_ms"]["p50"]
        assert p50 <= budget_ms, (
            f"task.SUBMITTED p50 {p50:.1f} ms exceeds 3x committed baseline "
            f"({base['task_submitted_p50_ms']:.0f} ms -> budget "
            f"{budget_ms:.0f} ms). Either fix the control-plane regression "
            "or re-anchor benchmarks/ENVELOPE_SMOKE_BASELINE.json with a "
            "justified bump."
        )
        assert drain_s <= budget_drain_s, (
            f"drain of {n} tasks took {drain_s:.1f}s, exceeds 3x committed "
            f"baseline ({base['drain_s']:.1f}s -> budget "
            f"{budget_drain_s:.1f}s). Either fix the throughput regression "
            "or re-anchor benchmarks/ENVELOPE_SMOKE_BASELINE.json with a "
            "justified bump."
        )
    finally:
        ray_tpu.shutdown()
