"""Control-plane flight recorder: lifecycle completeness, bounds, and
why-pending attribution (core/lifecycle.py).

Reference test models: python/ray/tests/test_task_events.py /
test_state_api.py — every submitted task must yield an ORDERED transition
chain ending in a terminal state, rings must never exceed their
configured size, and pending attribution must name the real blocker.
"""
import json
import os
import time

import ray_tpu
from ray_tpu.util import state as state_api


def _wait_until(cond, timeout=10.0, interval=0.1):
    """Cross-process lifecycle events are eventually consistent (worker/
    driver batches flush on event_flush_period_s; controller metrics
    drain on the telemetry cadence)."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _chain(events, kind, eid):
    evs = [e for e in events if e.get("kind") == kind and e.get("id") == eid]
    evs.sort(key=lambda e: e["ts"])
    return [e["state"] for e in evs]


def _ordered_subseq(chain, wanted):
    """True if ``wanted`` appears in ``chain`` in order (gaps allowed)."""
    it = iter(chain)
    return all(any(s == w for s in it) for w in wanted)


def test_direct_task_chain_and_lease_latency():
    """Direct-push tasks chart submitted → worker_assigned → running →
    finished across three processes (driver, controller, worker), and the
    lease chain records request→grant latency."""
    ray_tpu.init(num_cpus=2)
    try:

        @ray_tpu.remote
        def f(x):
            return x

        assert ray_tpu.get([f.remote(i) for i in range(3)], timeout=120) == [0, 1, 2]

        def finished_ids():
            evs = state_api.list_lifecycle_events(limit=100000)
            return {
                e["id"]
                for e in evs
                if e.get("kind") == "task"
                and e.get("name") == "f"
                and e["state"] == "FINISHED"
            }

        assert _wait_until(lambda: len(finished_ids()) == 3)
        evs = state_api.list_lifecycle_events(limit=100000)
        ids = {
            e["id"]
            for e in evs
            if e.get("kind") == "task" and e.get("name") == "f"
        }
        assert len(ids) == 3
        for tid in ids:
            chain = _chain(evs, "task", tid)
            assert chain[-1] == "FINISHED", chain
            assert _ordered_subseq(
                chain, ["SUBMITTED", "WORKER_ASSIGNED", "RUNNING", "FINISHED"]
            ), chain
        # Lease scheduling latency: REQUESTED -> GRANTED with a dwell.
        lease_grants = [
            e for e in evs if e.get("kind") == "lease" and e["state"] == "GRANTED"
        ]
        assert lease_grants and any("dwell_ms" in e for e in lease_grants)
        snap = state_api.summarize_lifecycle()
        assert snap["enabled"]
        dwell = snap["states"]["lease"]["REQUESTED"]["dwell_ms"]
        assert dwell["p50"] >= 0 and dwell["p99"] >= dwell["p50"]
    finally:
        ray_tpu.shutdown()


def test_controller_path_retry_chain(tmp_path):
    """A failed-then-retried task's chain passes through RETRYING and
    re-queues, ending FINISHED; worker startup (SPAWNED→REGISTERED)
    dwell pairs up."""
    ray_tpu.init(num_cpus=2, _system_config={"direct_normal_tasks": False})
    try:
        marker = str(tmp_path / "attempted")

        @ray_tpu.remote(max_retries=2, retry_exceptions=True)
        def flaky(path):
            if not os.path.exists(path):
                open(path, "w").close()
                raise RuntimeError("first attempt fails")
            return "ok"

        assert ray_tpu.get(flaky.remote(marker), timeout=120) == "ok"
        evs = state_api.list_lifecycle_events(limit=100000)
        ids = {
            e["id"]
            for e in evs
            if e.get("kind") == "task" and e.get("name") == "flaky"
        }
        assert len(ids) == 1
        chain = _chain(evs, "task", ids.pop())
        assert chain[-1] == "FINISHED", chain
        assert _ordered_subseq(
            chain,
            ["SUBMITTED", "QUEUED", "RUNNING", "RETRYING", "QUEUED",
             "RUNNING", "FINISHED"],
        ), chain
        # Worker startup dwell: the agent/head SPAWNED event pairs with
        # REGISTERED at the controller.
        assert _wait_until(
            lambda: "dwell_ms"
            in state_api.summarize_lifecycle()["states"]
            .get("worker", {})
            .get("SPAWNED", {})
        )
    finally:
        ray_tpu.shutdown()


def test_ring_never_exceeds_configured_size():
    ray_tpu.init(
        num_cpus=2,
        _system_config={"lifecycle_ring_size": 50, "direct_normal_tasks": False},
    )
    try:

        @ray_tpu.remote
        def f(x):
            return x

        # >= 4 transitions per task: 40 tasks overflow a 50-event ring.
        assert len(ray_tpu.get([f.remote(i) for i in range(40)], timeout=120)) == 40
        evs = state_api.list_lifecycle_events(limit=100000)
        assert len(evs) <= 50
        snap = state_api.summarize_lifecycle()
        assert snap["events"]["ring_size"] == 50
        assert snap["events"]["in_ring"] <= 50
        assert snap["events"]["recorded"] > 50  # ring dropped the oldest
        # Aggregates still saw everything the ring dropped.
        assert snap["states"]["task"]["FINISHED"]["count"] >= 40
    finally:
        ray_tpu.shutdown()


def test_pending_reason_resource_starved_and_infeasible():
    ray_tpu.init(num_cpus=1, _system_config={"direct_normal_tasks": False})
    try:

        @ray_tpu.remote(num_cpus=1)
        def hold(t):
            time.sleep(t)
            return 1

        @ray_tpu.remote(num_cpus=1)
        def quick():
            return 2

        a = hold.remote(1.5)
        time.sleep(0.3)  # let `hold` take the node's only CPU
        b = quick.remote()
        assert _wait_until(
            lambda: state_api.summarize_lifecycle()["pending_reasons"].get(
                "insufficient_resources", 0
            )
            >= 1
        )
        assert ray_tpu.get([a, b], timeout=60) == [1, 2]

        @ray_tpu.remote(resources={"GHOST": 1})
        def never():
            return 0

        never.remote()
        assert _wait_until(
            lambda: state_api.summarize_lifecycle()["pending_reasons"].get(
                "infeasible", 0
            )
            >= 1
        )
    finally:
        ray_tpu.shutdown()


def test_pending_reason_pg_gated():
    ray_tpu.init(num_cpus=2, _system_config={"direct_normal_tasks": False})
    try:
        from ray_tpu.util.placement_group import placement_group
        from ray_tpu.util.scheduling_strategies import (
            PlacementGroupSchedulingStrategy,
        )

        pg = placement_group([{"CPU": 64}], strategy="PACK")  # can never place

        @ray_tpu.remote(num_cpus=1)
        def inpg():
            return 1

        inpg.options(
            scheduling_strategy=PlacementGroupSchedulingStrategy(
                placement_group=pg
            )
        ).remote()
        assert _wait_until(
            lambda: state_api.summarize_lifecycle()["pending_reasons"].get(
                "pg_unready", 0
            )
            >= 1
        )
        evs = state_api.list_lifecycle_events(limit=100000)
        assert any(e.get("kind") == "pg" and e["state"] == "PENDING" for e in evs)
    finally:
        ray_tpu.shutdown()


def test_pg_and_actor_chains():
    ray_tpu.init(num_cpus=2)
    try:
        from ray_tpu.util.placement_group import (
            placement_group,
            remove_placement_group,
        )

        pg = placement_group([{"CPU": 1}], strategy="PACK")
        assert pg.ready(timeout=10)
        remove_placement_group(pg)
        evs = state_api.list_lifecycle_events(limit=100000)
        chain = _chain(evs, "pg", pg.id.hex())
        # 2-phase reservation charted: prepare (RESERVED) then commit.
        assert _ordered_subseq(
            chain, ["PENDING", "RESERVED", "CREATED", "REMOVED"]
        ), chain

        @ray_tpu.remote
        class A:
            def ping(self):
                return 1

        a = A.remote()
        assert ray_tpu.get(a.ping.remote(), timeout=120) == 1
        ray_tpu.kill(a)
        aid = a._actor_id.hex()
        assert _wait_until(
            lambda: "DEAD"
            in _chain(
                state_api.list_lifecycle_events(limit=100000), "actor", aid
            )
        )
        chain = _chain(state_api.list_lifecycle_events(limit=100000), "actor", aid)
        assert _ordered_subseq(
            chain, ["SUBMITTED", "QUEUED", "WORKER_ASSIGNED", "ALIVE", "DEAD"]
        ), chain
    finally:
        ray_tpu.shutdown()


def test_lifecycle_metric_tags_bounded():
    """Recorder metrics carry ONLY bounded tags (kind/state/reason —
    never task ids), keeping RTL004 and the series cap clean."""
    ray_tpu.init(num_cpus=2)
    try:

        @ray_tpu.remote
        def f():
            return 1

        ray_tpu.get([f.remote() for _ in range(3)], timeout=120)
        assert _wait_until(
            lambda: "task_state_transitions_total" in state_api.metrics_snapshot(),
            timeout=15,
        )
        snap = state_api.metrics_snapshot()
        for name in ("task_state_transitions_total", "task_state_dwell_ms"):
            for tags, _v in snap.get(name, {}).get("series", []):
                keys = {k for k, _ in tags}
                assert keys <= {"kind", "state"}, (name, keys)
        for tags, _v in snap.get("task_pending_reason_total", {}).get("series", []):
            assert {k for k, _ in tags} <= {"reason"}
        for tags, _v in snap.get("lease_latency_ms", {}).get("series", []):
            assert {k for k, _ in tags} == set()
    finally:
        ray_tpu.shutdown()


def test_summarize_tasks_capped_with_totals():
    ray_tpu.init(num_cpus=2, _system_config={"direct_normal_tasks": False})
    try:

        @ray_tpu.remote
        def f(x):
            return x

        ray_tpu.get([f.remote(i) for i in range(5)], timeout=120)
        s = state_api.summarize_tasks()
        assert s["f"]["FINISHED"] == 5
        t = s["_totals"]
        assert t["by_state"].get("FINISHED", 0) >= 5
        assert t["total"] >= 5 and not t["truncated"]
        # limit=0: names capped away, UNCAPPED totals still full.
        s0 = state_api.summarize_tasks(limit=0)
        assert set(s0) == {"_totals"}
        assert s0["_totals"]["by_state"].get("FINISHED", 0) >= 5
        assert s0["_totals"]["truncated"]
    finally:
        ray_tpu.shutdown()


def test_timeline_merges_lifecycle_and_spans(tmp_path, monkeypatch):
    """One `ray-tpu timeline` load carries task slices, scheduler
    lifecycle rows, AND user spans (with Chrome metadata records)."""
    monkeypatch.setenv("RAY_TPU_TRACE", "1")
    ray_tpu.init(num_cpus=2)
    from ray_tpu.util import tracing

    try:
        tracing.maybe_enable_from_env()

        @ray_tpu.remote
        def traced():
            return 1

        with tracing.start_span("user-span"):
            assert ray_tpu.get(traced.remote(), timeout=120) == 1
        assert _wait_until(
            lambda: any(
                e.get("kind") == "task" and e["state"] == "FINISHED"
                for e in state_api.list_lifecycle_events(limit=100000)
            )
        )
        out = str(tmp_path / "timeline.json")
        trace = state_api.timeline_chrome(out)
        cats = {e.get("cat") for e in trace}
        assert "lifecycle" in cats
        assert any(e.get("name") == "user-span" for e in trace)
        # process/thread name metadata makes merged timelines readable
        assert any(e.get("ph") == "M" for e in trace)
        with open(out) as fh:
            assert json.load(fh)
    finally:
        tracing.disable_tracing()
        ray_tpu.shutdown()


def test_span_sink_rotation(tmp_path, monkeypatch):
    """RAY_TPU_TRACE sinks are size-capped with a single rotation, and
    both halves (plus metadata) survive collect_spans."""
    from ray_tpu.util import tracing

    monkeypatch.setenv("RAY_TPU_TRACE_MAX_MB", "0.001")  # ~1 KiB cap
    tracing.enable_tracing(str(tmp_path))
    try:
        for _ in range(100):
            with tracing.start_span("spin"):
                pass
        tracing.flush()  # spans reach the file in batches; the cap holds line by line
        logs = os.listdir(os.path.join(str(tmp_path), "logs"))
        spans = [f for f in logs if f.startswith("spans-")]
        assert any(f.endswith(".jsonl.1") for f in spans)
        assert len(spans) == 2  # current + exactly one rotation
        total = sum(
            os.path.getsize(os.path.join(str(tmp_path), "logs", f))
            for f in spans
        )
        assert total < 4 * 1024  # bounded ~2x the cap
        events = tracing.collect_spans(str(tmp_path))
        assert any(
            e.get("ph") == "M" and e["name"] == "process_name" for e in events
        )
        assert any(
            e.get("ph") == "M" and e["name"] == "thread_name" for e in events
        )
        assert sum(1 for e in events if e.get("ph") == "X") > 0
    finally:
        tracing.disable_tracing()


def test_recorder_out_of_order_and_reopen_unit():
    """Unit: a late non-terminal half must not re-open a finished chain
    (ghost open entries), while a genuinely NEWER re-open (lineage
    reconstruction) still may; dwell never goes negative on reordered
    ingest."""
    from ray_tpu.core.lifecycle import LifecycleRecorder

    rec = LifecycleRecorder(ring_size=100)
    # Worker's FINISHED lands before the driver's SUBMITTED (flush race).
    rec.record("task", "t1", "RUNNING", ts=100.2)
    rec.record("task", "t1", "FINISHED", ts=100.3)
    rec.record("task", "t1", "SUBMITTED", ts=100.0)  # late, older ts
    assert ("task", "t1") not in rec._open  # no ghost re-open
    snap = rec.snapshot()
    assert snap["open"].get("task", {}) == {}
    # Genuine re-open: reconstruction arrives with a NEWER ts.
    rec.record("task", "t1", "RETRYING", ts=101.0)
    assert ("task", "t1") in rec._open
    rec.record("task", "t1", "FINISHED", ts=101.5)
    assert ("task", "t1") not in rec._open
    for (kind, state), dq in rec._dwell.items():
        assert all(v >= 0 for v in dq), (kind, state, list(dq))
    # A terminal event with an OLDER ts than the open entry (cross-host
    # clock skew) still closes the chain — no ghost open entry — and a
    # later non-terminal half stays stale.
    rec.record("task", "t2", "WORKER_ASSIGNED", ts=200.5)
    rec.record("task", "t2", "FINISHED", ts=200.2)  # skewed worker clock
    assert ("task", "t2") not in rec._open
    rec.record("task", "t2", "RUNNING", ts=200.3)  # late, pre-close ts
    assert ("task", "t2") not in rec._open
    assert rec.snapshot()["open"].get("task", {}) == {}


def test_recorder_pending_reason_dedup_unit():
    """Unit: why-pending counts once per reason CHANGE per entity, and an
    entry-less (evicted/unknown) entity never inflates the counter."""
    from ray_tpu.core.lifecycle import LifecycleRecorder

    rec = LifecycleRecorder(ring_size=100)
    rec.record("task", "t1", "QUEUED")
    for _ in range(5):  # pump re-visits must not re-count
        rec.pending_reason("task", "t1", "insufficient_resources")
    assert rec.snapshot()["pending_reasons"] == {"insufficient_resources": 1}
    rec.pending_reason("task", "t1", "no_idle_worker")  # change counts
    assert rec.snapshot()["pending_reasons"]["no_idle_worker"] == 1
    for _ in range(5):  # no open entry: never counted
        rec.pending_reason("task", "ghost", "infeasible")
    assert "infeasible" not in rec.snapshot()["pending_reasons"]


def test_envelope_smoke_breakdown_fields(tmp_path):
    """Tiny-depth envelope smoke (CPU, tier-1): the per-phase breakdown
    fields are present and non-negative in the row JSON."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "envelope_bench",
        os.path.join(os.path.dirname(__file__), "..", "benchmarks", "envelope.py"),
    )
    env = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(env)

    ray_tpu.init(num_cpus=4)
    try:
        rows = [env.bench_live_pgs(3), env.bench_queued_tasks(25)]
        for row in rows:
            row.update(env.lifecycle_phases())
        for row in rows:
            assert "phases" in row and row["phases"], row
            json.dumps(row)  # ENVELOPE_*.json-serializable
            for key, ph in row["phases"].items():
                assert ph["count"] >= 0, (key, ph)
                for k in ("p50", "p95", "p99"):
                    if k in ph:
                        assert ph[k] >= 0, (key, ph)
            assert isinstance(row["pending_reasons"], dict)
        ph = rows[1]["phases"]
        assert any(k.startswith("task.") for k in ph), ph
        assert any(k.startswith("lease.") for k in ph), ph
        assert any(k.startswith("pg.") for k in rows[0]["phases"])
    finally:
        ray_tpu.shutdown()
