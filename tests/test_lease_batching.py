"""Round-17 batched control plane: lease batches, pipelined pushes,
dynamic windows, and the resource topic bus.

Covers (ISSUE 17 satellite): batch-grant correctness under partial
grants and worker-pool spillback, deterministic growth/shrink of both
dynamic windows, ResourceViewMirror delta+reconcile equivalence to
polling under seeded out-of-order delivery, and a seeded chaos arm
proving no task loss when a batched push lands on a dying worker
(retry semantics unchanged from the per-task path).
"""
import asyncio
import random
import time
from types import SimpleNamespace

import pytest

import ray_tpu
from ray_tpu.core import normal_direct
from ray_tpu.core.normal_direct import NormalSubmitter, _NCall
from ray_tpu.core.pubsub import ResourceViewMirror


# =====================================================================
# ResourceViewMirror: delta + reconcile == polling
# =====================================================================

def _delta(node, seq, avail):
    return {"node": node, "seq": seq, "available": avail,
            "total": {"CPU": 8}, "draining": False, "avoid": None}


def test_mirror_applies_deltas_and_drops_stale():
    m = ResourceViewMirror()
    assert m.apply(_delta("a", 1, {"CPU": 5}))
    assert m.apply(_delta("a", 3, {"CPU": 2}))
    # reordered older delta must not regress the view
    assert not m.apply(_delta("a", 2, {"CPU": 7}))
    assert m.available("a") == {"CPU": 2}
    assert m.stale == 1 and m.applied == 2


def test_mirror_tombstone_blocks_resurrection():
    m = ResourceViewMirror()
    m.apply(_delta("a", 1, {"CPU": 5}))
    assert m.apply({"node": "a", "seq": 3, "removed": True})
    assert "a" not in m.nodes
    # a reordered pre-removal delta arrives late: seq floor rejects it
    assert not m.apply(_delta("a", 2, {"CPU": 7}))
    assert "a" not in m.nodes


def test_mirror_out_of_order_converges_to_polling(seed=1234):
    """Seeded scrambled delivery (reorder + duplicate + drop) followed by
    one reconcile snapshot lands the mirror exactly on the state a
    poller reading the authority would see."""
    rng = random.Random(seed)
    truth = {}  # node -> row; seqs per node
    seqs = {}
    deltas = []
    nodes = [f"n{i}" for i in range(8)]
    for _ in range(300):
        node = rng.choice(nodes)
        seqs[node] = seqs.get(node, 0) + 1
        if node in truth and rng.random() < 0.1:
            truth.pop(node)
            deltas.append({"node": node, "seq": seqs[node], "removed": True})
            continue
        row = {"available": {"CPU": rng.randint(0, 8)},
               "total": {"CPU": 8},
               "draining": rng.random() < 0.1,
               "avoid": rng.choice([None, "soft", "hard"])}
        truth[node] = row
        deltas.append({"node": node, "seq": seqs[node], **row})
    # at-most-once push channel: drop 20%, duplicate 10%, shuffle all
    delivered = [d for d in deltas if rng.random() >= 0.2]
    delivered += [d for d in delivered if rng.random() < 0.1]
    rng.shuffle(delivered)
    m = ResourceViewMirror()
    for d in delivered:
        m.ingest(d)
    # the reconcile snapshot (what the controller broadcasts periodically)
    snapshot = {"snapshot": True,
                "nodes": {n: {"seq": seqs[n], **row}
                          for n, row in truth.items()}}
    m.ingest(snapshot)
    polled = {n: {"available": r["available"], "total": r["total"],
                  "draining": r["draining"], "avoid": r["avoid"]}
              for n, r in truth.items()}
    assert m.nodes == polled
    assert m.reconciles == 1
    # post-reconcile deltas keep flowing (seq floors were preserved)
    some = next(iter(truth))
    seqs[some] += 1
    assert m.apply(_delta(some, seqs[some], {"CPU": 1}))


# =====================================================================
# Dynamic windows: deterministic growth / shrink (fake-peer harness)
# =====================================================================

class _FakeId:
    def __init__(self, n):
        self._h = f"{n:032x}"

    def hex(self):
        return self._h

    def __hash__(self):
        return hash(self._h)

    def __eq__(self, other):
        return isinstance(other, _FakeId) and self._h == other._h


class _FakeSpec:
    def __init__(self, n, max_retries=3):
        self.task_id = _FakeId(n)
        self.name = f"t{n}"
        self.runtime_env = None
        self.scheduling_strategy = None
        self.max_retries = max_retries
        self.retry_exceptions = False
        self.dependencies = []
        from ray_tpu.core.resources import ResourceSet

        self.resources = ResourceSet.from_dict({"CPU": 1})

    def scheduling_class(self):
        return ("CPU", 1)

    def return_ids(self):
        return []


class _FakeWorker:
    """Worker peer: records push batch sizes; completion is scripted."""

    closed = False

    def __init__(self, loop, hold=False):
        self.loop = loop
        self.hold = hold  # never resolve (keeps queue backlogged)
        self.fail_next = 0
        self.pushes = []  # [(batch_size, [task ids])]
        self._held = []

    def call_nowait(self, method, packed, inline=None):
        assert method == "push_task_batch"
        fut = self.loop.create_future()
        self.pushes.append(len(packed))
        if self.hold:
            self._held.append((fut, len(packed)))
            return fut
        if self.fail_next > 0:
            self.fail_next -= 1
            self.loop.call_soon(
                fut.set_exception, ConnectionError("injected batch loss")
            )
        else:
            self.loop.call_soon(fut.set_result, [([], None)] * len(packed))
        return fut

    async def notify(self, *a, **kw):
        pass


class _FakeController:
    """Controller peer: scripted lease grants and worker handouts."""

    closed = False

    def __init__(self, loop, worker_factory):
        self.loop = loop
        self.worker_factory = worker_factory
        self.lease_batch_counts = []  # the dynamic window, as requested
        self.grant_script = []  # per lease_batch call: max grants (None=all)
        self.miss_script = []  # per handed-out lease: True = pool miss
        self._next = 0

    async def call(self, method, *a, **kw):
        if method == "lease_batch":
            count = a[5]
            self.lease_batch_counts.append(count)
            cap = self.grant_script.pop(0) if self.grant_script else None
            n = count if cap is None else min(cap, count)
            grants = []
            for _ in range(n):
                self._next += 1
                grants.append({
                    "lease_id": self._next.to_bytes(8, "big"),
                    "agent_addr": "controller",
                    "node_id": "00" * 16,
                })
            return {"grants": grants}
        if method == "lease_worker_batch":
            outs = []
            for lid in a[0]:
                miss = self.miss_script.pop(0) if self.miss_script else False
                if miss:
                    outs.append(None)
                else:
                    outs.append({
                        "worker_addr": f"w{int.from_bytes(lid, 'big')}",
                        "worker_id": "ab" * 14,
                    })
            return outs
        if method == "lease_worker":
            # parked single-claim fallback for pool misses
            self.single_claims = getattr(self, "single_claims", 0) + 1
            return {
                "worker_addr": f"w{int.from_bytes(a[0], 'big')}",
                "worker_id": "cd" * 14,
            }
        if method == "worker_death_info":
            return None
        raise AssertionError(f"unexpected controller call {method}")

    async def notify(self, *a, **kw):
        pass


def _make_submitter(loop, controller, cfg_extra=None, monkeypatch=None):
    cfg = {
        "lifecycle_events": False,
        "lease_batching": True,
        "max_tasks_in_flight_per_lease": 2,
        "max_leases_per_scheduling_key": 10,
        "lease_batch_max": 16,
        "task_push_batch_max": 64,
        "worker_lease_timeout_s": 5.0,
    }
    cfg.update(cfg_extra or {})
    core = SimpleNamespace(
        config=cfg,
        peer=controller,
        memory_store=None,
        loop_runner=SimpleNamespace(loop=loop, submit=lambda c: None),
    )
    monkeypatch.setattr(normal_direct, "pack_normal_task", lambda s: s.task_id.hex())
    completed = []
    monkeypatch.setattr(
        normal_direct, "complete_results",
        lambda core_, spec, results, error: completed.append(spec.task_id.hex()),
    )
    failed = []
    monkeypatch.setattr(
        normal_direct, "fail_returns",
        lambda core_, spec, exc, serialized=None: failed.append(
            (spec.task_id.hex(), exc)
        ),
    )
    sub = NormalSubmitter(core)
    return sub, completed, failed


def _enqueue(sub, specs):
    for spec in specs:
        sub._enqueue(spec, _NCall(spec, None, spec.max_retries))


async def _drain(sub, timeout=10.0):
    deadline = time.monotonic() + timeout
    while sub.tasks and time.monotonic() < deadline:
        await asyncio.sleep(0.01)
    assert not sub.tasks, f"{len(sub.tasks)} tasks never completed"


def test_lease_window_slow_start_growth(monkeypatch):
    """Fully-granted full-window requests double the lease window
    deterministically: 1, 2, 4, 8, 16, then capped at lease_batch_max."""

    async def main():
        loop = asyncio.get_running_loop()
        workers = []

        def factory(addr):
            w = _FakeWorker(loop, hold=True)  # backlog never drains
            workers.append(w)
            return w

        ctl = _FakeController(loop, factory)
        sub, completed, failed = _make_submitter(loop, ctl, monkeypatch=monkeypatch)

        async def wp(addr):
            return factory(addr)

        sub._worker_peer = wp
        _enqueue(sub, [_FakeSpec(i) for i in range(400)])
        deadline = time.monotonic() + 5
        while len(ctl.lease_batch_counts) < 6 and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        assert ctl.lease_batch_counts[:5] == [1, 2, 4, 8, 16]
        ks = next(iter(sub.keys.values()))
        assert ks.lease_window == 16  # capped at lease_batch_max

    asyncio.run(main())


def test_lease_window_shrinks_on_partial_grant(monkeypatch):
    async def main():
        loop = asyncio.get_running_loop()
        ctl = _FakeController(loop, None)
        sub, completed, failed = _make_submitter(loop, ctl, monkeypatch=monkeypatch)
        held = []

        async def wp(addr):
            w = _FakeWorker(loop, hold=True)
            held.append(w)
            return w

        sub._worker_peer = wp
        # call 1 (count 1): full grant -> window 2
        # call 2 (count 2): partial grant (1 of 2) -> window 1
        # call 3 (count 1): full grant -> window 2 (recovery)
        ctl.grant_script = [None, 1, None]
        _enqueue(sub, [_FakeSpec(i) for i in range(200)])
        deadline = time.monotonic() + 5
        while len(ctl.lease_batch_counts) < 4 and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        assert ctl.lease_batch_counts[:4] == [1, 2, 1, 2]

    asyncio.run(main())


def test_lease_window_shrinks_on_worker_pool_miss(monkeypatch):
    """A pool miss (agent had no free worker) is spillback: the lease
    window halves and the missed grant falls back to the parked
    single-worker claim — which still produces a usable lease."""

    async def main():
        loop = asyncio.get_running_loop()
        ctl = _FakeController(loop, None)
        sub, completed, failed = _make_submitter(loop, ctl, monkeypatch=monkeypatch)

        async def wp(addr):
            return _FakeWorker(loop, hold=True)

        sub._worker_peer = wp
        # call 1: count 1, granted 1, handout MISSES -> window stays 1
        # (2 after full grant, halved back to 1 by the miss), and the
        # parked lease_worker claim is issued for the missed grant.
        ctl.miss_script = [True]
        _enqueue(sub, [_FakeSpec(i) for i in range(200)])
        deadline = time.monotonic() + 5
        while len(ctl.lease_batch_counts) < 2 and time.monotonic() < deadline:
            await asyncio.sleep(0.01)
        assert ctl.lease_batch_counts[:2] == [1, 1]
        assert getattr(ctl, "single_claims", 0) >= 1
        ks = next(iter(sub.keys.values()))
        assert ks.leases, "parked claim never produced a lease"

    asyncio.run(main())


def test_push_window_growth_and_batch_failure_retry(monkeypatch):
    """One lease: the push window doubles on clean full-window batches;
    a whole-batch connection loss halves it, burns ONE attempt per task,
    and requeues in order (retry semantics identical to per-task push)."""

    async def main():
        loop = asyncio.get_running_loop()
        ctl = _FakeController(loop, None)
        sub, completed, failed = _make_submitter(
            loop, ctl, cfg_extra={"task_push_batch_max": 16},
            monkeypatch=monkeypatch,
        )
        workers = []

        async def wp(addr):
            w = _FakeWorker(loop)
            workers.append(w)
            return w

        sub._worker_peer = wp
        # one lease only: every later lease_batch gets zero grants
        ctl.grant_script = [1] + [0] * 100000
        specs = [_FakeSpec(i) for i in range(100)]
        _enqueue(sub, specs)
        await _drain(sub)
        assert not failed
        assert sorted(completed) == sorted(s.task_id.hex() for s in specs)
        sizes = workers[0].pushes
        assert max(sizes) == 16, sizes  # grew to the configured cap
        assert sizes[0] == 2  # slow-start floor (push_init)

        # --- failure leg: fresh submitter, second batch lost on the wire
        ctl2 = _FakeController(loop, None)
        sub2, completed2, failed2 = _make_submitter(
            loop, ctl2, monkeypatch=monkeypatch
        )
        workers2 = []

        async def wp2(addr):
            w = _FakeWorker(loop)
            w.fail_next = 0 if workers2 else 1  # first worker loses batch 1
            workers2.append(w)
            return w

        sub2._worker_peer = wp2
        ctl2.grant_script = [1, 1] + [0] * 100000
        specs2 = [_FakeSpec(1000 + i) for i in range(20)]
        _enqueue(sub2, specs2)
        await _drain(sub2)
        assert not failed2
        assert sorted(completed2) == sorted(s.task_id.hex() for s in specs2)
        # the lost batch burned exactly one attempt per member task
        # (visible as a second worker being claimed after _lease_lost)
        assert len(workers2) >= 2

    asyncio.run(main())


def test_terminal_failure_after_attempts_exhausted(monkeypatch):
    """Batch losses consume per-task attempts; at zero the task fails
    with a worker-death error instead of requeueing forever."""

    async def main():
        loop = asyncio.get_running_loop()
        ctl = _FakeController(loop, None)
        sub, completed, failed = _make_submitter(loop, ctl, monkeypatch=monkeypatch)
        workers = []

        async def wp(addr):
            w = _FakeWorker(loop)
            w.fail_next = 99  # every batch to every worker is lost
            workers.append(w)
            return w

        sub._worker_peer = wp
        spec = _FakeSpec(7, max_retries=2)
        _enqueue(sub, [spec])
        await _drain(sub)
        assert not completed
        assert len(failed) == 1
        assert failed[0][0] == spec.task_id.hex()

    asyncio.run(main())


# =====================================================================
# Integration: real cluster, batched + legacy A/B, chaos arms
# =====================================================================

def test_batched_path_correct_and_observable():
    ray_tpu.init(num_cpus=4)
    try:
        @ray_tpu.remote
        def sq(x):
            return x * x

        assert ray_tpu.get([sq.remote(i) for i in range(300)], timeout=120) == [
            i * i for i in range(300)
        ]
        core = ray_tpu.core.api._require_worker()
        assert core._normal_sub is not None and core._normal_sub.batching
        # The controller ingests task events asynchronously (batched, with
        # yields every 2k) — get() returning does not mean the recorder has
        # caught up, so poll until the histogram reflects all 300 pushes.
        deadline = time.monotonic() + 20
        while True:
            snap = core._call("summarize_lifecycle")
            cp = snap["control_plane"]
            hist = cp["task_push_batch_size"]
            if hist and hist["count"] >= 1 and hist["sum"] >= 300:
                break
            if time.monotonic() > deadline:
                break
            time.sleep(0.1)
        assert hist and hist["count"] >= 1 and hist["sum"] >= 300
        # batching actually batched: mean tasks per frame > 1
        assert hist["avg"] > 1.0, hist
        lease_hist = cp["lease_batch_size"]
        assert lease_hist and lease_hist["count"] >= 1
        assert sum(cp["scheduler_fast_path_total"].values()) >= 1
    finally:
        ray_tpu.shutdown()


def test_legacy_knob_restores_per_task_path():
    ray_tpu.init(num_cpus=4, _system_config={"lease_batching": False})
    try:
        @ray_tpu.remote
        def sq(x):
            return x * x

        assert ray_tpu.get([sq.remote(i) for i in range(60)], timeout=120) == [
            i * i for i in range(60)
        ]
        core = ray_tpu.core.api._require_worker()
        assert core._normal_sub is not None and not core._normal_sub.batching
    finally:
        ray_tpu.shutdown()


def test_seeded_push_batch_fault_injection_no_task_loss():
    """Deterministic wire-level chaos: the first two push_task_batch
    frames out of the driver error (seeded FaultSchedule) — every task
    still completes through the per-task retry path."""
    from ray_tpu.util import chaos

    ray_tpu.init(num_cpus=4)
    try:
        chaos.install_fault_plan({
            "seed": 42,
            "rules": [{
                "method": "push_task_batch",
                "direction": "out",
                "action": "error",
                "count": 2,
            }],
        })

        @ray_tpu.remote(max_retries=5)
        def sq(x):
            return x * x

        assert ray_tpu.get(
            [sq.remote(i) for i in range(64)], timeout=120
        ) == [i * i for i in range(64)]
        log = chaos.injection_log()
        fired = [e for e in log if e["method"] == "push_task_batch"]
        assert len(fired) == 2, "fault plan never hit the batched push"
    finally:
        chaos.install_fault_plan(None)
        ray_tpu.shutdown()


def test_chaos_dying_workers_batched_push_no_task_loss():
    """Batched pushes against workers being SIGKILLed underneath them:
    retriable tasks all complete (no task loss, no duplicate-result
    corruption) — semantics unchanged from PR 13's per-task path."""
    from ray_tpu.util.chaos import WorkerKillerActor

    ray_tpu.init(num_cpus=4)
    try:
        killer = WorkerKillerActor.remote(
            kill_interval_s=0.3, max_kills=3, seed=17
        )
        ray_tpu.get(killer.run.remote(), timeout=120)

        @ray_tpu.remote(max_retries=10)
        def chunk(i):
            time.sleep(0.08)
            return i * i

        refs = [chunk.remote(i) for i in range(48)]
        assert ray_tpu.get(refs, timeout=180) == [i * i for i in range(48)]
        killed = ray_tpu.get(killer.stop_run.remote(), timeout=120)
        assert killed, "chaos killer never killed anything"
    finally:
        ray_tpu.shutdown()


def test_agent_mirror_tracks_controller_view():
    """The agent's push-fed ResourceViewMirror converges on the
    controller's authoritative resource view (delta stream + reconcile
    equivalence, end to end)."""
    from ray_tpu.core.cluster_utils import Cluster

    cluster = Cluster(head_resources={"CPU": 2})
    cluster.add_node(num_cpus=2)
    cluster.connect()
    try:
        @ray_tpu.remote(num_cpus=1)
        def warm():
            return 1

        assert sum(ray_tpu.get([warm.remote() for _ in range(8)], timeout=120)) == 8
        core = ray_tpu.core.api._require_worker()
        deadline = time.time() + 20
        ok = False
        while time.time() < deadline and not ok:
            rows = {n["node_id"]: n for n in core.list_state("nodes")
                    if n.get("state") == "ALIVE"}
            telem = [(n.get("telemetry") or {}).get("resource_mirror")
                     for n in rows.values()]
            mirrors = [t for t in telem if t]
            # the non-head agent's heartbeat reports a mirror that has
            # applied at least the initial snapshot covering all nodes
            ok = any(
                t["nodes"] == len(rows) and (t["applied"] or t["reconciles"])
                for t in mirrors
            )
            if not ok:
                time.sleep(0.25)
        assert ok, "agent resource mirror never converged"
    finally:
        cluster.shutdown()
