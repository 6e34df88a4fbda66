"""BackendExecutor: PG + worker group + rank env + training drive loop.

Reference: python/ray/train/_internal/backend_executor.py — PG creation
:219, worker start :135, rank assignment :369 (accelerator visibility,
:299 there, is the controller's here: each TrainWorker is a TPU actor),
``start_training`` :451, health-check + ``_restart`` :759 (elastic retry).

Fault tolerance (this repo's elastic extension of :759):

* **fast detection** — the executor subscribes to the controller's
  lifecycle DEATH_CHANNEL (core/lifecycle.py): a SIGKILLed worker or
  host pushes a death event in ~the TCP connection-loss latency, so
  ``next_results`` raises :class:`GangMemberDiedError` within its next
  poll slice (~1s) instead of waiting out a blocked collective or RPC
  timeout.
* **repair-in-place** — ``restart()`` keeps surviving ``TrainWorker``
  actors WARM: their loops are broken out of any barrier via
  ``abort_run`` and their sessions torn down, but the processes (and
  their warm imports/JITs) survive. Dead ranks are either replaced
  within ``elastic_grace_s`` (rejoin at the same world size — the next
  ``setup_sessions`` re-runs the jax rendezvous with the same shape) or,
  when ``ScalingConfig.min_workers`` allows, the gang RE-MESHES to the
  surviving count and resumes from checkpoint at the smaller
  data-parallel width. Only when neither is possible does it fall back
  to the legacy tear-down-and-rebuild.
"""
from __future__ import annotations

import logging
import time
import uuid
from typing import Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.exceptions import ActorDiedError, ActorError, TaskError, WorkerCrashedError
from ray_tpu.train.config import ScalingConfig
from ray_tpu.train.session import TrainContext
from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.util.placement_group import placement_group, remove_placement_group

logger = logging.getLogger("ray_tpu.train")


class TrainingFailedError(RuntimeError):
    pass


class GangMemberDiedError(ActorError):
    """A gang member (or its host) died mid-training — detected via the
    lifecycle death channel, not an RPC timeout."""

    def __init__(self, rank: int = -1, node: str = "", reason: str = "",
                 detect_ms: float = -1.0):
        self.rank = rank
        self.node = node
        self.reason = reason
        self.detect_ms = detect_ms
        super().__init__(
            f"train worker rank {rank} on node {node[:12]} died: {reason} "
            f"(detected in {detect_ms:.0f}ms)"
        )

    def __reduce__(self):
        return (GangMemberDiedError,
                (self.rank, self.node, self.reason, self.detect_ms))


# GangMemberDiedError is covered via its ActorError base.
TRAINABLE_FAILURES = (
    ActorDiedError, ActorError, WorkerCrashedError, TaskError,
)


# ---------------------------------------------------------------------------
# Driver-side recovery metrics (flushed by the driver's metric flusher
# like train_driver_wait_ms; surfaced by state.summarize_train()).
# ---------------------------------------------------------------------------
_RECOVER_MS_BOUNDARIES = (
    10, 50, 100, 250, 500, 1000, 2500, 5000, 15000, 60000, 300000,
)
_recovery_metrics = None


def recovery_metrics():
    global _recovery_metrics
    if _recovery_metrics is None:
        from ray_tpu.util.metrics import Counter, Histogram

        class _M:
            def __init__(self):
                self.recoveries = Counter(
                    "train_recoveries_total",
                    "Gang recoveries by mode (rejoin/remesh/rebuild)",
                    ("run", "mode"),
                )
                self.deaths = Counter(
                    "train_worker_deaths_total",
                    "Train gang member deaths observed by the executor",
                    ("run",),
                )
                self.detect_ms = Histogram(
                    "train_detect_ms",
                    "Failure detection latency (death to executor raise)",
                    _RECOVER_MS_BOUNDARIES, ("run",),
                )
                self.repair_ms = Histogram(
                    "train_repair_ms",
                    "Gang repair latency (abort + replace/shrink), by mode",
                    _RECOVER_MS_BOUNDARIES, ("run", "mode"),
                )
                self.resume_ms = Histogram(
                    "train_resume_ms",
                    "Post-repair resume latency (session setup + rendezvous)",
                    _RECOVER_MS_BOUNDARIES, ("run",),
                )

        _recovery_metrics = _M()
    return _recovery_metrics


class BackendExecutor:
    def __init__(
        self,
        scaling: ScalingConfig,
        experiment_name: str,
        storage_path: str,
        max_failures: int = 0,
        elastic_grace_s: float = 10.0,
        checkpoint_async: bool = False,
    ):
        self.scaling = scaling
        self.experiment_name = experiment_name
        self.storage_path = storage_path
        self.max_failures = max_failures
        self.elastic_grace_s = elastic_grace_s
        self.checkpoint_async = checkpoint_async
        self.pg = None
        self.worker_group: Optional[WorkerGroup] = None
        self._failures = 0
        # Fast failure detection (lifecycle death events).
        self._death_sub = None
        self._seen_deaths: set = set()
        self.last_failure: Optional[GangMemberDiedError] = None
        # One dict per recovery: {mode, detect_ms, repair_ms, resume_ms,
        # world_size, ts} — the chaos bench and tests read this.
        self.recovery_log: List[dict] = []

    # -- introspection ----------------------------------------------------
    @property
    def failures(self) -> int:
        """Gang failures absorbed so far (public face of the retry
        counter the TrainingFailedError message reports)."""
        return self._failures

    @property
    def world_size(self) -> int:
        """CURRENT gang width — shrinks after an elastic re-mesh."""
        if self.worker_group is not None:
            return len(self.worker_group)
        return self.scaling.num_workers

    # -- lifecycle --------------------------------------------------------
    def start(self):
        self.pg = placement_group(
            self.scaling.bundles(), strategy=self.scaling.placement_strategy
        )
        if not self.pg.wait(timeout_seconds=60):
            raise TrainingFailedError(
                f"placement group for {self.scaling.num_workers} workers "
                f"({self.scaling.worker_resources()}) not placeable within 60s; "
                f"the cluster has {ray_tpu.cluster_resources()}. init() "
                f"registers a TPU resource for the chips it detects "
                f"(/dev/accel*, /dev/vfio/<group>) or is given (num_tpus=)."
            )
        self.worker_group = WorkerGroup(
            self.scaling.num_workers,
            self.scaling.worker_resources(),
            placement_group=self.pg,
        )
        self._subscribe_deaths()

    def _subscribe_deaths(self):
        if self._death_sub is not None:
            return
        try:
            from ray_tpu.core.lifecycle import DEATH_CHANNEL
            from ray_tpu.experimental import pubsub

            self._death_sub = pubsub.subscribe(DEATH_CHANNEL)
        except Exception as e:  # noqa: BLE001 — detection degrades to RPC errors
            logger.warning("death-event subscription unavailable: %s", e)

    def _gang_identity(self):
        """(actor ids, node ids) of the CURRENT gang, for death-event
        filtering."""
        actors, nodes = set(), set()
        for w in self.worker_group.workers if self.worker_group else ():
            actors.add(w.actor._actor_id.hex())
            nodes.add(w.node_id)
        return actors, nodes

    def check_deaths(self) -> Optional[GangMemberDiedError]:
        """Drain the death channel; return an error for the first event
        that names a CURRENT gang member (or its node). Dedups by entity
        so the worker-death + actor-death pair of one kill counts once."""
        if self._death_sub is None or self.worker_group is None:
            return None
        import queue as _q

        actors, nodes = self._gang_identity()
        hit = None
        while True:
            try:
                msg = self._death_sub.get_nowait()
            except _q.Empty:
                break
            if not isinstance(msg, dict):
                continue
            kind, eid = msg.get("kind"), msg.get("id", "")
            key = msg.get("actor") or eid
            victim_rank, victim_node = -1, ""
            if kind == "node" and eid in nodes and msg.get("state") == "DEAD":
                victim_node = eid
                for w in self.worker_group.workers:
                    if w.node_id == eid:
                        victim_rank = w.world_rank
                        break
            elif kind == "actor" and eid in actors:
                key = eid
            elif kind == "worker" and msg.get("actor") in actors:
                key = msg.get("actor")
            else:
                continue
            if key in self._seen_deaths:
                continue
            self._seen_deaths.add(key)
            if victim_rank < 0:
                for w in self.worker_group.workers:
                    if w.actor._actor_id.hex() == key:
                        victim_rank, victim_node = w.world_rank, w.node_id
                        break
            # Cross-process wall clocks (controller stamped ts, we read
            # now): on multi-host deployments NTP skew biases this by the
            # host offset (clamped at 0). Precise cross-host detection
            # latency needs a clock-sync estimate — single-host (tests,
            # bench) is exact.
            detect_ms = max(0.0, (time.time() - float(msg.get("ts", 0)))) * 1000.0
            err = GangMemberDiedError(
                rank=victim_rank,
                node=victim_node or msg.get("node", ""),
                reason=msg.get("reason", msg.get("state", "died")),
                detect_ms=detect_ms,
            )
            if hit is None:
                hit = err
        return hit

    def setup_sessions(self, latest_checkpoint: Optional[str],
                       dataset_shards: Optional[Dict] = None,
                       ckpt_index_start: int = 0):
        assert self.worker_group is not None
        t0 = time.monotonic()
        group_name = f"__train__{uuid.uuid4().hex[:8]}"
        self._group_name = group_name
        refs = []
        for w in self.worker_group.workers:
            ctx = TrainContext(
                world_size=len(self.worker_group),
                world_rank=w.world_rank,
                local_rank=w.local_rank,
                node_rank=w.node_rank,
                experiment_name=self.experiment_name,
                storage_path=self.storage_path,
            )
            env = dict(self.scaling.worker_env or {})
            # Each rank gets its split index of every shard coordinator
            # (rank == split keeps shard assignment stable across ranks).
            shards = {
                name: (actor, w.world_rank)
                for name, actor in (dataset_shards or {}).items()
            }
            data_context = None
            if shards:
                from ray_tpu.data.context import DataContext

                # Ship the driver's ingest knobs — DataContext is
                # process-local and would otherwise silently reset to
                # defaults inside the train workers.
                data_context = DataContext.get_current().to_dict()
            refs.append(
                w.actor.setup_session.remote(
                    ctx, group_name, latest_checkpoint, env,
                    jax_distributed=self.scaling.use_jax_distributed,
                    dataset_shards=shards or None,
                    data_context=data_context,
                    checkpoint_async=self.checkpoint_async,
                    ckpt_index_start=ckpt_index_start,
                )
            )
        ray_tpu.get(refs)
        resume_ms = (time.monotonic() - t0) * 1000.0
        if self.recovery_log and "resume_ms" not in self.recovery_log[-1]:
            self.recovery_log[-1]["resume_ms"] = round(resume_ms, 1)
            recovery_metrics().resume_ms.observe(
                resume_ms, {"run": self.experiment_name}
            )

    def start_training(self, train_fn: Callable, config: Optional[dict]) -> List:
        assert self.worker_group is not None
        return [
            w.actor.run_train_fn.remote(train_fn, config)
            for w in self.worker_group.workers
        ]

    def next_results(self, run_refs: Optional[List] = None) -> Optional[List[dict]]:
        """One result per rank, or None when all loops finished.

        Three failure-surfacing paths race, fastest wins: the lifecycle
        death channel (a killed worker/host raises GangMemberDiedError
        within one poll slice), the ``run_refs`` (a loop that dies
        before its first report — including failing to even deserialize
        the train fn — surfaces its error), and the result refs
        themselves."""
        assert self.worker_group is not None
        death = self.check_deaths()
        if death is not None:
            self._note_detection(death)
            raise death
        result_refs = [
            w.actor.next_result.remote() for w in self.worker_group.workers
        ]
        result_set = set(result_refs)
        pending_run = list(run_refs or [])
        while True:
            ready, _ = ray_tpu.wait(
                result_refs + pending_run,
                num_returns=len(result_refs),
                timeout=0.5,
            )
            death = self.check_deaths()
            if death is not None:
                self._note_detection(death)
                raise death
            if sum(1 for r in ready if r in result_set) == len(result_refs):
                break
            for r in ready:
                if r not in result_set:
                    # raises the loop's error if it failed; a clean
                    # finish resolves next_result() to None shortly.
                    # Seen run refs leave the wait set — otherwise a
                    # finished loop would satisfy the quota instantly
                    # and turn this into a zero-delay spin.
                    ray_tpu.get(r)
                    pending_run.remove(r)
        results = ray_tpu.get(result_refs)
        done = [r is None for r in results]
        if all(done):
            return None
        if any(done):
            raise TrainingFailedError(
                "ranks reported unevenly: some training loops finished while "
                "others are still calling report()"
            )
        return results

    def _note_detection(self, err: GangMemberDiedError):
        self.last_failure = err
        m = recovery_metrics()
        tags = {"run": self.experiment_name}
        m.deaths.inc(1, tags)
        if err.detect_ms >= 0:
            m.detect_ms.observe(err.detect_ms, tags)

    def can_retry(self) -> bool:
        self._failures += 1
        return self.max_failures < 0 or self._failures <= self.max_failures

    # -- repair -----------------------------------------------------------
    def restart(self, run_refs: Optional[List] = None):
        """Repair the gang in place (reference `_restart` :759 rebuilt
        from zero; here surviving workers stay warm). Steps: break every
        survivor out of its barrier (abort_run), wait for the old loops
        to unwind, probe liveness, tear down surviving sessions, then
        rejoin (replacements within ``elastic_grace_s``) / re-mesh
        (``min_workers`` floor) / rebuild."""
        assert self.worker_group is not None
        t0 = time.monotonic()
        wg = self.worker_group
        # 1. Abort every loop (dead members' calls just error) so
        # survivors unwind out of collective barriers NOW.
        abort_refs = [
            w.actor.abort_run.remote("gang repair") for w in wg.workers
        ]
        ray_tpu.wait(abort_refs, num_returns=len(abort_refs), timeout=5.0)
        if run_refs:
            # Old loop threads must have EXITED before sessions are
            # rebuilt — a straggler calling report() later would land in
            # the fresh session and skew its rank pacing. Bounded: a
            # loop ignoring the abort forfeits the wait.
            ray_tpu.wait(list(run_refs), num_returns=len(run_refs), timeout=15.0)
        # 2. Who is actually alive?
        alive = wg.probe(timeout=5.0)
        dead_idx = [i for i, a in enumerate(alive) if not a]
        if dead_idx and self.last_failure is None:
            # The failure surfaced through the direct transport (a
            # closed caller→actor connection fails refs even faster than
            # the death channel); the lifecycle event carries the
            # authoritative death timestamp — wait briefly for it so
            # detect_ms is still recorded.
            deadline = time.monotonic() + 2.0
            while time.monotonic() < deadline:
                death = self.check_deaths()
                if death is not None:
                    self._note_detection(death)
                    break
                time.sleep(0.05)
        # 3. Surviving sessions: normal teardown (collective + jax
        # runtime membership die with the OLD group name; the actor and
        # its warm imports survive for the next setup_sessions).
        td = [
            wg.workers[i].actor.teardown.remote()
            for i, a in enumerate(alive) if a
        ]
        ray_tpu.wait(td, num_returns=len(td), timeout=30.0)
        mode = "none"
        if dead_idx:
            survivors = len(wg) - len(dead_idx)
            min_workers = self.scaling.min_workers
            if survivors > 0 and wg.replace(dead_idx, self.elastic_grace_s):
                mode = "rejoin"
            elif (
                min_workers is not None
                and 0 < min_workers <= survivors < len(wg)
            ):
                wg.shrink(dead_idx)
                mode = "remesh"
                logger.warning(
                    "elastic re-mesh: %d -> %d workers (floor %d)",
                    self.scaling.num_workers, len(wg), min_workers,
                )
            else:
                # No replacement in time and no (viable) elastic floor:
                # the legacy full rebuild. This is also the 0-survivors
                # path.
                mode = "rebuild"
                self.shutdown_workers()
                self.start()
        repair_ms = (time.monotonic() - t0) * 1000.0
        # Consume the detection: a later recovery whose failure surfaced
        # only through the transport must re-wait for ITS death event
        # above, not inherit this one's stale detect_ms.
        detect, self.last_failure = self.last_failure, None
        entry = {
            "mode": mode,
            "repair_ms": round(repair_ms, 1),
            "world_size": self.world_size,
            "dead_ranks": dead_idx,
            "ts": time.time(),
        }
        if detect is not None and detect.detect_ms >= 0:
            entry["detect_ms"] = round(detect.detect_ms, 1)
        self.recovery_log.append(entry)
        m = recovery_metrics()
        m.recoveries.inc(1, {"run": self.experiment_name, "mode": mode})
        m.repair_ms.observe(repair_ms, {"run": self.experiment_name, "mode": mode})
        logger.warning(
            "gang repair #%d: mode=%s dead=%s world=%d (%.0fms)",
            self._failures, mode, dead_idx, self.world_size, repair_ms,
        )

    def shutdown_workers(self):
        if self.worker_group is not None:
            self.worker_group.shutdown()
            self.worker_group = None
        if self.pg is not None:
            try:
                remove_placement_group(self.pg)
            except Exception:
                pass
            self.pg = None

    def shutdown(self):
        if self._death_sub is not None:
            try:
                self._death_sub.close()
            except Exception:
                pass
            self._death_sub = None
        if self.worker_group is not None:
            for w in self.worker_group.workers:
                try:
                    ray_tpu.get(w.actor.teardown.remote(), timeout=5)
                except Exception:
                    pass
        self.shutdown_workers()
