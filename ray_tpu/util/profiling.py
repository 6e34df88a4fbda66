"""On-demand distributed profiling: stack dumps, sampling CPU profiles,
attachable device traces, and incident auto-capture.

Reference: the dashboard reporter's py-spy endpoints (python/ray/dashboard/
modules/reporter/ — per-worker ``Stack Trace`` / ``CPU Flame Graph`` links)
and ``ray stack``. py-spy attaches to a pid from outside; here every
process profiles itself over its existing RPC channel, which works in
containers and needs no ptrace capability:

* **stack dumps** — :func:`dump_stacks` snapshots every thread
  (``sys._current_frames`` + thread names + held-lock annotations from the
  lockwatch watchdog). The controller fans ``dump_stacks`` out cluster-wide
  and :func:`merge_stack_dumps` deduplicates identical stacks across
  processes so a 100-worker dump reads as a handful of distinct states.
* **sampling CPU profiler** — :class:`CpuSampler` samples all threads at a
  bounded rate/duration, tags each sample with the task the executing
  thread is running (:func:`set_thread_task`, maintained by worker_main),
  and renders collapsed-stack text (:func:`collapsed_text`) or speedscope
  JSON (:func:`speedscope_json`). Busy/idle classification is leaf-frame
  based (a thread parked in ``wait``/``select``/``acquire`` is idle), and
  busy samples feed ``task_cpu_ms{name}`` through the metrics pipeline.
* **attachable device traces** — :func:`device_trace_start` /
  :func:`device_trace_stop` drive ``jax.profiler`` on an already-running
  process (no restart), writing into the same session ``profiles/`` root
  the runtime_env plugin uses so the existing list/fetch path applies.
* **incident auto-capture** — a continuous low-rate sampler
  (:class:`ContinuousSampler`, ``profiling_continuous_hz``) keeps a
  bounded ring of recent samples; detector hooks (lockwatch long-hold /
  order-cycle, recompile storms, serve SLO breaches) call
  :func:`incident` to flush stacks + the recent-sample ring + detector
  context into a bounded on-disk incident directory.

This module must import standalone (cheaply, no jax): workers, agents,
the controller, and drivers all load it at process start.
"""
from __future__ import annotations

import collections
import json
import logging
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger("ray_tpu.profiling")

# ---------------------------------------------------------------------------
# Task attribution: executing thread -> current task/actor-method name.
# worker_main._run stamps this around every task execution so CPU samples
# (and stack dumps) can attribute threads to named work.
# ---------------------------------------------------------------------------
_task_tags: Dict[int, str] = {}


def set_thread_task(name: Optional[str]):
    """Tag THIS thread as executing ``name`` (None clears the tag)."""
    ident = threading.get_ident()
    if name:
        _task_tags[ident] = name
    else:
        _task_tags.pop(ident, None)


def thread_task_tags() -> Dict[int, str]:
    return dict(_task_tags)


# Leaf frames that mean "parked, not burning CPU" — the sampling profiler
# is a wall profiler (it sees blocked threads too, like py-spy --idle);
# busy/idle classification keeps task_cpu_ms honest.
_IDLE_LEAF_FUNCS = frozenset(
    {
        "wait", "wait_for", "sleep", "select", "poll", "epoll", "kevent",
        "accept", "accept4", "acquire", "join", "get", "park",
        "_recv_msg", "recv", "recv_into", "read", "readinto", "settrace",
        "channel_wait", "_wait_for_tstate_lock", "epoll_wait",
    }
)
_IDLE_LEAF_FILES = ("selectors.py", "threading.py", "queue.py", "ssl.py")


def _frame_stack(frame) -> Tuple[Tuple[str, int, str], ...]:
    """(file, line, func) tuples, LEAF FIRST (cheap f_back walk — no
    traceback machinery on the sampling hot path)."""
    out = []
    while frame is not None and len(out) < 128:
        code = frame.f_code
        out.append((code.co_filename, frame.f_lineno, code.co_name))
        frame = frame.f_back
    return tuple(out)


def _is_idle(frames: Tuple[Tuple[str, int, str], ...]) -> bool:
    if not frames:
        return True
    fname, _line, func = frames[0]
    if func in _IDLE_LEAF_FUNCS:
        return True
    return fname.endswith(_IDLE_LEAF_FILES)


def _frame_label(f: Tuple[str, int, str]) -> str:
    fname, line, func = f
    mod = os.path.basename(fname)
    if mod.endswith(".py"):
        mod = mod[:-3]
    return f"{mod}.{func}"


def process_label() -> str:
    """Human label for this process (mirrors tracing._process_name, but
    importable before a session exists)."""
    wid = os.environ.get("RAY_TPU_WORKER_ID", "")
    if wid:
        return f"worker-{wid[:8]}"
    argv = " ".join(sys.argv[:2])
    if "controller" in argv:
        return "controller"
    if "node_agent" in argv:
        return "node_agent"
    return f"driver-{os.getpid()}"


# ---------------------------------------------------------------------------
# Stack dumps
# ---------------------------------------------------------------------------
def dump_stacks() -> dict:
    """Structured snapshot of every thread in THIS process.

    Deliberately lock-free with respect to application state: it touches
    only ``sys._current_frames`` (GIL), the threading registry, and the
    lockwatch meta lock via a bounded-timeout acquire — so dumping a
    process that is deadlocked (or the controller mid-storm) always
    returns.
    """
    threads = {t.ident: t for t in threading.enumerate()}
    held = _lockwatch_held_snapshot()
    tags = thread_task_tags()
    rows = []
    for ident, frame in sys._current_frames().items():
        t = threads.get(ident)
        frames = _frame_stack(frame)
        rows.append(
            {
                "ident": ident,
                "name": t.name if t is not None else "?",
                "daemon": bool(t.daemon) if t is not None else None,
                "task": tags.get(ident),
                "idle": _is_idle(frames),
                # root-first for human reading (like traceback output)
                "frames": [
                    {"file": f, "line": ln, "func": fn}
                    for f, ln, fn in reversed(frames)
                ],
                "held_locks": held.get(ident, []),
            }
        )
    rows.sort(key=lambda r: r["name"])
    return {
        "process": process_label(),
        "pid": os.getpid(),
        "ts": time.time(),
        "threads": rows,
    }


def _lockwatch_held_snapshot() -> Dict[int, List[dict]]:
    try:
        from ray_tpu.util import lockwatch

        return lockwatch.held_snapshot()
    except Exception as e:  # noqa: BLE001 — dump must work without the watchdog
        logger.debug("lockwatch held snapshot unavailable: %s", e)
        return {}


def format_stacks(dump: dict) -> str:
    """One process's dump as text (``ray stack`` style)."""
    out = [f"process {dump.get('process', '?')} (pid {dump.get('pid', '?')})"]
    for t in dump.get("threads", ()):
        head = f"--- Thread {t['name']} (id {t['ident']})"
        if t.get("task"):
            head += f" [task {t['task']}]"
        if t.get("idle"):
            head += " [idle]"
        out.append(head + " ---")
        for lk in t.get("held_locks", ()):
            out.append(
                f"    holds {lk['lock']} (acquired {lk['acquired_at']}, "
                f"{lk['held_ms']:.0f} ms ago)"
            )
        for f in t.get("frames", ()):
            out.append(f"  {f['file']}:{f['line']} in {f['func']}")
    return "\n".join(out)


def merge_stack_dumps(dumps: Dict[str, Any]) -> str:
    """Cluster-wide merged report: threads with IDENTICAL stacks (across
    processes) collapse into one block listing every occurrence — the
    100-idle-workers case reads as one entry, and the one wedged actor
    stands out. ``dumps``: {process_name: dump dict | error string}."""
    groups: Dict[tuple, List[str]] = {}
    meta: Dict[tuple, dict] = {}
    errors: List[str] = []
    for proc, dump in sorted(dumps.items()):
        if not isinstance(dump, dict):
            errors.append(f"{proc}: {dump}")
            continue
        for t in dump.get("threads", ()):
            key = tuple((f["file"], f["func"]) for f in t.get("frames", ()))
            who = f"{proc} / {t['name']}"
            if t.get("task"):
                who += f" [task {t['task']}]"
            for lk in t.get("held_locks", ()):
                who += f" (holds {lk['lock']} {lk['held_ms']:.0f}ms)"
            groups.setdefault(key, []).append(who)
            if key not in meta:
                meta[key] = t
    out = []
    for key, whos in sorted(groups.items(), key=lambda kv: -len(kv[1])):
        t = meta[key]
        out.append(f"== {len(whos)} thread(s) ==")
        for who in whos[:20]:
            out.append(f"  {who}")
        if len(whos) > 20:
            out.append(f"  ... and {len(whos) - 20} more")
        for f in t.get("frames", ()):
            out.append(f"    {f['file']}:{f['line']} in {f['func']}")
        out.append("")
    for err in errors:
        out.append(f"!! unavailable: {err}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# Sampling CPU profiler
# ---------------------------------------------------------------------------
_metrics = None


def _get_metrics():
    """Lazy metric singletons (this module imports before a session)."""
    global _metrics
    if _metrics is None:
        from ray_tpu.util.metrics import Counter, Histogram

        _metrics = {
            "samples": Counter(
                "profiling_samples_total",
                "CPU profiler samples taken in this process",
                ("mode",),
            ),
            "task_cpu": Histogram(
                "task_cpu_ms",
                "Sampled busy CPU time attributed to named tasks per "
                "profiling window",
                boundaries=(1, 5, 10, 25, 50, 100, 250, 500, 1000, 2500,
                            5000, 15000, 60000),
                tag_keys=("name",),
            ),
            "incidents": Counter(
                "profiling_incidents_total",
                "Incident capture bundles written, by detector trigger",
                ("trigger",),
            ),
        }
    return _metrics


class CpuSampler:
    """Threading-based sampling profiler over ``sys._current_frames``.

    Bounded by construction: fixed rate, fixed max unique stacks, and the
    run loop exits at ``duration_s`` even if nobody calls :meth:`stop`.
    Aggregates in-memory (stack -> count); a 10 s @ 100 Hz profile of a
    50-thread process stays well under a megabyte.
    """

    MAX_UNIQUE_STACKS = 10000

    def __init__(self, hz: float = 100.0, duration_s: Optional[float] = None,
                 mode: str = "on_demand"):
        self.hz = max(1.0, min(float(hz), 1000.0))
        self.duration_s = duration_s
        self.mode = mode
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # (thread, task, (file, func) frames leaf-first)
        #   -> [count, busy_count, representative frames with lines]
        self.stacks: Dict[tuple, list] = {}
        self.task_busy: Dict[str, int] = {}
        self.samples_total = 0
        self.started_at = 0.0
        self.stopped_at = 0.0

    # -- control -------------------------------------------------------
    def start(self):
        self.started_at = time.time()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="cpu-sampler"
        )
        self._thread.start()
        return self

    def stop(self) -> dict:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        self.stopped_at = time.time()
        self._flush_metrics()
        return self.result()

    def _run(self):
        interval = 1.0 / self.hz
        deadline = (
            time.monotonic() + self.duration_s if self.duration_s else None
        )
        my_ident = threading.get_ident()
        while not self._stop.is_set():
            if deadline is not None and time.monotonic() >= deadline:
                break
            t0 = time.monotonic()
            self._sample_once(my_ident)
            elapsed = time.monotonic() - t0
            self._stop.wait(max(0.001, interval - elapsed))

    def _sample_once(self, skip_ident: int):
        names = {t.ident: t.name for t in threading.enumerate()}
        tags = thread_task_tags()
        for ident, frame in sys._current_frames().items():
            if ident == skip_ident:
                continue
            frames = _frame_stack(frame)
            busy = not _is_idle(frames)
            task = tags.get(ident)
            # Aggregation key drops line numbers (the collapsed/speedscope
            # output is function-level anyway): a hot function sampled at
            # many lines must not fan out into thousands of unique stacks.
            key = (
                names.get(ident, "?"), task,
                tuple((f, fn) for f, _ln, fn in frames),
            )
            st = self.stacks.get(key)
            if st is None and len(self.stacks) < self.MAX_UNIQUE_STACKS:
                st = self.stacks[key] = [0, 0, frames]
            if st is not None:
                st[0] += 1
                if busy:
                    st[1] += 1
            # totals and task attribution count even past the unique-
            # stack cap — only the per-stack row is dropped
            if busy and task:
                self.task_busy[task] = self.task_busy.get(task, 0) + 1
            self.samples_total += 1

    # -- results -------------------------------------------------------
    def _flush_metrics(self):
        try:
            m = _get_metrics()
            if self.samples_total:
                m["samples"].inc(self.samples_total, {"mode": self.mode})
            ms_per = 1000.0 / self.hz
            for name, busy in self.task_busy.items():
                # task names are app-bounded; the registry cardinality cap
                # (metrics_max_series_per_metric) backstops misbehavers
                m["task_cpu"].observe(busy * ms_per, {"name": name})  # ray-tpu: lint-ignore[RTL004]
        except Exception as e:  # noqa: BLE001 — profiling must not kill the host process
            logger.debug("profiler metric flush failed: %s", e)

    def result(self) -> dict:
        ms_per = 1000.0 / self.hz
        rows = []
        for (tname, task, _key), (count, busy, frames) in sorted(
            self.stacks.items(), key=lambda kv: -kv[1][0]
        ):
            rows.append(
                {
                    "thread": tname,
                    "task": task,
                    "count": count,
                    "busy": busy,
                    # root-first labels, collapsed-stack ready
                    "frames": [_frame_label(f) for f in reversed(frames)],
                }
            )
        return {
            "process": process_label(),
            "pid": os.getpid(),
            "hz": self.hz,
            "duration_s": round(
                (self.stopped_at or time.time()) - self.started_at, 3
            ),
            "samples": self.samples_total,
            "ms_per_sample": ms_per,
            "task_cpu_ms": {
                k: round(v * ms_per, 1) for k, v in self.task_busy.items()
            },
            "stacks": rows,
        }


async def sample_async(duration_s: float, hz: float = 100.0) -> dict:
    """Profile THIS process for ``duration_s`` without blocking the
    caller's event loop (the sampler runs on its own thread; the handler
    just sleeps). Shared by the worker/agent RPC handlers and the
    controller's self-profile leg."""
    import asyncio

    duration_s = max(0.05, min(float(duration_s), 600.0))
    sampler = CpuSampler(hz=hz, duration_s=duration_s).start()
    await asyncio.sleep(duration_s)
    return sampler.stop()


def merge_cpu_results(results: Dict[str, Any]) -> dict:
    """Fan-out rollup: per-process results keyed by process name ->
    cluster-wide collapsed counts, task attribution, and totals."""
    collapsed: Dict[str, int] = {}
    task_cpu: Dict[str, float] = {}
    samples = 0
    procs: Dict[str, Any] = {}
    errors: Dict[str, str] = {}
    for proc, res in results.items():
        if not isinstance(res, dict):
            errors[proc] = str(res)
            continue
        procs[proc] = {
            "samples": res.get("samples", 0),
            "duration_s": res.get("duration_s"),
            "task_cpu_ms": res.get("task_cpu_ms", {}),
        }
        samples += res.get("samples", 0)
        for name, ms in res.get("task_cpu_ms", {}).items():
            task_cpu[name] = round(task_cpu.get(name, 0.0) + ms, 1)
        for row in res.get("stacks", ()):
            line = ";".join([proc] + row["frames"])
            collapsed[line] = collapsed.get(line, 0) + row["count"]
    return {
        "samples": samples,
        "task_cpu_ms": dict(
            sorted(task_cpu.items(), key=lambda kv: -kv[1])
        ),
        "collapsed": collapsed,
        "procs": procs,
        "errors": errors,
    }


def collapsed_text(merged: dict) -> str:
    """Brendan-Gregg collapsed-stack text (``flamegraph.pl`` /
    speedscope-importable): one ``frame;frame;... count`` line per stack."""
    return "\n".join(
        f"{stack} {count}"
        for stack, count in sorted(
            merged.get("collapsed", {}).items(), key=lambda kv: -kv[1]
        )
    )


def speedscope_json(merged: dict, name: str = "ray-tpu cpu profile",
                    ms_per_sample: float = 10.0) -> dict:
    """speedscope file-format JSON (sampled profile) from a merged
    result — one profile, each unique stack contributing one weighted
    sample (https://www.speedscope.app/file-format-schema.json)."""
    frame_index: Dict[str, int] = {}
    frames: List[dict] = []
    samples: List[List[int]] = []
    weights: List[float] = []
    for stack, count in merged.get("collapsed", {}).items():
        idxs = []
        for label in stack.split(";"):
            i = frame_index.get(label)
            if i is None:
                i = frame_index[label] = len(frames)
                frames.append({"name": label})
            idxs.append(i)
        samples.append(idxs)
        weights.append(count * ms_per_sample)
    total = sum(weights)
    return {
        "$schema": "https://www.speedscope.app/file-format-schema.json",
        "name": name,
        "exporter": "ray-tpu profile cpu",
        "shared": {"frames": frames},
        "profiles": [
            {
                "type": "sampled",
                "name": name,
                "unit": "milliseconds",
                "startValue": 0,
                "endValue": total,
                "samples": samples,
                "weights": weights,
            }
        ],
    }


# ---------------------------------------------------------------------------
# Continuous low-rate sampling + incident auto-capture
# ---------------------------------------------------------------------------
class ContinuousSampler:
    """Always-on low-rate sampler feeding a bounded ring of recent
    samples — the flight recorder for CPU time. Default OFF
    (``profiling_continuous_hz = 0``); at the recommended 5-20 Hz the
    measured overhead on the CPU micro-bench is well under the 3% budget
    (``python bench.py --cpu``: ``profiling_overhead_pct`` of its ingest
    arm; a host-side figure, no chip is involved)."""

    MAX_RING = 50000

    def __init__(self, hz: float, ring_s: float = 60.0):
        self.hz = max(0.1, min(float(hz), 100.0))
        self.ring_s = ring_s
        maxlen = min(self.MAX_RING, max(256, int(self.hz * ring_s * 8)))
        # (ts, thread_name, task, frames leaf-first, busy)
        self.ring: "collections.deque" = collections.deque(maxlen=maxlen)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._task_busy: Dict[str, int] = {}
        self._samples_since_flush = 0
        self._last_flush = time.monotonic()
        self._FLUSH_S = 10.0

    def start(self):
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="cpu-sampler-continuous"
        )
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        self._flush_metrics(time.monotonic())

    def _run(self):
        interval = 1.0 / self.hz
        my_ident = threading.get_ident()
        while not self._stop.is_set():
            t0 = time.monotonic()
            try:
                self._sample_once(my_ident)
            except Exception as e:  # noqa: BLE001 — sampler must never die
                logger.debug("continuous sample failed: %s", e)
            if t0 - self._last_flush >= self._FLUSH_S:
                self._flush_metrics(t0)
            elapsed = time.monotonic() - t0
            self._stop.wait(max(0.005, interval - elapsed))

    def _sample_once(self, skip_ident: int):
        now = time.time()
        names = {t.ident: t.name for t in threading.enumerate()}
        tags = thread_task_tags()
        for ident, frame in sys._current_frames().items():
            if ident == skip_ident:
                continue
            frames = _frame_stack(frame)
            busy = not _is_idle(frames)
            task = tags.get(ident)
            self.ring.append((now, names.get(ident, "?"), task, frames, busy))
            if busy and task:
                self._task_busy[task] = self._task_busy.get(task, 0) + 1
            self._samples_since_flush += 1

    def _flush_metrics(self, now_m: float):
        self._last_flush = now_m
        busy, self._task_busy = self._task_busy, {}
        n, self._samples_since_flush = self._samples_since_flush, 0
        try:
            m = _get_metrics()
            if n:
                m["samples"].inc(n, {"mode": "continuous"})
            ms_per = 1000.0 / self.hz
            for name, count in busy.items():
                m["task_cpu"].observe(count * ms_per, {"name": name})  # ray-tpu: lint-ignore[RTL004] — app-bounded task names, registry cap backstops
        except Exception as e:  # noqa: BLE001 — profiling must not kill the host
            logger.debug("continuous metric flush failed: %s", e)

    def recent_collapsed(self, seconds: Optional[float] = None) -> str:
        """Aggregate the ring's newest ``seconds`` into collapsed text
        (the incident bundle's ``samples.collapsed``)."""
        cutoff = time.time() - (seconds or self.ring_s)
        counts: Dict[str, int] = {}
        for ts, tname, task, frames, _busy in list(self.ring):
            if ts < cutoff:
                continue
            label = f"{tname}[{task}]" if task else tname
            line = ";".join([label] + [_frame_label(f) for f in reversed(frames)])
            counts[line] = counts.get(line, 0) + 1
        return "\n".join(
            f"{line} {n}"
            for line, n in sorted(counts.items(), key=lambda kv: -kv[1])
        )


_continuous: Optional[ContinuousSampler] = None
_continuous_lock = threading.Lock()


def ensure_continuous(hz: Optional[float] = None,
                      ring_s: Optional[float] = None) -> Optional[ContinuousSampler]:
    """Start the process-wide continuous sampler if configured
    (``profiling_continuous_hz`` > 0). Idempotent; called from every
    process entry point alongside telemetry startup."""
    global _continuous
    if hz is None:
        hz = float(_config_value("profiling_continuous_hz", 0.0))
    if ring_s is None:
        ring_s = float(_config_value("profiling_ring_s", 60.0))
    if hz <= 0:
        return _continuous
    with _continuous_lock:
        if _continuous is None:
            _continuous = ContinuousSampler(hz, ring_s).start()
    return _continuous


def continuous_sampler() -> Optional[ContinuousSampler]:
    return _continuous


def _stop_continuous_for_tests():
    global _continuous
    with _continuous_lock:
        if _continuous is not None:
            _continuous.stop()
            _continuous = None


def _config_value(name: str, default):
    """Config lookup preferring the cluster config this process was
    handed at registration (per-init ``_system_config`` overrides apply),
    like compile_tracker.maybe_install."""
    try:
        from ray_tpu.core import api

        core = api._global_worker
        if core is not None:
            return core.config.get(name, default)
        from ray_tpu.config import get_config

        return getattr(get_config(), name, default)
    except Exception:  # noqa: BLE001 — config unavailable (odd embedders)
        return default


# ---------------------------------------------------------------------------
# Incident auto-capture
# ---------------------------------------------------------------------------
# Bounded trigger vocabulary — these become metric tags and directory
# name prefixes.
INCIDENT_TRIGGERS = (
    "lockwatch_long_hold",
    "lockwatch_cycle",
    "recompile_storm",
    "slo_breach",
    "memory_pressure",
    "memory_leak",
    "error_spike",
    "manual",
)

_incident_last: Dict[str, float] = {}
_incident_lock = threading.Lock()
# Flight-recorder tail provider: the controller registers a callable
# returning recent lifecycle events so ITS incident bundles carry the
# scheduler context (workers have no recorder).
_recorder_tail_provider = None


def set_recorder_tail_provider(fn):
    global _recorder_tail_provider
    _recorder_tail_provider = fn


def incidents_root(session_dir: Optional[str] = None) -> str:
    session_dir = session_dir or _session_dir()
    return os.path.join(session_dir, "incidents")


def _session_dir() -> str:
    sd = os.environ.get("RAY_TPU_SESSION_DIR")
    if sd:
        return sd
    try:
        from ray_tpu.core import api

        if api._global_worker is not None:
            return api._global_worker.session_dir
        if api._session_dir:
            return api._session_dir
    except Exception as e:  # noqa: BLE001 — no session in this process
        logger.debug("no session dir for incidents: %s", e)
    return ""


def incident(trigger: str, detail: Optional[dict] = None,
             extra_files: Optional[Dict[str, str]] = None) -> Optional[str]:
    """Write one incident capture bundle; returns its directory (or None
    when disabled/rate-limited/sessionless). Bundle contents:

    - ``meta.json``    — trigger, detail, process, pid, timestamps
    - ``stacks.txt``   — full formatted stack dump of this process
    - ``samples.collapsed`` — recent continuous-sampler ring (if running)
    - ``lifecycle_tail.json`` — flight-recorder tail (controller only)
    - any ``extra_files`` the detector supplies ({name: text} — e.g. the
      store-pressure trigger's ``memory.json`` autopsy)

    Bounded on disk: newest ``profiling_incident_keep`` bundles are kept
    per incidents dir; per-trigger writes are rate-limited to one per
    ``profiling_incident_min_interval_s``. Never raises.
    """
    try:
        if trigger not in INCIDENT_TRIGGERS:
            trigger = "manual"
        if not _config_value("profiling_incidents", True):
            return None
        session_dir = _session_dir()
        if not session_dir:
            return None
        min_interval = float(
            _config_value("profiling_incident_min_interval_s", 30.0)
        )
        now = time.time()
        with _incident_lock:
            if now - _incident_last.get(trigger, 0.0) < min_interval:
                return None
            _incident_last[trigger] = now
        root = incidents_root(session_dir)
        iid = f"{trigger}-{int(now * 1000)}-{os.getpid()}"
        d = os.path.join(root, iid)
        os.makedirs(d, exist_ok=True)
        meta = {
            "id": iid,
            "trigger": trigger,
            "detail": detail or {},
            "process": process_label(),
            "pid": os.getpid(),
            "ts": now,
        }
        with open(os.path.join(d, "meta.json"), "w") as f:
            json.dump(meta, f, indent=1, default=str)
        with open(os.path.join(d, "stacks.txt"), "w") as f:
            f.write(format_stacks(dump_stacks()))
        cont = _continuous
        if cont is not None:
            samples = cont.recent_collapsed()
            if samples:
                with open(os.path.join(d, "samples.collapsed"), "w") as f:
                    f.write(samples)
        if _recorder_tail_provider is not None:
            try:
                tail = _recorder_tail_provider()
                with open(os.path.join(d, "lifecycle_tail.json"), "w") as f:
                    json.dump(tail, f, default=str)
            except Exception as e:  # noqa: BLE001 — tail is best-effort context
                logger.debug("recorder tail capture failed: %s", e)
        for name, text in (extra_files or {}).items():
            safe_name = os.path.basename(str(name)) or "extra.txt"
            try:
                with open(os.path.join(d, safe_name), "w") as f:
                    f.write(text)
            except OSError as e:
                logger.debug("incident extra file %s failed: %s", safe_name, e)
        _prune_incidents(root)
        try:
            _get_metrics()["incidents"].inc(1, {"trigger": trigger})
        except Exception as e:  # noqa: BLE001 — metrics may be unavailable
            logger.debug("incident metric failed: %s", e)
        logger.warning("incident captured: %s -> %s", trigger, d)
        return d
    except Exception as e:  # noqa: BLE001 — detectors must survive capture failure
        logger.debug("incident capture failed: %s", e)
        return None


def _prune_incidents(root: str):
    keep = int(_config_value("profiling_incident_keep", 20))
    try:
        entries = sorted(
            (e for e in os.listdir(root)
             if os.path.isdir(os.path.join(root, e)))
        )
    except OSError:
        return
    # ids embed epoch-ms, but prefixes differ — order by the embedded ts
    def _ts(e: str) -> int:
        parts = e.rsplit("-", 2)
        try:
            return int(parts[-2])
        except (ValueError, IndexError):
            return 0

    entries.sort(key=_ts)
    import shutil

    for e in entries[:-keep] if keep > 0 else entries:
        try:
            shutil.rmtree(os.path.join(root, e))
        except OSError as err:
            logger.debug("incident prune failed for %s: %s", e, err)


def list_incidents(session_dir: Optional[str] = None) -> List[dict]:
    """Rows: {id, trigger, ts, process, pid, path, files}."""
    root = incidents_root(session_dir)
    rows = []
    if not os.path.isdir(root):
        return rows
    for entry in sorted(os.listdir(root)):
        d = os.path.join(root, entry)
        if not os.path.isdir(d):
            continue
        row = {"id": entry, "path": d}
        meta_path = os.path.join(d, "meta.json")
        try:
            with open(meta_path) as f:
                row.update(json.load(f))
        except (OSError, ValueError) as e:
            logger.debug("unreadable incident meta %s: %s", meta_path, e)
        try:
            row["files"] = sorted(os.listdir(d))
        except OSError:
            row["files"] = []
        rows.append(row)
    rows.sort(key=lambda r: r.get("ts", 0))
    return rows


def get_incident(incident_id: str, session_dir: Optional[str] = None) -> dict:
    root = os.path.realpath(incidents_root(session_dir))
    d = os.path.realpath(os.path.join(root, incident_id))
    if os.path.commonpath([d, root]) != root:
        raise ValueError("incident path escapes the incidents dir")
    if not os.path.isdir(d):
        raise FileNotFoundError(f"no incident {incident_id!r}")
    row = {"id": incident_id, "path": d}
    try:
        with open(os.path.join(d, "meta.json")) as f:
            row.update(json.load(f))
    except (OSError, ValueError) as e:
        logger.debug("unreadable incident meta for %s: %s", incident_id, e)
    out = {}
    for name in sorted(os.listdir(d)):
        p = os.path.join(d, name)
        try:
            with open(p, errors="replace") as f:
                out[name] = f.read(1 << 20)
        except OSError as e:
            out[name] = f"<unreadable: {e}>"
    row["contents"] = out
    return row


def slo_breach_check(metric: str, value_ms: float):
    """Serve SLO hook: a TTFT observation past ``profiling_slo_ttft_ms``
    (0 = disabled) triggers an incident capture with the breach context.
    The capture itself (stack dump + ring aggregation + file writes)
    runs on a background thread — it must not stall the very request
    that was just flagged as too slow. The rate limiter is pre-checked
    here so a breach storm doesn't spawn a thread per request (and
    re-checked atomically inside :func:`incident`)."""
    threshold = float(_config_value("profiling_slo_ttft_ms", 0.0))
    if threshold <= 0 or value_ms <= threshold:
        return
    min_interval = float(_config_value("profiling_incident_min_interval_s", 30.0))
    if time.time() - _incident_last.get("slo_breach", 0.0) < min_interval:
        return
    threading.Thread(
        target=incident,
        args=("slo_breach",
              {"metric": metric, "value_ms": round(value_ms, 1),
               "threshold_ms": threshold}),
        daemon=True,
        name="incident-capture",
    ).start()


# ---------------------------------------------------------------------------
# Attachable device traces (jax.profiler on a live process)
# ---------------------------------------------------------------------------
_device_trace_lock = threading.Lock()
_device_trace: Optional[dict] = None  # {"dir", "capture", "t0"}


def device_trace_start(capture: str, base_dir: Optional[str] = None) -> dict:
    """Start a ``jax.profiler`` trace in THIS process (no restart —
    composes with the runtime_env plugin's capture dirs and the existing
    list/fetch path). One trace at a time per process. The Python
    tracer stays off and host events at level 2 stay on, so the capture
    shows ``tracing.phase`` spans and does not slow the traced threads."""
    global _device_trace
    safe = "".join(c if c.isalnum() or c in "._-" else "_" for c in capture)[:64]
    try:
        import jax
    except Exception as e:  # noqa: BLE001 — CPU-only / jax-less process
        return {"ok": False, "error": f"jax unavailable: {e}"}
    with _device_trace_lock:
        if _device_trace is not None:
            return {
                "ok": False,
                "error": f"trace already running ({_device_trace['capture']})",
            }
        from ray_tpu.runtime_env.jax_profiler import profiles_root

        out_dir = os.path.join(
            base_dir or profiles_root(_session_dir() or None),
            f"{safe}-pid{os.getpid()}",
        )
        os.makedirs(out_dir, exist_ok=True)
        try:
            # Python tracer off: it slows the very thread it watches. Host
            # level 2 keeps the program's own annotations (tracing.phase:
            # the engine's ``engine.*`` spans) beside the device's rows.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(out_dir, profiler_options=opts)
        except Exception as e:  # noqa: BLE001 — backend may not support tracing
            return {"ok": False, "error": f"start_trace failed: {e}"}
        _device_trace = {"dir": out_dir, "capture": safe, "t0": time.time()}
        return {"ok": True, "dir": out_dir}


def device_trace_stop() -> dict:
    """Stop the running trace and write the same ``profile.json`` meta
    the per-task runtime_env capture writes (so ``ray-tpu profile
    captures`` lists on-demand traces identically)."""
    global _device_trace
    with _device_trace_lock:
        if _device_trace is None:
            return {"ok": False, "error": "no trace running"}
        rec, _device_trace = _device_trace, None
    try:
        import jax

        jax.profiler.stop_trace()
    except Exception as e:  # noqa: BLE001 — a failed stop still reports the dir
        return {"ok": False, "dir": rec["dir"], "error": f"stop_trace failed: {e}"}
    meta = {
        "name": rec["capture"],
        "kind": "ondemand",
        "captured_at": rec["t0"],
        "duration_s": round(time.time() - rec["t0"], 4),
        "pid": os.getpid(),
        "process": process_label(),
    }
    try:
        with open(os.path.join(rec["dir"], "profile.json"), "w") as f:
            json.dump(meta, f)
    except OSError as e:
        logger.debug("device trace meta write failed: %s", e)
    return {"ok": True, "dir": rec["dir"], **meta}


def device_trace_control(action: str, capture: str = "",
                         base_dir: Optional[str] = None) -> dict:
    if action == "start":
        return device_trace_start(capture or "ondemand", base_dir)
    if action == "stop":
        return device_trace_stop()
    return {"ok": False, "error": f"unknown action {action!r}"}


def collect_device_traces(session_dir: str) -> List[dict]:
    """Chrome-trace events from captured XLA device traces: every
    ``*.trace.json[.gz]`` under the session profiles root (the
    TensorBoard layout jax.profiler writes) parsed and re-labelled with
    an ``xla:<capture>`` pid so they merge into one ``ray-tpu timeline``
    perfetto load alongside host spans and lifecycle rows. XLA
    timestamps are capture-relative; the device rows sit on their own
    tracks rather than aligning with wall-clock host slices."""
    import gzip

    from ray_tpu.runtime_env.jax_profiler import profiles_root

    events: List[dict] = []
    root = profiles_root(session_dir)
    if not os.path.isdir(root):
        return events
    for base, _dirs, names in os.walk(root):
        for name in names:
            if not (name.endswith(".trace.json.gz")
                    or name.endswith(".trace.json")):
                continue
            path = os.path.join(base, name)
            capture = os.path.relpath(base, root).split(os.sep)[0]
            try:
                if name.endswith(".gz"):
                    with gzip.open(path, "rt", encoding="utf-8",
                                   errors="replace") as f:
                        payload = json.load(f)
                else:
                    with open(path, encoding="utf-8", errors="replace") as f:
                        payload = json.load(f)
            except (OSError, ValueError) as e:
                logger.debug("unreadable device trace %s: %s", path, e)
                continue
            for ev in payload.get("traceEvents", ()):
                if not isinstance(ev, dict):
                    continue
                ev = dict(ev)
                ev["pid"] = f"xla:{capture}:{ev.get('pid', 0)}"
                ev.setdefault("cat", "device")
                events.append(ev)
    return events
