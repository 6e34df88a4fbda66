"""Distributed tracing: spans with cross-task context propagation.

Reference: python/ray/util/tracing/tracing_helper.py — opt-in tracing
wraps task/actor invocation and execution with spans and propagates the
trace context inside task metadata (:88-100). Re-designed without the
OpenTelemetry dependency: spans are written as Chrome-trace events to a
per-process JSONL file in the session log dir, and ``collect_spans``
merges them — the same file-based path the task timeline uses, so one
``chrome://tracing`` load shows both.

Propagation: when a span is active in the submitting process, a
``__trace_ctx__`` entry rides in the task's runtime_env; the executing
worker re-parents its spans under it (ambient context, like OTel's
context attach).
"""
from __future__ import annotations

import atexit
import contextlib
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

_state = threading.local()
_enabled = False
_sink_path: Optional[str] = None
_sink_lock = threading.Lock()
# Spans wait here, ``(thread name, record)`` each, and are encoded and reach
# the file in batches: when ``_FLUSH_COUNT`` have gathered, when the flusher
# thread finds some ``_FLUSH_AGE_S`` old, when ``collect_spans`` reads this
# process's own file, on ``disable_tracing`` and at exit (a process that
# leaves by ``os._exit`` loses what the last ``_FLUSH_AGE_S`` gathered).
# Opening the file for every span cost a ``phase`` 31-38 us on tmpfs and
# 270 on a disk against 1.3 without the sink; held back it costs 6.
_buffer: List[tuple] = []
_FLUSH_COUNT = 256
_FLUSH_AGE_S = 0.5
_flusher_stop: Optional[threading.Event] = None
# Sink bound (single rotation): when the JSONL file would exceed the cap
# it is renamed to <path>.1 (overwriting any previous rotation) and a
# fresh file starts — long RAY_TPU_TRACE=1 runs keep at most 2x the cap
# per process instead of growing without limit.
_sink_bytes = 0
_max_sink_bytes = 0
# Threads whose thread_name metadata has been written to the CURRENT
# sink file (guarded by _sink_lock; cleared on rotation so the fresh
# file is self-describing).
_named_tids: set = set()

TRACE_CTX_KEY = "__trace_ctx__"
TRACE_ENV_VAR = "RAY_TPU_TRACE"
TRACE_MAX_MB_VAR = "RAY_TPU_TRACE_MAX_MB"  # per-process sink cap (default 64)


def maybe_enable_from_env() -> bool:
    """Enable tracing when ``RAY_TPU_TRACE`` is set — how long-lived
    system actors (serve proxy/replicas) opt in without a driver-side
    call reaching their process. The env var propagates driver → node
    agent → worker with the rest of the cluster env."""
    if not _enabled and os.environ.get(TRACE_ENV_VAR, "").lower() in ("1", "true", "on"):
        enable_tracing(os.environ.get("RAY_TPU_SESSION_DIR") or None)
    return _enabled


def enable_tracing(session_dir: Optional[str] = None):
    """Turn on span recording in this process (reference:
    ``ray.init(_tracing_startup_hook=...)`` opt-in)."""
    global _enabled, _sink_path, _sink_bytes, _max_sink_bytes, _flusher_stop
    _enabled = True
    if session_dir is None:
        from ray_tpu.core import api

        session_dir = getattr(api, "_session_dir", None) or "/tmp/ray_tpu"
    logs = os.path.join(session_dir, "logs")
    os.makedirs(logs, exist_ok=True)
    try:
        cap_mb = float(os.environ.get(TRACE_MAX_MB_VAR, "64"))
    except ValueError:
        cap_mb = 64.0
    with _sink_lock:
        _sink_path = os.path.join(logs, f"spans-{os.getpid()}.jsonl")
        _max_sink_bytes = max(1, int(cap_mb * 1024 * 1024))
        try:
            _sink_bytes = os.path.getsize(_sink_path)
        except OSError:
            _sink_bytes = 0
        _named_tids.clear()
        if _flusher_stop is None:
            # A process that wrote a span and fell idle still hands it over.
            _flusher_stop = threading.Event()
            threading.Thread(target=_flush_loop, args=(_flusher_stop,), daemon=True,
                             name="trace-sink").start()
            atexit.register(flush)


def disable_tracing():
    """Stop span recording in this process (tests)."""
    global _enabled, _sink_path, _sink_bytes, _flusher_stop
    _enabled = False
    flush()
    with _sink_lock:
        _sink_path = None
        _sink_bytes = 0
        _named_tids.clear()
        if _flusher_stop is not None:
            _flusher_stop.set()
            _flusher_stop = None
            atexit.unregister(flush)


def tracing_enabled() -> bool:
    return _enabled


def _process_name() -> str:
    """Human label for this process's Chrome-trace row."""
    wid = os.environ.get("RAY_TPU_WORKER_ID", "")
    if wid:
        return f"worker-{wid[:8]}"
    argv = " ".join(sys.argv[:2])
    if "controller" in argv:
        return "controller"
    if "node_agent" in argv:
        return "node_agent"
    return f"driver-{os.getpid()}"


def _meta_event(name: str, tid: int, value: str) -> Dict[str, Any]:
    """Chrome-trace metadata ("ph":"M") event: process_name/thread_name
    records that label the pid/tid rows of merged timelines."""
    return {
        "name": name,
        "ph": "M",
        "ts": 0,
        "pid": os.getpid(),
        "tid": tid,
        "args": {"name": value},
    }


def _write(rec: Dict[str, Any]):
    if _sink_path is None:
        return
    entry = (threading.current_thread().name, rec)
    with _sink_lock:
        _buffer.append(entry)
        full = len(_buffer) >= _FLUSH_COUNT
    if full:
        flush()


def _flush_loop(stop: threading.Event):
    while not stop.wait(_FLUSH_AGE_S):
        flush()


def flush():
    """Write the buffered spans to this process's sink file, rotating it
    and naming its rows as they land."""
    global _sink_bytes
    with _sink_lock:
        waiting, _buffer[:] = list(_buffer), []
        if not waiting or _sink_path is None:
            return
        try:
            f = open(_sink_path, "ab")
            try:
                for thread_name, rec in waiting:
                    # Encoded bytes, not str length: the cap must track the real
                    # file size even for multi-byte span names/args.
                    line = (json.dumps(rec) + "\n").encode("utf-8")
                    tid = rec.get("tid")
                    if _sink_bytes + len(line) > _max_sink_bytes and _sink_bytes > 0:
                        # Single rotation: the previous half replaces any older
                        # .1 file, so disk use is bounded at ~2x the cap.
                        f.close()
                        os.replace(_sink_path, _sink_path + ".1")
                        _sink_bytes = 0
                        _named_tids.clear()
                        f = open(_sink_path, "ab")
                    out = []
                    if not _named_tids:
                        out.append(_meta_event("process_name", 0, _process_name()))
                        _named_tids.add(0)
                    if tid is not None and tid not in _named_tids:
                        _named_tids.add(tid)
                        out.append(_meta_event("thread_name", tid, thread_name))
                    for ln in [(json.dumps(m) + "\n").encode("utf-8") for m in out] + [line]:
                        f.write(ln)
                        _sink_bytes += len(ln)
            finally:
                f.close()
        except (OSError, ValueError):
            # Telemetry must never take down the traced path: a full disk or
            # removed session dir silently drops spans (the sink is
            # best-effort by design; spans also close inside engine pump
            # threads and request finally blocks).
            pass


def _new_id() -> str:
    """64 random bits as hex (a fifth of ``uuid.uuid4().hex[:16]``'s cost)."""
    return os.urandom(8).hex()


def current_context() -> Optional[Dict[str, str]]:
    """The active trace context, for injection into task metadata."""
    span = getattr(_state, "span", None)
    if span is None:
        return None
    return {"trace_id": span["trace_id"], "parent_id": span["span_id"]}


def attach_context(ctx: Optional[Dict[str, str]]):
    """Adopt a propagated context as the ambient parent (worker side)."""
    if ctx:
        _state.span = {
            "trace_id": ctx["trace_id"],
            "span_id": ctx["parent_id"],
            "name": "<remote-parent>",
        }


def inject_runtime_env(runtime_env: Optional[dict]) -> Optional[dict]:
    """Return runtime_env with the active trace context injected (no-op
    when tracing is off or no span is active)."""
    if not _enabled:
        return runtime_env
    ctx = current_context()
    if ctx is None:
        return runtime_env
    runtime_env = dict(runtime_env or {})
    runtime_env[TRACE_CTX_KEY] = ctx
    return runtime_env


def detach_context():
    """Clear the ambient context (end of a traced task execution) so a
    long-lived worker thread doesn't mis-parent later unrelated work."""
    _state.span = None


@contextlib.contextmanager
def start_span(name: str, attributes: Optional[Dict[str, Any]] = None):
    """Record one span; nested spans parent automatically."""
    if not _enabled:
        yield None
        return
    parent = getattr(_state, "span", None)
    span = {
        "trace_id": parent["trace_id"] if parent else _new_id(),
        "span_id": _new_id(),
        "parent_id": parent["span_id"] if parent else None,
        "name": name,
    }
    _state.span = span
    t0 = time.time()
    try:
        yield span
    finally:
        _write(
            {
                "name": name,
                "cat": "span",
                "ph": "X",  # Chrome trace "complete" event
                "ts": t0 * 1e6,
                "dur": (time.time() - t0) * 1e6,
                "pid": os.getpid(),
                "tid": threading.get_ident() % 100000,
                "args": {
                    **(attributes or {}),
                    "trace_id": span["trace_id"],
                    "span_id": span["span_id"],
                    "parent_id": span["parent_id"],
                },
            }
        )
        _state.span = parent


def record_span(
    name: str,
    start_ts: float,
    end_ts: float,
    ctx: Optional[Dict[str, str]] = None,
    attributes: Optional[Dict[str, Any]] = None,
):
    """Write one completed span explicitly parented under ``ctx`` (a
    ``current_context()`` capture). For cross-thread work — e.g. the LLM
    engine's pump thread finishing a request submitted from a replica
    handler thread — where the ambient thread-local parent can't flow."""
    if not _enabled:
        return
    span_id = _new_id()
    trace_id = ctx["trace_id"] if ctx else _new_id()
    parent_id = ctx["parent_id"] if ctx else None
    _write(
        {
            "name": name,
            "cat": "span",
            "ph": "X",
            "ts": start_ts * 1e6,
            "dur": max(0.0, end_ts - start_ts) * 1e6,
            "pid": os.getpid(),
            "tid": threading.get_ident() % 100000,
            "args": {
                **(attributes or {}),
                "trace_id": trace_id,
                "span_id": span_id,
                "parent_id": parent_id,
            },
        }
    )


# jax.profiler.TraceAnnotation once bound; False where JAX cannot be imported.
_annotation: Any = None


def _bind_annotation():
    """Importing ``jax.profiler`` opens no backend."""
    global _annotation
    try:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    except ImportError:
        _annotation = False
    return _annotation


class phase:
    """One phase of a hot loop, on every clock this process keeps.

    ``with phase("engine.admit", into):`` (a) is a
    ``jax.profiler.TraceAnnotation``: whatever ``jax.profiler`` trace is
    running in this process gets the span on its host plane, on the
    device planes' own clock, so a device idle gap can be laid to the
    phase the host was in; with or without a trace running it costs
    about a microsecond; (b) adds the elapsed ``time.perf_counter()``
    milliseconds to ``into[name]``; (c) with ``RAY_TPU_TRACE`` on, also
    hands the span to the JSONL sink, where ``ray-tpu timeline`` finds
    it. Make phases siblings, or parts of one nested in it: a reader that
    labels a gap with the span covering most of it (of equals the
    shortest) names the part a gap lies in, and would name a span around
    the whole loop every time. Never one per token."""

    __slots__ = ("name", "into", "_ann", "_t0", "_wall0")

    def __init__(self, name: str, into: Dict[str, float]):
        self.name = name
        self.into = into

    def __enter__(self):
        ann = _annotation if _annotation is not None else _bind_annotation()
        self._ann = ann(self.name) if ann else None
        if self._ann is not None:
            self._ann.__enter__()
        self._wall0 = time.time() if _enabled else 0.0
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        ms = (time.perf_counter() - self._t0) * 1e3
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.into[self.name] = self.into.get(self.name, 0.0) + ms
        if _enabled:
            record_span(self.name, self._wall0, time.time())
        return False


def collect_spans(session_dir: str) -> List[dict]:
    """Merge every process's span file (rotated ``.jsonl.1`` halves
    included) into one Chrome-trace event list."""
    flush()  # what this process still holds
    events: List[dict] = []
    logs = os.path.join(session_dir, "logs")
    if not os.path.isdir(logs):
        return events
    for fname in sorted(os.listdir(logs)):
        if not (
            fname.startswith("spans-")
            and (fname.endswith(".jsonl") or fname.endswith(".jsonl.1"))
        ):
            continue
        with open(os.path.join(logs, fname), encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        events.append(json.loads(line))
                    except json.JSONDecodeError:
                        pass
    return events
