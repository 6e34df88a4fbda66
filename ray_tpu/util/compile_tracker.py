"""XLA compilation tracking: count compiles, time them, detect storms.

Reference shape: Ray's dashboard counts GPU kernel launches per process;
the TPU/JAX analogue is XLA compilation — a silent recompile storm (a
jit'd function re-lowering every step because a shape or static arg
changes) turns a 5 ms step into a 30 s one with no error anywhere.

Three hooks, all install-once per process:
- ``jax.monitoring`` duration events — every ``backend_compile`` adds to
  ``jax_compilations_total`` / ``jax_compile_seconds_total``.
- ``jax.monitoring`` plain events — persistent-compilation-cache
  hits/misses (``jax_compile_cache_{hits,misses}_total``).
- a logging.Handler on ``jax._src.interpreters.pxla`` (the
  "Compiling <fn> with global shapes and types [...]" DEBUG line) —
  the only place jax exposes the FUNCTION NAME and argument shapes, which
  is what storm detection needs: N compiles of the same name inside a
  window flags a storm, and the last two shape strings are kept so the
  offending diff is visible through the state API and a warning log.

Everything no-ops (and imports nothing heavy) until ``install()`` /
``maybe_install()`` runs; ``maybe_install`` is called by the process
telemetry loop once jax appears in ``sys.modules``.
"""
from __future__ import annotations

import collections
import logging
import re
import sys
import threading
import time
from typing import Dict, Optional

logger = logging.getLogger("ray_tpu.compile_tracker")

_lock = threading.Lock()
_installed = False

# Raw totals (kept separately from the metrics Counters so snapshot()
# works without a metrics flush and in processes with no cluster).
_totals = {
    "compiles": 0,
    "compile_seconds": 0.0,
    "cache_hits": 0,
    "cache_misses": 0,
    "storms": 0,
}
# per-function compile history: name -> deque[(ts, shapes_str)]
_history: Dict[str, "collections.deque"] = {}
# per-function storm records: name -> {first_ts, last_ts, count, shapes, prev_shapes}
_storms: Dict[str, dict] = {}
# functions the health plane pinned into shape bucketing (storm actuator):
# workloads consult is_pinned()/maybe_bucket() to pad dynamic dims.
_pinned: set = set()
_metrics = None  # lazy _CompileMetrics
_storm_threshold = 5
_storm_window_s = 60.0
_MAX_TRACKED_FUNCTIONS = 256

# jax >= 0.4.30 logs the wrapper too, "Compiling jit(fn) with ...": callers
# (``maybe_bucket(name, n)``, the storm records) know the function's bare name.
_COMPILING_RE = re.compile(
    r"^Compiling (?:\w+\()?([^\s)]+)\)? with global shapes and types (.*?)\.?\s*(?:Argument mapping|$)")
_BACKEND_COMPILE = "backend_compile"


class _CompileMetrics:
    def __init__(self):
        from ray_tpu.util.metrics import Counter

        self.compiles = Counter(
            "jax_compilations_total", "XLA backend compilations in this process"
        )
        self.seconds = Counter(
            "jax_compile_seconds_total", "Seconds spent in XLA backend compilation"
        )
        self.cache_hits = Counter(
            "jax_compile_cache_hits_total", "Persistent compilation cache hits"
        )
        self.cache_misses = Counter(
            "jax_compile_cache_misses_total", "Persistent compilation cache misses"
        )
        self.storms = Counter(
            "jax_recompile_storms_total",
            "Recompilation storms detected (same function recompiled >= "
            "threshold times inside the window)",
        )


def _on_duration(event: str, duration: float, **kw):
    if _BACKEND_COMPILE not in event:
        return
    with _lock:
        _totals["compiles"] += 1
        _totals["compile_seconds"] += duration
    if _metrics is not None:
        _metrics.compiles.inc()
        _metrics.seconds.inc(max(0.0, duration))


def _on_event(event: str, **kw):
    if "cache_hit" in event:
        with _lock:
            _totals["cache_hits"] += 1
        if _metrics is not None:
            _metrics.cache_hits.inc()
    elif "cache_miss" in event:
        with _lock:
            _totals["cache_misses"] += 1
        if _metrics is not None:
            _metrics.cache_misses.inc()


class _PxlaHandler(logging.Handler):
    """Captures the per-compile "Compiling <fn> ..." line for names and
    shape strings. Attached with propagate=False on the pxla logger so
    forcing its level to DEBUG doesn't spray every compile line onto
    stderr through jax's own stream handler; records the user's OWN
    config would have emitted (prior effective level, e.g.
    jax_log_compiles' WARNING or an explicit DEBUG) are re-dispatched to
    the parent chain so install() never hides logs that were visible
    before it."""

    def __init__(self, prior_level: int, level=logging.DEBUG):
        super().__init__(level)
        self.prior_level = prior_level

    def emit(self, record: logging.LogRecord):
        try:
            if record.levelno >= self.prior_level:
                logging.getLogger("jax").handle(record)
            m = _COMPILING_RE.match(record.getMessage())
        except Exception:  # noqa: BLE001 — logging must never raise
            return
        if m is None:
            return
        _note_compile(m.group(1), m.group(2))


def _note_compile(name: str, shapes: str, now: Optional[float] = None):
    now = time.time() if now is None else now
    newly_storming = False
    prev_shapes = None
    with _lock:
        hist = _history.get(name)
        if hist is None:
            if len(_history) >= _MAX_TRACKED_FUNCTIONS:
                # drop the coldest function so a name explosion (lambdas)
                # can't grow without bound
                coldest = min(_history, key=lambda k: _history[k][-1][0])
                _history.pop(coldest, None)
            hist = _history[name] = collections.deque(maxlen=64)
        if hist:
            prev_shapes = hist[-1][1]
        hist.append((now, shapes))
        cutoff = now - _storm_window_s
        in_window = sum(1 for ts, _ in hist if ts >= cutoff)
        if in_window >= _storm_threshold:
            rec = _storms.get(name)
            if rec is None or now - rec["last_ts"] > _storm_window_s:
                newly_storming = True
                _totals["storms"] += 1
                _storms[name] = rec = {
                    "first_ts": now,
                    "count": 0,
                }
            rec.update(
                last_ts=now,
                count=rec["count"] + 1,
                window_count=in_window,
                shapes=shapes,
                prev_shapes=prev_shapes,
            )
    if newly_storming:
        if _metrics is not None:
            _metrics.storms.inc()
        logger.warning(
            "recompilation storm: %r compiled %d times in %.0fs — "
            "latest shapes %s (previous %s). A shape/static-arg is "
            "changing per call; pad/bucket inputs or hoist the jit.",
            name, in_window, _storm_window_s, shapes, prev_shapes,
        )
        try:
            from ray_tpu.util.profiling import incident

            incident(
                "recompile_storm",
                {"function": name, "window_count": in_window,
                 "shapes": shapes, "prev_shapes": prev_shapes},
            )
        except Exception as e:  # noqa: BLE001 — detection must survive capture failure
            logger.debug("storm incident capture failed: %s", e)


def install(storm_threshold: Optional[int] = None,
            storm_window_s: Optional[float] = None) -> bool:
    """Idempotent; returns True when the hooks are (now) installed.
    Requires jax to be importable — callers that must not trigger the
    import use :func:`maybe_install`."""
    global _installed, _metrics, _storm_threshold, _storm_window_s
    if storm_threshold is not None:
        _storm_threshold = int(storm_threshold)
    if storm_window_s is not None:
        _storm_window_s = float(storm_window_s)
    if _installed:
        return True
    try:
        import jax.monitoring as monitoring
    except Exception:  # noqa: BLE001 — no jax in this process
        return False
    with _lock:
        if _installed:
            return True
        _installed = True
    if _metrics is None:
        _metrics = _CompileMetrics()
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)
    pxla_logger = logging.getLogger("jax._src.interpreters.pxla")
    pxla_logger.addHandler(_PxlaHandler(prior_level=pxla_logger.getEffectiveLevel()))
    pxla_logger.setLevel(logging.DEBUG)
    pxla_logger.propagate = False
    return True


def maybe_install() -> bool:
    """install() only if jax is ALREADY imported (never triggers the
    multi-second TPU-runtime import from a control-plane process).
    Storm thresholds come from the cluster config the controller handed
    this process (per-init ``_system_config`` overrides apply), falling
    back to env/defaults when unconnected."""
    if _installed:
        return True
    if "jax" not in sys.modules:
        return False
    from ray_tpu.core import api

    core = api._global_worker
    if core is not None:
        threshold = core.config.get("compile_storm_threshold")
        window = core.config.get("compile_storm_window_s")
    else:
        from ray_tpu.config import get_config

        cfg = get_config()
        threshold = getattr(cfg, "compile_storm_threshold", None)
        window = getattr(cfg, "compile_storm_window_s", None)
    return install(storm_threshold=threshold, storm_window_s=window)


def pin_functions(names) -> dict:
    """Storm actuator target: mark ``names`` as shape-pinned in this
    process. Pinning changes no jax internals — it is advisory state the
    WORKLOAD consults via :func:`maybe_bucket` (pad a dynamic dim up to
    its power-of-2 bucket) or :func:`is_pinned` (choose a padded path).
    Returns the full pinned set so the actuator can audit it."""
    with _lock:
        for n in names or ():
            if isinstance(n, str) and n:
                _pinned.add(n)
        return {"pinned": sorted(_pinned)}


def is_pinned(name: str) -> bool:
    with _lock:
        return name in _pinned


def maybe_bucket(name: str, n: int) -> int:
    """Round ``n`` up to the next power of two IF the health plane pinned
    ``name`` (else return it unchanged). The storm-remediation contract:
    a recompile storm driven by a drifting dimension collapses to at most
    log2(max_n) compiles once the workload sizes through this."""
    if n <= 0 or not is_pinned(name):
        return n
    return 1 << (n - 1).bit_length()


def snapshot(max_functions: int = 20) -> dict:
    """Per-process compile stats for the state API / telemetry ship."""
    now = time.time()
    cutoff = now - _storm_window_s
    with _lock:
        funcs = {}
        for name, hist in _history.items():
            in_window = sum(1 for ts, _ in hist if ts >= cutoff)
            funcs[name] = {
                "count": len(hist),
                "window_count": in_window,
                "last_shapes": hist[-1][1] if hist else "",
            }
        top = dict(
            sorted(funcs.items(), key=lambda kv: -kv[1]["window_count"])[:max_functions]
        )
        return {
            "installed": _installed,
            "compiles": _totals["compiles"],
            "compile_seconds": round(_totals["compile_seconds"], 4),
            "cache_hits": _totals["cache_hits"],
            "cache_misses": _totals["cache_misses"],
            "storms_total": _totals["storms"],
            "storm_threshold": _storm_threshold,
            "storm_window_s": _storm_window_s,
            "active_storms": {
                name: dict(rec)
                for name, rec in _storms.items()
                if rec["last_ts"] >= cutoff
            },
            "pinned": sorted(_pinned),
            "functions": top,
        }


def _reset_for_tests():
    with _lock:
        _totals.update(compiles=0, compile_seconds=0.0, cache_hits=0,
                       cache_misses=0, storms=0)
        _history.clear()
        _storms.clear()
        _pinned.clear()
