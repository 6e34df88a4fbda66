"""The engine's scheduler thread on the profiler's clock.

``tracing.phase`` is the one primitive; ``LLMEngine.step`` is five sibling
phases (``engine.harvest_wait``, ``engine.emit``, ``engine.admit``,
``engine.prefill_wait``, ``engine.dispatch``) with no span around them;
every window found in flight is either overlapped (``spec_windows``) or
counted under the reason it was not (``spec_blocked_*``); the decode
program's layer carries ``paged.*`` scopes. The scheduler owns the slot
mirrors: the device is handed copies, and only a dispatched row advances.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.paged import PagedConfig
from ray_tpu.models.transformer import TransformerConfig, init_params
from ray_tpu.serve import llm_engine
from ray_tpu.serve.llm_engine import LLMEngine
from ray_tpu.util import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASE_FIELDS = [f"{name}_ms" for name in llm_engine._PHASES]
BLOCKED = ["spec_blocked_" + why for why in llm_engine._SPEC_BLOCKED]


@pytest.fixture(scope="module")
def tiny_model():
    cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    return cfg, init_params(jax.random.PRNGKey(7), cfg)


def _engine(cfg, params, *, window=2, overlap=True, **paged):
    pcfg = PagedConfig(**{**dict(block_size=8, num_blocks=33, max_batch=4,
                                 max_blocks_per_seq=8), **paged})
    return LLMEngine(params, cfg, pcfg, decode_window=window, overlap=overlap)


# ---------------------------------------------------------------------------
# tracing.phase
# ---------------------------------------------------------------------------
def test_phase_adds_milliseconds_and_writes_no_span_when_tracing_is_off(tmp_path):
    assert not tracing.tracing_enabled()
    into = {"engine.x": 1.0}
    with tracing.phase("engine.x", into):
        pass
    with tracing.phase("engine.y", into):
        pass
    assert into["engine.x"] > 1.0 and 0.0 <= into["engine.y"] < 50.0
    assert tracing.collect_spans(str(tmp_path)) == []


def test_phase_span_reaches_the_jsonl_sink_under_ray_tpu_trace(tmp_path, monkeypatch):
    monkeypatch.setenv(tracing.TRACE_ENV_VAR, "1")
    monkeypatch.setenv("RAY_TPU_SESSION_DIR", str(tmp_path))
    try:
        assert tracing.maybe_enable_from_env()
        into = {}
        with tracing.phase("engine.admit", into):
            pass
    finally:
        tracing.disable_tracing()
    spans = [e for e in tracing.collect_spans(str(tmp_path)) if e.get("ph") == "X"]
    assert [e["name"] for e in spans] == ["engine.admit"]
    assert spans[0]["dur"] >= 0 and into["engine.admit"] >= 0.0


def test_phase_without_jax_still_times(monkeypatch):
    # ``import jax.profiler`` raises ImportError while these are None.
    monkeypatch.setitem(sys.modules, "jax", None)
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    monkeypatch.setattr(tracing, "_annotation", None)
    into = {}
    with tracing.phase("engine.emit", into):
        pass
    assert tracing._annotation is False  # bound once, as absent
    assert into["engine.emit"] >= 0.0


def test_trace_annotation_lives_in_one_place():
    hits = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "ray_tpu")):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                with open(path, encoding="utf-8") as fh:
                    if "TraceAnnotation" in fh.read():
                        hits.append(os.path.relpath(path, REPO))
    assert hits == ["ray_tpu/util/tracing.py"]


# ---------------------------------------------------------------------------
# Step records
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("overlap", [False, True])
def test_every_recorded_step_carries_wall_and_phase_times(tiny_model, overlap):
    cfg, params = tiny_model
    eng = _engine(cfg, params, overlap=overlap)
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
    outs = eng.generate_batch(prompts, max_new_tokens=7)
    assert all(len(o) == 7 for o in outs)
    steps = list(eng.recorder.steps)
    assert steps
    for rec in steps:
        assert rec["wall_ms"] >= 0.0 and rec["overlapped"] in (0, 1)
        assert all(rec[f] >= 0.0 for f in PHASE_FIELDS), rec
        # siblings: none is counted inside another
        assert sum(rec[f] for f in PHASE_FIELDS) <= rec["wall_ms"] + 1.0, rec
    assert any(r["dispatch_ms"] > 0 for r in steps)
    assert any(r["harvest_wait_ms"] > 0 for r in steps)
    assert any(r["prefill_wait_ms"] > 0 for r in steps)
    assert sum(r["overlapped"] for r in steps) == eng.stats["spec_windows"]
    if not overlap:
        assert eng.stats["spec_windows"] == 0 and not any(eng.stats[k] for k in BLOCKED)


# ---------------------------------------------------------------------------
# Why a window was not overlapped
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("paged, lens", [
    # roomy pool: admissions, cap-finishes, prefill flushes
    ({}, [20, 5, 24, 9, 14, 24, 3, 11]),
    # four long answers want 16 of 12 blocks: preemption inside a speculated dispatch
    (dict(num_blocks=13, max_blocks_per_seq=4), [24, 24, 24, 24, 24, 24]),
], ids=["roomy", "preempting"])
def test_each_window_in_flight_is_overlapped_or_counted_once(tiny_model, paged, lens):
    cfg, params = tiny_model
    eng = _engine(cfg, params, **paged)
    todo = [([i + 1, i + 2, i + 3, i + 4], n) for i, n in enumerate(lens)]
    reqs = [eng.add_request(*todo.pop(0)) for _ in range(2)]
    found_in_flight = 0
    while eng.active_count() or eng.waiting:
        if found_in_flight == 3:  # the rest arrive with a window in flight and slots free
            reqs += [eng.add_request(*t) for t in todo]
            todo = []
        found_in_flight += eng._inflight is not None
        eng.step()
    assert [len(list(r.tokens(timeout=5))) for r in reqs] == lens
    s = eng.stats
    assert s["spec_windows"] + sum(s[k] for k in BLOCKED) == found_in_flight
    assert s["spec_windows"] > 0 and s["spec_blocked_finishing"] > 0
    assert s["spec_blocked_admission"] > 0
    if paged:  # the speculated dispatch that preempted was aborted, and counted
        assert s["preemptions"] > 0 and s["spec_blocked_dirty_cur"] > 0
    snap = eng.report_state()
    assert snap["overlap"]["blocked"] == {
        why: s["spec_blocked_" + why] for why in llm_engine._SPEC_BLOCKED}


def test_live_blocks_are_counted_against_the_table_at_each_dispatch(tiny_model):
    """``decode_blocks_live`` is what decode attention has to read (the
    blocks the occupied slots' tokens lie in as the window starts),
    ``decode_blocks_table`` what the padded gather reads: every slot's
    whole table."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, overlap=False)  # block 8, 4 slots, table of 8
    reqs = [eng.add_request(list(range(1, 6)), 6), eng.add_request(list(range(1, 12)), 6)]
    while eng.active_count() or eng.waiting:
        eng.step()
    assert [len(list(r.tokens(timeout=5))) for r in reqs] == [6, 6]
    s = eng.stats
    # the first token comes from the prefill, five more from three windows of
    # two, dispatched at lens 5, 7, 9 (1, 1, 2 blocks) and 11, 13, 15 (2, 2, 2)
    assert s["steps"] == 3
    assert s["decode_blocks_live"] == (1 + 1 + 2) + (2 + 2 + 2)
    assert s["decode_blocks_table"] == 3 * 4 * 8
    assert eng.report_state()["overlap"]["decode_live_block_pct"] == pytest.approx(100 * 10 / 96)


# ---------------------------------------------------------------------------
# slot state: one owner
# ---------------------------------------------------------------------------
def _on_a_64_byte_boundary(a):
    """``a``'s contents in a buffer that starts on a 64-byte boundary: the
    placement at which the CPU client aliases a numpy array, not copies it
    (where malloc puts the engine's own small mirrors is chance)."""
    raw = np.zeros(a.nbytes + 64, np.uint8)
    start = -raw.ctypes.data % 64
    out = raw[start:start + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


@pytest.mark.parametrize("name", ["tables", "lens", "temps", "cur"])
def test_the_device_is_handed_a_copy_of_each_mirror(tiny_model, name):
    """The scheduler writes its mirrors in place right after a dispatch
    (``lens`` advances, the harvest sets ``cur``, ``_free_slot`` resets a
    row): what a program was handed must not move with them."""
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    host = _on_a_64_byte_boundary(getattr(eng, name))
    host[...] = 3
    setattr(eng, name, host)
    eng._dirty.add(name)
    shipped = eng._ship()[name]
    host += 10
    assert (np.asarray(shipped) == 3).all()


@pytest.mark.parametrize("overlap", [False, True])
def test_only_a_dispatched_row_advances(tiny_model, overlap):
    """An idle row is 0 on the host from ``_free_slot`` on, however many
    windows it sits out; an occupied row stands where its last token goes."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, overlap=overlap)  # 4 slots, windows of 2
    prompts = [list(range(1, 6)), list(range(1, 12)), list(range(1, 4))]
    reqs = [eng.add_request(p, n) for p, n in zip(prompts, (40, 40, 3))]
    for _ in range(6):
        eng.step()
    assert eng.slots[3] is None and eng.slots[2] is None  # never used; finished
    assert reqs[2].remaining == 0
    assert eng.lens[3] == 0 and eng.lens[2] == 0
    ahead = eng.window if eng._inflight is not None else 0
    for i in (0, 1):
        assert eng.slots[i] is reqs[i] and 3 < len(reqs[i].generated) < 40
        # the last token harvested is not in the cache yet: it is written
        # at ``lens`` by the next window (already dispatched under overlap)
        assert eng.lens[i] == len(prompts[i]) + len(reqs[i].generated) - 1 + ahead
    while eng.active_count() or eng.waiting:
        eng.step()
    assert [len(list(r.tokens(timeout=5))) for r in reqs] == [40, 40, 3]
    assert (eng.lens == 0).all()


def _force_idle(eng):
    for i, req in enumerate(eng.slots):  # as if an eos stop had been harvested
        if req is not None:
            eng._free_slot(i)


def _force_finishing(eng):
    req = next(r for r in eng.slots if r is not None)
    req.max_new_tokens = len(req.generated) + eng.window


@pytest.mark.parametrize("reason, force", [
    ("idle", _force_idle),
    ("admission", lambda eng: eng.add_request([9, 8, 7], 4)),
    ("dirty_cur", lambda eng: eng._dirty.add("cur")),
    ("finishing", _force_finishing),
])
def test_a_blocked_window_is_counted_under_its_reason(tiny_model, reason, force):
    cfg, params = tiny_model
    eng = _engine(cfg, params)
    eng.add_request([5, 9, 2, 11], 40)
    eng.step()  # admit, prefill, first window dispatched
    assert eng._inflight is not None and eng._can_speculate() is None
    force(eng)
    assert eng._can_speculate() == reason
    before = dict(eng.stats)
    eng.step()
    moved = {k for k in ["spec_windows"] + BLOCKED if eng.stats[k] != before[k]}
    assert moved == {"spec_blocked_" + reason}
    assert eng.stats["spec_blocked_" + reason] == before["spec_blocked_" + reason] + 1
    assert eng.recorder.steps[-1]["overlapped"] == 0


def test_blocked_reasons_reach_the_registry_counter(tiny_model):
    from ray_tpu.serve.metrics import serve_metrics

    cfg, params = tiny_model
    eng = _engine(cfg, params)
    eng.metrics_tags = {"deployment": "phases", "replica": "r0"}
    eng.generate_batch([[1, 2, 3], [4, 5, 6]], max_new_tokens=5)
    eng._maybe_flush_metrics(force=True)
    counter = serve_metrics().engine_overlap_blocked
    assert counter.name == "serve_engine_overlap_blocked_total"
    mine = {dict(tags)["reason"]: value for _n, _t, _d, tags, value in counter._drain()
            if dict(tags)["deployment"] == "phases"}
    assert mine == {why: eng.stats["spec_blocked_" + why]
                    for why in llm_engine._SPEC_BLOCKED if eng.stats["spec_blocked_" + why]}
    assert mine["finishing"] > 0


# ---------------------------------------------------------------------------
# On the profiler's clock
# ---------------------------------------------------------------------------
_TRACED_ENGINE_DRIVER = """
import glob, json, os, sys
import jax, jax.numpy as jnp
from jax.profiler import ProfileData
from ray_tpu.models.paged import PagedConfig
from ray_tpu.models.transformer import TransformerConfig, init_params
from ray_tpu.serve.llm_engine import LLMEngine
from ray_tpu.util import profiling

cfg = TransformerConfig.tiny(dtype=jnp.float32, remat=False)
eng = LLMEngine(init_params(jax.random.PRNGKey(7), cfg), cfg,
                PagedConfig(block_size=8, num_blocks=33, max_batch=4, max_blocks_per_seq=8),
                decode_window=2, overlap=True)
eng.generate_batch([[1, 2, 3]], max_new_tokens=3)  # compile outside the trace
started = profiling.device_trace_control("start", "phases", sys.argv[1])
if not started["ok"]:
    print(json.dumps({"skip": started.get("error", "?")}))
    sys.exit(0)
try:
    eng.generate_batch([[i + 1, i + 2, i + 3] for i in range(6)], max_new_tokens=9)
finally:
    stopped = profiling.device_trace_control("stop")
assert stopped["ok"], stopped
[path] = glob.glob(os.path.join(stopped["dir"], "plugins", "profile", "*", "*.xplane.pb"))
names = {ev.name for plane in ProfileData.from_file(path).planes
         if plane.name.startswith("/host:") for line in plane.lines for ev in line.events}
print(json.dumps({"engine": sorted(n for n in names if n.startswith("engine."))}))
"""


def test_traced_engine_puts_five_sibling_phases_on_the_host_plane(tmp_path):
    # A fresh interpreter, as test_device_trace_control_rejects_double_start:
    # stop_trace dumps every computation the process has ever run.
    proc = subprocess.run(
        [sys.executable, "-c", _TRACED_ENGINE_DRIVER, str(tmp_path)], cwd=REPO,
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr[-3000:]}"
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    if "skip" in verdict:
        pytest.skip(f"backend can't trace: {verdict['skip']}")
    # the five, and nothing around them
    assert verdict["engine"] == sorted("engine." + name for name in llm_engine._PHASES)


# ---------------------------------------------------------------------------
# Scopes of the decode program
# ---------------------------------------------------------------------------
def test_decode_program_carries_the_paged_scopes(tiny_model):
    cfg, params = tiny_model
    text = _engine(cfg, params)._decode.as_text()
    # no ``paged.gather``: the gather of the padded table is the plain form of
    # ``paged.attend`` now, and the kernel has none
    assert "/paged.gather/" not in text
    for scope in ("paged.scatter", "paged.attend", "paged.mlp"):
        assert any("op_name=" in line and f"/{scope}/" in line
                   for line in text.splitlines()), scope
