"""The engine's scheduler thread on the profiler's clock.

``tracing.phase`` is the one primitive; ``LLMEngine.step`` is six sibling
phases (``engine.harvest_wait``, ``engine.emit``, ``engine.admit``,
``engine.prefill_wait``, ``engine.dispatch``, ``engine.record``) with no span
around them, and the parts of ``admit`` and ``dispatch`` nested in those two;
the time the device has nothing queued is counted by where the scheduler
thread was (``stats["starved_us_*"]``), apart from idleness for want of load;
every window found in flight is either overlapped (``spec_windows``) or
counted under the reason it was not (``spec_blocked_*``); the decode
program's layer carries ``paged.*`` scopes. The scheduler owns three slot
mirrors (``tables``, ``lens``, ``temps``): the device is handed copies, and
only a dispatched row advances. ``cur`` is the device's alone.
"""
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.paged import PagedConfig
from ray_tpu.serve import llm_engine
from ray_tpu.serve.llm_engine import LLMEngine
from ray_tpu.util import compile_tracker, tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASE_FIELDS = [f"{name}_ms" for name in llm_engine._PHASES]
SUB_FIELDS = [f"{sub}_ms" for sub in llm_engine._SUB_PHASES]
BUCKETS = ["starved_us_" + where for where in llm_engine._STARVED]
BLOCKED = ["spec_blocked_" + why for why in llm_engine._SPEC_BLOCKED]


def _tiny(which):
    """One of the four bodies behind ``paged_model(cfg)``, at its tests' size."""
    if which == "dense":
        from ray_tpu.models import transformer as m
        cfg = m.TransformerConfig.tiny(dtype=jnp.float32, remat=False)
    elif which == "latent":
        from ray_tpu.models import latent_moe as m
        cfg = m.LatentMoEConfig.tiny()
    elif which == "kda":
        from ray_tpu.models import kda_moe as m
        cfg = m.KDAMoEConfig.tiny(head_dim=32)
    else:
        from ray_tpu.models import hybrid_ssm as m
        cfg = m.HybridSSMConfig.tiny()
    return cfg, m.init_params(jax.random.PRNGKey(7), cfg)


@pytest.fixture(scope="module")
def tiny_model():
    return _tiny("dense")


def _engine(cfg, params, *, window=2, overlap=True, prefill_chunk=0, seed=0, **paged):
    pcfg = PagedConfig(**{**dict(block_size=8, num_blocks=33, max_batch=4,
                                 max_blocks_per_seq=8), **paged})
    return LLMEngine(params, cfg, pcfg, decode_window=window, overlap=overlap,
                     prefill_chunk=prefill_chunk, seed=seed)


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


def _lines(path):
    return sum(1 for _ in open(path)) if os.path.exists(path) else 0


@pytest.mark.parametrize("by", ["count", "age"])
def test_the_sink_keeps_spans_in_memory_and_writes_them_in_batches(tmp_path, monkeypatch, by):
    """Not a file opened for every span: the writer hands over a full batch
    itself, and a flusher thread what a process that fell idle still holds."""
    monkeypatch.setattr(tracing, "_FLUSH_AGE_S", 3600.0 if by == "count" else 0.05)
    tracing.enable_tracing(str(tmp_path))
    try:
        path = tracing._sink_path
        n = tracing._FLUSH_COUNT - 1 if by == "count" else 3
        for _ in range(n):
            with tracing.phase("engine.x", {}):
                pass
        if by == "count":
            assert _lines(path) == 0  # held back
            with tracing.phase("engine.x", {}):
                pass
        else:
            deadline = time.time() + 10
            while time.time() < deadline and not _lines(path):
                time.sleep(0.02)
        # the batch, with the two rows' names before it
        assert _lines(path) == (n + 1 if by == "count" else n) + 2
        with tracing.phase("engine.y", {}):
            pass
        # reading this process's own spans hands over what it still holds
        names = [e["name"] for e in tracing.collect_spans(str(tmp_path)) if e.get("ph") == "X"]
        assert names.count("engine.x") >= n and names[-1] == "engine.y"
    finally:
        tracing.disable_tracing()
    assert tracing._flusher_stop is None and not tracing._buffer


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
    assert len(PHASE_FIELDS) == 6 and len(SUB_FIELDS) == 8
    for rec in steps:
        assert rec["wall_ms"] >= 0.0 and rec["overlapped"] in (0, 1)
        assert all(rec[f] >= 0.0 for f in PHASE_FIELDS + SUB_FIELDS), rec
        # siblings: none is counted inside another, and the six cover the step
        assert sum(rec[f] for f in PHASE_FIELDS) <= rec["wall_ms"], rec
        # the parts lie inside their parent, which keeps its whole time
        for parent in ("admit", "dispatch"):
            parts = [f for f in SUB_FIELDS if f.startswith(parent + "_")]
            assert len(parts) == 4 and sum(rec[f] for f in parts) <= rec[parent + "_ms"], rec
        assert rec["record_ms"] > 0.0
        assert 0.0 <= rec["starved_admit_ms"] + rec["starved_dispatch_ms"] <= rec["starved_ms"]
    assert any(r["dispatch_ms"] > 0 for r in steps)
    assert all(any(r[f] > 0 for r in steps) for f in SUB_FIELDS)
    assert any(r["harvest_wait_ms"] > 0 for r in steps)
    assert any(r["prefill_wait_ms"] > 0 for r in steps)
    assert sum(r["overlapped"] for r in steps) == eng.stats["spec_windows"]
    if not overlap:
        assert eng.stats["spec_windows"] == 0 and not any(eng.stats[k] for k in BLOCKED)


# ---------------------------------------------------------------------------
# The account of the time the device waits for the host
# ---------------------------------------------------------------------------
def _starved(eng):
    return {k: eng.stats[k] for k in ["starved_us", "unloaded_us"] + BUCKETS}


@pytest.mark.parametrize("overlap", [False, True])
def test_the_buckets_sum_to_starved_us_after_every_step(tiny_model, overlap):
    cfg, params = tiny_model
    eng = _engine(cfg, params, overlap=overlap)
    for i in range(6):  # answers of unlike lengths: one ends, the rest need a window on an empty queue
        eng.add_request([i + 1, i + 2, i + 3], 9 + i)
    in_steps = 0.0
    while eng.active_count() or eng.waiting:
        eng.step()
        s = eng.stats
        assert sum(s[k] for k in BUCKETS) == s["starved_us"]
        assert all(isinstance(s[k], int) and s[k] >= 0 for k in BUCKETS + ["unloaded_us"])
    for rec in eng.recorder.steps:
        # only outside the two waits can the host know the queue empty
        assert rec["starved_ms"] <= (
            rec["wall_ms"] - rec["harvest_wait_ms"] - rec["prefill_wait_ms"] + 1.0), rec
        in_steps += rec["starved_ms"]
    # what lies between two steps is no step's; ``between`` is that and the
    # moments of a step outside its phases
    total, between = eng.stats["starved_us"], eng.stats["starved_us_between"]
    assert total - between - 1.0 <= in_steps * 1e3 <= total + 1.0
    assert eng.stats["starved_us"] > 0 and eng.stats["starved_us_dispatch_launch"] > 0
    snap = eng.report_state()["overlap"]
    assert snap["starved_us"] == {w: eng.stats["starved_us_" + w] for w in llm_engine._STARVED}
    assert 0.0 < snap["device_starved_pct"] <= 100.0


def test_a_speculated_step_adds_nothing_between_its_harvest_and_its_next_launch(tiny_model):
    """The window dispatched before the harvest is newer than the one read:
    the queue is not empty, whatever the host does meanwhile."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, overlap=True)
    eng.add_request([5, 9, 2, 11], 40)
    speculated = 0
    while eng.active_count() or eng.waiting:
        before = eng.stats["starved_us"]
        eng.step()
        rec = eng.recorder.steps[-1]
        if rec["overlapped"]:
            speculated += 1
            time.sleep(0.002)  # host time the device does not wait for
            assert rec["starved_ms"] == 0.0 and eng._empty_since is None
            assert eng.stats["starved_us"] == before
    assert speculated >= 5 and speculated == eng.stats["spec_windows"]


@pytest.mark.parametrize("overlap", [False, True])
def test_an_idle_engine_is_unloaded_not_starved(tiny_model, overlap):
    cfg, params = tiny_model
    eng = _engine(cfg, params, overlap=overlap)
    time.sleep(0.01)
    assert eng.step() is False  # never had work: idle since it was built
    assert eng.stats["unloaded_us"] >= 10_000 and eng.stats["starved_us"] == 0
    eng.generate_batch([[1, 2, 3]], max_new_tokens=5)
    assert eng.step() is False  # the verdict: no work left
    before = _starved(eng)
    assert before["starved_us"] > 0
    for _ in range(2):
        time.sleep(0.02)
        assert eng.step() is False
    after = _starved(eng)
    assert after["unloaded_us"] - before["unloaded_us"] >= 40_000
    assert {k: after[k] for k in after if k != "unloaded_us"} == {
        k: before[k] for k in before if k != "unloaded_us"}
    # work again: the wait for the host is counted again, the idleness is not
    eng.generate_batch([[4, 5, 6]], max_new_tokens=5)
    assert eng.stats["starved_us"] > after["starved_us"]


@pytest.mark.parametrize("overlap", [False, True])
def test_a_slow_launch_is_counted_in_dispatch_launch_alone(tiny_model, overlap):
    """A launch ends starvation when the call RETURNS: its own host time is
    part of what the device waited for."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, overlap=overlap)
    eng.add_request([5, 9, 2, 11], 40)
    eng.add_request([17, 1, 8], 40)
    eng.step()
    eng.step()
    decode = eng._decode

    def slow(*args):
        time.sleep(0.02)
        return decode(*args)

    eng._decode = slow
    # with overlap: the first ends in the window in flight, so no speculation:
    # the harvest comes first, and the other's next window after it
    _force_finishing(eng)
    before = _starved(eng)
    eng.step()
    eng._decode = decode
    moved = {k: v - before[k] for k, v in _starved(eng).items()}
    assert moved["starved_us_dispatch_launch"] >= 20_000
    others = sum(moved[k] for k in BUCKETS if k != "starved_us_dispatch_launch")
    assert others < 20_000 and moved["unloaded_us"] == 0
    assert moved["starved_us"] == moved["starved_us_dispatch_launch"] + others
    rec = eng.recorder.steps[-1]
    assert rec["starved_dispatch_ms"] >= 20.0 and rec["dispatch_launch_ms"] >= 20.0
    assert rec["starved_admit_ms"] < 20.0
    while eng.active_count():
        eng.step()


def test_starved_seconds_reach_the_registry_counter(tiny_model):
    from ray_tpu.serve.metrics import serve_metrics

    cfg, params = tiny_model
    eng = _engine(cfg, params)
    eng.metrics_tags = {"deployment": "starved", "replica": "r0"}
    # the first ends alone: the other's next window is launched on an empty queue
    reqs = [eng.add_request([1, 2, 3], 5), eng.add_request([4, 5, 6], 9)]
    while eng.active_count() or eng.waiting:
        eng.step()
    assert [len(r.generated) for r in reqs] == [5, 9]
    eng._maybe_flush_metrics(force=True)
    counter = serve_metrics().engine_device_starved
    assert counter.name == "serve_engine_device_starved_seconds_total"
    mine = {dict(tags)["where"]: value for _n, _t, _d, tags, value in counter._drain()
            if dict(tags)["deployment"] == "starved"}
    assert set(mine) <= set(llm_engine._STARVED) and len(llm_engine._STARVED) == 11
    assert mine == pytest.approx({w: eng.stats["starved_us_" + w] / 1e6
                                  for w in llm_engine._STARVED if eng.stats["starved_us_" + w]})
    assert mine["dispatch_launch"] > 0


# ---------------------------------------------------------------------------
# The decode window behind the prefill: first tokens reach ``cur`` on the device
# ---------------------------------------------------------------------------
def _flush_first(eng):
    """Force the flush-first order on every step: the dispatch gives up
    while first tokens are unread, as it does when it would have to preempt,
    so the flush comes first and the ship of the mirrors after it."""
    ensure = eng._ensure_decode_blocks
    eng._ensure_decode_blocks = lambda: not eng._pending_first and ensure()


def _prefill_steps_that_dispatched(eng):
    """Steps that launched a prefill and then dispatched a window themselves
    (a speculated window is dispatched before the step admits)."""
    return sum(1 for r in eng.recorder.steps
               if r["prefills"] and not r["overlapped"] and r["dispatch_launch_ms"] > 0)


# roomy: several requests admitted in one step, two that end AT their first
# token; tight: five of nine usable blocks' worth of answers, so a dispatch has
# to preempt on a step whose first tokens are unread
_POOLS = {"roomy": ({}, [20, 1, 24, 9, 14, 1, 3, 11]),
          "tight": (dict(num_blocks=10, max_blocks_per_seq=4), [24, 20, 16, 12, 8, 24])}


@pytest.mark.parametrize("pool", list(_POOLS))
@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("overlap", [False, True])
def test_the_same_seed_gives_the_same_tokens_behind_the_prefill_as_flushed_first(
        tiny_model, overlap, temperature, pool):
    """The order of the key splits is the same either way (admit's, then
    the window's; the flush takes none), an idle or doomed lane moves no
    other row's sample, and a preemption waits for the flush: a seed gives
    the same tokens whether the window queues behind the prefill or waits
    for the host to read it, as every step did before."""
    cfg, params = tiny_model
    paged, lens = _POOLS[pool]
    outs = {}
    for order in ("behind", "flushed_first"):
        eng = _engine(cfg, params, overlap=overlap, **paged)
        if order == "flushed_first":
            _flush_first(eng)
        reqs = [eng.add_request([i + 1, i + 2, i + 3, i + 4], n, temperature=temperature)
                for i, n in enumerate(lens)]
        while eng.active_count() or eng.waiting:
            eng.step()
            assert not eng._pending_first  # every step reads what it launched
        outs[order] = [r.generated for r in reqs], dict(eng.stats)
        s = eng.stats
        assert (s["windows_behind_prefill"] + s["prefill_flushed_first"]
                == _prefill_steps_that_dispatched(eng) > 0)
        assert sum(r["behind_prefill"] for r in eng.recorder.steps) == s["windows_behind_prefill"]
    (behind, sb), (flushed, sf) = outs["behind"], outs["flushed_first"]
    assert [len(o) for o in behind] == lens and behind == flushed
    assert sf["windows_behind_prefill"] == 0 and sf["prefill_flushed_first"] > 0
    assert sb["windows_behind_prefill"] > 0
    assert sb["preemptions"] == sf["preemptions"] and sb["steps"] == sf["steps"]
    if pool == "tight":  # the preemption fell on a step with first tokens unread
        assert sb["preemptions"] > 0 and sb["prefill_flushed_first"] > 0
    else:
        assert sb["prefill_flushed_first"] == 0
        # nothing is shipped for a first token in either order: ``cur`` is the device's
        assert sb["h2d_ships"] == sf["h2d_ships"]


@pytest.mark.parametrize("overlap", [False, True])
def test_a_first_token_is_emitted_before_its_windows(tiny_model, overlap):
    """dispatch, then the flush, then (``overlap`` off) the harvest of that
    window; a request that ends AT its first token leaves a lane in the
    window in flight, which the harvest discards."""
    from ray_tpu.models.generate import generate

    cfg, params = tiny_model
    eng = _engine(cfg, params, overlap=overlap)
    order, emitted = [], []
    for name in ("_dispatch_window", "_flush_prefills", "_harvest_window"):
        def logged(*a, _f=getattr(eng, name), _name=name, **kw):
            if _name != "_flush_prefills" or eng._pending_first:
                order.append(_name)
            return _f(*a, **kw)
        setattr(eng, name, logged)
    emit = eng._emit
    eng._emit = lambda i, tok: (emitted.append((eng.slots[i].rid, order[-1])), emit(i, tok))
    prompts = [[5, 9, 2, 11], [17, 1, 8], [30, 31, 32]]
    reqs = [eng.add_request(p, n) for p, n in zip(prompts, (7, 1, 7))]
    eng.step()
    assert order == ["_dispatch_window", "_flush_prefills"] + (
        [] if overlap else ["_harvest_window"])
    assert eng.recorder.steps[-1]["behind_prefill"] == 1
    assert reqs[1].remaining == 0 and eng.slots[1] is None  # ended in the flush
    if overlap:  # its lane rides the window in flight
        assert [i for i, _rid, _gen in eng._inflight[0]] == [0, 1, 2]
    late = eng.add_request([40, 41, 42], 5)  # takes the slot that was given back
    while eng.active_count() or eng.waiting:
        eng.step()
    for p, r in zip(prompts + [[40, 41, 42]], reqs + [late]):
        n = r.max_new_tokens
        assert r.generated == list(np.asarray(generate(params, cfg, jnp.asarray([p]), n)[0]))
    first = {}
    for rid, during in emitted:
        first.setdefault(rid, during)
    assert set(first.values()) == {"_flush_prefills"} and len(first) == 4


@pytest.mark.parametrize("overlap", [False, True])
def test_a_window_behind_a_prefill_starves_nobody_in_dispatch(tiny_model, overlap):
    """What the dispatch costs the host runs under the prefill program: a
    slow launch adds to no ``dispatch_*`` bucket, and the buckets still
    sum to ``starved_us``."""
    cfg, params = tiny_model
    eng = _engine(cfg, params, overlap=overlap)
    eng.add_request([5, 9, 2, 11], 40)
    eng.step()
    eng.step()
    decode = eng._decode

    def slow(*args):
        time.sleep(0.02)
        return decode(*args)

    eng._decode = slow
    eng.add_request([7, 8, 9], 40)  # with overlap: ``admission`` blocks the speculation
    before = _starved(eng)
    ships = eng.stats["h2d_ships"]
    eng.step()
    eng._decode = decode
    moved = {k: v - before[k] for k, v in _starved(eng).items()}
    rec = eng.recorder.steps[-1]
    assert rec["prefills"] == 1 and rec["behind_prefill"] == 1 and not rec["overlapped"]
    assert rec["dispatch_launch_ms"] >= 20.0 and rec["starved_dispatch_ms"] == 0.0
    assert all(moved[k] == 0 for k in BUCKETS if k.startswith("starved_us_dispatch_")), moved
    assert moved["starved_us_admit_launch"] > 0 and moved["unloaded_us"] == 0
    assert moved["starved_us"] == sum(moved[k] for k in BUCKETS)
    # tables, lens and temps: the first token is in the device's ``cur`` already
    assert eng.stats["h2d_ships"] - ships == 3 and not eng._dirty
    if not overlap:  # harvested: the device's ``cur`` is the occupied rows' last token
        last = [eng.slots[i].generated[-1] for i in (0, 1)]
        assert list(np.asarray(eng._dev["cur"])[:2]) == last and len(eng.slots[1].generated) == 3
    while eng.active_count():
        eng.step()


def test_windows_behind_prefill_reach_the_report_and_the_registry(tiny_model):
    from ray_tpu.serve.metrics import serve_metrics

    cfg, params = tiny_model
    paged, lens = _POOLS["tight"]
    eng = _engine(cfg, params, **paged)
    eng.metrics_tags = {"deployment": "behind", "replica": "r0"}
    reqs = [eng.add_request([i + 1, i + 2, i + 3, i + 4], n) for i, n in enumerate(lens)]
    while eng.active_count() or eng.waiting:
        eng.step()
    assert [len(r.generated) for r in reqs] == lens
    s = eng.stats
    assert s["windows_behind_prefill"] > 0 and s["prefill_flushed_first"] > 0
    snap = eng.report_state()
    assert snap["stats"]["windows_behind_prefill"] == s["windows_behind_prefill"]
    assert {k: snap["overlap"][k] for k in ("windows_behind_prefill", "prefill_flushed_first")} == {
        k: s[k] for k in ("windows_behind_prefill", "prefill_flushed_first")}
    assert all("behind_prefill" in r for r in snap["steps"])
    m = serve_metrics()
    for counter, name, key in (
            (m.engine_windows_behind_prefill, "serve_engine_windows_behind_prefill_total",
             "windows_behind_prefill"),
            (m.engine_prefill_flushed_first, "serve_engine_prefill_flushed_first_total",
             "prefill_flushed_first")):
        assert counter.name == name
        mine = [value for _n, _t, _d, tags, value in counter._drain()
                if dict(tags)["deployment"] == "behind"]
        assert mine == [s[key]]


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
    if paged:  # a preemption, under a window in flight or not, is no reason of its own
        assert s["preemptions"] > 0
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


@pytest.mark.parametrize("name", ["tables", "lens", "temps"])
def test_the_device_is_handed_a_copy_of_each_mirror(tiny_model, name):
    """The scheduler writes its mirrors in place right after a dispatch
    (``lens`` advances, ``_free_slot`` resets a row): what a program was
    handed must not move with them."""
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


def _run(eng, reqs):
    """Step ``eng`` until it is empty: per step, whether it found a window in
    flight, what it added to ``preemptions`` and ``finished``, and its record."""
    steps = []
    while eng.active_count() or eng.waiting:
        before = dict(eng.stats)
        in_flight = eng._inflight is not None
        eng.step()
        assert eng._dirty <= {"tables", "lens", "temps"}
        steps.append((in_flight, eng.stats["preemptions"] - before["preemptions"],
                      eng.stats["finished"] - before["finished"], eng.recorder.steps[-1]))
    return [r.generated for r in reqs], steps


@pytest.mark.parametrize("how", ["preempted", "stopped"])
def test_a_slot_given_back_under_a_window_in_flight_does_not_stop_the_next_speculation(
        tiny_model, how):
    """The next window takes its tokens from the in-flight window's own output
    on the device, and the host's ``lens`` are current for what it ships, so a
    preemption inside a speculated dispatch goes ahead, and so does the
    speculation after an eos stop was harvested under a speculated window. The
    tokens are those of the engine that reads every window before the next."""
    cfg, params = tiny_model
    if how == "preempted":  # four long answers want 16 of 12 blocks
        paged, todo = dict(num_blocks=13, max_blocks_per_seq=4), [
            ([i + 1, i + 2, i + 3, i + 4], 24, None) for i in range(6)]
    else:  # the second stops where no cap tells the scheduler it will
        plain = _engine(cfg, params, overlap=False).generate_batch([[17, 1, 8]], max_new_tokens=12)[0]
        at = next(k for k in range(4, 12) if plain[k] not in plain[:k])
        paged, todo = {}, [([5, 9, 2, 11], 40, None), ([17, 1, 8], 40, plain[at])]
    outs = {}
    for overlap in (False, True):
        eng = _engine(cfg, params, overlap=overlap, **paged)
        outs[overlap] = _run(eng, [eng.add_request(p, n, eos_id=eos) for p, n, eos in todo])
    (plain, _), (served, steps) = outs[False], outs[True]
    assert served == plain and all(served)
    if how == "preempted":  # the speculated dispatch preempted, and was not given up
        assert any(found and preempted and rec["overlapped"]
                   for found, preempted, _finished, rec in steps)
    else:
        assert len(served[1]) == at + 1 < 40
        gave_back = next(k for k, step in enumerate(steps) if step[2])
        assert steps[gave_back][3]["overlapped"]  # freed under the window after its own
        assert steps[gave_back + 1][0] and steps[gave_back + 1][3]["overlapped"]


@pytest.mark.parametrize("which", ["dense", "latent", "hybrid", "kda"])
def test_a_stale_token_on_an_idle_row_reaches_no_live_row(which):
    """An idle row of the device's ``cur`` holds whatever was left there. No
    live row's tokens depend on it, greedy or sampled: attention, state and
    sampling are per row, and an expert layer has no capacity for an idle
    row's pairs to use up."""
    cfg, params = _tiny(which)
    outs = []
    for stale in (0, 201):
        eng = _engine(cfg, params, prefill_chunk=16, seed=3)
        reqs = [eng.add_request([5, 9, 2, 11, 3], 14, temperature=0.8),
                eng.add_request(list(range(30, 50)), 3),  # two chunks; its row idles from its end on
                eng.add_request([17, 1, 8], 12)]
        while eng.active_count() or eng.waiting:
            cur = np.array(eng._dev["cur"])
            idle = [i for i, req in enumerate(eng.slots) if req is None or i in eng._prefilling]
            cur[idle] = stale
            eng._dev["cur"] = jax.device_put(cur, eng._dev["cur"].sharding)
            eng.step()
        outs.append([r.generated for r in reqs])
        assert [len(o) for o in outs[-1]] == [14, 3, 12]
    assert outs[0] == outs[1]


def test_the_engine_keeps_no_host_copy_of_cur(tiny_model):
    cfg, params = tiny_model
    eng = _engine(cfg, params, num_blocks=13, max_blocks_per_seq=4)
    assert not hasattr(eng, "cur") and eng._dirty == {"tables", "lens", "temps"}
    assert isinstance(eng._dev["cur"], jax.Array)  # made with the engine, before any program
    assert llm_engine._SPEC_BLOCKED == ("idle", "admission", "finishing")
    served, steps = _run(eng, [eng.add_request([i + 1, i + 2, i + 3, i + 4], 24) for i in range(6)])
    assert [len(o) for o in served] == [24] * 6 and eng.stats["preemptions"] > 0
    s = eng.stats
    assert s["spec_windows"] + sum(s[k] for k in BLOCKED) == sum(found for found, *_ in steps)
    assert set(eng.report_state()["overlap"]["blocked"]) == {"idle", "admission", "finishing"}


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
spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
         for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
         for line in plane.lines for ev in line.events if ev.name.startswith("engine.")]
print(json.dumps({"engine": spans}))
"""


def test_traced_engine_puts_six_sibling_phases_and_their_parts_on_the_host_plane(tmp_path):
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
    spans = verdict["engine"]
    tops = {"engine." + name for name in llm_engine._PHASES}
    parts = {"engine." + sub.replace("_", ".", 1) for sub in llm_engine._SUB_PHASES}
    # the six with the eight inside two of them, and nothing around them
    assert {name for name, _a, _b in spans} == tops | parts and len(parts) == 8

    def inside(a, b, outer):
        return [n for n, oa, ob in outer if oa <= a and b <= ob]

    top_spans = [s for s in spans if s[0] in tops]
    part_spans = [s for s in spans if s[0] in parts]
    for name, a, b in top_spans:  # siblings: none inside another
        assert inside(a, b, top_spans) == [name], (name, a, b)
    for name, a, b in part_spans:  # in its own parent, and in no other part
        assert inside(a, b, top_spans) == [name.rsplit(".", 1)[0]], (name, a, b)
        assert inside(a, b, part_spans) == [name], (name, a, b)


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


# ---------------------------------------------------------------------------
# A fixed ``prefill_chunk`` is a ladder of widths, each an executable from the
# engine's build on, compiled side by side; a call goes at the narrowest.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk, block_size, ladder", [
    (1024, 16, [256, 512, 1024]),   # the hybrid and KDA cells: tiles of 64
    (1024, 32, [256, 512, 1024]),   # the power cell: tiles of 128
    (1024, 64, [256, 512, 1024]),   # the latent cell: 256 is one tile
    (512, 16, [256, 512]),
    (256, 16, [256]),               # a half would lie under _WEIGHTS_WIDTH
    (768, 16, [384, 768]),
    (1536, 64, [768, 1536]),        # 768 halves to no whole tile of four blocks
    (32, 8, [32]),                  # the tests' own chunks: one width, as before
    (1000, 8, [1000]),              # an odd count of tiles does not halve
])
def test_the_ladder_is_the_chunk_and_its_halves_down_to_the_weights_width(chunk, block_size, ladder):
    assert llm_engine._chunk_ladder(chunk, block_size) == ladder
    assert all(w % (4 * block_size) == 0 or w == chunk for w in ladder)


class _Built:
    """An engine with ``prefill_chunk=1024`` and what its build compiled where."""

    def __init__(self, which, **kw):
        compile_tracker.install()
        self.cfg, self.params = _tiny(which)
        self.aot_threads = []  # the thread of every ``Lowered.compile`` of the build
        real = jax.stages.Lowered.compile

        def compile_(lowered, *a, **k):
            self.aot_threads.append(threading.get_ident())
            return real(lowered, *a, **k)

        before = self._compiled()
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax.stages.Lowered, "compile", compile_)
            self.eng = self.build(**kw)
        self.compiled = {k: v - before.get(k, 0) for k, v in self._compiled().items()
                         if v > before.get(k, 0)}
        self.main_thread = threading.get_ident()

    def build(self, **kw):
        pcfg = PagedConfig(block_size=16, num_blocks=521, max_batch=4, max_blocks_per_seq=130)
        kw = {**dict(decode_window=3, overlap=True, prefill_chunk=1024), **kw}
        return LLMEngine(self.params, self.cfg, pcfg, **kw)

    @staticmethod
    def _compiled():
        return {k: v["count"] for k, v in compile_tracker.snapshot(max_functions=10_000)["functions"].items()}


@pytest.fixture(scope="module", params=["dense", "hybrid"])
def laddered(request):
    return _Built(request.param)


def _widths_called(eng, monkeypatch):
    """Record every chunk call's width and its segments' (slot, generation, start, end)."""
    calls = []
    chunk_call = eng._chunk_call

    def spy(width, segs):
        calls.append((width, [(i, eng._slot_gen[i], start, end) for i, _r, _f, start, end in segs]))
        return chunk_call(width, segs)

    monkeypatch.setattr(eng, "_chunk_call", spy)
    return calls


def _prompt(cfg, n, seed=0):
    return np.random.default_rng(seed + n).integers(1, cfg.vocab_size, n).tolist()


def test_a_fixed_chunk_is_built_as_the_ladders_executables_side_by_side(laddered):
    """1,024, 512 and 256, each a compiled executable (not a jitted function
    that would compile on its first call) when ``__init__`` returns, compiled
    on threads of their own beside the main one, which compiled the decode
    program and then made the weights."""
    eng = laddered.eng
    assert eng._ladder == [256, 512, 1024]
    assert sorted(eng._prefill_chunk_fn) == eng._ladder
    assert all(isinstance(x, jax.stages.Compiled) for x in eng._prefill_chunk_fn.values())
    assert laddered.compiled.get("_chunk") == 3 and laddered.compiled.get("_decode") == 1
    decode_thread, *chunk_threads = laddered.aot_threads
    assert decode_thread == laddered.main_thread and len(chunk_threads) == 3
    assert laddered.main_thread not in chunk_threads and len(set(chunk_threads)) > 1


@pytest.mark.parametrize("lens, width", [
    ([5], 256), ([200], 256), ([256], 256), ([257], 512), ([100, 100], 256), ([300, 200], 1024),
    ([300, 100], 512), ([600], 1024), ([1024], 1024), ([1025], None), ([600, 500], None),
])
def test_a_call_goes_at_the_narrowest_width_whose_tiles_hold_it(laddered, lens, width):
    """Segments are padded to tiles of 64 at every width of this ladder: the
    narrowest that holds them, the widest when none narrower does, None past it."""
    assert laddered.eng._chunk_width(lens) == width


def test_one_width_when_a_half_would_lie_under_the_weights_width():
    built = _Built("dense", prefill_chunk=256)
    assert built.eng._ladder == [256] and list(built.eng._prefill_chunk_fn) == [256]
    assert built.compiled.get("_chunk") == 1
    assert [built.eng._chunk_width(lens) for lens in ([5], [256], [257])] == [256, 256, None]


def test_the_served_path_compiles_nothing_at_any_width(laddered, monkeypatch):
    """A first live call at EACH width of the ladder, a carried last chunk
    among them, leaves the process's compile count where the build left it:
    the build ran every executable once, and an executable cannot compile."""
    eng, cfg = laddered.eng, laddered.cfg
    calls = _widths_called(eng, monkeypatch)
    before = compile_tracker.snapshot()["compiles"]
    # (A dense model's prompts under the chunk are whole-prompt programs, compiled as met.)
    lens, widths = (((5, 300, 600, 1024 + 40), [256, 512, 1024, 1024, 256]) if eng._state_pools
                    else ((1024 + 40, 1024 + 300), [1024, 256, 1024, 512]))
    for n in lens:
        eng.generate_batch([_prompt(cfg, n)], 2)
    assert [w for w, _segs in calls] == widths
    assert compile_tracker.snapshot()["compiles"] == before


@pytest.mark.parametrize("lens", [(5, 300, 600, 1100, 1500), (1024 + 300, 40, 1024 + 124)],
                         ids=["short_and_carried", "last_chunks_of_a_half_and_a_quarter"])
def test_the_ladder_serves_the_tokens_of_the_one_widest_width(laddered, monkeypatch, lens):
    """The same requests through an engine whose ladder is forced to its one
    widest width (every call 1,024 wide, as before) and through the ladder:
    the same tokens, greedy, a long prompt's carried last chunk at a narrower
    width included; every assignment's segments cover its prompt once, in order,
    so no position runs twice; and the counter is the widths the calls had."""
    cfg = laddered.cfg
    prompts = [_prompt(cfg, n, seed=3) for n in lens]
    asked = [7, 4, 9, 5, 6][:len(lens)]

    def serve(eng):
        reqs = [eng.add_request(pr, m) for pr, m in zip(prompts, asked)]
        while eng.active_count() or eng.waiting:
            eng.step()
        return [r.generated for r in reqs]

    with monkeypatch.context() as mp:
        mp.setattr(llm_engine, "_chunk_ladder", lambda chunk, bs: [chunk])
        widest = laddered.build()
    assert widest._ladder == [1024]
    wide_calls = _widths_called(widest, monkeypatch)
    want = serve(widest)
    assert {w for w, _ in wide_calls} == {1024}
    eng = laddered.eng
    calls = _widths_called(eng, monkeypatch)
    before = dict(eng.stats)
    eng.recorder.steps.clear()
    assert serve(eng) == want and [len(g) for g in want] == asked
    widths = [w for w, _ in calls]
    assert min(widths) < 1024 and set(widths) <= set(eng._ladder)
    at = {}
    for _width, segs in calls:
        for slot, gen, start, end in segs:
            assert start == at.get((slot, gen), 0), (slot, gen, start)
            at[(slot, gen)] = end
    # A dense model's short prompts are whole-prompt programs: only the chunked ones are here.
    chunked = sorted(n for n in lens if eng._state_pools or n > 1024)
    assert sorted(at.values()) == chunked
    moved = {k: eng.stats[k] - before[k] for k in
             ("prefill_width_tokens", "prefill_tile_queries", "prefill_live_queries", "prefill_chunks")}
    assert moved["prefill_width_tokens"] == sum(widths) and moved["prefill_chunks"] == len(widths)
    assert moved["prefill_live_queries"] == sum(chunked)
    assert moved["prefill_live_queries"] <= moved["prefill_tile_queries"] <= moved["prefill_width_tokens"]
    assert moved["prefill_width_tokens"] < widest.stats["prefill_width_tokens"] == 1024 * len(wide_calls)
    assert sum(step["chunk_width"] for step in eng.recorder.steps) == sum(widths)
    assert eng.report_state()["prefill"]["width_tokens"] == eng.stats["prefill_width_tokens"]


@pytest.mark.parametrize("which", ["dense", "hybrid"])
def test_an_engine_without_a_fixed_chunk_builds_and_calls_what_it_did(which, monkeypatch):
    """No ladder, no thread, no chunk program compiled at build: the chunk
    program is the jitted function that compiles at each bucket it meets, and
    the counter of widths counts those buckets."""
    built = _Built(which, prefill_chunk=0, enable_prefix_cache=which == "dense")
    eng = built.eng
    assert eng._ladder == [] and "_chunk" not in built.compiled and built.compiled.get("_decode") == 1
    assert built.aot_threads == [built.main_thread]  # the decode program alone, here
    assert eng._prefill_chunk_fn._cache_size() == 0
    calls = _widths_called(eng, monkeypatch)
    doc = _prompt(built.cfg, 32)
    eng.generate_batch([doc], 2)
    eng.generate_batch([doc + _prompt(built.cfg, 5)], 2)  # dense: a suffix behind a hit of two blocks
    widths = [w for w, _ in calls]
    assert widths and set(widths) <= set(eng._widths)
    assert eng._prefill_chunk_fn._cache_size() == len(set(widths))
    assert eng.stats["prefill_width_tokens"] == sum(widths)
