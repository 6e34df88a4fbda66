"""The sessions cell replayed on the host: what the scheduler's policies
(eviction, registration, admission order, suffix buckets) do to the prefix
cache under the benchmark's own closed loop, with no device program.

``replay`` drives the REAL ``LLMEngine`` scheduler (``step``: harvest, emit,
``_admit``, ``_free_slot``, ``_finish_prefill``, ``_ensure_decode_blocks``,
dispatch) at the paged and engine settings of
``chipbench/configs/mistral-7b-serve-l24.json`` (1,601 blocks of 16, 32
slots, window 10, overlap, prefix cache) under the traffic of
``chipbench/traffic/sessions-shared.json`` made by
``chipbench/generators/sessions.py`` (48 clients, 4 system prompts of 512,
4 turns). Only the three compiled programs are stand-ins: they return
random tokens and note the width they were called at, the chunk program
(the packed signature) also the tiles each segment took. A client's next turn
joins the queue after the iteration in which its answer finished, as over
HTTP it arrives after that iteration's admission. It counts; it times
nothing, and no number from it is a device metric.

Three readings of the chip that it reproduces without being fitted to any,
at the phase of the cell's own window (``WINDOW``: 50 iterations of ramp,
then 137 = 40 s of ~290 ms), seeds 3900000022 and 3900000011, replay
against chip (ledger, PR 30, and my chip runs, PR 31, four same-seed pairs):

- hit share: plain LRU (PR 30's tree) 72.9-75.5% against ``prefix_hit_pct``
  72.6-72.9 and 71.4-73.6; this eviction 80.7-80.8% against 79.2-80.9;
- prefills an iteration: 3.2 against 399-410 in 125-128 windows = 3.2;
- evicted blocks an iteration: plain LRU 40.0-44.8 against 5,644-5,905 in
  125-128 windows = 45-47; this eviction 30.8-31.5 against 4,311-4,604 in
  138-142 = 31-33.

Since PR 39 a given-back slot publishes its answer's blocks and the
suffixes of one iteration share a packed chunk call. The replay read, and
the chip then read (PERF.md section 6, PR 39): hits 86.9-87.5% in the
window (88.8-89.1% later); 340-359 real prefill tokens an iteration in
1.19-1.27 program calls of 2.6-2.7 segments, 504-541 tokens wide in all
(widths 64: 7-10, 128: 28, 256: 31-40, 512: 80-81, 1,024: 11-18, 1,568:
0-3 of 163-174 calls), where one call a suffix made 3.2 calls.

Since PR 41 a step that launched a prefill dispatches its decode window
before it reads the prefill's tokens. The replay counts how often that
engages: 133-134 of the window's 137 iterations (the other 3-4 were
speculated), none flushed first, 2.94-2.96 mirrors shipped a window.

Under plain LRU one prefill call in eight was then a re-prefill over 512
tokens wide (11-16% of calls; 2.0-2.3% before PR 39; 16-18 segments of
439-445, 3.6-4.0%, in the window now that hits are counted on answers
too). What it also says, and no chip
run has checked: the fault is a transient of 48 conversations started
together. Over iterations 100-400 plain LRU reads 77.4-78.4% and 5.1-7.4%
wide calls; from iteration 400 on it takes the same victims as this
eviction, decision for decision (81.6% both). Use it to size the next
change to eviction, registration or bucket sizes before spending chip time.
"""
import json
import os

import numpy as np
import pytest

from chipbench.generators import sessions
from ray_tpu.models.paged import TRASH_BLOCK, PagedConfig
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.serve import llm_engine

_CHIPBENCH = os.path.join(os.path.dirname(__file__), "..", "chipbench")


def _cell():
    with open(os.path.join(_CHIPBENCH, "configs", "mistral-7b-serve-l24.json")) as f:
        conf = json.load(f)
    with open(os.path.join(_CHIPBENCH, "traffic", "sessions-shared.json")) as f:
        traffic = json.load(f)["params"]
    return conf, traffic


class _Programs:
    """Stand-ins with the signatures of the engine's three programs."""

    def __init__(self, seed: int, vocab: int, window: int):
        self.rng = np.random.default_rng(seed)
        self.vocab, self.window = vocab, window
        self.calls = []  # one entry a prefill or chunk call: (padded width, [a segment's tiles' width])

    def decode(self, params, cur, cache, tables, lens, temps, key):
        seq = self.rng.integers(0, self.vocab, (self.window, len(cur)), dtype=np.int32)
        return seq, seq[-1], np.asarray(lens) + self.window, cache

    @staticmethod
    def _put(cur, slots, toks):
        """``cur.at[slots].set(toks, mode="drop")`` on the host."""
        cur, slots = np.array(cur), np.atleast_1d(slots)
        keep = slots < len(cur)
        cur[slots[keep]] = np.atleast_1d(toks)[keep]
        return cur

    def prefill(self, params, toks, cache, block_row, len_slot, temp, key, cur):
        self.calls.append((toks.shape[1], [toks.shape[1]]))
        tok = np.int32(self.rng.integers(0, self.vocab))
        return tok, cache, self._put(cur, len_slot[1], tok)

    def chunk(self, params, toks, cache, table_rows, chunk_row, per_tile, temps, key, cur):
        """The packed signature. A segment's tiles follow one another under
        one slot's table row; a tile nobody uses lies on the trash block and
        counts no real query."""
        starts, _last_idx, slot_of, real, _state_of = per_tile
        live = real > 0
        assert (live == (table_rows != TRASH_BLOCK).any(axis=1)).all()
        tile = toks.shape[1] // len(starts)
        goes_on = (table_rows[1:] == table_rows[:-1]).all(axis=1) & (
            starts[1:] == starts[:-1] + tile)
        first = np.flatnonzero(live & ~np.concatenate([[False], goes_on]))
        ends = np.concatenate([first[1:], [int(live.sum())]])  # live tiles come first
        self.calls.append((toks.shape[1], [int(t) * tile for t in ends - first]))
        out = self.rng.integers(0, self.vocab, len(starts), dtype=np.int32)
        return out, cache, self._put(cur, slot_of, out)


def replay(seed: int, warmup: int, iterations: int, monkeypatch) -> dict:
    conf, traffic = _cell()
    eng_conf, vocab = conf["engine"], conf["vocab_size"]
    progs = _Programs(seed, vocab, eng_conf["decode_window"])
    monkeypatch.setattr(llm_engine, "init_paged_cache", lambda cfg, p: {})
    monkeypatch.setattr(
        llm_engine.LLMEngine, "_build_programs",
        lambda self, params: (progs.decode, progs.prefill, progs.chunk, params))
    eng = llm_engine.LLMEngine(
        None, TransformerConfig.tiny(), PagedConfig(**conf["paged"]),
        decode_window=eng_conf["decode_window"], overlap=eng_conf["overlap"],
        enable_prefix_cache=eng_conf["enable_prefix_cache"],
        prefill_chunk=eng_conf["prefill_chunk"])
    plan = sessions.plan(traffic, seed, 40.0, vocab)
    next_session = iter(range(10**9))

    class Client:
        def __init__(self):
            self.turns, self.req = [], None

        def speak(self):
            if self.req is not None:  # the answer joins the history
                self.history = self.history + self.req.generated
            if not self.turns:
                s = plan["session"](next(next_session))
                self.history = list(plan["systems"][s["system"]])
                self.turns = list(s["turns"])
            turn = self.turns.pop(0)
            self.history = self.history + turn["user"]
            self.req = eng.add_request(self.history, turn["max_new_tokens"])
            assert self.req.error is None, self.req.error

    clients = [Client() for _ in range(plan["clients"])]
    for c in clients:
        c.speak()
    base = None
    for it in range(warmup + iterations):
        if it == warmup:
            base, progs.calls = dict(eng.stats), []
            eng.recorder.steps.clear()
        eng.step()
        for c in clients:
            if c.req.remaining <= 0:
                c.speak()
    d = {k: eng.stats[k] - base[k] for k in base if isinstance(base[k], int)}
    widths = np.asarray([w for w, _ in progs.calls])
    segments = np.asarray([s for _, each in progs.calls for s in each])
    assert len(segments) == d["prefills"]  # the stand-ins count what the engine counts
    assert len(widths) - d["prefill_chunks"] == d["prefills"] - d["prefill_segments"]  # full prefills
    return {
        "hit_pct": 100.0 * d["prefix_hit_tokens"] / d["prefix_lookup_tokens"],
        "prefills_per_iteration": d["prefills"] / iterations,
        "evictions_per_iteration": d["prefix_evictions"] / iterations,
        "real_tokens_per_iteration": d["prompt_tokens"] / iterations,
        "padded_tokens_per_iteration": float(widths.sum()) / iterations,
        "calls": len(widths),
        "calls_per_iteration": len(widths) / iterations,
        "segments_per_chunk_call": d["prefill_segments"] / d["prefill_chunks"],
        # A conversation re-prefilled whole: a segment over 512 tokens wide.
        "wide_segments": int((segments > 512).sum()),
        "by_width": {int(w): int((widths == w).sum()) for w in np.unique(widths)},
        "preemptions": d["preemptions"],
        "widths": list(eng._widths),
        "stats": d,
        "steps": list(eng.recorder.steps),  # the last 256 at most
    }


WINDOW = dict(warmup=50, iterations=137)  # the cell's own: 15 s of ramp, 40 s measured
LATER = dict(warmup=100, iterations=300)


@pytest.mark.parametrize("phase,hit_floor,wide_limit", [(LATER, 88.0, 1.6), (WINDOW, 85.0, 4.1)],
                         ids=["iterations_100_400", "the_cells_window"])
@pytest.mark.parametrize("seed", [3900000022, 3900000011])
def test_sessions_replay_hit_share_and_wide_suffixes(seed, phase, hit_floor, wide_limit,
                                                     monkeypatch):
    """Eviction that knows the queue keeps the follow-up's history, and a
    given-back slot leaves its answer there too: of the prompt tokens
    looked up, >= 85% hit in the cell's window (read: 86.9-87.5; with
    answers not cached the traffic's ceiling was 82%), >= 88% later. What
    the hits leave reaches the device in <= 1.5 program calls an iteration
    (read: 1.19-1.27, of 2.6-2.7 segments a chunk call), each at a width
    the benchmark's warm-up has played. A conversation re-prefilled whole
    (a segment over 512 tokens) stays as rare as it was: 16-18 of 439-445
    in the window, where the pool fills for the first time, 9-14 of ~960
    later. Plain LRU read 72.9-75.5% hits and 11.4-16.0% wide calls."""
    from chipbench.drivers.serve import warm_requests

    got = replay(seed, monkeypatch=monkeypatch, **phase)
    print(json.dumps({k: v for k, v in got.items() if k not in ("stats", "steps")}), got["stats"])
    assert got["preemptions"] == 0
    assert got["hit_pct"] >= hit_floor, got
    assert got["calls_per_iteration"] <= 1.5, got
    assert got["segments_per_chunk_call"] >= 2.0, got
    assert 100.0 * got["wide_segments"] / got["stats"]["prefills"] <= wide_limit, got
    assert got["stats"]["prefix_published_blocks"] > 0
    # Nearly every iteration admits (94-99% on the chip, PERF.md section 5), and
    # each of those queues its window behind the chunk call, first tokens unread
    # (read: 133-134 of 137 in the window, 292-296 of 300 later; the rest were
    # speculated). No preemption, so none had to read first.
    assert got["stats"]["windows_behind_prefill"] / got["stats"]["steps"] >= 0.9, got["stats"]
    assert got["stats"]["prefill_flushed_first"] == 0
    # Every width was played once, through the served path, before the window:
    # a suffix of just that many tokens after the two blocks the warm-up shares.
    conf, _ = _cell()
    with open(os.path.join(_CHIPBENCH, "traffic", "sessions-shared.json")) as f:
        warm = json.load(f)["warm"]
    played = [p for p, _n in warm_requests(warm, conf["paged"], conf["vocab_size"], margin=22)]
    shared = min(played, key=len)  # the two blocks its chunk requests share
    warmed = {min(w for w in got["widths"] if w >= len(p) - len(shared))
              for p in played if len(p) > len(shared) and p[:len(shared)] == shared}
    assert set(got["by_width"]) <= warmed, (got["by_width"], warmed)
    # The fallback never engages here: ended sessions always leave a chain
    # that nobody waits for.
    assert got["stats"]["prefix_evictions_wanted"] == 0
    assert got["stats"]["prefix_evictions_spared"] > 0


def test_sessions_replay_the_starvation_account_closes(monkeypatch):
    """Over the cell's own window every microsecond the device had nothing
    queued lies in exactly one bucket, all of it is the host's doing (the
    closed loop never leaves the engine without work), and a step's share
    fits inside its wall time less the two waits. The stand-ins return at
    once, so this counts host time only: no device metric."""
    got = replay(3900000022, monkeypatch=monkeypatch, **WINDOW)
    d, steps = got["stats"], got["steps"]
    buckets = ["starved_us_" + where for where in llm_engine._STARVED]
    assert sum(d[k] for k in buckets) == d["starved_us"] > 0
    assert d["unloaded_us"] == 0
    assert len(steps) == WINDOW["iterations"]
    in_steps = sum(r["starved_ms"] for r in steps) * 1e3
    assert d["starved_us"] - d["starved_us_between"] - 1.0 <= in_steps <= d["starved_us"] + 1.0
    for r in steps:
        assert r["starved_ms"] <= r["wall_ms"] - r["harvest_wait_ms"] - r["prefill_wait_ms"] + 1.0, r
        assert r["starved_admit_ms"] + r["starved_dispatch_ms"] <= r["starved_ms"] + 1e-9
        assert sum(r[f"{name}_ms"] for name in llm_engine._PHASES) <= r["wall_ms"]
    # the prefill calls were launched on an empty queue (4 of 137 windows were
    # speculated); a decode window queued behind one of them starves nobody,
    # whatever its dispatch costs the host
    assert d["starved_us_admit_launch"] > 0
    assert d["starved_us_admit_plan"] > 0 and d["starved_us_admit_build"] > 0
    behind = [r for r in steps if r["behind_prefill"]]
    assert len(behind) == d["windows_behind_prefill"] >= 0.9 * len(steps)
    assert all(r["starved_dispatch_ms"] == 0.0 and r["dispatch_ms"] > 0.0 for r in behind)
