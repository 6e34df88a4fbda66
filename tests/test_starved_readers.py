"""The three readers of the engine's starvation account
(``chipbench/layer_metrics/device_starved_pct.py``, ``starved_dispatch_ms.py``,
``starved_admit_ms.py``) on hand-made facts with a known answer: from the
counters a program with the account carries, and from the fields every program
since PR 25 has, which is what the parent of the PR that added them reads."""
import copy

import pytest

from chipbench import manifest as M
from chipbench import validate

NAMES = ["device_starved_pct", "starved_dispatch_ms", "starved_admit_ms"]


def _step(**over):
    base = {"overlapped": 0, "prefills": 0, "dispatch_ms": 0.0, "emit_ms": 0.0, "admit_ms": 0.0}
    return {**base, **over}


# Five steps of a 2 s window. With the account: what the engine counted.
STEPS = [
    _step(dispatch_ms=9.0, emit_ms=2.0, admit_ms=12.0, prefills=3,
          starved_dispatch_ms=9.0, starved_admit_ms=4.0),
    _step(dispatch_ms=8.0, emit_ms=1.0, overlapped=1,
          starved_dispatch_ms=0.0, starved_admit_ms=0.0),
    _step(dispatch_ms=11.0, emit_ms=3.0, admit_ms=8.0, prefills=1,
          starved_dispatch_ms=10.5, starved_admit_ms=6.0),
    _step(emit_ms=1.0, admit_ms=30.0, prefills=2,  # a prefill flush and no window
          starved_dispatch_ms=0.0, starved_admit_ms=5.0),
    _step(dispatch_ms=10.0, emit_ms=2.0, admit_ms=0.1,
          starved_dispatch_ms=9.5, starved_admit_ms=0.1),
]
WITH = {"seconds": 2.0, "engine": {"stats": {"starved_us": 150_000, "unloaded_us": 7}, "steps": STEPS}}
# the same run by a program from before the account
WITHOUT = copy.deepcopy(WITH)
del WITHOUT["engine"]["stats"]["starved_us"], WITHOUT["engine"]["stats"]["unloaded_us"]
for _s in WITHOUT["engine"]["steps"]:
    del _s["starved_dispatch_ms"], _s["starved_admit_ms"]


@pytest.mark.parametrize("name, facts, expected", [
    ("device_starved_pct", WITH, 100 * 0.150 / 2.0),
    # dispatch + emit of the four steps that did not overlap: 11 + 14 + 1 + 12 of 2,000 ms
    ("device_starved_pct", WITHOUT, 100 * 38.0 / 2000.0),
    # over the four steps that dispatched: median of 9, 0, 10.5, 9.5
    ("starved_dispatch_ms", WITH, 9.25),
    # the same steps' dispatch_ms, 0 where overlapped: median of 9, 0, 11, 10
    ("starved_dispatch_ms", WITHOUT, 9.5),
    # over the three steps that began a prefill: median of 4, 6, 5
    ("starved_admit_ms", WITH, 5.0),
    # their whole admit phase, an upper bound: median of 12, 8, 30
    ("starved_admit_ms", WITHOUT, 12.0),
], ids=lambda v: v if isinstance(v, str) else ("" if isinstance(v, float) else
                                                "counted" if v is WITH else "parent"))
def test_reader_on_known_facts(name, facts, expected):
    assert M.reader("layer_metrics", name).read(facts) == pytest.approx(expected)


@pytest.mark.parametrize("name", NAMES)
def test_reader_finds_nothing_in_an_empty_window(name):
    """No step to take a median over is nothing to read, never a 0 that
    would pass for a fast scheduler; the share of an empty window is 0."""
    facts = {"seconds": 2.0, "engine": {"stats": {}, "steps": []}}
    got = M.reader("layer_metrics", name).read(facts)
    assert got == (0.0 if name == "device_starved_pct" else None)


@pytest.mark.parametrize("name", NAMES)
def test_reader_is_a_declared_per_layer_metric_of_both_serving_cells(name):
    manifest = M.load_manifest()
    assert validate.check_manifest(manifest) == []
    entry = M.find(manifest["per_layer"], name, "per-layer metric")
    reader = M.reader("layer_metrics", name)
    assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
        entry["unit"], entry["source"], entry["layer"], entry["moves"])
    assert entry["better"] == "lower" and entry["source"] == "program_counter"
    # the two cells it was declared for, then the cells later PRs appended
    assert entry["workloads"][:2] == ["serve-chat-steady", "serve-sessions-shared"]
    assert reader.__doc__ and "without the" in reader.__doc__  # says what the parent reads
    for cell in entry["workloads"]:
        assert name in {m["name"] for m in M.metrics_for(manifest, cell, "per_layer")}
    # appended together, in this order, behind the entries accepted before them
    # (and before what later PRs appended in their turn)
    declared = [m["name"] for m in manifest["per_layer"]]
    at = declared.index(NAMES[0])
    assert at >= 19 and declared[at:at + len(NAMES)] == NAMES
