"""The readers that PR 25 added, each on hand-made ``facts`` with a known
answer, on the facts of a program from before the spans, counters and kernel
names they read, and on facts with nothing to read.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q
"""
from __future__ import annotations

import pytest

from chipbench import manifest as M

FWD = ('bf16[96,4096,128]{2,1,0} custom-call(%a.1, %b.2, %c.3), '
       'custom_call_target="tpu_custom_call"')
BWD = ('bf16[96,4096,128]{2,1,0} custom-call(%a.1, %b.2, %c.3, %d.4, %e.5, %f.6), '
       'custom_call_target="tpu_custom_call"')


def op(seconds: float, detail: str = "", count: int = 2) -> dict:
    return {"seconds": seconds, "self_seconds": seconds, "count": count, "detail": detail}


def train_facts(ops: dict) -> dict:
    # two steps of 1.0 s each
    return {"trace": {"steps": 2, "modules": {"jit_step": op(2.0)}, "ops": ops}}


def serve_facts(stats: dict, gaps=None) -> dict:
    return {"engine": {"stats": stats}, "trace": None if gaps is None else {"idle_gaps": gaps}}


NAMED = train_facts({
    "flash_fwd.3": op(0.10, FWD), "flash_bwd_dq.1": op(0.06, BWD), "flash_bwd_dkv.1": op(0.08, BWD),
    # a consumer names the kernel among its operands: not the kernel
    "fusion.7": op(0.50, "bf16[3,4096,4096]{2,1,0} fusion(%flash_bwd_dq.1), kind=kLoop"),
})
UNNAMED = train_facts({
    "closed_call.10": op(0.10, FWD), "checkpoint.22": op(0.06, BWD), "checkpoint.23": op(0.08, BWD),
    "fusion.7": op(0.50, "bf16[3,4096,4096]{2,1,0} fusion(%checkpoint.22), kind=kLoop"),
})
COUNTED = {"steps": 20, "spec_windows": 3, "spec_blocked_idle": 1, "spec_blocked_admission": 4,
           "spec_blocked_dirty_cur": 2, "spec_blocked_finishing": 5}
GAPS = [["python3:engine.emit", 0.006], ["python3:engine.admit", 0.003],
        ["python3:np.asarray_jax.Array_", 0.002], ["unattributed", 0.009]]

CASES = [
    # reader, facts, answer
    ("flash_bwd_share_pct", NAMED, 100.0 * 0.14 / 2.0),
    ("flash_bwd_share_pct", UNNAMED, 100.0 * 0.14 / 2.0),  # before the names: by operands
    ("flash_bwd_share_pct", train_facts({"fusion.7": op(0.5)}), None),  # no kernel ran
    ("flash_bwd_share_pct", {"trace": None}, None),
    ("overlap_window_pct", serve_facts(COUNTED), 100.0 * 3 / 15),
    # before the reasons were counted: every window dispatched is found in flight once
    ("overlap_window_pct", serve_facts({"steps": 20, "spec_windows": 3}), 100.0 * 3 / 20),
    ("overlap_window_pct", serve_facts({k: 0 for k in COUNTED}), None),  # no window at all
    ("overlap_window_pct", serve_facts({}), None),
    ("idle_attributed_pct", serve_facts({}, GAPS), 100.0 * 0.009 / 0.020),
    ("idle_attributed_pct", serve_facts({}, GAPS[2:]), 0.0),  # before the spans: none to find
    ("idle_attributed_pct", serve_facts({}, []), None),
    ("idle_attributed_pct", serve_facts({}), None),  # no trace
]


@pytest.mark.parametrize("name, facts, answer", CASES,
                         ids=[f"{c[0]}-{i}" for i, c in enumerate(CASES)])
def test_reader_on_facts_with_a_known_answer(name, facts, answer):
    got = M.reader("layer_metrics", name).read(facts)
    assert got is None if answer is None else got == pytest.approx(answer)


def test_each_case_above_is_a_metric_of_the_manifest():
    declared = {m["name"] for m in M.load_manifest()["per_layer"]}
    assert {c[0] for c in CASES} <= declared
