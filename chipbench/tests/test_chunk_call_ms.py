"""The reader ``chunk_call_ms`` on hand-made ``facts`` with a known answer, on a
trace in which no chunk call ran, and on facts with no trace.

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q
"""
from __future__ import annotations

import pytest

from chipbench import manifest as M


def op(seconds: float, count: int) -> dict:
    return {"seconds": seconds, "self_seconds": seconds, "count": count, "detail": ""}


DECODE = {"jit__decode(3)": op(0.5, count=3)}
CASES = [
    # three chunk calls of two widths beside a decode window: a mean over the calls
    ({"trace": {"modules": {"jit__chunk(1)": op(0.144, count=2), "jit__chunk(2)": op(0.03, count=1),
                            **DECODE}}}, 58.0),
    ({"trace": {"modules": DECODE}}, None),  # no call ran
    ({"trace": None}, None),
    ({}, None),
]


@pytest.mark.parametrize("facts, answer", CASES, ids=["two_widths", "no_call", "no_trace", "no_facts"])
def test_chunk_call_ms_on_facts_with_a_known_answer(facts, answer):
    got = M.reader("layer_metrics", "chunk_call_ms").read(facts)
    assert got is None if answer is None else got == pytest.approx(answer)


def test_chunk_call_ms_is_a_metric_of_the_manifest_in_the_cells_that_chunk():
    (metric,) = [m for m in M.load_manifest()["per_layer"] if m["name"] == "chunk_call_ms"]
    assert metric["workloads"] == ["serve-sessions-shared", "serve-docqa-latent", "serve-chat-busy-ssm"]
    assert (metric["unit"], metric["moves"], metric["source"]) == ("ms", "tpot_p95_ms", "device_trace")
