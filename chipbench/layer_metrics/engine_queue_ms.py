"""From a request's arrival in the engine to the start of its prefill: the median
of ``queue_ms`` in the recorder's request ring over the window.
It moves the time to first token, which no bound could hold as an end-to-end
metric (see ``ttft_p95_ms_steady``): ``MOVES`` names the one the cell keeps."""
import statistics

LAYER = "Engine scheduler"
UNIT, MOVES, SOURCE = "ms", "tpot_p95_ms", "program_span"


def read(facts: dict):
    xs = [r["queue_ms"] for r in facts["engine"]["requests"] if r.get("queue_ms") is not None]
    return statistics.median(xs) if xs else None
