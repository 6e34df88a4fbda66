"""Device time in the prefill and chunk programs over the device's busy time."""
from chipbench.trace_reduce import seconds_matching

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


PROGRAMS = r"jit__prefill|jit__chunk"


def read(facts: dict):
    if not facts.get("trace"):
        return None
    seconds, _ = seconds_matching(facts["trace"]["modules"], PROGRAMS)
    return 100.0 * seconds / facts["trace"]["busy_s"]
