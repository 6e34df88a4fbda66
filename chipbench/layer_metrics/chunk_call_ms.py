"""Device time of one chunk call: the traced time of the chunk program over its
runs (where a cell has several widths, a mean over them). ``PROGRAM`` is the
pattern that finds the program among the trace's ``XLA Modules``."""
from chipbench.trace_reduce import seconds_matching

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "ms", "tpot_p95_ms", "device_trace"


PROGRAM = r"jit__chunk"


def read(facts: dict):
    if not facts.get("trace"):
        return None
    seconds, runs = seconds_matching(facts["trace"]["modules"], PROGRAM)
    return 1e3 * seconds / runs if runs else None
