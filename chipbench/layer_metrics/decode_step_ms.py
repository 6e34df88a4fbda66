"""Device time of one decode step: the traced time of the decode-window program
over its runs and the window's steps. ``PROGRAM`` is the pattern that finds
the program among the trace's ``XLA Modules``."""
from chipbench.trace_reduce import seconds_matching

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "ms", "tpot_p95_ms", "device_trace"


PROGRAM = r"jit__decode"


def step_seconds(facts: dict):
    if not facts.get("trace"):
        return None
    seconds, runs = seconds_matching(facts["trace"]["modules"], PROGRAM)
    if not runs:
        return None
    return seconds / runs / facts["engine"]["decode_window"]


def read(facts: dict):
    s = step_seconds(facts)
    return None if s is None else s * 1e3
