"""Device time of one run of the step program (``PROGRAM`` among ``XLA Modules``)."""
from chipbench.trace_reduce import seconds_matching

LAYER = "Compiled train step"
UNIT, MOVES, SOURCE = "ms", "train_tokens_per_s_per_chip", "device_trace"


PROGRAM = r"jit_step"


def step_seconds(facts: dict):
    if not facts.get("trace"):
        return None
    seconds, runs = seconds_matching(facts["trace"]["modules"], PROGRAM)
    return seconds / runs if runs else None


def read(facts: dict):
    s = step_seconds(facts)
    return None if s is None else s * 1e3
