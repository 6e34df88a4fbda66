"""What an admission costs the device: over the steps that began a prefill
(``prefills > 0``), the median of the step field ``starved_admit_ms``, the
milliseconds of ``engine.admit`` (its parts: plan, build, key, launch) during
which nothing was queued: as a rule what precedes the return of the step's
first prefill or chunk call, since the rest runs under that program.

A program without the field reads the median of ``admit_ms`` over the same
steps: an UPPER BOUND, the whole phase whether or not the device had work."""
import statistics

LAYER = "Engine scheduler"
UNIT, MOVES, SOURCE = "ms", "tpot_p95_ms", "program_counter"


def read(facts: dict):
    steps = [s for s in facts["engine"]["steps"] if s["prefills"] > 0]
    if not steps:
        return None
    return statistics.median(s.get("starved_admit_ms", s["admit_ms"]) for s in steps)
