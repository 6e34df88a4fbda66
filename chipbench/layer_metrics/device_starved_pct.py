"""Of the window, the share in which the device had nothing queued while the
engine had work for it: ``stats['starved_us']`` (the scheduler thread's own
account: from the host's read of an output of the newest program call to the
return of the next call, whole microseconds, idleness for want of load kept
apart in ``unloaded_us``) over the window's seconds. It is taken over the
whole window with tracing on or off, where the trace's idle share is of 4 s.

A program without the counter reads a LOWER BOUND from the step ring: over
the steps that did not overlap their window (``overlapped == 0``), the
milliseconds of ``dispatch`` and ``emit``, which then run on an empty queue
from end to end. The part of ``admit`` before its first program call and the
time between two steps are missing from it."""
LAYER = "Engine scheduler"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "program_counter"


def read(facts: dict):
    eng, seconds = facts["engine"], facts["seconds"]
    if not seconds > 0:
        return None
    if "starved_us" in eng["stats"]:
        return 100.0 * eng["stats"]["starved_us"] / (seconds * 1e6)
    return 100.0 * sum(s["dispatch_ms"] + s["emit_ms"] for s in eng["steps"]
                       if not s["overlapped"]) / (seconds * 1e3)
