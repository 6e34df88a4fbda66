"""What a decode window's dispatch costs the device: over the steps that
dispatched one (``dispatch_ms > 0``), the median of the step field
``starved_dispatch_ms``, the milliseconds of ``engine.dispatch`` (its parts:
blocks, key, ship, launch) during which nothing was queued. A window
dispatched behind one still in flight costs 0.

A program without the field reads, over the same steps, the median of
``dispatch_ms`` where the step did not overlap (``overlapped == 0``) and 0
where it did: a synchronous dispatch runs on an empty queue from end to end,
so there the two are the same number."""
import statistics

LAYER = "Engine scheduler"
UNIT, MOVES, SOURCE = "ms", "tpot_p95_ms", "program_counter"


def read(facts: dict):
    steps = [s for s in facts["engine"]["steps"] if s["dispatch_ms"] > 0]
    if not steps:
        return None
    return statistics.median(
        s["starved_dispatch_ms"] if "starved_dispatch_ms" in s
        else (0.0 if s["overlapped"] else s["dispatch_ms"]) for s in steps)
