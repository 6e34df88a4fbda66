"""Of the seconds in the trace's longest idle gaps, the share laid to a span of
the engine's scheduler thread: a gap is labelled with the host event that
covers most of it, if that is at least half (``trace_reduce._attribute``), and
``SPANS`` finds the engine's phases (``tracing.phase``: ``engine.harvest_wait``,
``engine.emit``, ``engine.admit``, ``engine.prefill_wait``,
``engine.dispatch``) among the labels. A program without the spans reads 0."""
import re

LAYER = "Engine scheduler"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"
SPANS = r":engine\."


def read(facts: dict):
    gaps = (facts.get("trace") or {}).get("idle_gaps")
    total = sum(seconds for _label, seconds in gaps or ())
    if not total > 0:
        return None
    rx = re.compile(SPANS)
    return 100.0 * sum(seconds for label, seconds in gaps if rx.search(label)) / total
