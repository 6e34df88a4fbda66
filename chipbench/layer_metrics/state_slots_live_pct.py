"""Rows of state kept by slot that the dispatched windows read and wrote, over the
rows the engine held (``stats['state_slots_live'] / stats['state_slots_table']``):
what the bytes of a decode step's state scale with."""
LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "program_counter"


def read(facts: dict):
    s = facts["engine"]["stats"]
    if not s.get("state_slots_table"):
        return None
    return 100.0 * s["state_slots_live"] / s["state_slots_table"]
