"""Mean number of occupied slots over the scheduler's steps of the window that had
any, over ``max_batch`` (the recorder's step ring)."""
LAYER = "Engine scheduler"
UNIT, MOVES, SOURCE = "%", "serve_tokens_per_s", "program_counter"


def read(facts: dict):
    active = [s["active"] for s in facts["engine"]["steps"] if s["active"] > 0]
    if not active:
        return None
    return 100.0 * sum(active) / len(active) / facts["engine"]["max_batch"]
