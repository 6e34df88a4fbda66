"""Per step, the worker's wall time less the time the device was busy, over the
traced steps: what the loop (batch from the host, dispatch, the wait) adds."""
LAYER = "Train loop"
UNIT, MOVES, SOURCE = "ms", "train_tokens_per_s_per_chip", "device_trace"


def read(facts: dict):
    t = facts.get("trace")
    if not t:
        return None
    return (t["step_wall_s"] - t["busy_s"]) * 1e3 / t["steps"]
