"""Time in collective operations during which nothing else runs on that device,
over the device time of the step program; mean over the chips."""
from chipbench.layer_metrics import train_step_ms

LAYER = "Mesh plans and collectives"
UNIT, MOVES, SOURCE = "%", "train_tokens_per_s_per_chip", "device_trace"


def read(facts: dict):
    t = facts.get("trace")
    if not t:
        return None
    step_s = train_step_ms.step_seconds(facts)
    if step_s is None:
        return None
    return 100.0 * t["collective_exposed_s"] / (step_s * t["steps"])
