"""Device time of the Pallas flash-attention kernels (forward, dQ, dK/dV) over
the device time of the step program. ``KERNELS`` finds them among the trace's
operations: the program gives its ``pallas_call``s no name, so the trace calls
them after whatever scope they were traced in (``closed_call.10``,
``checkpoint.22``); what they share is the custom call's target."""
from chipbench.layer_metrics import train_step_ms
from chipbench.trace_reduce import seconds_matching

LAYER = "Flash attention kernels"
UNIT, MOVES, SOURCE = "%", "train_tokens_per_s_per_chip", "device_trace"
KERNELS = r'custom_call_target="tpu_custom_call"'


def read(facts: dict):
    step_s = train_step_ms.step_seconds(facts)
    if step_s is None:
        return None
    seconds, runs = seconds_matching(facts["trace"]["ops"], KERNELS)
    return 100.0 * seconds / (step_s * facts["trace"]["steps"]) if runs else None
