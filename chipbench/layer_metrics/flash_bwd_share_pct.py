"""Device time of the two backward flash kernels (dQ, dK/dV) over the device
time of the step program. ``BACKWARD`` finds them by the names the program
gives its ``pallas_call``s (the trace calls a custom call after the innermost
scope of its name stack): by name alone, because an operation's detail names
its operands, and the kernels' consumers would match too. A program from
before the kernels were named has no such operation: there the backward
kernels are the Pallas kernels (``flash_share_pct.KERNELS``) less the forward
one (``flash_fwd_roofline.FORWARD``, told by its three operands)."""
from chipbench.layer_metrics import train_step_ms
from chipbench.layer_metrics.flash_fwd_roofline import FORWARD
from chipbench.layer_metrics.flash_share_pct import KERNELS
from chipbench.trace_reduce import seconds_matching

LAYER = "Flash attention kernels"
UNIT, MOVES, SOURCE = "%", "train_tokens_per_s_per_chip", "device_trace"
BACKWARD = r"flash_bwd_(dq|dkv)"


def read(facts: dict):
    step_s = train_step_ms.step_seconds(facts)
    if step_s is None:
        return None
    ops = facts["trace"]["ops"]
    names_only = {name: {"seconds": row["seconds"], "count": row["count"]}
                  for name, row in ops.items()}
    seconds, runs = seconds_matching(names_only, BACKWARD)
    if not runs:
        kernels_s, runs = seconds_matching(ops, KERNELS)
        seconds = kernels_s - seconds_matching(ops, FORWARD)[0]
    return 100.0 * seconds / (step_s * facts["trace"]["steps"]) if runs else None
