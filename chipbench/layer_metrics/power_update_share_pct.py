"""Device time of the power retention state update kernel (``power_state_update``)
over the device time of the decode-window program (``jit__decode``) in the traced
window."""
from chipbench.layer_metrics import _power_retention as P
from chipbench.layer_metrics import decode_step_ms
from chipbench.trace_reduce import seconds_matching

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    seconds, calls = P.kernel_seconds(facts)
    if not calls:
        return None
    program, runs = seconds_matching(facts["trace"]["modules"], decode_step_ms.PROGRAM)
    return 100.0 * seconds / program if runs else None
