"""The least time one decode step of the power retention decoder could take on this
chip's memory over the time it took: bytes the step must move
(``work_power_retention.decode_step_bytes``: every matrix once, the head too, and
each LIVE slot's float32 state read and written at the least layout; the model has
no cache by token) over the peak bandwidth, over ``decode_step_ms``. Live slots are
the program's own count a dispatched window (``state_slots_live``) over the traced
seconds, which the step time is of too. The share is of the whole step."""
from chipbench import work_power_retention as work
from chipbench.layer_metrics import _hybrid_ssm as H
from chipbench.layer_metrics import _power_retention as P
from chipbench.layer_metrics import decode_step_ms
from chipbench.peaks import peaks_for

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    from chipbench.weights_power_retention import Dims

    step_s = decode_step_ms.step_seconds(facts)
    slots = H.live_slots(facts)
    if step_s is None or slots is None or not P.is_mine(facts):
        return None
    least = (work.decode_step_bytes(Dims.from_config(facts["dims"]), slots)
             / peaks_for(facts["peaks_of"])["hbm_bytes_per_s"])
    print(f"[chipbench] power decode step: {step_s * 1e3:.3f} ms at {slots:.1f} live slots; "
          f"least by bytes {least * 1e3:.3f} ms", flush=True)
    return 100.0 * least / step_s
