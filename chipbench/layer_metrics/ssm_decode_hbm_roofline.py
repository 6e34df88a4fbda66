"""The least time one decode step of the hybrid state-space decoder could take on
this chip's memory over the time it took: bytes the step must move
(``work_hybrid_ssm.decode_step_bytes``: every matrix once, each LIVE slot's float32
state read and written, K and V of the tokens really cached) over the peak
bandwidth, over ``decode_step_ms``. Live slots are the program's own count a
dispatched window (``state_slots_live``) over the traced seconds, which the step
time is of too; the share is of the whole step."""
from chipbench import work_hybrid_ssm as work
from chipbench.layer_metrics import _hybrid_ssm as H
from chipbench.layer_metrics import decode_step_ms
from chipbench.peaks import peaks_for

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    from chipbench.weights_hybrid_ssm import Dims

    step_s = decode_step_ms.step_seconds(facts)
    slots = H.live_slots(facts)
    if step_s is None or slots is None or "mamba_d_state" not in facts["dims"]:
        return None
    cached = H.cached_tokens(facts, slots)
    if cached is None:
        return None
    dims = Dims.from_config(facts["dims"])
    least = work.decode_step_bytes(dims, slots, cached) / peaks_for(facts["peaks_of"])["hbm_bytes_per_s"]
    print(f"[chipbench] ssm decode step: {step_s * 1e3:.3f} ms at {slots:.1f} live slots and "
          f"{cached:.0f} cached tokens; least by bytes {least * 1e3:.3f} ms", flush=True)
    return 100.0 * least / step_s
