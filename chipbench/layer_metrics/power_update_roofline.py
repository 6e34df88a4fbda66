"""The power retention state update kernel's share of its roofline: the least
time the chip could take for one call (``work_power_retention.power_update_work``
at the live slots of the traced windows: their states read and written once at
the LEAST layout's 8,256 x 129 numbers a head against the memory's peak, or
thirteen operations an entry against the bf16 peak, whichever is larger: bytes,
by far) over the traced time of one call of ``power_state_update``. Both terms
are printed."""
from chipbench import work_power_retention as work
from chipbench.layer_metrics import _hybrid_ssm as H
from chipbench.layer_metrics import _power_retention as P
from chipbench.peaks import peaks_for

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    from chipbench.weights_power_retention import Dims

    seconds, calls = P.kernel_seconds(facts)
    slots = H.live_slots(facts)
    if not calls or slots is None or not P.is_mine(facts):
        return None
    flops, bytes_ = work.power_update_work(Dims.from_config(facts["dims"]), slots)
    peaks = peaks_for(facts["peaks_of"])
    by_flops, by_bytes = flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"]
    print(f"[chipbench] power_state_update: {seconds / calls * 1e6:.1f} us a call over {calls} calls; "
          f"least by operations {by_flops * 1e6:.1f} us, by bytes {by_bytes * 1e6:.1f} us "
          f"({slots:.1f} live slots)", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (seconds / calls)
