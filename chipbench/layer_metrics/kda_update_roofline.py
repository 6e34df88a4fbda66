"""The KDA state update kernel's share of its roofline: the least time the chip
could take for one call (``work_kda_moe.kda_update_work`` at the live slots of the
traced windows: their states read and written once against the memory's peak, or
seven operations an entry against the bf16 peak, whichever is larger: bytes, by
far) over the traced time of one call of ``kda_state_update``. Both terms are
printed."""
from chipbench import work_kda_moe as work
from chipbench.layer_metrics import _hybrid_ssm as H
from chipbench.layer_metrics import _kda_moe as K
from chipbench.peaks import peaks_for

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    from chipbench.weights_kda_moe import Dims

    seconds, calls = K.kernel_seconds(facts)
    slots = H.live_slots(facts)
    if not calls or slots is None or not K.is_mine(facts):
        return None
    flops, bytes_ = work.kda_update_work(Dims.from_config(facts["dims"]), slots)
    peaks = peaks_for(facts["peaks_of"])
    by_flops, by_bytes = flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"]
    print(f"[chipbench] kda_state_update: {seconds / calls * 1e6:.1f} us a call over {calls} calls; "
          f"least by operations {by_flops * 1e6:.1f} us, by bytes {by_bytes * 1e6:.1f} us "
          f"({slots:.1f} live slots)", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (seconds / calls)
