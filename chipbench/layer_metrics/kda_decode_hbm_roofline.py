"""The least time one decode step of the KDA / latent-attention expert decoder could
take on this chip's memory over the time it took: bytes the step must move
(``work_kda_moe.decode_step_bytes``: every matrix that every token multiplies once,
the held experts TOUCHED, each LIVE slot's float32 state read and written, the
latent rows of the tokens really cached) over the peak bandwidth, over
``decode_step_ms``. Live slots are the program's own count a dispatched window
(``state_slots_live``) over the traced seconds, which the step time is of too;
touched experts a decode step from the ``moe_*`` counters as
``latent_decode_hbm_roofline`` takes them. The share is of the whole step."""
from chipbench import work_kda_moe as work
from chipbench.layer_metrics import _hybrid_ssm as H
from chipbench.layer_metrics import _kda_moe as K
from chipbench.layer_metrics import decode_step_ms
from chipbench.layer_metrics.latent_decode_hbm_roofline import touched_a_decode_step
from chipbench.peaks import peaks_for

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    from chipbench.weights_kda_moe import Dims

    step_s = decode_step_ms.step_seconds(facts)
    slots = H.live_slots(facts)
    if step_s is None or slots is None or not K.is_mine(facts):
        return None
    dims = Dims.from_config(facts["dims"])
    cached, touched = H.cached_tokens(facts, slots), touched_a_decode_step(facts, dims)
    if cached is None or touched is None:
        return None
    least = (work.decode_step_bytes(dims, slots, cached, touched)
             / peaks_for(facts["peaks_of"])["hbm_bytes_per_s"])
    print(f"[chipbench] kda decode step: {step_s * 1e3:.3f} ms at {slots:.1f} live slots, "
          f"{touched:.1f} touched experts and {cached:.0f} cached tokens; least by bytes "
          f"{least * 1e3:.3f} ms", flush=True)
    return 100.0 * least / step_s
