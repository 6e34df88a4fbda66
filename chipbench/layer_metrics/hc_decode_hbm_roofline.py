"""The least time one decode step of the multi-stream latent-attention expert decoder
could take on this chip's memory over the time it took: bytes the step must move
(``work_hyper_latent_moe.decode_step_bytes``: every matrix that every token multiplies once,
the held experts TOUCHED, the latent rows of the tokens really cached, and the streams of
the occupied slots through the mixes of every sublayer with their ``phi``) over the peak
bandwidth, over ``decode_step_ms``. Touched experts a step as
``latent_decode_hbm_roofline`` has them."""
from chipbench import work_hyper_latent_moe as work
from chipbench.layer_metrics import _hyper_latent_moe as H
from chipbench.layer_metrics import _latent_moe as L
from chipbench.layer_metrics import decode_step_ms
from chipbench.layer_metrics.latent_decode_hbm_roofline import touched_a_decode_step
from chipbench.peaks import peaks_for

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    if not H.is_mine(facts):
        return None
    from chipbench.weights_hyper_latent_moe import Dims

    step_s = decode_step_ms.step_seconds(facts)
    cached = L.cached_tokens(facts)
    dims = Dims.from_config(facts["dims"])
    touched = touched_a_decode_step(facts, dims)
    if step_s is None or cached is None or touched is None:
        return None
    least = (work.decode_step_bytes(dims, L.mean_active(facts), cached, touched)
             / peaks_for(facts["peaks_of"])["hbm_bytes_per_s"])
    return 100.0 * least / step_s
