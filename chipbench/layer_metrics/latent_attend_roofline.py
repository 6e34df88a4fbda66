"""The decode attention kernel's share of its roofline: the least time the chip
could take for one call (``work_latent_moe.latent_attend_work``: every head scoring
and summing each cached row against the bf16 peak, or each cached row once with
the queries and sums against the memory's, whichever is larger: at 242 operations
a byte the two are nearly one) over the traced time of one call. Both terms are
printed. Cached tokens as ``_latent_moe.cached_tokens`` has them."""
from chipbench import work_latent_moe as work
from chipbench.layer_metrics import _latent_moe as L
from chipbench.peaks import peaks_for
from chipbench.weights_latent_moe import Dims

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    seconds, calls = L.kernel_seconds(facts)
    cached = L.cached_tokens(facts)
    if not calls or cached is None:
        return None
    flops, bytes_ = work.latent_attend_work(
        Dims.from_config(facts["dims"]), L.mean_active(facts), cached)
    peaks = peaks_for(facts["peaks_of"])
    by_flops, by_bytes = flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"]
    print(f"[chipbench] latent_attend: {seconds / calls * 1e6:.1f} us a call over {calls} calls; "
          f"least by operations {by_flops * 1e6:.1f} us, by bytes {by_bytes * 1e6:.1f} us "
          f"({cached:.0f} cached tokens)", flush=True)
    return 100.0 * max(by_flops, by_bytes) / (seconds / calls)
