"""The least time the residual streams' mixes could take on this chip's memory over the
time their parts took in the traced window: the token places mixed there (the engine's
``hc_places_mixed`` a second of the window, times the traced seconds) at
``work_hyper_latent_moe.mix_bytes`` (the streams read twice and written once a sublayer,
its input written and its output read, ``phi`` once a sublayer a call) over the peak
bandwidth, over ``_hyper_latent_moe.part_seconds``: the yardstick of a kernel that fuses
the maps and the mixes."""
from chipbench import work_hyper_latent_moe as work
from chipbench.layer_metrics import _hyper_latent_moe as H
from chipbench.peaks import peaks_for

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    parts = H.part_seconds(facts)  # {} for another family's configuration
    places = H.places_mixed(facts)
    if not parts or not places:
        return None
    from chipbench.weights_hyper_latent_moe import Dims

    dims = Dims.from_config(facts["dims"])
    traced = places / facts["seconds"] * facts["trace"]["window_s"]
    bytes_ = work.mix_bytes(dims, traced, H.calls_traced(facts))
    least = bytes_ / peaks_for(facts["peaks_of"])["hbm_bytes_per_s"]
    print(f"[chipbench] the streams' mixes: {traced:.0f} places x sublayers in the traced window, "
          f"{bytes_ / 1e9:.3f} GB at the least; their parts took {sum(parts.values()) * 1e3:.1f} ms, "
          f"least by bytes {least * 1e3:.1f} ms", flush=True)
    return 100.0 * least / sum(parts.values())
