"""The least time one decode step of the latent-attention expert decoder could take
on this chip's memory over the time it took: bytes the step must read
(``work_latent_moe.decode_step_bytes``: every matrix that every token multiplies once,
the held experts TOUCHED, the latent rows of the tokens really cached) over the peak
bandwidth, over ``decode_step_ms``.

Touched experts a step come from the program's counters, which count decode steps and
the chunk calls the host read alike. The decode steps' layer-steps are known (windows x
steps a window x expert layers); the rest are chunk calls', and a chunk call is taken
to touch every held expert, so what is left for the decode steps is never too many."""
from chipbench import work_latent_moe as work
from chipbench.layer_metrics import _latent_moe as L
from chipbench.layer_metrics import decode_step_ms
from chipbench.peaks import peaks_for
from chipbench.weights_latent_moe import Dims

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def touched_a_decode_step(facts: dict, dims):
    """Held experts touched in one decode step, summed over the expert layers."""
    got = L.counts(facts)
    e = facts["engine"]
    if got is None or not e["stats"].get("steps"):
        return None
    _pairs, touched, layer_steps = got
    layers = work.expert_layers(dims)
    decode = min(layer_steps, e["stats"]["steps"] * e["decode_window"] * layers)
    if not decode:
        return None
    touched_decode = max(0.0, touched - dims.held * (layer_steps - decode))
    return layers * touched_decode / decode


def read(facts: dict):
    step_s = decode_step_ms.step_seconds(facts)
    cached = L.cached_tokens(facts)
    dims = Dims.from_config(facts["dims"])
    touched = touched_a_decode_step(facts, dims)
    if step_s is None or cached is None or touched is None:
        return None
    least = work.decode_step_bytes(dims, cached, touched) / peaks_for(facts["peaks_of"])["hbm_bytes_per_s"]
    return 100.0 * least / step_s
