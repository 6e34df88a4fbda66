"""The least time one decode step of the selecting decoder could take on this chip's
memory over the time it took: bytes the step must read
(``work_sparse_latent_moe.decode_step_bytes``: every matrix that every token multiplies
once, the held experts TOUCHED, and of the cache what selection and the window leave: the
full layers' index keys of the tokens really cached and their chosen rows, the sliding
layers' windows) over the peak bandwidth, over ``decode_step_ms``. Touched experts a step
as ``latent_decode_hbm_roofline`` has them.

Prints what the attention over the chosen rows and the sliding layers' reads cost beside
their least: the attention by its operations (``attend_flops``), the window by the rows
its reads covered in the window's calls (the engine's ``window_rows_read``, decode steps
and chunk calls alike) beside the rows a window holds."""
from chipbench import work_sparse_latent_moe as work
from chipbench.layer_metrics import _latent_moe as L
from chipbench.layer_metrics import _sparse_latent_moe as S
from chipbench.layer_metrics import decode_step_ms
from chipbench.layer_metrics.latent_decode_hbm_roofline import touched_a_decode_step
from chipbench.peaks import peaks_for

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    if not S.is_mine(facts):
        return None
    from chipbench.weights_sparse_latent_moe import Dims

    step_s = decode_step_ms.step_seconds(facts)
    cached = L.cached_tokens(facts)
    dims = Dims.from_config(facts["dims"])
    touched = touched_a_decode_step(facts, dims)
    if step_s is None or cached is None or touched is None:
        return None
    slots = L.mean_active(facts)
    peaks = peaks_for(facts["peaks_of"])
    attend = S.decode_part(facts, "attend")
    if attend is not None:
        least = work.attend_flops(dims, slots * min(cached / slots, dims.topk)) / peaks["bf16_flops"]
        print(f"[chipbench] attention over the chosen rows: {attend[0] * 1e6:.1f} us a full layer a "
              f"step; least by operations {least * 1e6:.1f} us", flush=True)
    covered = facts["engine"]["stats"].get("window_rows_read", 0)
    print(f"[chipbench] sliding layers: their reads covered {covered} rows in the window's calls "
          f"({work.window_bytes(dims, covered) / 1e9:.3f} GB); a decode step's windows hold "
          f"{slots * min(cached / slots, dims.window):.0f} a layer", flush=True)
    least = work.decode_step_bytes(dims, slots, cached, touched) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / step_s
