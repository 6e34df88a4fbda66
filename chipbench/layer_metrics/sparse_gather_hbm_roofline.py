"""The gather's share of the memory's roofline in the traced decode steps: the least time
the chip could take to read the latent rows the selection chose, ``index_topk`` (or the
context, where that is less) a slot a full layer a step, each once
(``work_sparse_latent_moe.gather_bytes``: a row's 576 numbers, not the 640 the pool lays
them out as), over the traced time of the decode program's gather part
(``_sparse_latent_moe.decode_part``: the rows and the block ids the positions become)."""
from chipbench import work_sparse_latent_moe as work
from chipbench.layer_metrics import _sparse_latent_moe as S
from chipbench.peaks import peaks_for

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    got = S.decode_part(facts, "gather")
    if got is None:
        return None
    seconds, slots, cached, dims = got
    selected = slots * min(cached / slots, dims.topk)
    least = work.gather_bytes(dims, selected) / peaks_for(facts["peaks_of"])["hbm_bytes_per_s"]
    print(f"[chipbench] gather: {seconds * 1e6:.1f} us a full layer a step; least by bytes "
          f"{least * 1e6:.1f} us ({selected:.0f} rows)", flush=True)
    return 100.0 * least / seconds
