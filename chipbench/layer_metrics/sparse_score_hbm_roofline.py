"""The index score's share of the memory's roofline in the traced decode steps: the least
time the chip could take to read the index keys of the tokens REALLY cached, once a slot a
full layer a step, with the index queries and their weights
(``work_sparse_latent_moe.score_work``: bytes from the contexts, never from the table's
width, so the share cannot pass 100 by a read the program was spared), over the traced
time of the decode program's score part (``_sparse_latent_moe.decode_part``)."""
from chipbench import work_sparse_latent_moe as work
from chipbench.layer_metrics import _sparse_latent_moe as S
from chipbench.peaks import peaks_for

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    got = S.decode_part(facts, "score")
    if got is None:
        return None
    seconds, slots, cached, dims = got
    flops, bytes_ = work.score_work(dims, slots, cached)
    peaks = peaks_for(facts["peaks_of"])
    by_flops, by_bytes = flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"]
    print(f"[chipbench] index score: {seconds * 1e6:.1f} us a full layer a step; least by bytes "
          f"{by_bytes * 1e6:.1f} us, by operations {by_flops * 1e6:.1f} us "
          f"({cached:.0f} cached tokens)", flush=True)
    return 100.0 * by_bytes / seconds
