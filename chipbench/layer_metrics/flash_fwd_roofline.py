"""The forward flash kernel's share of its roofline: the least time the chip
could take for one call (``work.flash_fwd_work``: the causal half of QK^T and
PV against the bf16 peak, or q, k, v, o and the logsumexp once against the
memory's, whichever is larger) over the traced time of one call. At these
shapes operations bound it. ``FORWARD`` tells the forward kernel from the two
backward ones by its three operands (q, k, v): the kernels carry no name."""
from chipbench import work
from chipbench.peaks import peaks_for
from chipbench.trace_reduce import seconds_matching
from chipbench.weights import Dims

LAYER = "Flash attention kernels"
UNIT, MOVES, SOURCE = "%", "train_tokens_per_s_per_chip", "device_trace"
FORWARD = r'custom-call\([^%]*%[^%]*%[^%]*%[^%]*custom_call_target="tpu_custom_call"'


def read(facts: dict):
    if not facts.get("trace"):
        return None
    seconds, calls = seconds_matching(facts["trace"]["ops"], FORWARD)
    if not calls:
        return None
    t = facts["train"]
    flops, bytes_ = work.flash_fwd_work(Dims.from_config(facts["dims"]),
                                        t["sequences"] // facts["chips"], t["seq_len"])
    peaks = peaks_for(facts["peaks_of"])
    least = max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)
