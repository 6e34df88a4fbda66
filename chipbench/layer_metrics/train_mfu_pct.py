"""Model FLOP/s utilization: ``work.train_flops_per_token`` (6 for each parameter
that multiplies, so no embedding lookup; causal attention counted once;
recomputation not counted) times this run's tokens per second per chip, over the
chip's published bf16 peak."""
from chipbench import work
from chipbench.end_to_end import train_tokens_per_s_per_chip
from chipbench.peaks import peaks_for
from chipbench.weights import Dims

LAYER = "Compiled train step"
UNIT, MOVES, SOURCE = "%", "train_tokens_per_s_per_chip", "host_clock"


def read(facts: dict):
    dims = Dims.from_config(facts["dims"])
    rate = train_tokens_per_s_per_chip.read(facts)
    flops = work.train_flops_per_token(dims, facts["train"]["seq_len"])
    return 100.0 * flops * rate / peaks_for(facts["peaks_of"])["bf16_flops"]
