"""Token-expert pairs a touched expert multiplies (``stats['moe_pairs_here'] /
stats['moe_experts_touched']``): how near the expert layer's load is to the
deployment's, where sixteen chips' tokens meet at each expert."""
from chipbench.layer_metrics import _latent_moe as L

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "tokens", "tpot_p95_ms", "program_counter"


def read(facts: dict):
    got = L.counts(facts)
    if got is None or not got[1]:
        return None
    return got[0] / got[1]
