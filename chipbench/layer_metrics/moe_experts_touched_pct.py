"""Held experts with at least one token-expert pair, over the held experts, a counted
expert layer and step or call (``stats['moe_experts_touched'] / (held x
stats['moe_layer_steps'])``): what share of the experts' weights a step has to read."""
from chipbench.layer_metrics import _latent_moe as L

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "program_counter"


def read(facts: dict):
    got = L.counts(facts)
    if got is None:
        return None
    _pairs, touched, layer_steps = got
    return 100.0 * touched / (int(facts["dims"]["n_routed_experts"]) * layer_steps)
