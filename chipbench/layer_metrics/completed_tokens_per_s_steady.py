"""Output tokens received over the window. Below the knee this is the offered load:
it shows that the cell ran at its rate, and must decide no PR there."""
from chipbench.end_to_end import serve_tokens_per_s

LAYER = "Client side"
UNIT, MOVES, SOURCE = "tokens/s", "tpot_p95_ms", "host_clock"


def read(facts: dict):
    return serve_tokens_per_s.read(facts)
