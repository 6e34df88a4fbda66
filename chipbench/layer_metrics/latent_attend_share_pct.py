"""Device time of the decode attention kernel over the device's busy time in the
traced window."""
from chipbench.layer_metrics import _latent_moe as L

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    seconds, calls = L.kernel_seconds(facts)
    return 100.0 * seconds / facts["trace"]["busy_s"] if calls else None
