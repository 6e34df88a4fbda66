"""Device time of the residual streams' mixes (the maps with their Sinkhorn rounds, and
``Hres X + Hpost^T y``; in the decode program and in the chunk calls) over the device's busy
time in the traced window. The parts are found by what they write
(``_hyper_latent_moe.types``) and printed one by one; ``hc.in`` is fused into the norm
behind it and is not among them, so the share is a floor."""
from chipbench.layer_metrics import _hyper_latent_moe as H

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    parts = H.part_seconds(facts)
    if not parts:
        return None
    busy = facts["trace"]["busy_s"]
    print("[chipbench] the streams' mixes by part, % of busy: "
          + ", ".join(f"{part} {100.0 * s / busy:.2f}" for part, s in sorted(parts.items())), flush=True)
    return 100.0 * sum(parts.values()) / busy
