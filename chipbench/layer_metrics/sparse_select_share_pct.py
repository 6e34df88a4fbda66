"""Device time of the selection (index score, sort, gather of the chosen rows and the
attention over them: the full layers' four parts, in the decode program and in the chunk
calls) over the device's busy time in the traced window. The parts are found by what
they write (``_sparse_latent_moe.shapes``) and printed one by one."""
from chipbench.layer_metrics import _sparse_latent_moe as S

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    parts = S.part_seconds(facts)
    if not parts:
        return None
    busy = facts["trace"]["busy_s"]
    print("[chipbench] selection by part, % of busy: "
          + ", ".join(f"{part} {100.0 * s / busy:.2f}" for part, s in sorted(parts.items())), flush=True)
    return 100.0 * sum(parts.values()) / busy
