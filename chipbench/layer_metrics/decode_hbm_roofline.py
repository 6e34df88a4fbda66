"""The least time one decode step could take on this chip's memory over the time it
took: bytes the step must read (``work.decode_step_bytes``: every weight once,
K and V of the tokens really cached, not of the padded table) over the peak
bandwidth, over ``decode_step_ms``. Decode is bound by bandwidth, not operations.
Cached tokens: mean occupied slots times the mean context (prompt plus half the
answer) of the requests that finished in the window."""
from chipbench import work
from chipbench.layer_metrics import decode_step_ms
from chipbench.peaks import peaks_for
from chipbench.weights import Dims

LAYER = "Paged programs"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "device_trace"


def read(facts: dict):
    step_s = decode_step_ms.step_seconds(facts)
    e = facts["engine"]
    done = [r for r in e["requests"] if r.get("output_tokens")]
    active = [s["active"] for s in e["steps"] if s["active"] > 0]
    if step_s is None or not done or not active:
        return None
    context = sum(r["prompt_tokens"] + r["output_tokens"] / 2 for r in done) / len(done)
    cached = context * sum(active) / len(active)
    dims = Dims.from_config(facts["dims"])
    least = work.decode_step_bytes(dims, cached) / peaks_for(facts["peaks_of"])["hbm_bytes_per_s"]
    return 100.0 * least / step_s
