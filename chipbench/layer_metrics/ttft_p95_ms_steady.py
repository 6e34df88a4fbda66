"""Time to first token, from the instant a request was due to be sent to its
first token at the client: 95th percentile over the requests due inside the
window. A request that failed waits until the run gave up on it.

Not an end-to-end metric: over six seeds it spread by 57% of its median (the
order of the arrivals decides the worst burst of a 40 s window), where two
runs of one seed agree within 2-5%: no bound the contract allows holds it. It
stands here, to be read beside ``tpot_p95_ms``, and must decide no PR."""
from chipbench.stats import percentile

LAYER = "Client side"
UNIT, MOVES, SOURCE = "ms", "tpot_p95_ms", "host_clock"


def sample(facts: dict) -> list:
    c = facts["client"]
    due = [r for r in c["requests"] if c["t0"] <= r["due"] < c["t1"] and r["sent"] is not None]
    return [((r["first"] if r["ok"] else c["gave_up"]) - r["due"]) * 1e3 for r in due]


def read(facts: dict):
    xs = sample(facts)
    return percentile(xs, 95) if xs else None
