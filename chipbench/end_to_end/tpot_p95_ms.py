"""Time per output token of one request: (last token - first token) over
(tokens - 1) on the client's clock; 95th percentile over the requests that
finished inside the window. Not the gap between frames: a decode window
delivers its tokens together."""
from chipbench.stats import percentile

UNIT, SOURCE = "ms", "host_clock"


def sample(facts: dict) -> list:
    c = facts["client"]
    return [(r["last"] - r["first"]) * 1e3 / (r["received"] - 1) for r in c["requests"]
            if r["ok"] and r["received"] > 1 and c["t0"] <= r["last"] < c["t1"]]


def read(facts: dict):
    xs = sample(facts)
    return percentile(xs, 95) if xs else None
