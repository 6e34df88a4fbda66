"""Output tokens that reached the clients inside the window, over the
window's seconds."""
UNIT, SOURCE = "tokens/s", "host_clock"


def read(facts: dict):
    c = facts["client"]
    return sum(r["tokens_in_window"] for r in c["requests"]) / (c["t1"] - c["t0"])
