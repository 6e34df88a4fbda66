"""Median time from send to first token. In a closed loop with more clients than
slots it is the length of the queue, and must decide no PR there."""
import statistics

LAYER = "Client side"
UNIT, MOVES, SOURCE = "ms", "serve_tokens_per_s", "host_clock"


def read(facts: dict):
    xs = [(r["first"] - r["sent"]) * 1e3 for r in facts["client"]["requests"]
          if r["ok"] and facts["client"]["t0"] <= r["sent"] < facts["client"]["t1"]]
    return statistics.median(xs) if xs else None
