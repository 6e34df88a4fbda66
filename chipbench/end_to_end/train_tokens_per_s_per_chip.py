"""Tokens of the steps completed in the window, over the window's seconds
and the cell's chips. Every step ends in ``block_until_ready`` in the worker;
the window closes with the step that passes ``--seconds``."""
UNIT, SOURCE = "tokens/s/chip", "host_clock"


def read(facts: dict):
    t = facts["train"]
    return t["steps"] * t["tokens_per_step"] / (t["t1"] - t["t0"]) / facts["chips"]
