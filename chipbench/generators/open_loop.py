"""Open loop: independent users. Requests are sent on a schedule whatever the
system does. Poisson arrivals at a fixed rate; prompt and output lengths
log-normal and clipped; every prompt is random tokens, so no two share a block.

``plan(params, seed, seconds, vocab)`` returns the whole schedule: the load
runs for ``ramp_s`` before the window opens and ``seconds`` after.
"""
from __future__ import annotations

import numpy as np

from chipbench.generators import quantiles

KIND = "open"


def plan(params: dict, seed: int, seconds: float, vocab: int) -> dict:
    rng = np.random.default_rng(seed)
    span = params["ramp_s"] + seconds
    n = int(np.ceil(params["rate_per_s"] * span))
    p, o = params["prompt_tokens"], params["output_tokens"]
    prompts = rng.permutation(quantiles.lognormal_set(n, p["median"], p["sigma"], p["min"], p["max"]))
    outputs = rng.permutation(quantiles.lognormal_set(n, o["median"], o["sigma"], o["min"], o["max"]))
    gaps = rng.permutation(quantiles.exponential_set(n, params["rate_per_s"]))
    due = np.cumsum(gaps) - gaps[0]
    requests = [
        {"cid": i, "due_s": float(due[i]), "max_new_tokens": int(outputs[i]),
         "prompt": rng.integers(0, vocab, int(prompts[i])).tolist()}
        for i in range(n) if due[i] < span
    ]
    return {"kind": KIND, "requests": requests}
