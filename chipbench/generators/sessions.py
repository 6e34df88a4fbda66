"""Closed loop of conversations. ``clients`` callers each wait for a reply
before they speak again. A session starts from one of ``system_prompts``
shared prompts, then takes ``turns`` turns: a turn appends a user message and
asks for an answer; the next turn's prompt is the whole history, answers
included. A finished session is replaced by a new one.

``plan`` returns the shared prompts and a pure function ``session(j)`` that
gives the j-th session's script; user and answer lengths are fixed sets that
the seed only reorders.
"""
from __future__ import annotations

import numpy as np

from chipbench.generators import quantiles

KIND = "closed"
CYCLE = 64  # sessions after which the sets of lengths repeat


def plan(params: dict, seed: int, seconds: float, vocab: int) -> dict:
    rng = np.random.default_rng(seed)
    systems = [rng.integers(0, vocab, params["system_tokens"]).tolist()
               for _ in range(params["system_prompts"])]
    turns = params["turns"]
    u, a = params["user_tokens"], params["answer_tokens"]
    users = rng.permutation(quantiles.uniform_set(CYCLE * turns, u["min"], u["max"]))
    answers = rng.permutation(quantiles.uniform_set(CYCLE * turns, a["min"], a["max"]))
    order = rng.permutation(CYCLE) % len(systems)

    def session(j: int) -> dict:
        srng = np.random.default_rng([seed, j])
        k = j % CYCLE
        return {
            "system": int(order[k]),
            "turns": [{"user": srng.integers(0, vocab, int(users[k * turns + t])).tolist(),
                       "max_new_tokens": int(answers[k * turns + t])} for t in range(turns)],
        }

    return {"kind": KIND, "clients": int(params["clients"]), "systems": systems, "session": session}
