"""What the readers of the latent-attention expert decoder's metrics share: the
tokens cached over the batch, the decode kernel's traced calls, the counters."""
from chipbench.trace_reduce import seconds_matching

KERNEL = r"^latent_attend"  # the ``name=`` of the decode attention ``pallas_call``


def cached_tokens(facts: dict):
    """Tokens in the cache over the batch during a decode step: mean occupied
    slots times the mean context (prompt plus half the answer) of the requests
    that finished in the window. None where nothing finished or ran."""
    e = facts["engine"]
    done = [r for r in e["requests"] if r.get("output_tokens")]
    active = [s["active"] for s in e["steps"] if s["active"] > 0]
    if not done or not active:
        return None
    context = sum(r["prompt_tokens"] + r["output_tokens"] / 2 for r in done) / len(done)
    return context * sum(active) / len(active)


def mean_active(facts: dict) -> float:
    active = [s["active"] for s in facts["engine"]["steps"] if s["active"] > 0]
    return sum(active) / len(active)


def kernel_seconds(facts: dict) -> tuple:
    """(seconds, calls) of the kernel in the traced window, by its name alone:
    an operation's detail names its operands, and the kernel's consumers would
    match too. (0, 0) with no trace or no such operation."""
    if not facts.get("trace"):
        return 0.0, 0
    names_only = {name: {"seconds": row["seconds"], "count": row["count"]}
                  for name, row in facts["trace"]["ops"].items()}
    return seconds_matching(names_only, KERNEL)


def counts(facts: dict):
    """(pairs here, experts touched, layer-steps counted) over the window, or
    None where the program keeps no such counters or counted nothing."""
    s = facts["engine"]["stats"]
    if not s.get("moe_layer_steps"):
        return None
    return s["moe_pairs_here"], s["moe_experts_touched"], s["moe_layer_steps"]
