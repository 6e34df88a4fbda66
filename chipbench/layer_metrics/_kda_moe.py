"""What the readers of the KDA / latent-attention expert decoder's metrics share:
the state update's traced calls, and what a decode step of this model has to
move. Live slots and cached tokens are ``_hybrid_ssm``'s (the program's
``state_slots_*`` counters, whatever the state is), the touched experts a decode
step ``latent_decode_hbm_roofline``'s (the ``moe_*`` counters)."""
from chipbench.trace_reduce import seconds_matching

KERNEL = r"^kda_state_update"  # the ``name=`` of the state update's ``pallas_call``


def is_mine(facts: dict) -> bool:
    """The configuration is this family's (a cell of another has no such key)."""
    return "kda_lower_bound" in facts["dims"]


def kernel_seconds(facts: dict) -> tuple:
    """(seconds, calls) of the kernel in the traced window, by its name alone
    (an operation's detail names its operands, and the kernel's consumers
    would match too). (0, 0) with no trace or no such operation."""
    if not facts.get("trace"):
        return 0.0, 0
    names_only = {name: {"seconds": row["seconds"], "count": row["count"]}
                  for name, row in facts["trace"]["ops"].items()}
    return seconds_matching(names_only, KERNEL)
