"""What the readers of the power retention decoder's metrics share: the state
update's traced calls. Live slots are ``_hybrid_ssm``'s (the program's
``state_slots_*`` counters and the step field ``state_slots_live`` over the traced
seconds, whatever the state is)."""
from chipbench.trace_reduce import seconds_matching

KERNEL = r"^power_state_update"  # the ``name=`` of the state update's ``pallas_call``


def is_mine(facts: dict) -> bool:
    """The configuration is this family's (a cell of another has another type)."""
    return facts["dims"].get("model_type") == "brumby"


def kernel_seconds(facts: dict) -> tuple:
    """(seconds, calls) of the kernel in the traced window, by its name alone
    (an operation's detail names its operands, and the kernel's consumers
    would match too). (0, 0) with no trace or no such operation."""
    if not facts.get("trace"):
        return 0.0, 0
    names_only = {name: {"seconds": row["seconds"], "count": row["count"]}
                  for name, row in facts["trace"]["ops"].items()}
    return seconds_matching(names_only, KERNEL)
