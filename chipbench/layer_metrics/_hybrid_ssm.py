"""What the readers of the hybrid state-space decoder's metrics share: the slots
live in a dispatched window (the program's counter, over the traced seconds
where there are any), the tokens cached over the batch, the state update's
traced calls."""
from chipbench.trace_reduce import seconds_matching

KERNEL = r"^ssm_state_update"  # the ``name=`` of the state update's ``pallas_call``


def live_slots(facts: dict):
    """Mean occupied slots of the dispatched windows whose time the other
    readers divide by: those of the steps recorded inside the traced seconds
    (the step field ``state_slots_live``: a kernel's traced time at 60 live
    slots over its bytes at the whole window's mean of 40 would read half of
    what it reached, and the other way round above 100%); with no trace, or a
    program that records no such field, of the whole measured window
    (``stats['state_slots_live']`` over the windows). None where the program
    keeps no such counter or dispatched nothing."""
    s, e, trace = facts["engine"]["stats"], facts["engine"], facts.get("trace") or {}
    if not s.get("state_slots_table") or not e.get("max_batch"):
        return None
    if "host_t0" in trace:
        traced = [step["state_slots_live"] for step in e["steps"]
                  if trace["host_t0"] <= step["ts"] <= trace["host_t1"]
                  and step.get("state_slots_live")]
        if traced:
            return sum(traced) / len(traced)
    return s["state_slots_live"] / (s["state_slots_table"] / e["max_batch"])


def cached_tokens(facts: dict, slots: float):
    """Tokens in the K/V cache over the batch during a decode step: ``slots``
    times the mean context (prompt plus half the answer) of the requests that
    finished in the window. None where nothing finished."""
    done = [r for r in facts["engine"]["requests"] if r.get("output_tokens")]
    if not done:
        return None
    return slots * sum(r["prompt_tokens"] + r["output_tokens"] / 2 for r in done) / len(done)


def kernel_seconds(facts: dict) -> tuple:
    """(seconds, calls) of the kernel in the traced window, by its name alone
    (an operation's detail names its operands, and the kernel's consumers
    would match too). (0, 0) with no trace or no such operation."""
    if not facts.get("trace"):
        return 0.0, 0
    names_only = {name: {"seconds": row["seconds"], "count": row["count"]}
                  for name, row in facts["trace"]["ops"].items()}
    return seconds_matching(names_only, KERNEL)
