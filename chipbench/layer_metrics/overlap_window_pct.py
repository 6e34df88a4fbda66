"""Of the decode windows the scheduler found in flight, the share it overlapped:
dispatched the next window before reading this one's tokens
(``stats['spec_windows']``) over that plus the windows it did not, which the
engine counts by reason (``stats['spec_blocked_<reason>']``, ``REASONS``). A
program from before the reasons were counted has only the windows it
dispatched (``stats['steps']``): each is found in flight exactly once, so over
a window of many they are the same count, to within the one at each end."""
LAYER = "Engine scheduler"
UNIT, MOVES, SOURCE = "%", "tpot_p95_ms", "program_counter"
REASONS = ("idle", "admission", "dirty_cur", "finishing")


def read(facts: dict):
    s = facts["engine"]["stats"]
    if "spec_windows" not in s:
        return None
    blocked = [s.get("spec_blocked_" + why) for why in REASONS]
    found = s["spec_windows"] + sum(blocked) if None not in blocked else s.get("steps", 0)
    return 100.0 * s["spec_windows"] / found if found else None
