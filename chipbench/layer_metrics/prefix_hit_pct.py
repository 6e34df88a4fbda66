"""Share of looked-up prompt tokens that the prefix cache served, inside the window
(``stats['prefix_hit_tokens'] / stats['prefix_lookup_tokens']``)."""
LAYER = "Engine scheduler"
UNIT, MOVES, SOURCE = "%", "serve_tokens_per_s", "program_counter"


def read(facts: dict):
    s = facts["engine"]["stats"]
    if not s.get("prefix_lookup_tokens"):
        return None
    return 100.0 * s["prefix_hit_tokens"] / s["prefix_lookup_tokens"]
