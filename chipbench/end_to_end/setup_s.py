"""Process start to the start of the timed window: loading, warming up and,
in a run that compiles, compilation."""
UNIT, SOURCE = "s", "host_clock"


def read(facts: dict):
    return facts["setup_s"]
