"""Traffic generators, found by name: pure functions of parameters and seed."""
