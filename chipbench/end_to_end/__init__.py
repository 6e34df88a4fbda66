"""One reader per end-to-end metric, found by the metric's name. Each is
taken by the benchmark itself, on its own clock."""
