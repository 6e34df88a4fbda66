"""chipbench: the data-driven benchmark of ray_tpu on the chip.

    python3 -m chipbench --workload <name> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` once in a new process and prints, as the
last line of its standard output, one JSON object (see ``validate.py``).
``README.md`` in this directory says how a later PR adds a cell, a
configuration, a traffic mix or a per-layer metric as files of its own.
"""
