"""The chip benchmark of this repository: one command, data-driven cells.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the repository root names the cells, configurations
and metrics; each configuration, traffic mix and per-layer metric is a
file of its own under this directory, found by its name.
"""
