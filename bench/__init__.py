"""Chip benchmark of the served path: one cell of BENCHMARK.json per run.

Run from the root of a checkout as ``python3 bench/run.py --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.  Each configuration, traffic mix,
cell and metric is a file of its own under this directory, found by the name
that BENCHMARK.json gives it.
"""
