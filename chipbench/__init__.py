"""Chip benchmark for the DSBA solvers and the paged serving stack.

Entry point: ``python -m chipbench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout. Cells,
configurations, traffic mixes and per-layer metrics are named in
``BENCHMARK.json`` and found as files under this directory.
"""
