"""Chip benchmark of the DyDD + DD-KF assimilation engine.

Run one cell with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout; the cells,
metrics and bounds are in ``BENCHMARK.json`` there.
"""
