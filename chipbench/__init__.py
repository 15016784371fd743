"""Chip benchmark for the aggregation cascade.

Run one cell on the machine that holds its chips::

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the checkout's root names the cells; everything that
belongs to one configuration, traffic mix, data generator, path driver,
reference or metric sits in a file of its own under this directory and is
found by the name ``BENCHMARK.json`` gives it (see ``harness``).
"""
