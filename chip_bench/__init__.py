"""Chip benchmark of the served recursive-query path.

One command runs one cell (a graph deployment under a traffic mix) once:

    python3 chip_bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that defines a cell is data found by name: ``BENCHMARK.json`` at
the repository root lists the cells and metrics, ``configs/<name>.json``
holds a deployment, ``traffic/<name>.json`` a traffic mix, and
``end_to_end/<metric>.py`` / ``metrics/<metric>.py`` one reader per metric.
"""
