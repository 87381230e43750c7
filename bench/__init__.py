"""Chip benchmark of the serving path: one cell of ``BENCHMARK.json`` per run.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell once on the machine it is started on and prints one JSON line.
Configurations, traffic mixes, limits and metric readers are files found by
the names in ``BENCHMARK.json``; see ``bench/run.py``.
"""
