"""The chip benchmark of this repository: one cell per run, driven by data.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Cells, configurations, traffic mixes and per-layer metrics are found by name
in ``bench/configs``, ``bench/traffic`` and ``bench/metrics``.
"""
