"""On-chip benchmark of the Biathlon serving path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON line.  Every
configuration, traffic mix, metric and model kind is a file found by its
name: ``bench/configs/<config>.json``, ``bench/traffic/<mix>.json``,
``bench/metrics/<metric>.py`` and ``bench/models/<kind>.py``.
"""
