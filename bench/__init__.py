"""Chip benchmark of the MING path.

One run of one cell::

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the checkout root names the cells.  Each cell's
model configuration (``bench/configs/<config>.json``), traffic mix
(``bench/traffic/<mix>.json``) and per-layer metric readers
(``bench/metrics/<metric>.py``) are files of their own, found by name
(:mod:`bench.registry`).  Nothing here imports the program except to
drive it: the plain reference (:mod:`bench.reference`), the traffic
generator, the trace reduction, the FLOP and byte counts and the peaks
table are the benchmark's own.
"""
