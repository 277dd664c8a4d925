"""On-chip benchmark of the serving path: one cell of ``BENCHMARK.json``
per run of ``python3 bench/run.py``.  See ``bench/run.py``."""
