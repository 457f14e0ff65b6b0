"""The chip benchmark: one cell of ``BENCHMARK.json`` per run of
``bench/run.py``. Configurations, traffic mixes and per-layer metrics
are found by name under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``."""
