"""The fixed parts of the VeilGraph benchmark: graph generation, traffic,
plain references, the trace reduction, the peak table and the run loop.

Per-cell data (configurations, traffic mixes) and per-layer metric readers
live beside this package, in ``bench/configs``, ``bench/traffic`` and
``bench/metrics``, and are found by the names in ``BENCHMARK.json``.
"""
