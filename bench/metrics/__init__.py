"""Metrics, end to end and per layer, one module each, named as in
``BENCHMARK.json``.

Each module has ``read(run) -> float | None``, where ``run`` is a
``bench.harness.RunRecord``; None means the run holds nothing for the
metric to read, and the metric is left out of the result line.
"""
