"""Metric readers, one file per metric of BENCHMARK.json, named as the
metric is.  Each has ``read(run) -> float | None``: ``run`` is
``bench.harness.Run``; ``None`` where the run holds nothing to read, and the
metric is then left out of the result line."""
