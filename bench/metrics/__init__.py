"""Per-layer metric readers, one file per metric, found by the metric's
name in ``BENCHMARK.json``. Each has ``read(ctx) -> float | None``, where
``ctx`` is a ``bench.harness.cli.TraceContext``; ``None`` means the run
had nothing to read, and the metric is left out of its line."""
