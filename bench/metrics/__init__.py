"""Per-layer metric readers, one module per metric named in
``BENCHMARK.json``. Each has ``read(run) -> float | None`` over a
``benchlib.runner.RunData``; ``None`` means it found nothing to read,
and the harness leaves the metric out of the result line."""
