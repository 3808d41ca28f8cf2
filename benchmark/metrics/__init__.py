"""One reader per per-layer metric, ``<metric name>.py``, loaded by path.
Each defines ``read(readings) -> float | None`` over a traced run's
``benchmark.run.Readings``; ``None`` means there was nothing to read, and
the metric is left out of the line."""
