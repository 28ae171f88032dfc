"""Per-layer metric readers: ``metrics/<metric>.py`` defines
``read(ctx) -> float | None`` over a ``harness.MetricContext``.  A reader
that finds nothing to read returns None and the metric is left out."""
