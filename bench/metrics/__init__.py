"""Per-layer metric readers.  ``<base>.py`` reads every metric named
``<base>`` or ``<base>.<cells>``: ``read(ctx)`` takes the run's
``harness.MetricContext`` and returns the number, or ``None`` where the run
has nothing to read (the harness then leaves the metric out)."""
