"""Per-layer metric readers, one module per metric, named as in
``BENCHMARK.json``.  Each has ``read(window)`` (a ``run.Window``) that
returns the metric's value, or None where the run gives it nothing to read."""
