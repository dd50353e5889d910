"""Per-layer metrics: one module per metric, named as in BENCHMARK.json.

Each module has ``read(run) -> float | None``.  ``run`` carries
``summary`` (``tracing.Summary`` of the traced window), ``products`` (the
products completed in it) and ``least_s`` (the roofline time of one
product, ``work.least_seconds``).  A metric that finds nothing to read
returns None and is left out of the run's line.
"""
