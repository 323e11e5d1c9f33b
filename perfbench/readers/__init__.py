"""One reader per kind of per-layer metric, found by the ``reader``
name in ``perfbench/metrics/<metric>.json``.  ``read(metric, ctx)``
returns the value, or None where it finds nothing to read."""
