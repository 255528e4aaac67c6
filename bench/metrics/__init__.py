"""One reader per per-layer metric, ``<metric name>.py``, each with
``MOVES`` (the end-to-end metric it should move) and ``read(readings)``,
which returns the metric's value or None where the trace holds nothing
for it."""
