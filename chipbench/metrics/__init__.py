"""Per-layer metric readers, one file a metric: ``read(ctx)`` returns the
metric's value from the run's trace, counters or clock, or None when the
run holds nothing to read (the harness then leaves the metric out). A
metric ``name.part`` is read by ``name.part.py`` or, failing that, by
``name.py``: the part only says which end-to-end metric it moves."""
