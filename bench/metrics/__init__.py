"""Per-layer metric readers, one file each, found by the metric's name.

Each module defines ``LAYER``, ``UNIT``, ``MOVES`` (the end-to-end
metric it should move) and ``read(ctx)``, which returns the metric's
value from a traced run's context (see ``bench/run.py``'s
``TracedContext``) or ``None`` when there is nothing to read; the harness
then leaves the metric out of the line."""
