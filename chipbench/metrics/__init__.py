"""Per-layer metric readers, one file per metric (``<metric>.py``), and the
trace reductions they share (``reduce.py``)."""
