"""On-chip benchmark of the simulator: see ``run.py`` and ``harness.py``."""
