"""The on-chip benchmark: one cell a run (``bench/run.py``)."""
