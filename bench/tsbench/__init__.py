"""Harness of the on-chip benchmark: one cell, one run (``bench/run.py``)."""
