"""The benchmark: one harness (``run.py``) driven by the data files beside it."""
