"""The benchmark: one command runs one cell once (``bench/run.py``)."""
