"""On-chip benchmark of the served path: harness, traffic, reference and
metric readers (see ``bench/run.py``)."""
