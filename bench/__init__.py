"""On-chip benchmark of the placement daemon (see ``bench/run.py``)."""
