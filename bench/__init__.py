"""The on-chip benchmark of the graph engine (see ``run.py``)."""
