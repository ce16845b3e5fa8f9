"""The on-chip benchmark of the served SRDS path (see ``run.py``)."""
