"""On-chip benchmark of the archive service (see run.py)."""
