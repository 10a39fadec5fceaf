"""On-chip benchmark of the digit-serial CNN server (run as ``python3 bench/run.py``)."""
