"""On-chip serving benchmark: one command runs one cell once (see run.py)."""
