"""On-chip benchmark of the protected training job (see run.py)."""
