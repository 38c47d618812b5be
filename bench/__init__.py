"""The on-chip benchmark of the batched verify path (see bench/run.py)."""
