"""The on-chip benchmark of the sparse-GP system: one cell per run, see run.py."""
