"""What every cell shares: the run's life cycle, traces, compile counts, checks."""
