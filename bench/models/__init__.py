"""The system under test, one module per model kind: data from the seed,
the start point, and the facade as the configuration builds it."""
