"""Per-layer metric readers, one file per metric named as in BENCHMARK.json.

Each file defines `read(ctx)`: ctx holds the reduced `trace`
(bench/harness/trace.py), `chips`, the device's `peaks`, and what the
cell's driver counted (for training `steps`, `rows_per_chip` and `work`,
the least work of one step on one chip). A reader that finds nothing to
read returns None, and the metric is left out of the line. Op selectors
shared by several readers are in ops.py.
"""
