"""Tests of the benchmark itself: `python -m pytest bench/tests` from the
root of the checkout. They run on the CPU at each cell's sizes in
`bench/tests/cells/<cell>.json`; nothing here measures speed. A one-chip
cell runs in the test's process; a four-chip cell runs in a child process
on four host devices (`--xla_force_host_platform_device_count=4`): a
rehearsal at 4,096 rows a device takes about 11 s on a CPU host
(`bench/tests/tiny.py`)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
