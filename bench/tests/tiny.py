"""Tiny sizes of each cell for rehearsals on the CPU, and a way to run a
cell in-process with the look for a chip skipped. Above 1,024 rows per
device the program's statistics run their jnp twins off the TPU."""
import io
import json
import time

TINY = {
    "sgpr-airline.train": {"rows_per_chip": 4096, "M": 64},
}
SEED = 2 ** 33 + 17


def run_cell(name: str, *, seconds: float = 1.0, seed: int = SEED,
             trace: bool = False) -> dict:
    """Run `name` at its tiny size through bench/run.py's emit(); returns
    the parsed last stdout line."""
    import jax

    from bench import run
    from bench.harness import cell as cells
    from bench.harness.compile_log import CompileLog

    out = io.StringIO()
    cell = cells.Cell.load(name, seed=seed, seconds=seconds, trace=trace,
                           t0=time.monotonic(), devices=jax.devices(),
                           compiles=CompileLog(), config_overrides=TINY[name],
                           out=out)
    assert run.emit(cell) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
