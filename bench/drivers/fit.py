"""Full-batch training through the facade's public `fit`.

Set-up makes the data and the start point from the seed, builds the facade
once and drives it through `setup_steps` Adam steps with one `fit` call
(`log_every=1`, so each step's loss is read back and its end is stamped).
Those steps compile the step program and time it. The window is then one
`fit(..., params=<where set-up left off>, steps=k)` call on the same
object, k sized from the set-up steps to fill `--seconds`; it ends in
`block_until_ready`. The facade keeps its step program across `fit`
calls, so the timed call builds nothing: no trace and no read of the
persistent cache. Whatever a `fit` call would build stays inside the window,
since users pay it, and the run reports the compile-log counts of the window
on an earlier line.

The reference follows both calls from the same start: the set-up steps,
then the window's k steps from where those left off with a fresh Adam
state, as each `fit` call starts one. The set-up steps' losses and change,
and the window's last loss and the change after it, are compared.

Traffic keys: driver, setup_steps.
"""
from __future__ import annotations

import contextlib
import gc
import io
import time

import jax
import numpy as np

from bench.harness import check, trace
from bench.harness.cell import Outcome
from bench.reference import common as ref_common


class _Stamps(io.TextIOBase):
    """A stdout that records when each line ends: `fit(log_every=1)` prints
    one line per step after reading its loss back from the device."""

    def __init__(self):
        self.times = []

    def write(self, s: str) -> int:
        if "\n" in s:
            self.times.append(time.perf_counter())
        return len(s)


def prepare(cell) -> dict:
    """Data, start point, the facade, and its set-up steps through `fit`."""
    cfg, traffic = cell.config, cell.traffic
    steps0 = int(traffic["setup_steps"])
    n = cfg["rows_per_chip"] * cell.chips
    data = cell.model.make_data(cfg, cell.seed, n)
    p0 = cell.model.init_params(cfg, data, cell.seed)
    jax.block_until_ready((data, p0))
    model = cell.model.build(cfg, cell.mesh)
    fit_kw = dict(cfg["fit_kwargs"])

    stamps = _Stamps()
    t_fit = time.perf_counter()
    with contextlib.redirect_stdout(stamps):
        cell.model.fit(model, data, p0, steps0, log_every=1, **fit_kw)
    ends = [t_fit] + stamps.times
    # the first step compiles; the fastest later one times a step
    step_s = (min(np.diff(ends[1:])) if len(ends) > 2
              else ends[-1] - ends[0])
    return {"n": n, "data": data, "p0": p0, "model": model, "fit_kw": fit_kw,
            "steps0": steps0, "losses": [float(x) for x in model.history],
            "p_set": jax.device_get(model.params), "step_s": step_s,
            "step_ends_s": list(np.diff(ends)),
            "window_steps": max(1, round(cell.seconds / step_s))}


def window(cell, st: dict) -> dict:
    """The timed `fit` call, from where set-up left off, on the same object."""
    model, k = st["model"], st["window_steps"]
    before = cell.compiles.snapshot()
    with cell.tracing() as tdir:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            t0 = time.perf_counter()
            cell.model.fit(model, st["data"], model.params, k, **st["fit_kw"])
            jax.block_until_ready(model.params)
            wall = time.perf_counter() - t0
    return {"steps": k, "wall_s": wall, "trace_dir": tdir,
            "losses": [float(x) for x in model.history],
            "params": jax.device_get(model.params),
            "compile_log": cell.compiles.since(before,
                                               cell.compiles.snapshot())}


def reference(cell, st: dict, precision: str = "highest",
              rows: slice = slice(None), setup: bool = True) -> dict:
    """The reference over `rows` of the data: the set-up steps from the
    start (losses, first gradient, params) and the window's steps from
    where those left off with a fresh Adam state (window_losses,
    window_params). With setup=False the window's steps start from the
    start point itself."""
    opt = {**cell.config["optimizer"], "lr": st["fit_kw"]["lr"]}
    vg = cell.reference.value_and_grad_fn(cell.config, st["data"], precision,
                                          rows)
    losses, g1, p_set = ref_common.train(vg, st["p0"], st["steps0"], opt)
    w_losses, _, p_win = ref_common.train(vg, p_set if setup else st["p0"],
                                          st["window_steps"], opt)
    return {"losses": losses, "grad": g1, "params": p_set,
            "window_losses": w_losses, "window_params": p_win}


def numbers(st: dict, got: dict, ref: dict) -> tuple:
    """({loss_gap, change_gap, window_loss_gap, window_change_gap}, worst
    leaf, leaves compared) of what the program gave (`got`: losses and
    params after set-up, window_losses and window_params) against the
    reference's."""
    gap, worst, kept = check.change_gap(st["p0"], got["params"],
                                        ref["params"], ref["grad"])
    w_gap, w_worst, _ = check.change_gap(st["p0"], got["window_params"],
                                         ref["window_params"], ref["grad"])
    nums = {"loss_gap": check.loss_gap(got["losses"], ref["losses"]),
            "change_gap": gap,
            "window_loss_gap": check.loss_gap(got["window_losses"][-1:],
                                              ref["window_losses"][-1:]),
            "window_change_gap": w_gap}
    return nums, {"setup": worst, "window": w_worst}, kept


def program(st: dict, w: dict) -> dict:
    """What the program gave, in the shape `numbers` reads."""
    return {"losses": st["losses"], "params": st["p_set"],
            "window_losses": w["losses"], "window_params": w["params"]}


def run(cell):
    st = prepare(cell)
    n, k = st["n"], st["window_steps"]
    setup_s = time.monotonic() - cell.t0
    set_up = cell.compiles.snapshot()
    cell.log(phase="setup", setup_s=setup_s, rows=n, setup_steps=st["steps0"],
             losses=st["losses"], step_s=st["step_s"],
             step_ends_s=st["step_ends_s"], window_steps=k)
    # inside setup_s; recorded apart, since only a checkout's first run pays it
    cell.log(phase="first_compile", compiled_s=set_up["compiled_s"],
             cache_misses=set_up["cache_misses"],
             cache_read_s=set_up["cache_read_s"],
             cache_hits=set_up["cache_hits"])

    w = window(cell, st)
    finite = bool(np.isfinite(w["losses"]).all())
    cell.log(phase="window", steps=k, wall_s=w["wall_s"], rows_per_step=n,
             compile_log=w["compile_log"],
             compile_share=w["compile_log"]["backend_compile_s"] / w["wall_s"],
             last_loss=w["losses"][-1] if w["losses"] else None)
    memory = cell.memory_peak()
    del st["model"]
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference(cell, st)
    nums, worst, kept = numbers(st, program(st, w), ref)
    cell.log(phase="reference", seconds=time.perf_counter() - t_ref,
             losses=ref["losses"],
             window_losses=ref["window_losses"], worst_leaf=worst,
             leaves_compared=kept)
    return Outcome(
        attempted=k, failed=0 if finite else k,
        metrics={"train_rows_per_s": n * k / w["wall_s"], "setup_s": setup_s},
        numbers=nums, memory=memory, trace_dir=w["trace_dir"],
        layer={"steps": k, "rows_per_chip": cell.config["rows_per_chip"],
               "work": cell.work.step(cell.config,
                                      cell.config["rows_per_chip"])})
