"""The readers of the program's own host spans (`gp.fit`, `gp.adam.step`):
fit_compiles.train, build_pct.train and build_idle_pct.train."""
import pytest

from bench.harness import cell as cells
from bench.harness.trace import WINDOW_SPAN, Event, Trace

MS = 1e6  # ns
D0, D1 = "/device:TPU:0", "/device:TPU:1"


def _step(i, start, dur, compiles, build_s):
    return Event("gp.adam.step", start, dur,
                 {"_r": 1, "step_num": i, "fit": 7, "compiles": compiles,
                  "build_s": build_s})


def _trace(host_extra=()):
    """A 1 s window; a set-up fit before it and the timed fit inside it.

    The timed fit's step 0 builds 0-100 ms while both devices idle; step 1
    builds 100-300 ms while device 0 runs step 0 until 250 ms and device 1
    until 150 ms; steps 2 and 3 build nothing."""
    ops = {D0: [Event("%suffstats_pallas.1 = custom-call()", 100 * MS,
                      150 * MS),
                Event("%suffstats_bwd_pallas.1 = custom-call()", 320 * MS,
                      600 * MS)],
           D1: [Event("%suffstats_pallas.1 = custom-call()", 100 * MS,
                      50 * MS),
                Event("%suffstats_bwd_pallas.1 = custom-call()", 320 * MS,
                      600 * MS)]}
    host = [Event(WINDOW_SPAN, 0, 1000 * MS),
            # the set-up call, before the window: not counted
            Event("gp.fit", -900 * MS, 800 * MS, {"fit": 6, "compiles": 9}),
            _step(0, -890 * MS, 500 * MS, 9, 0.45),
            Event("gp.fit", 0, 990 * MS,
                  {"fit": 7, "facade": "SparseGPRegression", "compiles": 2,
                   "cache_hits": 2, "cache_misses": 0, "build_s": 0.3}),
            _step(0, 0, 100 * MS, 1, 0.1),
            _step(1, 100 * MS, 200 * MS, 1, 0.2),
            _step(2, 300 * MS, 1 * MS, 0, 0.0),
            _step(3, 301 * MS, 1 * MS, 0, 0.0),
            Event("PjitFunction(step)", 0, 100 * MS)]
    return Trace.build(ops, host + list(host_extra))


def _read(name, t):
    return cells.metric_reader(name)({"trace": t, "chips": len(t.devices)})


def test_fit_compiles_counts_the_window_fit():
    assert _read("fit_compiles.train", _trace()) == 2


def test_build_pct_sums_the_window_steps():
    assert _read("build_pct.train", _trace()) == pytest.approx(
        100 * (0.1 + 0.2) / 1.0)


def test_build_idle_averages_over_devices():
    # step 0: 100 ms idle on both; step 1 (100-300 ms): device 0 idle
    # 250-300 (50 ms), device 1 idle 150-300 (150 ms)
    want = 100 * (0.100 + (0.050 + 0.150) / 2) / 1.0
    assert _read("build_idle_pct.train", _trace()) == pytest.approx(want)
    assert _read("build_idle_pct.train", _trace()) <= _read(
        "idle_pct.train", _trace())


def test_a_step_that_built_nothing_is_not_counted():
    # a step that idles the device but built nothing
    idle_step = _step(4, 930 * MS, 60 * MS, 0, 0.0)
    t = _trace([idle_step])
    want = 100 * (0.100 + (0.050 + 0.150) / 2) / 1.0
    assert _read("build_idle_pct.train", t) == pytest.approx(want)
    assert _read("build_pct.train", t) == pytest.approx(30.0)


def test_spans_are_clipped_to_the_window():
    # a fit whose step 0 began 100 ms before the window and built 150 ms:
    # only its 50 ms inside the window count, as build and as idle time
    ops = {D0: [Event("fusion.1", 50 * MS, 950 * MS)]}
    host = [Event(WINDOW_SPAN, 0, 1000 * MS),
            Event("gp.fit", -100 * MS, 1100 * MS, {"fit": 0, "compiles": 1}),
            _step(0, -100 * MS, 150 * MS, 1, 0.15),
            Event("gp.fit", 1100 * MS, 10 * MS, {"fit": 1, "compiles": 5})]
    t = Trace.build(ops, host)
    assert _read("fit_compiles.train", t) == 1
    assert _read("build_pct.train", t) == pytest.approx(5.0)
    assert _read("build_idle_pct.train", t) == pytest.approx(5.0)


def test_nothing_without_program_spans():
    t = Trace.build({D0: [Event("fusion", 0, 10 * MS)]},
                    [Event(WINDOW_SPAN, 0, 20 * MS),
                     Event("PjitFunction(step)", 0, 5 * MS)])
    for name in ("fit_compiles.train", "build_pct.train",
                 "build_idle_pct.train"):
        assert _read(name, t) is None


def test_no_build_in_the_window_reads_zero():
    t = Trace.build({D0: [Event("fusion", 0, 10 * MS)]},
                    [Event(WINDOW_SPAN, 0, 20 * MS),
                     Event("gp.fit", 0, 12 * MS, {"fit": 0, "compiles": 0}),
                     _step(0, 1 * MS, 1 * MS, 0, 0.0)])
    assert _read("fit_compiles.train", t) == 0
    assert _read("build_pct.train", t) == 0
    assert _read("build_idle_pct.train", t) == 0
