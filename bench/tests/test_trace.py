"""The reduction from trace events to busy time, idle share, layer time and
the breakdown, and the per-layer readers on top of it."""
import pytest

from bench.harness import cell as cells
from bench.harness.trace import WINDOW_SPAN, Event, Trace, union_length

MS = 1e6  # ns


def _trace():
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    ops = {
        d0: [Event("fusion.1", 0 * MS, 10 * MS),
             Event("%suffstats_pallas.1 = (f32[8,8]) custom-call(f32[8,8] "
                   "%copy.2)", 10 * MS, 50 * MS),
             Event("%all-reduce.3 = f32[8,8] all-reduce(f32[8,8] "
                   "%suffstats_pallas.1)", 60 * MS, 2 * MS),
             Event("fusion.4", 61 * MS, 4 * MS),  # overlaps the all-reduce
             Event("%suffstats_bwd_pallas.1 = (f32[8,8]) custom-call()",
                   80 * MS, 40 * MS),
             Event("%divide_fusion = f32[8] fusion(f32[8] "
                   "%jit_suffstats_bwd_pallas_.7)", 120 * MS, 0.5 * MS),
             Event("late", 190 * MS, 30 * MS)],  # half outside the window
        d1: [Event("%suffstats_pallas.1 = (f32[8,8]) custom-call()", 0 * MS,
                   100 * MS),
             Event("%all-reduce.3 = f32[8,8] all-reduce()", 100 * MS, 4 * MS)],
    }
    host = [Event(WINDOW_SPAN, 0, 200 * MS), Event("dispatch", 120 * MS, 50 * MS),
            Event("outside", 300 * MS, 10 * MS)]
    return Trace.build(ops, host)


def test_union_length_merges_overlaps():
    assert union_length([(0, 10), (5, 15), (20, 30)]) == 25
    assert union_length([]) == 0


def test_busy_idle_and_window():
    t = _trace()
    assert t.window_s == pytest.approx(0.2)
    # device 0: 0-60, 60-65, 80-120.5, 190-200 (clipped) = 115.5 ms
    assert t.busy_s("/device:TPU:0") == pytest.approx(0.1155)
    assert t.busy_s("/device:TPU:1") == pytest.approx(0.104)
    assert t.mean_busy_s() == pytest.approx((0.1155 + 0.104) / 2)


def test_layer_attribution():
    from bench.metrics import ops

    t = _trace()
    assert t.op_s("/device:TPU:0", ops.is_psi) == pytest.approx(0.090)
    assert t.op_s("/device:TPU:1", ops.is_psi) == pytest.approx(0.100)
    # a collective is not a statistics op
    assert not ops.is_psi(t.ops["/device:TPU:0"][2])


def test_breakdown():
    t = _trace()
    top = t.top_ops(2)
    assert top[0][0] == "suffstats_pallas.1"
    assert top[0][1] == pytest.approx((0.050 + 0.100) / 2)
    gaps = t.idle_gaps(10)
    # device 0 idle: 65-80 (15 ms, no span), 120.5-190 (69.5 ms, "dispatch")
    assert gaps[0] == ["dispatch", pytest.approx(0.0695)]
    assert gaps[1] == ["no host span", pytest.approx(0.015)]


def _ctx(**kw):
    peaks = cells.device_peaks("TPU v5 lite")
    base = {"trace": _trace(), "chips": 2, "peaks": peaks, "steps": 2,
            "rows_per_chip": 10,
            "work": {"flops": 1e12, "psi_flops": 9e11, "psi_bytes": 1e6}}
    base.update(kw)
    return base


def test_readers():
    read = cells.metric_reader
    c = _ctx()
    mean_busy = (0.1155 + 0.104) / 2
    assert read("idle_pct.train")(c) == pytest.approx(
        100 * (1 - mean_busy / 0.2))
    assert read("step_mfu")(c) == pytest.approx(100 * 2e12 / 0.2 / 197e12)
    psi = (0.090 + 0.100) / 2
    assert read("psi_roofline")(c) == pytest.approx(
        100 * 2 * 9e11 / 197e12 / psi)
    assert read("psi_time_pct")(c) == pytest.approx(100 * psi / mean_busy)


def test_readers_return_nothing_without_their_ops():
    t = Trace.build({"/device:TPU:0": [Event("fusion", 0, 10)]}, [])
    c = _ctx(trace=t)
    assert cells.metric_reader("psi_roofline")(c) is None
    assert cells.metric_reader("psi_time_pct")(c) is None


def _recorded():
    """A TPU v5e trace of a gplvm-q8.train window (4 steps of 65,536 rows),
    kept as instruction names and times (fixtures/gplvm_window.json)."""
    import json
    from pathlib import Path

    fx = json.loads((Path(__file__).parent / "fixtures"
                     / "gplvm_window.json").read_text())
    ev = lambda rows: [Event(n, float(s), float(d)) for n, s, d in rows]
    host = ev(fx["host"]) + [Event(WINDOW_SPAN, 0.0, float(fx["window_ns"]))]
    return Trace.build({fx["device"]: ev(fx["ops"])}, host)


def test_recorded_trace_reduction():
    from bench.metrics import ops

    t = _recorded()
    assert t.window_s == pytest.approx(1.650, abs=1e-3)
    assert t.mean_busy_s() == pytest.approx(0.749, abs=1e-3)
    top = t.top_ops(3)
    assert [name for name, _ in top[:2]] == ["suffstats_bwd_pallas.1",
                                             "suffstats_pallas.1"]
    psi = t.mean_op_s(ops.is_psi)
    assert psi == pytest.approx(top[0][1] + top[1][1], rel=1e-9)
    # the fit call's retrace and reload of the step program idle the chip
    gaps = t.idle_gaps(10)
    assert gaps[0][0] == "PjitFunction(step)" and gaps[0][1] > 0.3
    assert len(gaps) == 10 and all(g[1] > 0 for g in gaps)


def test_recorded_trace_readers():
    t = _recorded()
    ctx = _ctx(trace=t, chips=1, steps=4,
               work={"flops": 3.321e11, "psi_flops": 3.320e11,
                     "psi_bytes": 8.0e7})
    idle = cells.metric_reader("idle_pct.train")(ctx)
    assert idle == pytest.approx(100 * (1 - 0.749 / 1.650), abs=0.1)
    share = cells.metric_reader("psi_time_pct")(ctx)
    assert 95 < share < 100
    roof = cells.metric_reader("psi_roofline")(ctx)
    assert 0 < roof < 100
