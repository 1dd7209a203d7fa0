"""The least-work counts, at sizes small enough to check by hand."""
import pytest

from bench.work import common, sgpr


def test_sgpr_statistics_one_of_each():
    # forward 2+4+2+2, reverse 2+4+2+2+2+4
    w = sgpr.statistics(1, 1, 1, 1)
    assert (w["fwd_flops"], w["bwd_flops"], w["flops"]) == (10, 16, 26)
    assert w["transcendentals"] == 2


def test_statistics_linear_in_rows():
    a, b = sgpr.statistics(1000, 64, 8, 4), sgpr.statistics(2000, 64, 8, 4)
    assert b["flops"] == 2 * a["flops"]


def test_dominant_terms():
    n, M, Q = 1 << 17, 1024, 8
    # exact statistics: K^T K forward and dK = K G in reverse
    assert sgpr.statistics(n, M, Q, 1)["flops"] == pytest.approx(
        4 * n * M * M, rel=0.03)


def test_step_totals_add_up():
    cfg = {"M": 4, "Q": 2, "D": 3}
    w = sgpr.step(cfg, 10)
    assert w["flops"] == (w["psi_flops"] + w["epilogue_flops"]
                          + w["optimizer_flops"])
    assert w["optimizer_flops"] == 10 * (4 * 2 + 2 + 2)
    e = common.epilogue(4, 2, 3)
    assert e["flops"] == 3 * (2 * 64 / 3 + 2 * 64 + 16 * 3 + 2 * 16 * 2)


def test_transcendentals_are_not_flops():
    w = sgpr.step({"M": 1000, "Q": 8, "D": 1}, 131072)
    assert w["transcendentals"] > 0
    assert w["flops"] == w["psi_flops"] + w["epilogue_flops"] + w[
        "optimizer_flops"]
