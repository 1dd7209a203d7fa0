"""The GP-LVM's least-work counts, at sizes small enough to check by hand."""
from bench.work import common, gplvm


def test_statistics_one_of_each():
    # forward 8+4+4+2+4+2+3+2, reverse 4+4+2+1+8+4+3+8+8
    w = gplvm.statistics(1, 1, 1, 1)
    assert (w["fwd_flops"], w["bwd_flops"], w["flops"]) == (29, 42, 71)
    assert w["transcendentals"] == 3 + 2 + 2 + 1


def test_pairs_dominate_and_rows_scale():
    n, M, Q, D = 1 << 16, 256, 8, 128
    w = gplvm.statistics(n, M, Q, D)
    # 4 n M^2 Q forward, 12 n M^2 Q reverse
    assert abs(w["flops"] / (16 * n * M * M * Q) - 1) < 0.08
    assert w["transcendentals"] > 2 * n * M * M
    w2 = gplvm.statistics(2 * n, M, Q, D)
    assert abs(w2["flops"] / w["flops"] - 2) < 1e-4


def test_step_counts_q_of_x_in_the_optimizer():
    cfg = {"M": 4, "Q": 2, "D": 3}
    w = gplvm.step(cfg, 10)
    assert w["optimizer_flops"] == 10 * (10 * 2 * 2 + 4 * 2 + 2 + 2)
    assert w["flops"] == (w["psi_flops"] + w["epilogue_flops"]
                          + w["optimizer_flops"])
    assert w["epilogue_flops"] == common.epilogue(4, 2, 3)["flops"]
