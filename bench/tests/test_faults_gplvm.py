"""A GP-LVM run whose timed path is broken underneath comes out not correct:
once for each fault of the model kind. The look for a chip is skipped, and
the rest of the run, reference and comparison included, is the benchmark's.
"""
import jax
import jax.numpy as jnp
import pytest

from bench.tests.tiny import run_cell

TRAIN = ["gplvm-q8.train"]
LOCAL = ("q_mu", "q_logS")


def _wrap_loss(monkeypatch, change):
    """The facade's loss, built through `change(loss)`."""
    from repro.core import distributed

    build = distributed.gplvm_loss_dist
    monkeypatch.setattr(distributed, "gplvm_loss_dist",
                        lambda mesh, **kw: change(build(mesh, **kw)))


def _frozen_q(monkeypatch):
    """q(X) takes no gradient, so Adam leaves q_mu and q_logS where they
    started."""
    def change(loss):
        return lambda p, Y: loss(
            dict(p, **{k: jax.lax.stop_gradient(p[k]) for k in LOCAL}), Y)
    _wrap_loss(monkeypatch, change)


def _no_kl(monkeypatch):
    """The bound without its KL term."""
    from repro.core import gplvm

    monkeypatch.setattr(gplvm, "kl_qp", lambda mu, logS: jnp.zeros((), mu.dtype))


def _half_rows(monkeypatch):
    """The loss over the first half of the rows and their q(X), the mean
    over those."""
    def change(loss):
        def half(p, Y):
            h = len(Y) // 2
            return loss(dict(p, **{k: p[k][:h] for k in LOCAL}), Y[:h])
        return half
    _wrap_loss(monkeypatch, change)


@pytest.mark.parametrize("fault", [_frozen_q, _no_kl, _half_rows],
                         ids=["frozen_q", "no_kl", "half_rows"])
@pytest.mark.parametrize("name", TRAIN)
def test_gplvm_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    line = run_cell(name)
    assert line["correct"] is False, line["check"]
