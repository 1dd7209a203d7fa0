"""A run whose timed path is broken underneath comes out not correct: once
for each fault a cell can have. The look for a chip is skipped, and the
rest of the run, reference and comparison included, is the benchmark's."""
import jax.numpy as jnp
import pytest

from bench.tests.tiny import run_cell

TRAIN = ["sgpr-airline.train"]


def _unchanged(monkeypatch):
    from repro.core import inference

    monkeypatch.setattr(inference, "adam_update",
                        lambda grads, state, params, config:
                        (params, state, jnp.zeros(())))


def _half_batch(monkeypatch):
    """The loss over the first half of the rows, the mean over those."""
    from repro.core import distributed

    sgpr = distributed.sgpr_loss_dist

    def sgpr_half(mesh, **kw):
        loss = sgpr(mesh, **kw)
        return lambda p, X, Y: loss(p, X[: len(X) // 2], Y[: len(Y) // 2])

    monkeypatch.setattr(distributed, "sgpr_loss_dist", sgpr_half)


def _calls(monkeypatch, change):
    """Wrap the facade's `fit` so that `change(call number, kwargs)` alters
    the arguments of its calls: the first is set-up's, the second the
    window's."""
    from repro.gp import models

    orig, calls = models.SparseGPRegression.fit, []

    def fit(self, *a, **kw):
        calls.append(kw.get("params"))
        change(len(calls), calls, kw)
        return orig(self, *a, **kw)

    monkeypatch.setattr(models.SparseGPRegression, "fit", fit)


def _window_from_start(monkeypatch):
    """The window's call ignores `params=` and starts from set-up's start."""
    def change(i, calls, kw):
        if i > 1:
            kw["params"] = calls[0]
    _calls(monkeypatch, change)


def _window_one_step(monkeypatch):
    """The window's call does one step, whatever it is asked for. The
    window is asked for three or more, whatever the CPU's step time."""
    from bench.drivers import fit

    prepare = fit.prepare

    def prepare_three(cell):
        st = prepare(cell)
        return dict(st, window_steps=max(st["window_steps"], 3))

    monkeypatch.setattr(fit, "prepare", prepare_three)

    def change(i, calls, kw):
        if i > 1:
            kw["steps"] = 1
    _calls(monkeypatch, change)


@pytest.mark.parametrize(
    "fault", [_unchanged, _half_batch, _window_from_start, _window_one_step],
    ids=["unchanged_state", "half_batch", "window_from_start",
         "window_one_step"])
@pytest.mark.parametrize("name", TRAIN)
def test_training_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    line = run_cell(name)
    assert line["correct"] is False, line["check"]
