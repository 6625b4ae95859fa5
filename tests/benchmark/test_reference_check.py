"""reference_check.compare() on hand-made arrays: the gate that decides
`correct` must fail a wrong loss, a wrong gradient and a wrong update,
each alone, and pass what is within its tolerances. The optimizer rules
are held to their textbook first step."""

import numpy as np
import pytest

from benchmarks import reference_check

TOL = {"loss_rtol": 1e-3, "grad_rtol": 0.05, "grad_norm_rtol": 0.02,
       "grad_tail_rtol": 0.05, "update_rtol": 1e-3}
MOMENTUM = {"optimizer": "momentum", "learning_rate": 0.1, "momentum": 0.9}
ADAM = {"optimizer": "adam", "learning_rate": 1e-3, "adam_beta1": 0.9,
        "adam_beta2": 0.999, "adam_epsilon": 1e-8}


def _first_step(config, grad_noise=0.0, update_scale=1.0, seed=0):
    """(rule, kwargs of compare) for two parameters stepped once by the
    rule itself on the reference gradient plus noise."""
    rng = np.random.default_rng(seed)
    rule = reference_check.rule_of(config)
    names = ["w", "b"]
    before = [rng.standard_normal((8, 4)).astype(np.float32),
              rng.standard_normal(4).astype(np.float32)]
    ref_grads = [rng.standard_normal(p.shape).astype(np.float32)
                 for p in before]
    grads = [g + grad_noise * rng.standard_normal(g.shape).astype(np.float32)
             for g in ref_grads]
    after = [p + update_scale * (rule.first_update(config, p, g) - p)
             for p, g in zip(before, grads)]
    if config["optimizer"] == "momentum":
        state = {n: {"Velocity": g} for n, g in zip(names, grads)}
    else:
        state = {n: {"Moment1": np.float32(0.1) * g,
                     "Moment2": np.float32(0.001) * g * g}
                 for n, g in zip(names, grads)}
    return rule, dict(config=config, names=names, before=before, after=after,
                      state=state, ref_grads=ref_grads, tol=TOL)


@pytest.mark.parametrize("config", [MOMENTUM, ADAM],
                         ids=lambda c: c["optimizer"])
@pytest.mark.parametrize("fault,ok", [
    ({}, True),
    ({"grad_noise": 0.01}, True),              # 1 % off: inside 5 %
    ({"grad_noise": 0.2}, False),              # a wrong backward pass
    ({"update_scale": 1.01}, False),           # a wrong optimizer apply
    ({"loss": 2.31}, False),                   # a wrong forward pass
], ids=["exact", "noise-within", "gradient", "update", "loss"])
def test_compare_fails_each_fault_alone(config, fault, ok):
    fault = dict(fault)
    loss = fault.pop("loss", 2.3)
    rule, kwargs = _first_step(config, **fault)
    found = reference_check.compare(rule, loss=loss, ref_loss=2.3, **kwargs)
    assert found["ok"] is ok
    assert found["tolerances"] == TOL
    if "grad_noise" in fault:
        assert found["grad_rel_err"] == pytest.approx(fault["grad_noise"],
                                                      rel=0.5)
        assert found["grad_err_mostly_in"][0] == "w"


def test_a_tolerance_of_null_is_not_held_and_the_rest_are():
    """A cell whose gradient direction cannot be held (its file says why)
    still fails on the gradient's size and on the head's gradient."""
    loose = dict(TOL, grad_rtol=None)
    rule, kwargs = _first_step(MOMENTUM, grad_noise=0.2)
    kwargs["tol"] = dict(loose, grad_tail_rtol=0.5)
    found = reference_check.compare(rule, loss=2.3, ref_loss=2.3, **kwargs)
    assert found["ok"] and found["grad_rel_err"] > TOL["grad_rtol"]
    kwargs["tol"] = loose                     # the head's gradient: 20 % off
    assert not reference_check.compare(rule, loss=2.3, ref_loss=2.3,
                                       **kwargs)["ok"]
    kwargs["state"] = {n: {"Velocity": 1.5 * s["Velocity"]}
                       for n, s in kwargs["state"].items()}
    kwargs["tol"] = dict(loose, grad_tail_rtol=None, update_rtol=None)
    found = reference_check.compare(rule, loss=2.3, ref_loss=2.3, **kwargs)
    assert not found["ok"] and found["grad_norm_rel_diff"] > 0.4


def test_momentum_first_step_is_plain_sgd():
    rule = reference_check.rule_of(MOMENTUM)
    p, g = np.float32([1.0, -2.0]), np.float32([0.5, 0.25])
    assert rule.first_update(MOMENTUM, p, g) == pytest.approx([0.95, -2.025])
    assert rule.applied_gradient(MOMENTUM, {"Velocity": g}) is g
    assert rule.SLOTS == ("Velocity",)


def test_adam_first_step_moves_each_weight_by_the_learning_rate():
    rule = reference_check.rule_of(ADAM)
    p, g = np.float32([1.0, -2.0, 3.0]), np.float32([0.5, -0.25, 1e-3])
    moved = rule.first_update(ADAM, p, g) - p
    assert moved == pytest.approx(-1e-3 * np.sign(g), rel=1e-3)
    assert rule.applied_gradient(
        ADAM, {"Moment1": np.float32(0.1) * g}) == pytest.approx(g)
