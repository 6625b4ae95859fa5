"""Optimizer rule `momentum`: heavy-ball momentum without Nesterov
(v <- mu v + g; p <- p - lr v), written out in numpy float32 for the first
step from a zero velocity. The sizes come from the configuration file
(`learning_rate`, `momentum`)."""

import numpy as np

OP_TYPE = "momentum"      # the program's optimizer op, one per parameter
SLOTS = ("Velocity",)     # its accumulators, read back after the step


def applied_gradient(config, state):
    """The gradient the first step applied, from the accumulators after
    it: the velocity started at zero, so it is the gradient."""
    return state["Velocity"]


def first_update(config, param, grad):
    """The parameter after the first step on `grad`."""
    return param - np.float32(config["learning_rate"]) * grad
