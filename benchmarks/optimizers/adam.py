"""Optimizer rule `adam` (Kingma & Ba 2015, with the bias correction
folded into the step size as the reference framework's adam op has it),
written out in numpy float32 for the first step from zero moments. The
sizes come from the configuration file (`learning_rate`, `adam_beta1`,
`adam_beta2`, `adam_epsilon`)."""

import numpy as np

OP_TYPE = "adam"                  # the program's optimizer op
SLOTS = ("Moment1",)     # the accumulator read back after the step


def applied_gradient(config, state):
    """The gradient the first step applied, from the accumulators after
    it: the first moment started at zero, so it is (1 - beta1) g."""
    return state["Moment1"] / np.float32(1.0 - config["adam_beta1"])


def first_update(config, param, grad):
    """The parameter after the first step on `grad`."""
    b1, b2 = config["adam_beta1"], config["adam_beta2"]
    m1 = np.float32(1.0 - b1) * grad
    m2 = np.float32(1.0 - b2) * grad * grad
    lr_t = np.float32(config["learning_rate"] * np.sqrt(1.0 - b2) / (1.0 - b1))
    return param - lr_t * m1 / (np.sqrt(m2) + np.float32(config["adam_epsilon"]))
