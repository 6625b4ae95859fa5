"""Parameter initializers emitting init ops onto the startup program
(reference: python/paddle/fluid/initializer.py:27-338)."""

from __future__ import annotations

import math

import numpy as np

__all__ = [
    "Constant", "Uniform", "Normal", "Xavier", "MSRA",
    "ConstantInitializer", "UniformInitializer", "NormalInitializer",
    "XavierInitializer", "MSRAInitializer", "LogOfUniformInitializer",
    "SoftplusInverseLogUniformInitializer", "ValuesInitializer",
    "force_init_on_cpu", "init_on_cpu",
]

import contextlib

_force_init_on_cpu = False


def force_init_on_cpu() -> bool:
    return _force_init_on_cpu


@contextlib.contextmanager
def init_on_cpu():
    """Kept for API parity: on TPU the executor places init where the program
    runs, so this is a no-op marker (reference initializer.py init_on_cpu)."""
    global _force_init_on_cpu
    old, _force_init_on_cpu = _force_init_on_cpu, True
    try:
        yield
    finally:
        _force_init_on_cpu = old


class Initializer:
    def __call__(self, var, block):
        raise NotImplementedError


class ConstantInitializer(Initializer):
    def __init__(self, value=0.0, force_cpu=False):
        self.value = value

    def __call__(self, var, block):
        return block.append_op(
            type="fill_constant", outputs={"Out": var},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "value": float(self.value)})


class UniformInitializer(Initializer):
    def __init__(self, low=-1.0, high=1.0, seed=0):
        self.low, self.high, self.seed = low, high, seed

    def __call__(self, var, block):
        return block.append_op(
            type="uniform_random", outputs={"Out": var},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "min": self.low, "max": self.high, "seed": self.seed})


class NormalInitializer(Initializer):
    def __init__(self, loc=0.0, scale=1.0, seed=0):
        self.loc, self.scale, self.seed = loc, scale, seed

    def __call__(self, var, block):
        return block.append_op(
            type="gaussian_random", outputs={"Out": var},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "mean": self.loc, "std": self.scale, "seed": self.seed})


def _fan_in_out(var):
    shape = var.shape
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    receptive = int(np.prod(shape[2:])) if len(shape) > 2 else 1
    fan_in = shape[1] * receptive
    fan_out = shape[0] * receptive
    return fan_in, fan_out


class XavierInitializer(Initializer):
    """Glorot init (reference initializer.py XavierInitializer)."""

    def __init__(self, uniform=True, fan_in=None, fan_out=None, seed=0):
        self.uniform, self.fan_in, self.fan_out, self.seed = (
            uniform, fan_in, fan_out, seed)

    def __call__(self, var, block):
        fi, fo = _fan_in_out(var)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        if self.uniform:
            limit = math.sqrt(6.0 / (fi + fo))
            return UniformInitializer(-limit, limit, self.seed)(var, block)
        std = math.sqrt(2.0 / (fi + fo))
        return NormalInitializer(0.0, std, self.seed)(var, block)


class MSRAInitializer(Initializer):
    """He init (reference initializer.py MSRAInitializer)."""

    def __init__(self, uniform=True, fan_in=None, seed=0):
        self.uniform, self.fan_in, self.seed = uniform, fan_in, seed

    def __call__(self, var, block):
        fi, _ = _fan_in_out(var)
        fi = self.fan_in if self.fan_in is not None else fi
        if self.uniform:
            limit = math.sqrt(6.0 / fi)
            return UniformInitializer(-limit, limit, self.seed)(var, block)
        std = math.sqrt(2.0 / fi)
        return NormalInitializer(0.0, std, self.seed)(var, block)


def _in_place(block, var, op_type, **attrs):
    return block.append_op(type=op_type, inputs={"X": var},
                           outputs={"Out": var}, attrs=attrs)


class LogOfUniformInitializer(Initializer):
    """log(u), u ~ U(low, high): Mamba-2's `A_log` (A = -exp(A_log) is
    then -u; published range 1 to 16)."""

    def __init__(self, low=1.0, high=16.0, seed=0):
        self.low, self.high, self.seed = low, high, seed

    def __call__(self, var, block):
        UniformInitializer(self.low, self.high, self.seed)(var, block)
        return _in_place(block, var, "log")


class SoftplusInverseLogUniformInitializer(Initializer):
    """softplus^-1(dt0) = log(exp(dt0) - 1) with dt0 log-uniform in
    [low, high] and floored at `floor`: Mamba-2's `dt_bias`, so that the
    step size softplus(dt_bias) starts at dt0 (published 0.001 to 0.1,
    floor 1e-4). In float32 exp(dt0) - 1 is off by up to 1e-3 of itself
    at the floor: noise in an initial value, not in the model."""

    def __init__(self, low=0.001, high=0.1, floor=1e-4, seed=0):
        self.low, self.high, self.floor, self.seed = low, high, floor, seed

    def __call__(self, var, block):
        UniformInitializer(math.log(self.low), math.log(self.high),
                           self.seed)(var, block)
        _in_place(block, var, "exp")
        _in_place(block, var, "clip", min=self.floor, max=float("inf"))
        _in_place(block, var, "exp")
        _in_place(block, var, "scale", scale=1.0, bias=-1.0)
        return _in_place(block, var, "log")


class ValuesInitializer(Initializer):
    """The given float values, element for element (a bias that starts
    at a logit, an identity's multiple): `assign_value` of the list."""

    def __init__(self, values):
        self.values = [float(v) for v in values]

    def __call__(self, var, block):
        assert len(self.values) == int(np.prod(var.shape)), var.shape
        return block.append_op(
            type="assign_value", outputs={"Out": var},
            attrs={"shape": list(var.shape), "dtype": var.dtype,
                   "fp32_values": self.values})


Constant = ConstantInitializer
Uniform = UniformInitializer
Normal = NormalInitializer
Xavier = XavierInitializer
MSRA = MSRAInitializer
