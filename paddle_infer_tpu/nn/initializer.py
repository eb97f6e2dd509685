"""Weight initializers (reference: python/paddle/nn/initializer/,
fluid/initializer.py). Each initializer maps (shape, dtype) -> jax array."""
from __future__ import annotations

import contextlib
import functools
import math

import numpy as np
import jax

from ..core import dtype as dtypes
from ..core import random as prandom


def _fans(shape):
    shape = tuple(shape)
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    receptive = int(np.prod(shape[2:]))
    return shape[1] * receptive, shape[0] * receptive


_abstract = False


@contextlib.contextmanager
def abstract_parameters():
    """While active, every initializer returns a ``jax.ShapeDtypeStruct``
    instead of an array: layers are built with their parameters' shapes
    and dtypes, no random bits are drawn and nothing lands on the device.
    For loaders that bind every parameter from a checkpoint right after
    construction — a default-initialised fp32 copy of a model that is
    about to be overwritten can be more than the device holds."""
    global _abstract
    prev, _abstract = _abstract, True
    try:
        yield
    finally:
        _abstract = prev


class Initializer:
    def __init_subclass__(cls, **kw):
        # layers call initializers from several places (create_parameter,
        # the mp layers, MoE stacks): the abstract switch sits on the one
        # thing they all go through
        super().__init_subclass__(**kw)
        call = cls.__dict__.get("__call__")
        if call is None:
            return

        @functools.wraps(call)
        def guarded(self, shape, dtype="float32"):
            if _abstract:
                return jax.ShapeDtypeStruct(tuple(shape),
                                            dtypes.convert_dtype(dtype))
            return call(self, shape, dtype)

        cls.__call__ = guarded

    def __call__(self, shape, dtype="float32"):
        raise NotImplementedError


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def __call__(self, shape, dtype="float32"):
        import jax.numpy as jnp

        return jnp.full(tuple(shape), self.value,
                        dtype=dtypes.convert_dtype(dtype))


class Normal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def __call__(self, shape, dtype="float32"):
        x = jax.random.normal(prandom.next_key(), tuple(shape),
                              dtype=dtypes.convert_dtype(dtype))
        return x * self.std + self.mean


class TruncatedNormal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def __call__(self, shape, dtype="float32"):
        x = jax.random.truncated_normal(prandom.next_key(), -2.0, 2.0,
                                        tuple(shape),
                                        dtype=dtypes.convert_dtype(dtype))
        return x * self.std + self.mean


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0):
        self.low, self.high = low, high

    def __call__(self, shape, dtype="float32"):
        return jax.random.uniform(prandom.next_key(), tuple(shape),
                                  dtype=dtypes.convert_dtype(dtype),
                                  minval=self.low, maxval=self.high)


class XavierUniform(Initializer):
    def __init__(self, fan_in=None, fan_out=None):
        self.fan_in, self.fan_out = fan_in, fan_out

    def __call__(self, shape, dtype="float32"):
        fi, fo = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        limit = math.sqrt(6.0 / (fi + fo))
        return jax.random.uniform(prandom.next_key(), tuple(shape),
                                  dtype=dtypes.convert_dtype(dtype),
                                  minval=-limit, maxval=limit)


class XavierNormal(Initializer):
    def __init__(self, fan_in=None, fan_out=None):
        self.fan_in, self.fan_out = fan_in, fan_out

    def __call__(self, shape, dtype="float32"):
        fi, fo = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        std = math.sqrt(2.0 / (fi + fo))
        return jax.random.normal(prandom.next_key(), tuple(shape),
                                 dtype=dtypes.convert_dtype(dtype)) * std


class KaimingUniform(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in = fan_in
        self.negative_slope = negative_slope

    def __call__(self, shape, dtype="float32"):
        fi, _ = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = math.sqrt(2.0 / (1 + self.negative_slope ** 2))
        limit = gain * math.sqrt(3.0 / fi)
        return jax.random.uniform(prandom.next_key(), tuple(shape),
                                  dtype=dtypes.convert_dtype(dtype),
                                  minval=-limit, maxval=limit)


class KaimingNormal(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in = fan_in
        self.negative_slope = negative_slope

    def __call__(self, shape, dtype="float32"):
        fi, _ = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = math.sqrt(2.0 / (1 + self.negative_slope ** 2))
        std = gain / math.sqrt(fi)
        return jax.random.normal(prandom.next_key(), tuple(shape),
                                 dtype=dtypes.convert_dtype(dtype)) * std


class Assign(Initializer):
    def __init__(self, value):
        self.value = value

    def __call__(self, shape, dtype="float32"):
        import jax.numpy as jnp

        arr = jnp.asarray(np.asarray(self.value),
                          dtype=dtypes.convert_dtype(dtype))
        return arr.reshape(tuple(shape))


class Orthogonal(Initializer):
    """reference nn/initializer/orthogonal.py: QR-based (semi-)orthogonal
    init; rows or columns are orthonormal, scaled by gain."""

    def __init__(self, gain=1.0):
        self.gain = gain

    def __call__(self, shape, dtype="float32"):
        import jax.numpy as jnp

        shape = tuple(shape)
        if len(shape) < 2:
            raise ValueError("Orthogonal requires >= 2 dims")
        rows = shape[0]
        cols = 1
        for s in shape[1:]:
            cols *= s
        flat = (max(rows, cols), min(rows, cols))
        a = jax.random.normal(prandom.next_key(), flat,
                              dtypes.convert_dtype(dtype))
        q, r = jnp.linalg.qr(a)
        # sign correction makes the distribution uniform over O(n)
        q = q * jnp.sign(jnp.diagonal(r))[None, :]
        if rows < cols:
            q = q.T
        return (self.gain * q[:rows, :cols]).reshape(shape)


class Dirac(Initializer):
    """reference nn/initializer/dirac.py: identity-preserving conv init
    (weight[i, i % in, center...] = 1)."""

    def __init__(self, groups=1):
        self.groups = groups

    def __call__(self, shape, dtype="float32"):
        import jax.numpy as jnp

        shape = tuple(shape)
        if len(shape) < 3:
            raise ValueError("Dirac requires a conv weight (>= 3 dims)")
        out_c, in_c = shape[0], shape[1]
        if out_c % self.groups != 0:
            raise ValueError(
                f"out_channels {out_c} not divisible by groups "
                f"{self.groups}")
        w = np.zeros(shape, np.float32)
        centers = tuple(s // 2 for s in shape[2:])
        per_group = out_c // self.groups
        # only min(per_group, in_c) channels per group carry the identity
        # tap; the rest stay zero (reference dirac_ semantics)
        for g in range(self.groups):
            for k in range(min(per_group, in_c)):
                w[(g * per_group + k, k) + centers] = 1.0
        return jnp.asarray(w, dtypes.convert_dtype(dtype))


def calculate_gain(nonlinearity: str, param=None) -> float:
    """Recommended init gain per activation (reference
    fluid/initializer.py calculate_gain; the standard Kaiming table)."""
    ones = {"linear", "conv1d", "conv2d", "conv3d", "conv1d_transpose",
            "conv2d_transpose", "conv3d_transpose", "sigmoid"}
    if nonlinearity in ones:
        return 1.0
    if nonlinearity == "tanh":
        return 5.0 / 3.0
    if nonlinearity == "relu":
        return math.sqrt(2.0)
    if nonlinearity == "leaky_relu":
        a = 0.01 if param is None else float(param)
        return math.sqrt(2.0 / (1.0 + a ** 2))
    if nonlinearity == "selu":
        return 3.0 / 4.0
    raise ValueError(f"unsupported nonlinearity: {nonlinearity!r}")


_GLOBAL_WEIGHT_INIT = None
_GLOBAL_BIAS_INIT = None


def set_global_initializer(weight_init, bias_init=None):
    """Override the default initializers Layer.create_parameter uses when
    no explicit one is given (reference initializer.py
    set_global_initializer).  Pass ``None, None`` to restore defaults."""
    global _GLOBAL_WEIGHT_INIT, _GLOBAL_BIAS_INIT
    _GLOBAL_WEIGHT_INIT = weight_init
    _GLOBAL_BIAS_INIT = bias_init


def _default_initializer(is_bias: bool):
    if is_bias:
        return _GLOBAL_BIAS_INIT if _GLOBAL_BIAS_INIT is not None \
            else Constant(0.0)
    return _GLOBAL_WEIGHT_INIT if _GLOBAL_WEIGHT_INIT is not None \
        else XavierUniform()
