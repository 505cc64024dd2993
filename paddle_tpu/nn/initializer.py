"""Weight initializers.

Reference parity: `python/paddle/nn/initializer/` (Constant, Normal,
TruncatedNormal, Uniform, Xavier*, Kaiming*, Assign, Orthogonal, Dirac) —
the reference implements these as ops appended to the startup program /
eager fills; here each initializer is a pure function of (shape, dtype, key).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import dtype as dtype_mod
from ..framework import random as rng
from ..framework.core import Tensor


def calculate_gain(nonlinearity, param=None):
    gains = {
        "sigmoid": 1.0,
        "linear": 1.0,
        "conv1d": 1.0, "conv2d": 1.0, "conv3d": 1.0,
        "conv1d_transpose": 1.0, "conv2d_transpose": 1.0, "conv3d_transpose": 1.0,
        "tanh": 5.0 / 3.0,
        "relu": math.sqrt(2.0),
        "leaky_relu": math.sqrt(2.0 / (1 + (param if param is not None else 0.01) ** 2)),
        "selu": 3.0 / 4.0,
    }
    if nonlinearity not in gains:
        raise ValueError(f"unsupported nonlinearity: {nonlinearity}")
    return gains[nonlinearity]


def _fan_in_out(shape):
    shape = tuple(shape)
    if len(shape) < 2:
        fan_in = fan_out = shape[0] if shape else 1
    else:
        # conv weights are [out_c, in_c, *kernel]; linear is [in, out]
        receptive = math.prod(shape[2:]) if len(shape) > 2 else 1
        if len(shape) > 2:
            fan_in = shape[1] * receptive
            fan_out = shape[0] * receptive
        else:
            fan_in, fan_out = shape[0], shape[1]
    return fan_in, fan_out


class Initializer:
    def __call__(self, shape, dtype=None, key=None):
        raise NotImplementedError

    def _key(self, key):
        return key if key is not None else rng.next_key()


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def __call__(self, shape, dtype=None, key=None):
        d = dtype_mod.convert_dtype(dtype) if dtype else dtype_mod.get_default_dtype()
        return jnp.full(tuple(shape), self.value, d)


class Normal(Initializer):
    """ONE compiled call draws, scales and casts: no float32 array of the
    leaf's size is ever held, so a leaf of gigabytes (stacked expert
    weights) can be born in its dtype beside a nearly full device. ``std``
    0 fills with the mean at no cost (a model whose values a checkpoint or
    a harness is about to replace)."""

    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def __call__(self, shape, dtype=None, key=None):
        d = dtype_mod.convert_dtype(dtype) if dtype else dtype_mod.get_default_dtype()
        if not self.std:
            return jnp.full(tuple(shape), self.mean, d)
        return _normal(self._key(key), self.mean, self.std,
                       shape=tuple(shape), dtype=jnp.dtype(d))


def _normal_impl(key, mean, std, *, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std
            + mean).astype(dtype)


_normal = jax.jit(_normal_impl, static_argnames=("shape", "dtype"))


class TruncatedNormal(Initializer):
    def __init__(self, mean=0.0, std=1.0, a=-2.0, b=2.0):
        self.mean, self.std, self.a, self.b = mean, std, a, b

    def __call__(self, shape, dtype=None, key=None):
        d = dtype_mod.convert_dtype(dtype) if dtype else dtype_mod.get_default_dtype()
        out = jax.random.truncated_normal(
            self._key(key), self.a, self.b, tuple(shape), jnp.float32
        )
        return (out * self.std + self.mean).astype(d)


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0):
        self.low, self.high = low, high

    def __call__(self, shape, dtype=None, key=None):
        d = dtype_mod.convert_dtype(dtype) if dtype else dtype_mod.get_default_dtype()
        out = jax.random.uniform(
            self._key(key), tuple(shape), jnp.float32, self.low, self.high
        )
        return out.astype(d)


class XavierUniform(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype=None, key=None):
        fi, fo = _fan_in_out(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        limit = self.gain * math.sqrt(6.0 / (fi + fo))
        return Uniform(-limit, limit)(shape, dtype, key)


class XavierNormal(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype=None, key=None):
        fi, fo = _fan_in_out(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        std = self.gain * math.sqrt(2.0 / (fi + fo))
        return Normal(0.0, std)(shape, dtype, key)


class KaimingUniform(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in = fan_in
        self.negative_slope = negative_slope
        self.nonlinearity = nonlinearity

    def __call__(self, shape, dtype=None, key=None):
        fi, _ = _fan_in_out(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = calculate_gain(self.nonlinearity, self.negative_slope)
        limit = gain * math.sqrt(3.0 / fi)
        return Uniform(-limit, limit)(shape, dtype, key)


class KaimingNormal(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in = fan_in
        self.negative_slope = negative_slope
        self.nonlinearity = nonlinearity

    def __call__(self, shape, dtype=None, key=None):
        fi, _ = _fan_in_out(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = calculate_gain(self.nonlinearity, self.negative_slope)
        std = gain / math.sqrt(fi)
        return Normal(0.0, std)(shape, dtype, key)


class Assign(Initializer):
    def __init__(self, value):
        self.value = value

    def __call__(self, shape, dtype=None, key=None):
        d = dtype_mod.convert_dtype(dtype) if dtype else dtype_mod.get_default_dtype()
        v = self.value
        if isinstance(v, Tensor):
            v = v.numpy()
        arr = jnp.asarray(np.asarray(v), d)
        if tuple(arr.shape) != tuple(shape):
            arr = arr.reshape(tuple(shape))
        return arr


class Orthogonal(Initializer):
    def __init__(self, gain=1.0):
        self.gain = gain

    def __call__(self, shape, dtype=None, key=None):
        d = dtype_mod.convert_dtype(dtype) if dtype else dtype_mod.get_default_dtype()
        shape = tuple(shape)
        rows = shape[0]
        cols = math.prod(shape[1:])
        flat = jax.random.normal(self._key(key), (max(rows, cols), min(rows, cols)))
        q, r = jnp.linalg.qr(flat)
        q = q * jnp.sign(jnp.diagonal(r))
        if rows < cols:
            q = q.T
        return (self.gain * q[:rows, :cols]).reshape(shape).astype(d)


class Dirac(Initializer):
    def __init__(self, groups=1):
        self.groups = groups

    def __call__(self, shape, dtype=None, key=None):
        d = dtype_mod.convert_dtype(dtype) if dtype else dtype_mod.get_default_dtype()
        out_c, in_c = shape[0], shape[1]
        kernel = shape[2:]
        w = np.zeros(tuple(shape), np.float32)
        center = tuple(k // 2 for k in kernel)
        per_group = out_c // self.groups
        for g in range(self.groups):
            for i in range(min(per_group, in_c)):
                w[(g * per_group + i, i) + center] = 1.0
        return jnp.asarray(w, d)


# paddle aliases
constant = Constant
normal = Normal
uniform = Uniform


def set_global_initializer(weight_init, bias_init=None):
    from . import layer as _layer_mod  # noqa

    _GLOBAL[0] = weight_init
    _GLOBAL[1] = bias_init


_GLOBAL = [None, None]


class Bilinear(Initializer):
    """Bilinear-interpolation kernel init for transposed-conv upsampling
    (parity: paddle.nn.initializer.Bilinear): weight [C_out, C_in, K, K]
    gets the standard bilinear upsampling stencil per channel pair's
    diagonal."""

    def __call__(self, shape, dtype=None, key=None):
        import numpy as np

        d = dtype_mod.convert_dtype(dtype) if dtype \
            else dtype_mod.get_default_dtype()
        if len(shape) != 4:
            raise ValueError(
                f"Bilinear initializer expects a 4-D conv weight, got "
                f"shape {list(shape)}")
        if shape[2] != shape[3]:
            raise ValueError(
                "Bilinear initializer requires a square kernel "
                f"(got {shape[2]}x{shape[3]})")
        kh, kw = shape[2], shape[3]
        f_h = (kh + 1) // 2
        c_h = (2 * f_h - 1 - f_h % 2) / (2.0 * f_h)
        og = np.ogrid[:kh, :kw]
        filt = ((1 - abs(og[0] / f_h - c_h))
                * (1 - abs(og[1] / f_h - c_h)))
        # reference fills EVERY channel pair with the stencil
        # (`nn/initializer/Bilinear.py:108`)
        w = np.broadcast_to(filt, tuple(shape)).astype(np.float32)
        return jnp.asarray(w, d)
