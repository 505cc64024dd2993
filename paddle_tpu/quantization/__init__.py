"""Quantization: QAT fake-quant + PTQ observers.

Reference parity: `python/paddle/quantization/` — `QuantConfig`, `QAT`
(fake-quant insertion with straight-through estimator), `PTQ` (observer
collection + convert), quanted layer variants.

TPU-first design: int8 matmuls on TPU go through XLA's native int8 MXU path;
fake-quant here is the standard symmetric per-tensor/per-channel STE
(quantize→dequantize in the forward, identity gradient), so a QAT model
trains in one compiled step like any other model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor
from ..nn import functional as F
from ..nn.layer.common import Linear
from ..nn.layer.conv import Conv2D
from ..nn.layer.layers import Layer
from ..ops.dispatch import apply

__all__ = ["QuantConfig", "QAT", "PTQ", "FakeQuanterWithAbsMax",
           "AbsmaxObserver", "quant_dequant", "Int8Linear",
           "convert_to_int8", "quantize_weight_int8",
           "quantize_kv", "dequantize_kv"]


def _fake_quant(x, scale, bits=8):
    qmax = 2.0 ** (bits - 1) - 1
    s = jnp.maximum(scale, 1e-9) / qmax
    q = jnp.clip(jnp.round(x / s), -qmax - 1, qmax)
    deq = q * s
    # straight-through estimator: forward uses deq, gradient sees identity
    return x + jax.lax.stop_gradient(deq - x)


def quant_dequant(x, scale, bits=8):
    return apply("fake_quant",
                 lambda a, sc: _fake_quant(a, sc, bits), (x, scale))


class FakeQuanterWithAbsMax(Layer):
    """Parity: FakeQuanterWithAbsMaxObserver — running abs-max scale +
    quant/dequant with STE."""

    def __init__(self, moving_rate=0.9, bit_length=8, name=None):
        super().__init__()
        self.moving_rate = moving_rate
        self.bit_length = bit_length
        self.register_buffer("scale", Tensor(jnp.ones(())))
        self._initialized = False

    def forward(self, x):
        if self.training:
            cur = jnp.max(jnp.abs(x._data)).astype(jnp.float32)
            if not self._initialized:
                new = cur
                self._initialized = True
            else:
                new = (self.moving_rate * self.scale._data
                       + (1 - self.moving_rate) * cur)
            self.scale._data = jax.lax.stop_gradient(new)
        return quant_dequant(x, self.scale, self.bit_length)


class AbsmaxObserver(Layer):
    """PTQ observer: tracks abs-max without quantizing."""

    def __init__(self, quant_bits=8):
        super().__init__()
        self.quant_bits = quant_bits
        self.register_buffer("scale", Tensor(jnp.zeros(())))

    def forward(self, x):
        cur = jnp.max(jnp.abs(x._data)).astype(jnp.float32)
        self.scale._data = jnp.maximum(self.scale._data, cur)
        return x

    def cal_thresholds(self):
        return float(np.asarray(self.scale._data))


class QuantedLinear(Layer):
    def __init__(self, inner: Linear, activation_quanter, weight_quanter):
        super().__init__()
        self.inner = inner
        self.weight = inner.weight
        self.bias = inner.bias
        self.activation_quanter = activation_quanter
        self.weight_quanter = weight_quanter

    def forward(self, x):
        if self.activation_quanter is not None:
            x = self.activation_quanter(x)
        w = self.weight
        if self.weight_quanter is not None:
            w = self.weight_quanter(w)
        return F.linear(x, w, self.bias)


class QuantConfig:
    """Parity: `paddle.quantization.QuantConfig` — maps layer types to
    quanter factories."""

    def __init__(self, activation=None, weight=None):
        self.activation = self._resolve(activation) \
            or (lambda: FakeQuanterWithAbsMax())
        self.weight = self._resolve(weight) \
            or (lambda: FakeQuanterWithAbsMax())
        self._types = (Linear, Conv2D)

    @staticmethod
    def _resolve(q):
        """Accept a factory callable or a name registered via
        @quanter(name)."""
        if isinstance(q, str):
            try:
                return _QUANTER_REGISTRY[q]
            except KeyError:
                raise ValueError(
                    f"no quanter registered under {q!r}; register with "
                    "@paddle.quantization.quanter(name)") from None
        return q

    def add_type_config(self, layer_types, activation=None, weight=None):
        if not isinstance(layer_types, (list, tuple)):
            layer_types = [layer_types]
        self._types = tuple(set(self._types) | set(layer_types))
        if activation:
            self.activation = self._resolve(activation)
        if weight:
            self.weight = self._resolve(weight)


def _swap_layers(model, config, act_factory, w_factory):
    for name, sub in list(model._sub_layers.items()):
        if isinstance(sub, Linear):
            model._sub_layers[name] = QuantedLinear(
                sub, act_factory(), w_factory())
            object.__setattr__(model, name, model._sub_layers[name])
        else:
            _swap_layers(sub, config, act_factory, w_factory)
    return model


class QAT:
    """Parity: `paddle.quantization.QAT(config).quantize(model)`."""

    def __init__(self, config: QuantConfig | None = None):
        self.config = config or QuantConfig()

    def quantize(self, model, inplace=True):
        return _swap_layers(model, self.config, self.config.activation,
                            self.config.weight)

    def convert(self, model, inplace=True):
        return model


class PTQ:
    """Parity: `paddle.quantization.PTQ` — insert observers, calibrate with
    data, then freeze scales into fake-quant layers."""

    def __init__(self, config: QuantConfig | None = None):
        self.config = config or QuantConfig(
            activation=lambda: AbsmaxObserver(),
            weight=lambda: AbsmaxObserver())

    def quantize(self, model, inplace=True):
        return _swap_layers(model, self.config, self.config.activation,
                            self.config.weight)

    def convert(self, model, inplace=True):
        """Replace observers with fixed-scale fake quanters."""
        for sub in model.sublayers():
            if isinstance(sub, QuantedLinear):
                for attr in ("activation_quanter", "weight_quanter"):
                    obs = getattr(sub, attr)
                    if isinstance(obs, AbsmaxObserver):
                        fq = FakeQuanterWithAbsMax(moving_rate=1.0)
                        fq.scale._data = obs.scale._data
                        fq._initialized = True
                        fq.eval()
                        setattr(sub, attr, fq)
        return model


class BaseObserver(Layer):
    """Parity: paddle.quantization.BaseObserver — subclass and implement
    forward() to collect statistics and scales()."""

    def scales(self):
        raise NotImplementedError

    def zero_points(self):
        return None


class BaseQuanter(Layer):
    """Parity: paddle.quantization.BaseQuanter — a trainable fake-quant
    layer base (FakeQuanterWithAbsMax is the in-tree subclass)."""

    def scales(self):
        raise NotImplementedError

    def zero_points(self):
        return None


def quanter(name):
    """Parity: paddle.quantization.quanter — class decorator registering a
    quanter under `name` so QuantConfig can refer to it by string."""
    def deco(cls):
        _QUANTER_REGISTRY[name] = cls
        return cls

    return deco


_QUANTER_REGISTRY: dict = {}

__all__ += ["BaseObserver", "BaseQuanter", "quanter"]


def quantize_weight_int8(w):
    """Per-output-channel symmetric int8 weight-only quantization —
    THE shared helper (models/generation decode packs and Int8Linear
    both use it, so the decode path and the inference layer cannot
    diverge on scale/clip semantics). w [..., in, out] ->
    {"q": int8 same shape, "s": fp32 [..., 1, out]}."""
    w32 = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-12)
    q = jnp.clip(jnp.round(w32 / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s}


def quantize_kv(x):
    """Per-position symmetric int8 KV quantization — THE shared helper
    for the int8 KV-cache path (`PT_SERVE_KV_INT8`): the serving
    engine's quantize-on-write
    (`serving/families/dense_gqa.py:_pool_forward`) and the reference
    round-trip (`models/generation.py` ``kv_int8=True``) both route
    through it, so the two paths cannot diverge on scale/clip
    semantics. Amax is over the trailing head_dim axis: x [..., d] ->
    (q int8 [..., d], s fp32 [...]) — one scale per (position, kv_head),
    which is exactly per (layer, block, slot, kv_head) once written into
    the block pool, so scales are content-derived and shared prefix
    blocks share their scales."""
    x32 = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x32), axis=-1) / 127.0
    s = jnp.maximum(s, 1e-12)
    q = jnp.clip(jnp.round(x32 / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def dequantize_kv(q, s, dtype):
    """Inverse of :func:`quantize_kv`: q int8 [..., d] and s fp32 [...]
    back to ``dtype``. fp32 multiply then one cast — bit-identical
    whether it runs in the engine's row read or the reference
    round-trip."""
    return (q.astype(jnp.float32) * s[..., None]).astype(dtype)


def _int8_linear_fn(xa, wq, ws, ba=None, *, mode="weight_only",
                    act_scale=None):
    if mode == "int8":
        a_s = jnp.float32(act_scale / 127.0)
        xq = jnp.clip(jnp.round(xa.astype(jnp.float32) / a_s),
                      -127, 127).astype(jnp.int8)
        acc = jax.lax.dot_general(
            xq, wq, (((xq.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
        y = acc.astype(jnp.float32) * (ws * a_s)
    else:
        y = jax.lax.dot_general(
            xa, wq.astype(xa.dtype),
            (((xa.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * ws.astype(jnp.float32)
    y = y.astype(xa.dtype)
    if ba is not None:
        y = y + ba
    return y


class Int8Linear(Layer):
    """True int8-EXECUTING linear (not fake-quant simulation).

    Reference parity: the reference runs QAT/PTQ output through
    quantized PHI kernels / TRT int8 (`paddle/fluid/inference/tensorrt`,
    quantized GPU ops); here the execution paths are XLA-native:

    - ``mode='weight_only'``: weights stored per-output-channel int8 and
      dequantized in-register inside the matmul — HBM weight traffic
      halves vs bf16 (the decode-bandwidth lever; identical math to
      `models/generation._mm`).
    - ``mode='int8'``: activations are ALSO quantized (per-tensor, the
      PTQ-calibrated scale) and the product runs as an s8 x s8 -> s32
      `lax.dot_general`, hitting the int8 MXU peak (~2x bf16 on v5e);
      the s32 accumulator is rescaled by act_scale * w_scale.
    """

    def __init__(self, inner: Linear, act_scale=None, mode="weight_only"):
        super().__init__()
        if mode not in ("weight_only", "int8"):
            raise ValueError(f"Int8Linear mode {mode!r}")
        if mode == "int8" and act_scale is None:
            raise ValueError(
                "mode='int8' needs a calibrated activation scale (run "
                "PTQ, then convert_to_int8(model, mode='int8'))")
        self.mode = mode
        pack = quantize_weight_int8(inner.weight._data)  # [in, out]
        self.register_buffer("w_q", Tensor(pack["q"]))
        self.register_buffer("w_scale", Tensor(pack["s"]))
        self.bias = inner.bias
        self.act_scale = (float(act_scale)
                          if act_scale is not None else None)

    def forward(self, x):
        # per-layer state travels as STATIC kwargs on a module-level fn:
        # a closure over `self` would key the dispatch primitive cache by
        # instance identity, pinning every converted layer's weights in
        # the (eviction-free) cache and compiling one jit per instance
        args = (x, self.w_q, self.w_scale)
        if self.bias is not None:
            args = args + (self.bias,)
        return apply("int8_linear", _int8_linear_fn, args,
                     mode=self.mode, act_scale=self.act_scale)


def convert_to_int8(model, mode="weight_only", inplace=True):
    """Replace quantized (or plain) Linear layers with int8-EXECUTING
    `Int8Linear`. `QuantedLinear` layers (PTQ/QAT output) contribute
    their calibrated activation scale for ``mode='int8'``; plain Linear
    layers convert in ``weight_only`` mode only (no activation scale).
    ``inplace=False`` deep-copies first so the caller keeps the fp
    model (the A/B case).
    """
    if not inplace:
        import copy

        model = copy.deepcopy(model)
    for name, sub in list(model._sub_layers.items()):
        if isinstance(sub, QuantedLinear):
            act_scale = None
            q = sub.activation_quanter
            if q is not None and hasattr(q, "scale"):
                act_scale = float(np.asarray(q.scale._data))
                if act_scale <= 0:
                    act_scale = None
            layer_mode = mode
            if mode == "int8" and act_scale is None:
                # uncalibrated observer (no calibration forward ran):
                # stay numerically safe, but say so — a silently
                # downgraded model benches bf16 matmuls while the user
                # expects the int8 MXU path
                import warnings

                warnings.warn(
                    f"convert_to_int8: layer {name!r} has no calibrated "
                    "activation scale (did the PTQ calibration forward "
                    "run?); downgrading it to weight_only",
                    stacklevel=2)
                layer_mode = "weight_only"
            new = Int8Linear(sub.inner, act_scale, layer_mode)
            model._sub_layers[name] = new
            object.__setattr__(model, name, new)
        elif isinstance(sub, Linear):
            if mode == "weight_only":
                new = Int8Linear(sub, None, "weight_only")
                model._sub_layers[name] = new
                object.__setattr__(model, name, new)
        else:
            convert_to_int8(sub, mode, inplace=True)
    return model
