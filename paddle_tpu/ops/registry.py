"""Op registry.

Reference parity: the YAML op registry (`paddle/phi/api/yaml/ops.yaml`) and
kernel registration/dispatch (`PD_REGISTER_KERNEL`,
`phi/core/kernel_registry.h:397` / `KernelFactory::SelectKernelOrThrowError`,
`phi/core/kernel_factory.h:324`).

TPU-first design: there is exactly one "backend" — XLA — so the reference's
(op, backend, layout, dtype) kernel key collapses to the op name, with an
optional per-platform override slot used to swap in Pallas kernels for hot
ops (flash-attention etc.) the way the reference swaps CUDA kernels for
cuDNN/CUTLASS ones. The registry records every op that flows through
:func:`paddle_tpu.ops.dispatch.apply`, giving introspection (`list_ops`) and
a hook point for profiling and AMP without codegen.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from ..framework import device as _device


@dataclass
class OpRecord:
    name: str
    calls: int = 0
    kernels: dict = field(default_factory=dict)  # platform -> callable


_OPS: dict[str, OpRecord] = {}


def _record(name: str) -> OpRecord:
    rec = _OPS.get(name)
    if rec is None:
        rec = _OPS[name] = OpRecord(name)
    return rec


def register_kernel(op_name: str, platform: str = "tpu"):
    """Register a platform-specific kernel override (e.g. a Pallas kernel).

    The override replaces the default jax/XLA implementation when the default
    jax backend matches ``platform``. Signature must match the default
    implementation's ``fn(*arrays, **static)``.
    """

    def deco(fn):
        _record(op_name).kernels[platform] = fn
        return fn

    return deco


def deregister_kernel(op_name: str, platform: str = "tpu"):
    """Drop a platform override so the op falls back to the default XLA
    implementation (tests that registered an interpret-mode kernel for
    the CPU undo it with this)."""
    rec = _OPS.get(op_name)
    if rec is not None:
        rec.kernels.pop(platform, None)


def platform_kernels(platform: str = "tpu"):
    """All (op_name, kernel) overrides registered for ``platform``."""
    return [(name, rec.kernels[platform])
            for name, rec in sorted(_OPS.items())
            if platform in rec.kernels]


def lookup_kernel(op_name: str):
    rec = _OPS.get(op_name)
    if rec is None or not rec.kernels:
        return None
    return rec.kernels.get(_device.platform())


def count_call(op_name: str):
    _record(op_name).calls += 1


def list_ops():
    return sorted(_OPS)


def op_stats():
    return {name: rec.calls for name, rec in sorted(_OPS.items())}
