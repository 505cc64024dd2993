"""Pallas TPU kernel overrides (the reference's hand-written CUDA/CUTLASS
kernel layer — `phi/kernels/fusion/`, external flashattn — reimagined as
Mosaic kernels). Importing this package registers every kernel for platform
'tpu'; the registry only selects them when running on TPU."""
from . import autotune as _autotune  # noqa: F401 — registers the flash family
from . import flash_attention as _fa
from . import head_flash as _hf
from . import search  # noqa: F401 — the kernel search harness

_fa.register(platform="tpu")
_hf.register(platform="tpu")

flash_attention_kernel = _fa.flash_attention_kernel
register_flash_attention = _fa.register
hb_flash = _hf.hb_flash


def check_tpu_lowering():
    """Lower every registered Pallas kernel for the TPU platform.

    Runs on any host (no chip needed): ``jax.export(platforms=['tpu'])``
    lowers each kernel to its Mosaic module, including the block-mapping
    checks that interpret mode skips, and raises on the first kernel
    that fails. It is the CHEAP in-process pre-flight
    (``__graft_entry__.entry()``, the bench): it stops before the
    Mosaic / XLA-TPU compile, so it cannot see a layout the compiler
    refuses or a kernel that overflows scoped VMEM. Those are caught by
    compiling the same shapes for a described ``v5e:2x2`` topology —
    ``tests/test_chip_compile.py``, never in a process that holds the
    chip.

    Coverage is registry-driven: each kernel registers a
    ``check_lowering`` self-check attribute alongside itself, so new
    Pallas kernels are covered automatically (a kernel without one is a
    hard error — an unchecked kernel is exactly how round 2 failed).
    """
    from .. import registry

    kernels = registry.platform_kernels("tpu")
    for name, fn in kernels:
        check = getattr(fn, "check_lowering", None)
        if check is None:
            raise RuntimeError(
                f"Pallas kernel {name!r} registered without a "
                f"check_lowering self-check; attach one in its register()")
        check()
