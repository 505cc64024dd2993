"""Test configuration.

Mirrors the reference's test strategy (SURVEY.md §4): single-host, no real
multi-chip hardware — distributed logic is exercised on a *virtual 8-device
CPU mesh* (`xla_force_host_platform_device_count`), the same trick as the
reference's fake `custom_cpu` plugin device (`test/custom_runtime/`).

IMPORTANT: these env vars must be set before jax initializes its backends,
hence this file must not import jax before setting them.
"""
import os

# unit tests run on the virtual CPU mesh whatever the caller's
# environment says; the chip is reached only by chip_smoke.py.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# persistent compilation cache: deep-model tests are compile-dominated on
# the CPU mesh. Same placement rule as every entry point
# (utils/xla_cache.py): JAX_COMPILATION_CACHE_DIR if set, else
# <repo>/.jax_cache
from paddle_tpu.utils.xla_cache import enable_compilation_cache  # noqa: E402

_cache_dir = enable_compilation_cache()
# ... and one directory a pytest-xdist worker: six workers writing one
# directory lost a different token-identity case each run, and two
# builders lost a worker to a segmentation fault inside the cache (ROADMAP
# C10). A serial run keeps the directory above.
if os.environ.get("PYTEST_XDIST_WORKER"):
    _cache_dir = os.path.join(_cache_dir, os.environ["PYTEST_XDIST_WORKER"])
    os.makedirs(_cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _cache_dir)

# blackbox postmortems off by default under pytest: many tests raise
# engine/NaN errors ON PURPOSE (often with the monitor enabled), and each
# would otherwise litter a serving_blackbox.json into the cwd. Tests that
# prove the dump path set PT_SERVE_BLACKBOX to a tmp path explicitly.
os.environ.setdefault("PT_SERVE_BLACKBOX", "0")

# numpy-parity tests need true fp32 contractions; production keeps the fast
# MXU default (bf16 inputs / fp32 accumulate), tunable via paddle flags.
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(autouse=True)
def _seed_all():
    import paddle_tpu

    paddle_tpu.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture(autouse=True, scope="module")
def _reset_auto_mesh():
    """Tear down any mesh a module left behind through the implicit
    ensure_env() path (one module's collective must not put the rest of
    the suite under a surprise 8-device mesh — pytest-randomly exposed
    this). Module-scoped, not per-test: a module fixture's model may
    legitimately live on the auto mesh for the whole module. Explicit
    fleet.init/init_mesh fixtures manage their own teardown."""
    yield
    from paddle_tpu.distributed import env as _env

    e = _env.get_env()
    if e is not None and getattr(e, "auto_initialized", False):
        _env.reset_env()


@pytest.fixture(scope="session")
def mesh_dp2_sep4():
    """The shared 2x4 (dp, sep) mesh for sequence-parallel attention
    tests (ring + ulysses)."""
    from jax.sharding import Mesh

    devs = np.array(jax.devices()).reshape(2, 4)
    return Mesh(devs, ("dp", "sep"))


def attn_qkv(b=2, s=64, h=2, d=16, seed=0):
    """Deterministic [b, s, h, d] q/k/v triples for attention parity."""
    rng = np.random.RandomState(seed)
    return (rng.randn(b, s, h, d).astype(np.float32) for _ in range(3))
