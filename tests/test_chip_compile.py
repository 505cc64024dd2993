"""The Pallas kernels held to the chip's own compiler, without the chip.

``check_lowering`` (``jax.export``) stops before the Mosaic / XLA-TPU
compile, so it cannot see a vector layout Mosaic refuses or a kernel
that overflows scoped VMEM — both happened (docs/KERNELS.md "Lowering
pre-flight"). The TPU compiler is installed here and compiles for a chip
that is described and not attached: every ``lowering_cases()`` entry of
every registered kernel — the shapes each ``check_lowering`` lists,
which include the main path's widths (Llama-2-7B training attention,
the 7B engine's paged read, BERT-base) — is compiled for one chip of a
``v5e:2x2`` topology, one parametrised case each. Nothing runs, so this
says nothing about results or speed, and it is never reported as a chip
run.

The persistent compile cache is off around these compiles: an entry
written for a described chip cannot be read back without one, and the
next run would warn and compile again.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

import paddle_tpu.ops.pallas  # noqa: E402,F401 — registers the kernels
from paddle_tpu.ops import registry  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(autouse=True, scope="module")
def _as_on_the_chip():
    """Persistent cache off (module docstring), and the matmul precision
    the chip runs with: conftest pins 'highest' for the numpy-parity
    tests, under which the in-kernel fp32 dots take several MXU passes
    and the tuned 1024-wide flash blocks no longer fit VMEM — a program
    nobody runs."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_default_matmul_precision)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_default_matmul_precision", prev[1])
    cc.reset_cache()


def _cases():
    out = []
    for name, fn in registry.platform_kernels("tpu"):
        for label, case_fn, specs in fn.lowering_cases():
            out.append(pytest.param(case_fn, specs, id=f"{name}-{label}"))
    return out


def test_every_registered_kernel_lists_its_cases():
    """A kernel without ``lowering_cases`` would silently escape the
    compiles below (same contract as ``check_lowering``)."""
    kernels = registry.platform_kernels("tpu")
    assert {n for n, _ in kernels} >= {
        "flash_attention", "flash_attention_headbatch",
        "paged_attention", "paged_attention_int8"}
    for name, fn in kernels:
        assert fn.lowering_cases(), name


@pytest.mark.parametrize("fn,specs", _cases())
def test_kernel_compiles_for_described_v5e(topo, fn, specs):
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_under_a_mesh_compiles_per_shard(topo):
    """XLA refuses to partition a Mosaic kernel ("Mosaic kernels cannot
    be automatically partitioned") — what stopped the dp x mp train step
    on four chips. ``_per_shard`` wraps the call in a shard_map over the
    mesh (batch over 'dp', heads over 'mp'): compiled here for the
    described 2x2, each device's program holds the kernel at the LOCAL
    shape."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
    b, s, h, d = 4, 1024, 8, 128
    spec = NamedSharding(mesh, P("dp", None, "mp", None))
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=spec)

    def local(q, k, v, kadd, seed):
        lb, _, lh, _ = q.shape
        assert (lb, lh) == (b // 2, h // 2)  # the shard, not the whole
        t = [x.transpose(0, 2, 1, 3).reshape(lb * lh, s, d)
             for x in (q, k, v)]
        out = fa._flash_bhsd(*t, True, d ** -0.5, False)
        return out.reshape(lb, lh, s, d).transpose(0, 2, 1, 3)

    def step(q, k, v):
        part = (mesh, "dp", "mp", b // 2, h // 2, h // 2)
        return jax.grad(lambda *a: fa._per_shard(
            local, part, *a, None, None).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(step).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_headbatch_refuses_what_mosaic_refuses():
    """d=64 is out of the head-batched family: a clear error at the
    entry, an empty candidate space in the search — never a Mosaic
    'unsupported shape cast' from inside a run."""
    from paddle_tpu.ops.pallas import head_flash, search

    q = jnp.zeros((2, 512, 12, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="head_dim 64"):
        head_flash.hb_flash(q, q, q, causal=False)
    fam = search.FAMILIES["flash_headbatch"]
    assert all(shape[5] % 128 == 0 for shape in fam.shapes())
