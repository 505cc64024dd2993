"""utils/timing.device_sync — the transfer-backed fence every wall-clock
measurement in this repo relies on (a fetched value cannot arrive before
the work that produces it has finished)."""
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.utils.timing import device_sync


def test_returns_input_unchanged():
    x = jnp.arange(6.0).reshape(2, 3)
    out = device_sync(x)
    assert out is x
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_pytree_and_scalar_and_empty():
    tree = {"a": jnp.ones((3,)), "b": [jnp.zeros(())]}
    assert device_sync(tree) is tree
    assert device_sync(jnp.float32(2.0)) is not None
    # no array leaves: must not raise
    assert device_sync({"note": "no arrays"}) is not None
    assert device_sync(None) is None


def test_fences_computation():
    # after device_sync the value must be host-readable instantly and
    # correct — i.e. the computation actually ran
    y = device_sync(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    assert float(np.asarray(y)[0, 0]) == 64.0


@pytest.mark.slow
def test_longcontext_bench_smoke_emits_json():
    import pathlib

    root = str(pathlib.Path(__file__).resolve().parents[1])
    proc = subprocess.run(
        [sys.executable, "benchmarks/longcontext_bench.py", "--smoke"],
        capture_output=True, text=True, timeout=600, cwd=root)
    assert proc.returncode == 0, proc.stderr[-500:]
    import json

    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert lines, proc.stdout
    rec = json.loads(lines[-1])
    assert rec["metric"] == "llama_longcontext_train_tokens_per_sec_per_chip"
    assert rec["value"] > 0
