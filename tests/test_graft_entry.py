"""Driver-contract tests: __graft_entry__.entry / dryrun_multichip."""
import os
import sys

import jax
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_env():
    yield
    from paddle_tpu.distributed import env as env_mod

    env_mod.reset_env()


def _graft():
    sys.path.insert(0, _ROOT)
    import __graft_entry__ as g

    return g


def test_entry_jits():
    g = _graft()
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (2, 256, 8192)


@pytest.mark.slow
def test_dryrun_multichip_8():
    g = _graft()
    g.dryrun_multichip(8)


def test_bench_smoke_emits_one_json_line():
    """Driver contract: bench.py prints exactly one parseable JSON line
    with the required keys in an explicit CPU run (JAX_PLATFORMS=cpu),
    and the line says which device it ran on."""
    import json
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py")], env=env,
        cwd=_ROOT, capture_output=True, text=True, timeout=900)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1, proc.stdout + proc.stderr
    rec = json.loads(lines[0])
    assert {"metric", "value", "unit", "vs_baseline"} <= set(rec)
    assert rec["value"] > 0
    assert rec["platform"] == "cpu" and rec["device_kind"]
