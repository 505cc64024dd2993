"""``chip_smoke.py`` rehearsed on the CPU, and the compile cache's
placement rule.

The chip itself is reached only through the builder's chip tool; what
tier-1 can hold is that the same script, asked to rehearse, passes every
phase at tiny size (Pallas in interpret mode), that its four-chip phase
passes on four virtual devices, and that WITHOUT being asked it refuses
to run where JAX finds no TPU. Two rehearsal children and one refusal
child — the script is one process by design, so each run is one child.
"""
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "chip_smoke.py")


def _run(*argv, env_extra=None, cwd=_ROOT, script=_SCRIPT):
    env = {k: v for k, v in os.environ.items()
           # the child decides its own platform and device count
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    env.update(env_extra or {})
    proc = subprocess.run([sys.executable, script, *argv], env=env,
                          cwd=cwd, capture_output=True, text=True,
                          timeout=600)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    return proc, lines


@pytest.fixture(scope="module")
def rehearsal():
    return _run("--rehearse")


@pytest.fixture(scope="module")
def rehearsal4():
    return _run("--rehearse", "--chips", "4")


def test_rehearsal_passes_every_phase(rehearsal):
    proc, lines = rehearsal
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert list(phases) == ["device", "train", "drop_train_state",
                            "serve", "compile_cache"]
    assert all(phases[p]["ok"] for p in
               ("device", "train", "drop_train_state", "serve"))
    # the last line is the contract line, and nothing else is on it
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": last["device"]["kind"], "count": 1}}


def test_rehearsal_train_line(rehearsal):
    train = next(ln for ln in rehearsal[1] if ln.get("phase") == "train")
    assert train["flash"]["engaged"] > 0 and train["flash"]["fallback"] == 0
    assert train["retraces_after_warmup"] == 0
    assert train["losses"][2] < train["losses"][0]
    assert len(train["step_ms_block_until_ready"]) == 3
    assert len(train["step_ms_device_sync"]) == 3


@pytest.mark.parametrize("engine,read_path,held_to", [
    ("default", "row gather", "generate()"),
    ("kv_int8", "row gather", "generate(kv_int8=True)"),
])
def test_rehearsal_serve_engine(rehearsal, engine, read_path, held_to):
    serve = next(ln for ln in rehearsal[1] if ln.get("phase") == "serve")
    e = serve[engine]
    assert e["read_path"] == read_path and e["held_to"] == held_to
    assert e["programs_compiled"] == 3
    assert e["compiles_while_serving"] == 0
    assert e["prefix_hit_tokens"] > 0 and e["spec_accepted_tokens"] > 0
    # identical, or an adjudicated near-tie — never an unexplained diff
    assert e["identical"] + len(e["near_ties"]) == e["of"] == 8
    for tie in e["near_ties"]:
        assert max(tie["gaps"]) <= tie["tol"]


def test_four_chip_phase_on_virtual_devices(rehearsal4):
    proc, lines = rehearsal4
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    # with --chips 4 the script runs this phase and no other
    assert [ln["phase"] for ln in lines if "phase" in ln] == [
        "device", "multichip", "compile_cache"]
    multi = lines[1]
    assert multi["ok"] and multi["max_loss_diff"] <= multi["loss_tolerance"]
    assert len(multi["param_bytes_per_device"]) == 4
    assert multi["dp_collectives"] > 0
    assert {"all-reduce", "all-gather"} & set(multi["mp_collective_ops"])
    assert lines[-1]["ok"] is True and lines[-1]["device"]["count"] == 4


def test_no_tpu_and_no_rehearsal_fails():
    """Not asked to rehearse, no TPU: non-zero exit and ``"ok": false`` —
    it must fail, not continue on the CPU."""
    proc, lines = _run(env_extra={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False
    assert [ln.get("phase") for ln in lines[:-1]] == ["device"]
    assert "no --rehearse" in lines[0]["failed"][0]


def test_script_alone_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is no program to smoke: non-zero exit, no ok line."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(_SCRIPT).read())
    proc, lines = _run("--rehearse", cwd=str(tmp_path), script=str(alone),
                       env_extra={"PYTHONPATH": ""})
    assert proc.returncode != 0
    assert lines[-1] == {"ok": False, "device": None}


@pytest.mark.parametrize("env_dir", [None, "set"])
def test_compile_cache_placement(env_dir, tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code (JAX
    reads the variable itself). Unset: one fixed path in the checkout."""
    import jax

    from paddle_tpu.utils import xla_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: updates.__setitem__(key, value))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert xla_cache.enable_compilation_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in updates
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo_dir = os.path.join(_ROOT, ".jax_cache")
        assert xla_cache.enable_compilation_cache() == repo_dir
        assert updates["jax_compilation_cache_dir"] == repo_dir
        assert os.path.isdir(repo_dir)
