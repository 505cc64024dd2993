"""``tools/bench_kda_state.py`` (the kept microbenchmark of the
linear-attention layers' state path, PERF.md section 6, PR 48) at its
``--smoke`` size on the CPU: every form runs, and the kernel agrees with
the step recurrence. No time printed here means anything."""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FORMS = ["xla_verify", "xla_decode", "traversal", "kernel_verify",
         "kernel_decode"]


def _run(capsys, monkeypatch, *argv):
    spec = importlib.util.spec_from_file_location(
        "bench_kda_state", os.path.join(ROOT, "tools", "bench_kda_state.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.setattr(sys, "argv", ["bench_kda_state.py", *argv])
    monkeypatch.setattr(sys, "path", list(sys.path))
    tool.main()
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def test_the_microbenchmark_runs_every_form(capsys, monkeypatch):
    lines = _run(capsys, monkeypatch, "--smoke", "--calls", "1")
    timed = [ln for ln in lines if "form" in ln]
    assert [ln["form"] for ln in timed] == FORMS
    assert all(ln["finite"] and ln["ms"] > 0 and ln["least_ms"] > 0
               for ln in timed)
    (gap,) = [ln["gap"] for ln in lines if "gap" in ln]
    assert gap["out_max"] > 0.1 and gap["state_max"] > 1
    for name in ("verify_out", "verify_state", "decode_out",
                 "decode_state"):
        assert gap[name] < 1e-5, (name, gap)


@pytest.mark.parametrize("form", ["kernel_verify", "kernel_decode"])
def test_one_form_alone(form, capsys, monkeypatch):
    lines = _run(capsys, monkeypatch, "--smoke", "--calls", "1", "--forms",
                 form)
    assert [ln["form"] for ln in lines if "form" in ln] == [form]
