"""``tools/bench_row_read.py`` (the kept single-call microbenchmark of one
layer's live-rows read, PERF.md section 6, PR 47) at its ``--smoke``
size on the CPU: every family's calls run, the reads a tree has are the
ones timed, and the kernel and the XLA read agree on what they read. No
time printed here means anything."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(capsys, monkeypatch, *argv):
    spec = importlib.util.spec_from_file_location(
        "bench_row_read", os.path.join(ROOT, "tools", "bench_row_read.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    monkeypatch.setattr(sys, "argv", ["bench_row_read.py", *argv])
    monkeypatch.setattr(sys, "path", list(sys.path))
    tool.main()
    return tool, [json.loads(ln) for ln in
                  capsys.readouterr().out.splitlines() if ln.startswith("{")]


@pytest.mark.parametrize("family,reads", [
    ("latent_moe", {"kernel"}), ("linear_latent_moe", {"kernel"}),
    ("hybrid_ssm", {"kernel", "xla"}), ("window_moe", {"kernel"})])
def test_the_microbenchmark_runs_every_call_of_a_family(
        family, reads, capsys, monkeypatch):
    tool, lines = _run(capsys, monkeypatch, "--smoke", "--calls", "1",
                       "--layers", "2", "--families", family)
    assert {ln["call"] for ln in lines} == {"round_1", "round_5",
                                            "chunk_at_0"}
    assert {ln["read"] for ln in lines} == reads
    assert all(ln["family"] == family and ln["live_rows"] > 0
               and ln["least_ms"] >= 0 for ln in lines)
    for call in ("round_1", "round_5", "chunk_at_0"):
        probes = [ln["probe"] for ln in lines if ln["call"] == call]
        assert np.isfinite(probes).all() and np.abs(probes).max() > 0
        for other in probes[1:]:  # the XLA read over the same rows
            np.testing.assert_allclose(other, probes[0], atol=2e-4)


def test_the_sweep_steers_the_latent_forms_constants_and_puts_them_back(
        capsys, monkeypatch):
    from paddle_tpu.ops.pallas import row_attention as RA

    kept = RA._Q_ROWS, RA._SIDE_ROWS
    tool, lines = _run(capsys, monkeypatch, "--smoke", "--calls", "1",
                       "--layers", "1", "--families", "latent_moe",
                       "--sweep")
    swept = [ln for ln in lines if "q_rows" in ln]
    assert {(ln["row_blocks"], ln["q_rows"], ln["side_rows"])
            for ln in swept} == set(tool.SWEEP)
    assert {ln["call"] for ln in swept} == {"round_5"}  # (no 896 in smoke)
    assert (RA._Q_ROWS, RA._SIDE_ROWS) == kept
    # the constants move the schedule, not what is read
    want = next(ln["probe"] for ln in lines
                if ln["call"] == "round_5" and "q_rows" not in ln)
    for ln in swept:
        if ln["row_blocks"] == 16:
            np.testing.assert_allclose(ln["probe"], want, atol=2e-4)
