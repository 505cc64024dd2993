"""``run.py`` end to end on the CPU at a tiny size (Pallas interpreted):
it prints its device, names no device metric — and with the timed path
broken underneath, ``correct`` comes out false."""
import contextlib
import io
import json
import subprocess
import sys

import pytest

import tiny


def run(workload, seed, hooks=None, trace=0):
    import run as runner

    tiny.interpret_flash()
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        result = runner.run_cell(workload, seed, 1.5, trace,
                                 files=tiny.files(), require_chip=False,
                                 hooks=hooks)
    return result, buf.getvalue()


@pytest.mark.parametrize("workload",
                         ["tiny-train", "tiny-backlog", "tiny-steady"])
def test_runs_end_to_end_and_names_no_device_metric(workload):
    result, out = run(workload, 11)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}  # a CPU number never gets a device name
    assert "rehearsal" in result
    assert '"line": "setup"' in out and '"line": "compare"' in out
    json.dumps(result)


def test_without_a_chip_the_command_exits_non_zero_and_prints_no_result():
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "train-1chip-s4096", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tiny.HERE + "/../../..", env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith('{"correct"')
                   for line in p.stdout.splitlines())
    assert "not 'tpu'" in p.stderr


class StuckStep:
    """A train step that returns its state unchanged after the first
    call: it hands back the last loss and never steps again."""

    def __init__(self, inner):
        self.inner, self.calls, self.last = inner, 0, None

    def __call__(self, *batch):
        self.calls += 1
        if self.calls == 1:
            self.last = self.inner(*batch)
        return self.last


def test_a_step_that_leaves_its_state_unchanged_is_not_correct():
    result, out = run("tiny-train", 11, hooks={"wrap_step": StuckStep})
    assert result["correct"] is False
    numbers = {r["name"]: r for ln in out.splitlines()
               if '"line": "compare"' in ln
               for r in json.loads(ln)["numbers"]}
    assert not numbers["update_norm_rel_gap"]["ok"]


class HalfBatch:
    """A train step that leaves out a part of the batch: every row is
    replaced by the first."""

    def __init__(self, inner):
        self.inner = inner

    def __call__(self, ids, labels):
        import paddle_tpu as pt

        i, l = ids.numpy().copy(), labels.numpy().copy()
        i[1:], l[1:] = i[:1], l[:1]
        return self.inner(pt.to_tensor(i), pt.to_tensor(l))


def test_a_step_that_leaves_out_part_of_the_batch_is_not_correct():
    result, out = run("tiny-train", 11, hooks={"wrap_step": HalfBatch})
    assert result["correct"] is False


def alter_tokens(engine):
    """An engine whose every 5th emitted token is altered where it is
    produced (the altered token is what the request goes on from)."""
    emit, count = engine._emit, [0]

    def bad_emit(req, tok, now):
        count[0] += 1
        if count[0] % 5 == 0:
            tok = (tok + 1) % engine.model.config.vocab_size
        return emit(req, tok, now)

    engine._emit = bad_emit
    return engine


@pytest.mark.parametrize("workload", ["tiny-backlog", "tiny-steady"])
def test_altered_tokens_are_not_correct(workload):
    result, out = run(workload, 11, hooks={"wrap_engine": alter_tokens})
    assert result["correct"] is False
