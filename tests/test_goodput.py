"""Training goodput plane tests (ISSUE 20 — monitor/goodput +
monitor/watchdog, docs/OBSERVABILITY.md "Training goodput plane").

Tier-1 proof of the tentpole invariants:

* the ledger telescopes EXACTLY — ``sum(buckets.values()) == wall_s``
  in float, through a real `fit()` with async stepping, a checkpoint,
  a skipped NaN batch, and a resume, with the monitor off AND on (the
  on-path is a subprocess so import-time enablement is real);
* ``PT_GOODPUT=0`` runs no ledger and produces byte-identical losses
  (the always-on plane never perturbs the numerics);
* the hang watchdog trips on a stalled step, writes a blackbox
  artifact naming the hung step with all-thread stacks, stands down
  during quiet buckets, and feeds ``/healthz`` liveness.
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.monitor import exporter, goodput, watchdog

REPO = str(Path(__file__).parent.parent)


def _build(seed=0, lr=5e-2):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=net.parameters())
    model = paddle.Model(net)
    model.prepare(opt, nn.MSELoss())
    return model


def _dataset(n=48, poison_batch=None, batch=8):
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((n, 8)).astype("float32")
    ys = xs @ rng.standard_normal((8, 1)).astype("float32")
    if poison_batch is not None:
        xs[poison_batch * batch:(poison_batch + 1) * batch] = np.nan
    return [(xs[i], ys[i]) for i in range(n)]


class _GrabLedger(paddle.callbacks.Callback):
    """Captures the run's active ledger (fit owns it; deactivation
    happens after on_train_end, so the hook window sees it armed)."""

    def __init__(self):
        self.ledger = None
        self.active_during_run = None

    def on_train_batch_end(self, step, logs=None):
        if self.ledger is None:
            self.ledger = goodput.active()
            self.active_during_run = self.ledger is not None


def _assert_telescopes(snap):
    assert set(snap["buckets"]) == set(goodput.BUCKETS)
    total = 0.0
    for b in goodput.BUCKETS:  # canonical order: the exactness contract
        total += snap["buckets"][b]
    assert total == snap["wall_s"], (total, snap["wall_s"])
    assert all(v >= 0.0 for v in snap["buckets"].values()), snap["buckets"]


# -- ledger unit -------------------------------------------------------------

def test_ledger_telescopes_exactly():
    led = goodput.Ledger()
    led.enter("productive_step")
    time.sleep(0.01)
    led.exit()
    led.enter("input_wait")
    led.exit()
    snap = led.snapshot()
    _assert_telescopes(snap)
    assert snap["steps"] == 1
    assert snap["buckets"]["productive_step"] >= 0.01
    assert snap["goodput_frac"] == (snap["buckets"]["productive_step"]
                                    / snap["wall_s"])


def test_ledger_nested_and_retro_charge_never_double_count():
    led = goodput.Ledger()
    led.enter("productive_step")
    led.enter("checkpoint_save_blocking")  # nested: parent is displaced
    time.sleep(0.01)
    led.exit()
    time.sleep(0.01)
    # part of the step's elapsed was really a compile: retro-charge it
    # out of the open frame (the TrainStep bracket's shape)
    led.charge("compile", 0.005)
    led.exit()
    snap = led.snapshot()
    _assert_telescopes(snap)
    assert snap["buckets"]["checkpoint_save_blocking"] >= 0.01
    assert snap["buckets"]["compile"] == 0.005
    assert snap["buckets"]["productive_step"] > 0.0  # exclusive remainder
    assert snap["steps"] == 1  # charge() never bumps the step count


def test_ledger_reclassify_exit_counts_nan_step():
    led = goodput.Ledger()
    led.enter("productive_step")
    led.exit("nan_replay_or_skip")  # the skip path re-labels the frame
    snap = led.snapshot()
    _assert_telescopes(snap)
    assert snap["steps"] == 0 and snap["nan_steps"] == 1


def test_ledger_rejects_unknown_bucket():
    led = goodput.Ledger()
    with pytest.raises(ValueError, match="unknown goodput bucket"):
        led.enter("coffee_break")


def test_open_frame_snapshot_still_telescopes():
    led = goodput.Ledger()
    led.enter("productive_step")
    time.sleep(0.005)
    snap = led.snapshot()  # mid-frame: exclusive elapsed-so-far counts
    _assert_telescopes(snap)
    assert snap["buckets"]["productive_step"] > 0.0
    led.exit()


# -- fit integration (monitor OFF: the always-on path) -----------------------

def test_fit_ledger_invariant_with_ckpt_nan_and_resume(tmp_path):
    """The acceptance fit: checkpointing + a poisoned batch under
    nan_policy='skip' + a resume — every phase lands in its bucket and
    the telescoping equality stays exact."""
    ck = str(tmp_path / "ck")
    grab = _GrabLedger()
    m = _build()
    m.fit(_dataset(poison_batch=3), batch_size=8, epochs=1, shuffle=False,
          verbose=0, log_freq=1, nan_policy="skip", checkpoint_dir=ck,
          callbacks=[grab])
    assert grab.active_during_run
    snap = grab.ledger.snapshot()
    _assert_telescopes(snap)
    assert snap["steps"] == 5          # 6 batches, one skipped
    assert snap["nan_steps"] == 1
    assert snap["buckets"]["productive_step"] > 0.0
    # the skipped batch's replay + discarded dispatch was re-labelled
    assert snap["buckets"]["nan_replay_or_skip"] > 0.0
    # fit ends with the ledger retired and every slot disarmed
    assert goodput.active() is None
    from paddle_tpu.jit import train_step as ts
    assert ts._goodput is None

    grab2 = _GrabLedger()
    m2 = _build(seed=1)
    # epochs=2: the checkpoint covers epoch 0, so the resume actually
    # trains (a fully-covered resume would run zero batches)
    m2.fit(_dataset(), batch_size=8, epochs=2, shuffle=False, verbose=0,
           resume_from=ck, callbacks=[grab2])
    snap2 = grab2.ledger.snapshot()
    _assert_telescopes(snap2)
    # restore-from-checkpoint time is its own bucket, not "other"
    assert snap2["buckets"]["restore_resume"] > 0.0
    assert goodput.active() is None


def test_goodput_off_no_ledger_and_byte_identical_losses(monkeypatch):
    """PT_GOODPUT=0 is the escape hatch: no ledger is created — and the
    ledgered run's losses are byte-identical to the unledgered run's
    (the plane is clock arithmetic only; it never touches the step)."""

    def _losses():
        sink = []

        class Cap(paddle.callbacks.Callback):
            def on_train_batch_end(self, step, logs=None):
                sink.append(float(logs["loss"]))

        m = _build()
        m.fit(_dataset(), batch_size=8, epochs=2, shuffle=False,
              verbose=0, log_freq=1, callbacks=[Cap()])
        return sink

    monkeypatch.setenv("PT_GOODPUT", "0")
    grab = _GrabLedger()
    m = _build()
    m.fit(_dataset(), batch_size=8, epochs=1, shuffle=False, verbose=0,
          callbacks=[grab])
    assert grab.active_during_run is False  # no ledger ever armed
    off = _losses()
    monkeypatch.setenv("PT_GOODPUT", "1")
    on = _losses()
    assert off == on  # float-exact, not approx: the plane is inert


# -- fit integration (monitor ON: run_end carries the account) ---------------

_MONITOR_ON_SCRIPT = r"""
import json, os, sys
os.environ["PT_MONITOR"] = "1"
os.environ["PT_MONITOR_SINK"] = sys.argv[1]
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn

paddle.seed(0)
net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))
opt = paddle.optimizer.AdamW(learning_rate=5e-2,
                             parameters=net.parameters())
model = paddle.Model(net)
model.prepare(opt, nn.MSELoss())
rng = np.random.default_rng(0)
xs = rng.standard_normal((48, 8)).astype("float32")
ys = xs @ rng.standard_normal((8, 1)).astype("float32")
xs[24:32] = np.nan  # poison batch 3
ds = [(xs[i], ys[i]) for i in range(48)]
model.fit(ds, batch_size=8, epochs=1, shuffle=False, verbose=0,
          log_freq=1, nan_policy="skip",
          checkpoint_dir=sys.argv[2])
print("FIT_OK")
"""


@pytest.mark.slow
def test_run_end_goodput_monitor_on(tmp_path):
    """With the monitor armed the StepLogger's run_end line embeds the
    final ledger account — and JSON round-trips floats exactly, so the
    telescoping proof survives the sink."""
    sink = str(tmp_path / "run.jsonl")
    proc = subprocess.run(
        [sys.executable, "-c", _MONITOR_ON_SCRIPT, sink,
         str(tmp_path / "ck")],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0 and "FIT_OK" in proc.stdout, (
        proc.stdout[-2000:], proc.stderr[-2000:])
    end = None
    with open(sink) as f:
        for raw in f:
            try:
                line = json.loads(raw)
            except ValueError:
                continue
            if line.get("event") == "run_end":
                end = line
    assert end is not None and "goodput" in end, end
    snap = end["goodput"]
    _assert_telescopes(snap)
    assert snap["steps"] == 5 and snap["nan_steps"] == 1
    # the compile bracket retro-charged the first step's XLA compile
    assert snap["buckets"]["compile"] > 0.0
    # checkpoint_dir forced at least the final blocking save cost
    assert snap["buckets"]["checkpoint_save_blocking"] > 0.0
    # the shared step EMA landed as the monitor/step_ms_ema gauge
    gauges = (end.get("totals") or {}).get("gauges") or {}
    assert gauges.get("monitor/step_ms_ema", 0) > 0.0


# -- hang watchdog -----------------------------------------------------------

@pytest.fixture
def _quiet_run():
    """A fresh EMA world + an active ledger, torn down afterwards."""
    goodput.reset_run()
    led = goodput.activate(goodput.Ledger())
    yield led
    goodput.deactivate(led)
    goodput.reset_run()


def test_watchdog_trips_and_blackbox_names_hung_step(
        tmp_path, monkeypatch, _quiet_run):
    art = str(tmp_path / "hang_blackbox.json")
    monkeypatch.setenv("PT_HANG_BLACKBOX", art)
    goodput.observe_step_ms(10.0, step=3)
    wd = watchdog.Watchdog(factor=1.0, min_s=0.05, policy="warn",
                           poll_s=0.02).start()
    try:
        deadline = time.time() + 5.0
        # (the trip is counted before its artifact is written: wait for
        # both, or a loaded machine reads the file before it exists)
        while (wd._trips == 0 or not os.path.exists(art)) \
                and time.time() < deadline:
            time.sleep(0.02)
        assert wd._trips >= 1
        st = wd.state()
        assert st["hung"] is True and st["last_step"] == 3
        # /healthz carries the liveness verdict (satellite 2)
        h = exporter.health()
        assert h["hung"] is True
        assert h["last_step_age_s"] is not None
        assert h["degraded"] is True
        # the artifact parses and names the hung step with stacks
        with open(art) as f:
            hb = json.loads(f.read())
        assert hb["reason"] == "hang_watchdog"
        trip = hb["state"]["training_watchdog"]["last_trip"]
        assert trip["hung_step"] == 4
        assert trip["last_completed_step"] == 3
        assert trip["stacks"]  # all-thread dump: the diagnosable part
        # a completed step re-arms the latch (trip count is monotone,
        # the hung flag is not)
        goodput.observe_step_ms(10.0, step=4)
        deadline = time.time() + 5.0
        while wd.state()["hung"] and time.time() < deadline:
            time.sleep(0.02)
        assert wd.state()["hung"] is False or wd._trips >= 2
    finally:
        wd.stop()
    assert watchdog.state() == {}  # stopped: /healthz drops the fields


def test_watchdog_stands_down_during_quiet_buckets(_quiet_run):
    """A first-signature compile can dwarf any EMA — the judge must not
    call a legitimate slow phase a hang."""
    goodput.observe_step_ms(10.0, step=1)
    _quiet_run.enter("compile")
    wd = watchdog.Watchdog(factor=1.0, min_s=0.05, policy="warn",
                           poll_s=0.02).start()
    try:
        time.sleep(0.4)
        assert wd._trips == 0
    finally:
        wd.stop()
        _quiet_run.exit()


def test_watchdog_no_judgement_before_first_step(_quiet_run):
    wd = watchdog.Watchdog(factor=1.0, min_s=0.01, policy="warn",
                           poll_s=0.02)
    assert wd.deadline_s() is None  # no EMA: nothing to judge against
    wd.start()
    try:
        time.sleep(0.2)
        assert wd._trips == 0
    finally:
        wd.stop()


def test_watchdog_policy_off_never_starts():
    wd = watchdog.Watchdog(policy="off")
    assert wd.start() is wd
    assert wd._thread is None
    wd.stop()


def test_healthz_without_watchdog_has_no_liveness_fields():
    h = exporter.health()
    assert "hung" not in h and "last_step_age_s" not in h
