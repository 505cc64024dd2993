"""hapi callbacks.

Reference parity: `python/paddle/hapi/callbacks.py` — Callback base,
CallbackList dispatch, ProgBarLogger, ModelCheckpoint, EarlyStopping,
LRScheduler callback.
"""
from __future__ import annotations

import os
import time

import numpy as np


class Callback:
    def set_params(self, params):
        self.params = params

    def set_model(self, model):
        self.model = model

    def on_train_begin(self, logs=None):
        pass

    def on_train_end(self, logs=None):
        pass

    def on_train_error(self, error=None):
        """Fired (before the exception re-raises) when the fit loop dies —
        the hook that lets sinks flush a terminal record instead of
        leaving a truncated artifact. ``on_train_end`` is NOT called on
        the error path (parity: the reference only ends clean runs)."""
        pass

    def on_checkpoint(self, step, logs=None):
        """Fired when a resilience checkpoint COMPLETES (manifest
        published — not when the async save starts): ``step`` is what a
        relaunch would now resume from."""
        pass

    def on_slo_breach(self, breach=None):
        """Fired when the live telemetry plane's SLO watchdog declares a
        burn-rate breach (``monitor/live.py``; docs/OBSERVABILITY.md
        "Live telemetry plane"). ``breach`` is the structured event dict
        (metric, target, fast/slow burn rates, window sizes).
        Observation-only for now — the ROADMAP 3b SLA-aware scheduler is
        the intended consumer. Only fires while live telemetry is armed
        (``PT_SLO_*`` targets set)."""
        pass

    def on_eval_begin(self, logs=None):
        pass

    def on_eval_end(self, logs=None):
        pass

    def on_predict_begin(self, logs=None):
        pass

    def on_predict_end(self, logs=None):
        pass

    def on_epoch_begin(self, epoch, logs=None):
        pass

    def on_epoch_end(self, epoch, logs=None):
        pass

    def on_train_batch_begin(self, step, logs=None):
        pass

    def on_train_batch_end(self, step, logs=None):
        pass

    def on_eval_batch_begin(self, step, logs=None):
        pass

    def on_eval_batch_end(self, step, logs=None):
        pass


class CallbackList:
    def __init__(self, callbacks):
        self.callbacks = list(callbacks)

    def set_params(self, params):
        for c in self.callbacks:
            c.set_params(params)

    def set_model(self, model):
        for c in self.callbacks:
            c.set_model(model)

    def __getattr__(self, name):
        if not name.startswith("on_"):
            raise AttributeError(name)

        def call(*args, **kwargs):
            for c in self.callbacks:
                getattr(c, name)(*args, **kwargs)

        return call


class ProgBarLogger(Callback):
    """Parity: hapi ProgBarLogger (per-epoch step/loss/metric lines)."""

    def __init__(self, log_freq=1, verbose=2):
        self.log_freq = log_freq
        self.verbose = verbose

    def on_epoch_begin(self, epoch, logs=None):
        self.epoch = epoch
        self.steps = self.params.get("steps")
        self._t0 = time.time()
        if self.verbose and self.epoch is not None:
            print(f"Epoch {epoch + 1}/{self.params.get('epochs')}")

    def on_train_batch_end(self, step, logs=None):
        logs = logs or {}
        if self.verbose > 1 and step % self.log_freq == 0:
            ips = (step + 1) / max(time.time() - self._t0, 1e-9)
            items = " - ".join(
                f"{k}: {np.asarray(v).item():.4f}"
                if np.ndim(v) == 0 or np.size(v) == 1 else f"{k}: {v}"
                for k, v in logs.items() if k != "batch_size")
            print(f"step {step + 1}/{self.steps or '?'} - {items}"
                  f" - {ips:.2f} step/s")

    def on_eval_end(self, logs=None):
        if self.verbose:
            logs = logs or {}
            items = " - ".join(
                f"{k}: {np.asarray(v).item():.4f}"
                if np.ndim(v) == 0 or np.size(v) == 1 else f"{k}: {v}"
                for k, v in logs.items() if k != "batch_size")
            print(f"Eval - {items}")


class ModelCheckpoint(Callback):
    """Parity: hapi ModelCheckpoint (save every N epochs)."""

    def __init__(self, save_freq=1, save_dir=None):
        self.save_freq = save_freq
        self.save_dir = save_dir

    def on_epoch_end(self, epoch, logs=None):
        if self.save_dir and epoch % self.save_freq == 0:
            path = os.path.join(self.save_dir, str(epoch))
            self.model.save(path)

    def on_train_end(self, logs=None):
        if self.save_dir:
            self.model.save(os.path.join(self.save_dir, "final"))


class EarlyStopping(Callback):
    """Parity: hapi EarlyStopping."""

    def __init__(self, monitor="loss", mode="auto", patience=0, verbose=1,
                 min_delta=0, baseline=None, save_best_model=True):
        self.monitor = monitor
        self.patience = patience
        self.min_delta = abs(min_delta)
        self.baseline = baseline
        self.save_best_model = save_best_model
        self.verbose = verbose
        if mode == "auto":
            mode = "min" if "loss" in monitor or "err" in monitor else "max"
        self.mode = mode
        self.stopped_epoch = 0
        self.wait = 0
        self.best = None
        self.stop_training = False

    def _better(self, cur, best):
        if self.mode == "min":
            return cur < best - self.min_delta
        return cur > best + self.min_delta

    def on_eval_end(self, logs=None):
        logs = logs or {}
        if self.monitor not in logs:
            return
        cur = float(np.asarray(logs[self.monitor]).reshape(-1)[0])
        if self.best is None or self._better(cur, self.best):
            self.best = cur
            self.wait = 0
        else:
            self.wait += 1
            if self.wait >= self.patience:
                self.stop_training = True
                self.model.stop_training = True


class LRSchedulerCallback(Callback):
    """Parity: hapi LRScheduler callback — steps the optimizer's scheduler."""

    def __init__(self, by_step=True, by_epoch=False):
        self.by_step = by_step
        self.by_epoch = by_epoch

    def _sched(self):
        from ..optimizer.lr import LRScheduler

        opt = getattr(self.model, "_optimizer", None)
        lr = getattr(opt, "_learning_rate", None)
        return lr if isinstance(lr, LRScheduler) else None

    def on_train_batch_end(self, step, logs=None):
        s = self._sched()
        if self.by_step and s is not None:
            s.step()

    def on_epoch_end(self, epoch, logs=None):
        s = self._sched()
        if self.by_epoch and s is not None:
            s.step()


class MonitorCallback(Callback):
    """Stream per-step runtime telemetry to a JSONL sink
    (`paddle_tpu.monitor.StepLogger`): one line per train batch with loss,
    ips, and the counter diff (retraces, sync fences, collective bytes...)
    attributable to that step. Auto-added by `config_callbacks` when the
    monitor is enabled (``PT_MONITOR=1``); sink path from ``path`` or
    ``PT_MONITOR_SINK``. Step ids are monotonic across epochs."""

    def __init__(self, path=None):
        self.path = path
        self._logger = None

    def on_train_begin(self, logs=None):
        from ..monitor import StepLogger

        params = getattr(self, "params", {}) or {}
        self._logger = StepLogger(self.path, meta={
            "source": "hapi.fit",
            "epochs": params.get("epochs"),
            "steps_per_epoch": params.get("steps"),
            "batch_size": params.get("batch_size"),
        })

    def on_train_batch_end(self, step, logs=None):
        if self._logger is None:
            return
        logs = logs or {}
        params = getattr(self, "params", {}) or {}
        # deferred-sync contract (docs/ASYNC_PIPELINE.md): fit leaves the
        # loss as a lazy device scalar between log windows; forcing it
        # here would re-introduce the per-step host round-trip. Log the
        # loss only on steps where fit already materialized it.
        loss = logs.get("loss")
        if not isinstance(loss, (int, float, np.floating, np.integer)):
            loss = None
        self._logger.log_step(loss=loss,
                              num_samples=params.get("batch_size"))

    def on_checkpoint(self, step, logs=None):
        # the run_end line (clean or crashed) then names the exact step a
        # relaunch will resume from (StepLogger last_checkpoint_step)
        if self._logger is not None:
            self._logger.note_checkpoint(step)

    def on_train_end(self, logs=None):
        if self._logger is not None:
            self._logger.close()
            self._logger = None

    def on_train_error(self, error=None):
        # flush the terminal run_end line with the error, so the JSONL
        # distinguishes "crashed at step N" from "file truncated at N"
        if self._logger is not None:
            self._logger.close(error=error)
            self._logger = None


class _SLOBridge(Callback):
    """Bridges live-telemetry SLO breaches (``monitor.live.subscribe``)
    into the callback chain: every callback's ``on_slo_breach`` fires
    synchronously with the breach. Subscribes only while a run is
    active and only when live telemetry is armed — with live off this
    callback is four no-op method calls per run, zero per step."""

    def __init__(self, cbks):
        self._cbks = cbks
        self._armed = False

    def on_train_begin(self, logs=None):
        from ..monitor import live

        if live.enabled():
            live.subscribe(self._dispatch)
            self._armed = True

    def _dispatch(self, breach):
        for c in self._cbks:
            if not isinstance(c, _SLOBridge):
                c.on_slo_breach(breach)

    def _unsubscribe(self):
        if self._armed:
            from ..monitor import live

            live.unsubscribe(self._dispatch)
            self._armed = False

    def on_train_end(self, logs=None):
        self._unsubscribe()

    def on_train_error(self, error=None):
        self._unsubscribe()


def config_callbacks(callbacks=None, model=None, batch_size=None, epochs=None,
                     steps=None, verbose=2, save_freq=1, save_dir=None,
                     metrics=None, mode="train", log_freq=1):
    cbks = list(callbacks or [])
    if not any(isinstance(c, ProgBarLogger) for c in cbks) and verbose:
        # cadence matches fit's loss-materialization windows, so the
        # printed values are host floats already — no extra device sync
        cbks.append(ProgBarLogger(log_freq=log_freq, verbose=verbose))
    if not any(isinstance(c, LRSchedulerCallback) for c in cbks):
        cbks.append(LRSchedulerCallback())
    if save_dir and not any(isinstance(c, ModelCheckpoint) for c in cbks):
        cbks.append(ModelCheckpoint(save_freq, save_dir))
    if mode == "train" and not any(isinstance(c, MonitorCallback)
                                   for c in cbks):
        from ..monitor import enabled as _monitor_enabled

        if _monitor_enabled():
            cbks.append(MonitorCallback())
    if mode == "train" and not any(isinstance(c, _SLOBridge)
                                   for c in cbks):
        cbks.append(_SLOBridge(cbks))
    lst = CallbackList(cbks)
    lst.set_model(model)
    lst.set_params({
        "batch_size": batch_size, "epochs": epochs, "steps": steps,
        "verbose": verbose, "metrics": metrics or [],
    })
    return lst


class ReduceLROnPlateau(Callback):
    """Parity: hapi ReduceLROnPlateau (`hapi/callbacks.py:1172`): shrink
    the optimizer LR by ``factor`` after ``patience`` epochs without
    improvement on ``monitor``."""

    def __init__(self, monitor="loss", factor=0.1, patience=10, verbose=1,
                 mode="auto", min_delta=1e-4, cooldown=0, min_lr=0.0):
        self.monitor = monitor
        self.factor = factor
        self.patience = patience
        self.verbose = verbose
        if mode == "auto":
            mode = "min" if "loss" in monitor or "err" in monitor else "max"
        self.mode = mode
        self.min_delta = abs(min_delta)
        self.cooldown = cooldown
        self.min_lr = min_lr
        self.cooldown_counter = 0
        self.wait = 0
        self.best = None

    def _better(self, cur, best):
        if self.mode == "min":
            return cur < best - self.min_delta
        return cur > best + self.min_delta

    def _epoch_end(self, logs):
        logs = logs or {}
        if self.monitor not in logs:
            return
        cur = float(np.asarray(logs[self.monitor]).reshape(-1)[0])
        if self.cooldown_counter > 0:
            # cooldown epochs never count toward patience (Keras/paddle)
            self.cooldown_counter -= 1
            self.wait = 0
            if self.best is None or self._better(cur, self.best):
                self.best = cur
            return
        if self.best is None or self._better(cur, self.best):
            self.best = cur
            self.wait = 0
            return
        self.wait += 1
        if self.wait >= self.patience:
            opt = getattr(self.model, "_optimizer", None)
            if opt is None:
                return
            try:
                old = float(opt.get_lr())
            except Exception:
                return
            new = max(old * self.factor, self.min_lr)
            if new < old:
                opt.set_lr(new)
                if self.verbose:
                    print(f"ReduceLROnPlateau: lr {old:.3e} -> {new:.3e}")
            self.cooldown_counter = self.cooldown
            self.wait = 0

    # exactly one hook counts per epoch: train logs feed plain monitors,
    # eval logs feed 'eval_*' monitors (both fire every epoch when eval
    # data is present, so using both would double-count patience)
    def on_epoch_end(self, epoch, logs=None):
        if not self.monitor.startswith("eval_"):
            self._epoch_end(logs)

    def on_eval_end(self, logs=None):
        if not self.monitor.startswith("eval_"):
            return
        logs = logs or {}
        val = logs.get(self.monitor,
                       logs.get(self.monitor[len("eval_"):]))
        if val is not None:
            self._epoch_end({self.monitor: val})


def _scalar_logs(logs):
    out = {}
    for k, v in (logs or {}).items():
        try:
            out[k] = float(np.asarray(v).reshape(-1)[0])
        except Exception:
            continue
    return out


class VisualDL(Callback):
    """Parity: hapi VisualDL (`hapi/callbacks.py:883`) — logs epoch
    scalars to a visualdl LogWriter. Requires the external `visualdl`
    package (same optional dependency as the reference)."""

    def __init__(self, log_dir="vdl_log"):
        try:
            import visualdl
        except ImportError as e:
            from ..framework.errors import UnavailableError

            raise UnavailableError(
                "VisualDL callback needs the optional 'visualdl' package "
                "(not bundled; the reference has the same dependency). "
                "Metrics are available via ProgBarLogger / custom "
                "Callback.on_epoch_end") from e
        self.log_dir = log_dir
        self._writer = visualdl.LogWriter(logdir=log_dir)
        self._epoch = 0

    def on_epoch_end(self, epoch, logs=None):
        self._epoch = epoch
        for k, v in _scalar_logs(logs).items():
            self._writer.add_scalar(f"train/{k}", v, epoch)

    def on_eval_end(self, logs=None):
        for k, v in _scalar_logs(logs).items():
            self._writer.add_scalar(f"eval/{k}", v, self._epoch)

    def on_train_end(self, logs=None):
        self._writer.close()


class WandbCallback(Callback):
    """Parity: hapi WandbCallback (`hapi/callbacks.py:999`) — streams
    epoch scalars to a wandb run. Requires the external `wandb` package."""

    def __init__(self, project=None, **wandb_init_kwargs):
        try:
            import wandb
        except ImportError as e:
            from ..framework.errors import UnavailableError

            raise UnavailableError(
                "WandbCallback needs the optional 'wandb' package (not "
                "bundled; the reference has the same dependency)") from e
        self._wandb = wandb
        self._run = wandb.init(project=project, **wandb_init_kwargs) \
            if wandb.run is None else wandb.run

    def on_epoch_end(self, epoch, logs=None):
        self._run.log({f"train/{k}": v
                       for k, v in _scalar_logs(logs).items()},
                      step=epoch)

    def on_eval_end(self, logs=None):
        self._run.log({f"eval/{k}": v
                       for k, v in _scalar_logs(logs).items()})

    def on_train_end(self, logs=None):
        self._run.finish()
