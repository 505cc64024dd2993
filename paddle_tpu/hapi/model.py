"""hapi: the Keras-like high-level Model API.

Reference parity: `paddle.Model` (`python/paddle/hapi/model.py:1050` fit,
`:1741` evaluate/predict), `Model.prepare(optimizer, loss, metrics)`,
`save/load`.

TPU-first design: `fit` drives the whole-step compiled TrainStep
(jit/train_step.py) — every batch is ONE XLA execution including the
optimizer — rather than the reference's per-op dygraph loop. Evaluation
jits the forward via a cached no-grad program. Everything else (callbacks,
metrics, DataLoader handling, save/load) keeps the reference surface.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

from .callbacks import config_callbacks
from ..autograd.tape import no_grad
from ..framework.core import Tensor
from ..framework.io import load as _load, save as _save
from ..io.reader import DataLoader
from ..jit.train_step import AsyncStepper, TrainStep
from ..monitor import _register as _monitor_register
from ..monitor import blackbox as _blackbox
from ..monitor import goodput as _gp
from ..monitor import heartbeat as _heartbeat
from ..monitor import memory as _memory
from ..monitor import watchdog as _watchdog
from ..monitor.numerics import NonFiniteError as _NonFiniteError

# Telemetry slots (see paddle_tpu.monitor): None unless PT_MONITOR wired
# them. `_spans` (monitor/spans.py) records fit/evaluate phase brackets
# and the deliberate metric materializations as `sync` attribution spans.
_monitor = None
_spans = None


def _fast_forward(src, n):
    """Yield ``src``'s batches after discarding the first ``n`` —
    host-side only (the resume fast-forward). Hand-rolled because the
    DataLoader's iterator implements ``__next__`` without ``__iter__``,
    which ``itertools.islice`` / ``yield from`` reject."""
    it = iter(src)
    for _ in range(n):
        try:
            next(it)
        except StopIteration:
            return
    while True:
        try:
            yield next(it)
        except StopIteration:
            return


def _to_tensor_list(batch):
    if isinstance(batch, (list, tuple)):
        return [b if isinstance(b, Tensor) else Tensor(np.asarray(b))
                for b in batch]
    return [batch if isinstance(batch, Tensor) else Tensor(np.asarray(batch))]


def _fetch_scalars(tensors):
    """ONE counted host transfer for a batch of lazy device scalars
    (``hapi/host_syncs`` is the guard metric for the ≤1-sync-per-window
    contract) — the single sync primitive `fit`/`evaluate` share."""
    import jax

    m = _monitor
    if m is not None:
        m.on_host_sync()
    sp = _spans
    t0 = time.perf_counter() if sp is not None else None
    out = [float(np.asarray(a).reshape(-1)[0])
           for a in jax.device_get([t._data for t in tensors])]
    if sp is not None:
        sp.record("hapi/fetch_scalars", "sync", t0, lane="sync_fences",
                  args={"n": len(tensors)})
    return out


class _LazyLoss:
    """A deferred training metric: number-like, synced on first read.

    `fit` hands these to callbacks between log windows so the loop never
    blocks on the device — but a USER callback that reads the value
    (``float(logs["loss"])``, ``np.asarray``, a comparison) must still
    get honest number semantics, and that read IS a host sync, so it is
    materialized on demand and counted via the same ``hapi/host_syncs``
    hook as the deliberate window syncs. Reading every step (e.g. a
    user-constructed ``ProgBarLogger(log_freq=1)``) therefore re-creates
    per-step syncing — visibly, in the guard counter, as the user asked.
    """

    __slots__ = ("_tensor", "_value")

    def __init__(self, tensor):
        self._tensor = tensor
        self._value = None

    def _materialize(self):
        if self._value is None:
            self._value = _fetch_scalars([self._tensor])[0]
        return self._value

    def __float__(self):
        return self._materialize()

    def __array__(self, dtype=None):
        a = np.asarray(self._materialize())
        return a.astype(dtype) if dtype is not None else a

    def item(self):
        return self._materialize()

    def __lt__(self, other):
        return self._materialize() < other

    def __le__(self, other):
        return self._materialize() <= other

    def __gt__(self, other):
        return self._materialize() > other

    def __ge__(self, other):
        return self._materialize() >= other

    def __eq__(self, other):
        return self._materialize() == other

    def __hash__(self):
        return object.__hash__(self)

    def __repr__(self):
        return (f"{self._value!r}" if self._value is not None
                else "<lazy device scalar>")


def _materialize_logs(logs):
    """Fetch every lazy scalar in ``logs`` to the host in ONE transfer,
    returning plain-float logs — everything downstream (ProgBarLogger,
    MonitorCallback, user callbacks) sees host floats and cannot
    accidentally re-sync."""
    lazy = {k: v for k, v in logs.items()
            if isinstance(v, (Tensor, _LazyLoss))}
    if not lazy:
        return dict(logs)
    out = dict(logs)
    pre = {k: v for k, v in lazy.items()
           if isinstance(v, _LazyLoss) and v._value is not None}
    todo = {k: v for k, v in lazy.items() if k not in pre}
    for k, v in pre.items():
        out[k] = v._value
    if todo:
        vals = _fetch_scalars([
            v._tensor if isinstance(v, _LazyLoss) else v
            for v in todo.values()])
        for k, f in zip(todo, vals):
            out[k] = f
    return out


class _TrainState:
    """fit's blackbox state provider: what a crash/hang postmortem sees
    of the training loop — step, last materialized loss, the goodput
    ledger snapshot, and the async pipeline's in-flight depth. Registered
    per-fit as a bound method so the recorder's WeakMethod lets it die
    with the run (monitor/blackbox.py)."""

    __slots__ = ("_stepper", "_ledger", "step", "loss", "__weakref__")

    def __init__(self, stepper, ledger):
        self._stepper = stepper
        self._ledger = ledger
        self.step = 0
        self.loss = None

    def state(self):
        out = {"step": self.step, "last_loss": self.loss,
               "in_flight": self._stepper.in_flight}
        if self._ledger is not None:
            out["goodput"] = self._ledger.snapshot()
        return out


def _input_wait_iter(ledger, it):
    """Bracket each batch fetch as goodput ``input_wait``: blocking in
    the data iterator (loader compute, prefetch starvation) lands in its
    own bucket instead of inflating the step or ``other`` residual."""
    it = iter(it)
    while True:
        ledger.enter("input_wait")
        try:
            item = next(it)
        except StopIteration:
            return
        finally:
            ledger.exit()
        yield item


class Model:
    """Parity: `paddle.Model(network, inputs=None, labels=None)`."""

    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._train_step = None
        self.stop_training = False

    # -- setup --
    def prepare(self, optimizer=None, loss=None, metrics=None, amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        if metrics is None:
            self._metrics = []
        elif isinstance(metrics, (list, tuple)):
            self._metrics = list(metrics)
        else:
            self._metrics = [metrics]
        if optimizer is not None and loss is not None:
            self._train_step = TrainStep(
                self.network, optimizer, self._loss_fn)
        return self

    def _loss_fn(self, net, *batch):
        n_in = len(batch) - 1 if len(batch) > 1 else 1
        inputs, labels = batch[:n_in], batch[n_in:]
        outs = net(*inputs)
        if self._loss is None:
            return outs if isinstance(outs, Tensor) else outs[0]
        loss = self._loss(outs, *labels)
        return loss.mean() if loss.ndim else loss

    # -- per-batch ops (parity: Model.train_batch / eval_batch / predict_batch) --
    def _train_batch_lazy(self, inputs, labels=None):
        """One compiled step; the loss comes back as a LAZY device scalar
        (jax dispatch is async — no host round-trip here). `fit` consumes
        this path and defers materialization to its log cadence."""
        batch = _to_tensor_list(inputs) + (
            _to_tensor_list(labels) if labels is not None else [])
        return self._train_step(*batch)

    def train_batch(self, inputs, labels=None, update=True):
        loss = self._train_batch_lazy(inputs, labels)
        # Paddle-parity return type at the PUBLIC boundary: the one-off
        # eager API hands back host numpy, and this .numpy() is the only
        # sync on the path
        return [loss.numpy()]

    @no_grad()
    def _eval_batch_lazy(self, inputs, labels=None):
        """Forward + loss with the loss left ON DEVICE; metric state still
        updates eagerly (the Metric API is numpy-facing). Returns
        (lazy mean-loss Tensor | None)."""
        batch = _to_tensor_list(inputs)
        labels = _to_tensor_list(labels) if labels is not None else []
        outs = self.network(*batch)
        loss = None
        if self._loss is not None and labels:
            loss = self._loss(outs, *labels)
            loss = loss.mean() if loss.ndim else loss
        for m in self._metrics:
            m.update(*[np.asarray(x) for x in m.compute(outs, *labels)])
        return loss

    def eval_batch(self, inputs, labels=None):
        loss = self._eval_batch_lazy(inputs, labels)
        # public boundary: materialize exactly here (Paddle-parity floats)
        return [] if loss is None else [float(np.asarray(loss.numpy()))]

    @no_grad()
    def predict_batch(self, inputs):
        outs = self.network(*_to_tensor_list(inputs))
        if isinstance(outs, (list, tuple)):
            return [o.numpy() for o in outs]
        return [outs.numpy()]

    # -- loops --
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None, max_in_flight=2,
            device_prefetch=0, nan_check=None, resume_from=None,
            checkpoint_dir=None, checkpoint_keep=None, nan_policy=None,
            shard_plan=None):
        """Parity: `paddle.Model.fit` — with an asynchronous device
        pipeline (docs/ASYNC_PIPELINE.md). Steps dispatch through an
        :class:`AsyncStepper` keeping up to ``max_in_flight`` compiled
        steps outstanding, and the per-step loss stays ON DEVICE: logs
        carry lazy scalars that are materialized (one host transfer) only
        every ``log_freq`` steps and at epoch end — not once per step,
        which would put a blocking host fetch between every two steps.
        ``device_prefetch > 0`` additionally
        wraps the loader in a :class:`~paddle_tpu.io.DevicePrefetchIterator`
        staging that many batches ahead in device memory.

        ``nan_check=True`` arms the numerics sentinel FOR THIS FIT on
        the model's TrainStep (monitor/numerics.py): one fused
        finite-flag scalar per step; on first failure the loop dies with
        a :class:`~paddle_tpu.monitor.numerics.NonFiniteError` naming
        the step and first bad leaf, after ``Callback.on_train_error``
        fired. ``None`` (default) follows the global ``PT_NANCHECK``
        state; ``False`` forces it off for this fit. The TrainStep's
        own ``nan_check`` setting is restored when fit returns.

        Resilience (docs/RESILIENCE.md): ``checkpoint_dir`` arms a
        :class:`~paddle_tpu.resilience.CheckpointManager` — periodic
        async sharded checkpoints on a cadence planned from the measured
        save cost (``PT_CKPT_OVERHEAD_PCT``), each save quiescing the
        AsyncStepper first, plus a final checkpoint at train end.
        ``resume_from`` restores params / optimizer state / LR schedule /
        PRNG / step counters and the data-iterator position from the
        newest COMPLETE checkpoint under that directory (torn ones are
        skipped) — resharding into the current mesh placements, so the
        resumed (dp×mp) need not match the saved one. ``nan_policy=
        "skip"`` forces the sentinel on and hands its failures to a
        :class:`~paddle_tpu.resilience.NaNSkipPolicy`: the poisoned
        batch is dropped (params/LR/step untouched — the step never
        happened) and training continues, aborting only after
        ``PT_NANSKIP_MAX`` consecutive failures.

        Automatic sharding (docs/AUTOSHARD.md): ``shard_plan`` — a
        ``shard_plan.json`` path (or loaded
        :class:`~paddle_tpu.autoshard.ShardPlan`) from
        ``tools/shard_plan.py plan`` — initializes the global
        (dp×mp×pp) mesh at the plan's degrees and places every
        parameter by its planned / rule-derived PartitionSpec before
        the first step: a hybrid run with no hand-written specs. A
        pp>1 plan additionally wraps the network's repeated block run
        into the staged pipeline container (``autoshard.stage_model``
        — the planned ``n_micro`` microbatches must divide the batch)
        and re-points the optimizer at the stacked parameters; losses
        stay on the pp=1 curve. Defaults to the
        ``PT_SHARD_PLAN`` env stamp the planner's launcher sets, so a
        launched script needs no code either (``resume_from`` likewise
        defaults from the ``PT_SHARD_RESUME`` stamp `shard_plan.py
        resume` sets). Combines with ``resume_from``: the checkpoint
        reshards into the NEW plan's placements on load, so the saved
        (dp×mp) need not match."""
        assert self._train_step is not None, "call prepare() first"
        # training goodput plane (docs/OBSERVABILITY.md): one wall-clock
        # ledger per run, created before any setup so plan-apply/restore
        # time is inside the wall. PT_GOODPUT=0 opts out entirely (and
        # stands down the hang watchdog, whose deadline has no EMA
        # source without fit feeding it). Armed — slots wired, watchdog
        # started — only after setup can no longer raise outside the
        # teardown paths below.
        ledger = (_gp.Ledger()
                  if os.environ.get("PT_GOODPUT", "1") not in ("", "0")
                  else None)
        if shard_plan is None:
            shard_plan = os.environ.get("PT_SHARD_PLAN") or None
        if resume_from is None:
            # `shard_plan.py resume` stamps the checkpoint dir into the
            # workers; an hapi script relaunched that way must resume,
            # not silently retrain from step 0
            resume_from = os.environ.get("PT_SHARD_RESUME") or None
        shard_batch = None
        if shard_plan is not None:
            from ..autoshard import apply_plan, load_plan, stage_model
            from ..autoshard import shard_batch as _shard_batch

            # mesh + param placement BEFORE resume/compile: the restore
            # reshards into these placements, and the first step's
            # lowering sees them
            plan = load_plan(shard_plan)
            apply_plan(plan, self.network)
            if plan.mesh.get("pp", 1) > 1:
                # a pipelined plan: wrap the block run into the staged
                # shard_map container (param values unchanged — the
                # pp>1 run stays on the pp=1 loss curve), re-point the
                # optimizer at the stacked parameters, and rebuild the
                # compiled step around the staged network. The restore
                # below then reshards INTO the stacked placements
                # (canonical per-block checkpoint keys —
                # docs/RESILIENCE.md stage-move reshard)
                staged = stage_model(self.network, plan)
                if staged is not self.network:
                    self.network = staged
                    if self._optimizer is not None:
                        self._optimizer._parameter_list = list(
                            staged.parameters())
                    self._train_step = TrainStep(
                        self.network, self._optimizer, self._loss_fn)
            if plan.batch and batch_size != plan.batch and not isinstance(
                    train_data, DataLoader):
                import warnings

                # the plan's HBM-fit verdict and comms account were
                # computed FOR plan.batch — a different executed batch
                # voids both (a bigger one can OOM a "fits" plan)
                warnings.warn(
                    f"fit(shard_plan=): batch_size={batch_size} differs "
                    f"from the planned global batch {plan.batch}; the "
                    f"plan's HBM-fit and comms estimates assumed "
                    f"{plan.batch}", stacklevel=2)
            if plan.mesh.get("dp", 1) > 1:
                # batches must join the dp split, or XLA lowers the step
                # with the batch REPLICATED and data parallelism is
                # compiled out (the plan's memory/comms account assumed
                # dp-sharded inputs — autoshard/lowering.py lowers the
                # candidates that way)
                shard_batch = _shard_batch
        policy = None
        if nan_policy is not None:
            if nan_policy != "skip":
                raise ValueError(
                    f"fit: nan_policy must be None or 'skip' "
                    f"(got {nan_policy!r})")
            from ..resilience.numerics_policy import NaNSkipPolicy

            policy = NaNSkipPolicy()
            nan_check = True  # the policy rides the sentinel's replay
        start_epoch = 0
        skip_batches = 0
        global_step = 0
        if resume_from is not None:
            from ..resilience import resume as _resume

            crash = int(os.environ.get("PADDLE_RESTART_COUNT", "0")
                        or 0) > 0
            if ledger is not None:
                ledger.enter("restore_resume")
            try:
                scalars = _resume.restore_latest(
                    self.network, self._optimizer, resume_from,
                    train_step=self._train_step, crash_resume=crash)
            finally:
                if ledger is not None:
                    ledger.exit()
            if scalars is not None:
                start_epoch = int(scalars.get("epoch", 0))
                skip_batches = int(scalars.get("batch_in_epoch", 0))
                global_step = int(scalars.get("step", 0))
        mgr = None
        if checkpoint_dir is not None:
            from ..resilience.checkpoint_manager import CheckpointManager

            mgr = CheckpointManager(checkpoint_dir, keep=checkpoint_keep)

        def _ckpt_state(ep, batch_in_epoch, step):
            from ..resilience import resume as _resume

            return _resume.capture(
                self.network, self._optimizer, epoch=ep,
                batch_in_epoch=batch_in_epoch, step=step)
        loader = train_data if isinstance(train_data, DataLoader) else \
            DataLoader(train_data, batch_size=batch_size, shuffle=shuffle,
                       drop_last=drop_last, num_workers=num_workers)
        if skip_batches:
            # the mid-epoch fast-forward replays the loader and discards
            # the first `skip_batches` batches — that only reproduces the
            # pre-crash data under a DETERMINISTIC order. Probe the
            # actual loader (fit-built or user-supplied): an unseeded
            # RandomSampler draws from global numpy state, which the
            # checkpoint cannot capture.
            from ..io.sampler import RandomSampler

            sampler = getattr(getattr(loader, "batch_sampler", None),
                              "sampler", None)
            if isinstance(sampler, RandomSampler) and getattr(
                    sampler, "generator", None) is None:
                import warnings

                warnings.warn(
                    "fit(resume_from=...) is resuming mid-epoch over an "
                    "unseeded shuffling loader: the resumed permutation "
                    "differs from the pre-crash one, so the skipped "
                    "batches are NOT the ones already trained (some "
                    "samples repeat, others are missed this epoch). Use "
                    "shuffle=False or a seeded sampler for exact "
                    "resume.", stacklevel=2)
        try:
            steps = len(loader)
        except Exception:
            steps = None
        cbks = config_callbacks(
            callbacks, model=self, epochs=epochs, steps=steps,
            batch_size=batch_size, verbose=verbose, save_freq=save_freq,
            save_dir=save_dir, metrics=[m.name() for m in self._metrics],
            log_freq=log_freq)
        self.stop_training = False
        cbks.on_train_begin()
        self.network.train()
        stepper = AsyncStepper(self._train_step, max_in_flight=max_in_flight)
        # per-fit sentinel override, applied only now that setup can no
        # longer raise outside the restoring finally below (a failed
        # loader/callback/stepper init must not leak the override)
        prev_nan_check = self._train_step._nan_check
        if nan_check is not None:
            self._train_step._nan_check = bool(nan_check)
        notified_ckpt = None
        # loop position for the terminal checkpoint: (next epoch, next
        # batch) a resume of this run would execute
        pos = (start_epoch, skip_batches)
        # arm the goodput plane: activate the ledger (wiring the
        # module `_goodput` slots), start the hang watchdog, open the
        # fleet heartbeat when a launcher stamped PT_HEARTBEAT_DIR, and
        # join the blackbox as the training state provider. Teardown
        # runs on BOTH exits, after the MonitorCallback's run_end line
        # (which reads the still-active ledger).
        _gp.reset_run()
        tstate = _TrainState(stepper, ledger)
        _blackbox.register("training", tstate.state)
        wdog = None
        hb = None
        if ledger is not None:
            _gp.activate(ledger)
            wdog = _watchdog.Watchdog().start()
        hb_dir = os.environ.get("PT_HEARTBEAT_DIR")
        if hb_dir:
            try:
                hb = _heartbeat.HeartbeatWriter(hb_dir)
            except OSError:
                hb = None  # telemetry must never kill training

        def _goodput_teardown():
            if wdog is not None:
                wdog.stop()
            if hb is not None:
                hb.close()
            if ledger is not None:
                _gp.deactivate(ledger)
        try:
            for epoch in range(start_epoch, epochs):
                cbks.on_epoch_begin(epoch)
                sp = _spans
                t_epoch = time.perf_counter() if sp is not None else None
                it = 0
                logs = {}
                skip_now = skip_batches if epoch == start_epoch else 0
                data_src = loader
                if skip_now:
                    # resume fast-forward: the batches trained before
                    # the checkpoint are consumed from the RAW loader,
                    # host-side only (deterministic loaders replay the
                    # same order) — never staged device-ward by the
                    # prefetcher below, which would pay one useless H2D
                    # transfer per discarded batch
                    data_src = _fast_forward(loader, skip_now)
                epoch_iter = enumerate(data_src, start=skip_now)
                prefetch = None
                if device_prefetch:
                    from ..io.prefetch import DevicePrefetchIterator

                    prefetch = DevicePrefetchIterator(
                        data_src, depth=device_prefetch)
                    epoch_iter = enumerate(prefetch, start=skip_now)
                if ledger is not None:
                    epoch_iter = _input_wait_iter(ledger, epoch_iter)
                try:
                    for step, batch in epoch_iter:
                        cbks.on_train_batch_begin(step)
                        batch = batch if isinstance(batch, (list, tuple)) \
                            else [batch]
                        tensors = _to_tensor_list(batch)
                        if shard_batch is not None:
                            tensors = [shard_batch(t) for t in tensors]
                        t_step = time.perf_counter()
                        if ledger is not None:
                            ledger.enter("productive_step")
                        try:
                            loss = stepper(*tensors)
                        except _NonFiniteError as e:
                            if ledger is not None:
                                # dispatch + sentinel replay that ended
                                # in a drop: not productive wall-clock
                                ledger.exit("nan_replay_or_skip")
                            if policy is None:
                                raise
                            # skip-and-continue: the sentinel raised
                            # BEFORE the rebind, so params/opt/LR/step
                            # are exactly pre-batch — drop it and move
                            # on (record_failure raises past the budget).
                            # on_train_batch_end is deliberately NOT
                            # fired (end hooks carry training-progress
                            # semantics — LRSchedulerCallback steps the
                            # schedule there, and a skipped step must
                            # not advance it), but the batch does count
                            # toward num_iters so the loop stays bounded
                            # on a poison-heavy stream
                            policy.record_failure(e)
                            it += 1
                            if num_iters is not None and it >= num_iters:
                                break
                            continue
                        if ledger is not None:
                            ledger.exit()
                        if policy is not None:
                            policy.record_success()
                        global_step += 1
                        step_ms = (time.perf_counter() - t_step) * 1e3
                        tstate.step = global_step
                        if ledger is not None:
                            # the shared step-time EMA (watchdog deadline,
                            # ckpt cadence, monitor/step_ms_ema gauge);
                            # StepLogger feeds it when no ledger is active
                            _gp.observe_step_ms(step_ms, step=global_step)
                        # lazy between windows; number-like (counted,
                        # sync-on-read) if a user callback touches it
                        logs = {"loss": _LazyLoss(loss)}
                        if step % log_freq == 0:
                            # the window's one host sync — aligned with
                            # ProgBarLogger's print cadence
                            logs = _materialize_logs(logs)
                        lv = logs.get("loss")
                        cur_loss = (float(lv)
                                    if isinstance(lv, (int, float))
                                    else None)
                        if cur_loss is not None:
                            tstate.loss = cur_loss
                        if hb is not None:
                            # fleet heartbeat: loss only on materialized
                            # windows (never force a host sync for
                            # telemetry) — windows align across ranks,
                            # so the launcher's desync detector compares
                            # same-step losses
                            hb.beat(global_step, loss=cur_loss,
                                    step_ms=step_ms,
                                    buckets=ledger.snapshot()["buckets"]
                                    if ledger is not None else None)
                        cbks.on_train_batch_end(step, logs)
                        pos = (epoch, step + 1)
                        if mgr is not None:
                            mgr.maybe_save(
                                global_step,
                                lambda ep=epoch, s=step, g=global_step:
                                _ckpt_state(ep, s + 1, g),
                                stepper=stepper)
                            mgr.poll()
                            if (mgr.last_complete_step is not None
                                    and mgr.last_complete_step
                                    != notified_ckpt):
                                notified_ckpt = mgr.last_complete_step
                                cbks.on_checkpoint(notified_ckpt)
                        it += 1
                        if num_iters is not None and it >= num_iters:
                            break
                finally:
                    if prefetch is not None:
                        prefetch.close()
                # exact final metrics: fence the pipeline, then one sync
                t_drain = time.perf_counter()
                stepper.drain()
                if ledger is not None:
                    # the drain wait finishes already-dispatched steps —
                    # productive wall, charged without bumping the step
                    # count (charge() never increments `steps`)
                    ledger.charge("productive_step",
                                  time.perf_counter() - t_drain)
                logs = _materialize_logs(logs)
                led = _memory._ledger
                if led is not None:
                    # phase-bracket census: post-drain live buffers are
                    # the epoch's steady-state footprint
                    led.census(tag="hapi/fit_epoch")
                if sp is not None:
                    sp.record("hapi/fit_epoch", "phase", t_epoch,
                              args={"epoch": epoch})
                cbks.on_epoch_end(epoch, logs)
                pos = (epoch + 1, 0)
                if eval_data is not None and (epoch + 1) % eval_freq == 0:
                    self.evaluate(eval_data, batch_size=batch_size,
                                  verbose=verbose, callbacks=callbacks)
                    self.network.train()
                if self.stop_training:
                    break
            if mgr is not None:
                # terminal checkpoint: the finished run's final state is
                # durable, and resuming it is a no-op (epoch == epochs).
                # Skipped when this step is already durably checkpointed
                # (resume of a finished run) — rewriting a complete
                # checkpoint in place buys nothing and risks tearing it
                if (mgr.last_save_step != global_step
                        and mgr.last_complete_step != global_step):
                    mgr.save(global_step,
                             _ckpt_state(pos[0], pos[1], global_step),
                             stepper=stepper)
                mgr.finalize()
                if mgr.last_complete_step is not None \
                        and mgr.last_complete_step != notified_ckpt:
                    notified_ckpt = mgr.last_complete_step
                    cbks.on_checkpoint(notified_ckpt)
        except BaseException as e:  # noqa: BLE001 — flush sinks, re-raise
            if mgr is not None:
                # publish any save whose writer ALREADY finished (poll,
                # never join: a crashing run must not block on a stalled
                # writer before its postmortem flushes) — the run_end
                # record then names the true resume point
                try:
                    mgr.poll()
                    if mgr.last_complete_step is not None \
                            and mgr.last_complete_step != notified_ckpt:
                        cbks.on_checkpoint(mgr.last_complete_step)
                except Exception:  # noqa: BLE001 — original error wins
                    pass
            cbks.on_train_error(f"{type(e).__name__}: {e}")
            # after on_train_error: the crashed run's run_end line (and
            # its blackbox dump) read the still-active ledger above
            _goodput_teardown()
            raise
        finally:
            # per-fit override only: later fits follow the global state
            # again unless they pass their own nan_check
            self._train_step._nan_check = prev_nan_check
        cbks.on_train_end()
        # after on_train_end: MonitorCallback's run_end carries
        # `goodput` only while the ledger is still active
        _goodput_teardown()

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None):
        loader = eval_data if isinstance(eval_data, DataLoader) else \
            DataLoader(eval_data, batch_size=batch_size, shuffle=False,
                       num_workers=num_workers)
        cbks = config_callbacks(callbacks, model=self, verbose=verbose,
                                mode="eval")
        self.network.eval()
        for m in self._metrics:
            m.reset()
        losses = []
        sp = _spans
        t_eval = time.perf_counter() if sp is not None else None
        cbks.on_eval_begin()
        for step, batch in enumerate(loader):
            batch = batch if isinstance(batch, (list, tuple)) else [batch]
            n_in = len(batch) - 1 if len(batch) > 1 else 1
            res = self._eval_batch_lazy(batch[:n_in], batch[n_in:])
            if res is not None:
                losses.append(res)  # lazy device scalars
        logs = {}
        if losses:
            # one host transfer for the whole eval pass (counted as a
            # single hapi/host_syncs), instead of one per batch
            logs["loss"] = float(np.mean(_fetch_scalars(losses)))
        for m in self._metrics:
            acc = m.accumulate()
            names = m.name()  # paddle metrics return a list of names
            if isinstance(names, (list, tuple)):
                vals = acc if isinstance(acc, (list, tuple)) else [acc]
                logs.update(zip(names, vals))
            else:
                logs[names] = acc
        led = _memory._ledger
        if led is not None:
            led.census(tag="hapi/evaluate")
        if sp is not None:
            sp.record("hapi/evaluate", "phase", t_eval)
        cbks.on_eval_end(logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        loader = test_data if isinstance(test_data, DataLoader) else \
            DataLoader(test_data, batch_size=batch_size, shuffle=False,
                       num_workers=num_workers)
        self.network.eval()
        outputs = []
        for batch in loader:
            batch = batch if isinstance(batch, (list, tuple)) else [batch]
            # a (inputs, label) dataset reused for predict: drop the label
            # (reference slices by the `inputs` spec; heuristic without one)
            n_in = (len(self._inputs) if self._inputs
                    else len(batch) - 1 if len(batch) > 1 else 1)
            outputs.append(self.predict_batch(batch[:n_in]))
        if stack_outputs and outputs:
            n_out = len(outputs[0])
            return [np.concatenate([o[i] for o in outputs])
                    for i in range(n_out)]
        return outputs

    # -- persistence (parity: Model.save/load -> .pdparams/.pdopt) --
    def save(self, path, training=True):
        dirname = os.path.dirname(path)
        if dirname:
            os.makedirs(dirname, exist_ok=True)
        _save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            state = getattr(self._optimizer, "state_dict", lambda: {})()
            _save(state, path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        self.network.set_state_dict(_load(path + ".pdparams"))
        opt_path = path + ".pdopt"
        if (not reset_optimizer and self._optimizer is not None
                and os.path.exists(opt_path)):
            if hasattr(self._optimizer, "set_state_dict"):
                self._optimizer.set_state_dict(_load(opt_path))

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def summary(self, input_size=None, dtype=None):
        from .summary import summary

        return summary(self.network, input_size, dtypes=dtype)


_monitor_register(sys.modules[__name__])
