"""Whole-train-step compilation: forward + backward + optimizer in ONE
XLA program.

Reference parity: this is the TPU answer to the reference's static-graph
training path — `Executor.run(program)` over a ProgramDesc containing
forward, backward (appended by `append_backward`) and optimizer ops,
executed by the StandaloneExecutor (`new_executor/standalone_executor.h:34`).
Where the reference builds that program from graph-mode Python, we *trace*
the eager code: the tape (`autograd/tape.py`) records on jax tracers, the
optimizer rules are pure (`optimizer.py` `_init_state`/`_update`), so one
`jax.jit` captures the complete step — gradients, clipping, weight decay,
multi-precision masters, LR — and XLA fuses and overlaps everything
(including the GSPMD gradient collectives under a mesh). Parameter and
optimizer-state buffers are DONATED, so the step runs in-place in HBM like
the reference's inplace-addto pass.

This is the engine under `hapi.Model.fit`'s compiled path, `bench.py`, and
the multichip dry-run.
"""
from __future__ import annotations

import sys
import time
from collections import deque

import jax
import jax.numpy as jnp

from ..autograd import tape
from ..framework import random as rng
from ..framework.core import Tensor
from ..monitor import _register as _monitor_register
from ..monitor import numerics as _numerics

# Telemetry slots (see paddle_tpu.monitor): None unless PT_MONITOR wired
# them. `_spans` feeds the flight recorder (monitor/spans.py): step
# dispatch vs trace+compile, donation rebinds, AsyncStepper fence waits.
# `_nancheck` is the numerics sentinel's slot (monitor/numerics.py):
# None unless PT_NANCHECK armed it — per-instance `nan_check=True`
# overrides it without touching the global slot. `_goodput` is armed
# only while a fit() goodput ledger is active (monitor/goodput.py):
# it retro-charges fresh-signature compile time out of the enclosing
# productive_step bucket.
_monitor = None
_spans = None
_nancheck = None
_goodput = None


class TrainStep:
    """Compile `(model, optimizer, loss_fn)` into one cached XLA program.

    loss_fn(model, *batch) -> scalar loss Tensor. Default: model(*batch)
    is the loss. Retraces per batch (shape, dtype) signature.

    Usage:
        step = TrainStep(model, opt, lambda m, x, y: m(x, y))
        loss = step(x, y)          # Tensors or arrays

    donate=True enables XLA buffer donation (in-place HBM update — halves
    peak memory for params+optimizer state). The cost: optimizer-state
    arrays snapshotted between steps (e.g. a held state_dict) are
    invalidated by the next call, so keep it off when checkpointing
    mid-run from external references.

    nan_check=True arms the numerics sentinel for this instance
    (monitor/numerics.py): the compiled step returns one extra fused
    isfinite scalar over loss/grads/updates, fetched per step; the first
    failure replays the batch and raises NonFiniteError naming the first
    bad leaf. None (default) follows the global PT_NANCHECK state.
    While armed, donation is suspended — replay needs the pre-step
    params intact.
    """

    def __init__(self, model, optimizer, loss_fn=None, donate=False,
                 nan_check=None):
        self._model = model
        self._opt = optimizer
        self._loss_fn = loss_fn or (lambda m, *batch: m(*batch))
        self._donate = donate
        self._nan_check = nan_check
        self._params = [
            p for p in model.parameters() if not p.stop_gradient
        ]
        self._buffers = [b for _, b in model.named_buffers()]
        # optimizer state lives here in functional form, aligned to _params
        self._state: list[dict] = []
        self._masters: list = []
        self._step_count = 0
        self._cache = {}
        self._retraced = False

    # -- functional per-param update mirroring Optimizer.step's eager loop --
    def _param_update(self, p, arr, g, state, master, lr, step):
        opt = self._opt
        opt._current_param = p
        opt._current_reg = getattr(p, "regularizer", None)
        attrs = getattr(p, "optimize_attr", None)
        lr_p = lr * float(attrs.get("learning_rate", 1.0)) if attrs else lr
        low_prec = arr.dtype.name in ("bfloat16", "float16")
        if opt._multi_precision and low_prec:
            work = master
            g_arr = g.astype(jnp.float32)
        else:
            work = arr
            g_arr = g.astype(arr.dtype)
        work = opt._apply_decoupled_decay(work, lr_p, p)
        new_w, new_state = opt._update(work, g_arr, state, lr_p, step)
        mask = getattr(opt, "_param_masks", {}).get(id(p))
        if mask is not None:
            # ASP sparsity mask baked into the compiled step as a constant
            new_w = new_w * mask.astype(new_w.dtype)
        if opt._multi_precision and low_prec:
            return new_w.astype(arr.dtype), new_state, new_w
        return new_w, new_state, None

    def _ensure_state(self):
        if self._state:
            return
        opt = self._opt
        self._step_count = opt._global_step
        # state created OUTSIDE a step parks in its at-rest placement:
        # under ZeRO offload that is pinned host memory
        # (_initial_state_placement); the compiled step stages it in
        ip = getattr(opt, "_initial_state_placement", None)
        place_m = ip if ip is not None else opt._place_master
        place_s = ((lambda st: {k: ip(v) for k, v in st.items()})
                   if ip is not None else opt._place_state)
        for p in self._params:
            arr = p._data
            low_prec = arr.dtype.name in ("bfloat16", "float16")
            existing = opt._accumulators.get(id(p))
            if opt._multi_precision and low_prec:
                master = opt._master_weights.get(id(p))
                if master is None:
                    master = place_m(arr.astype(jnp.float32))
                self._state.append(existing if existing is not None else
                                   place_s(opt._init_state(master)))
                self._masters.append(master)
            else:
                self._state.append(existing if existing is not None else
                                   place_s(opt._init_state(arr)))
                self._masters.append(None)

    def _sync_optimizer(self):
        """Mirror functional state back onto the Optimizer's dict form so
        optimizer.state_dict()/checkpointing sees compiled-path training."""
        opt = self._opt
        opt._global_step = self._step_count
        for p, st, m in zip(self._params, self._state, self._masters):
            opt._accumulators[id(p)] = st
            opt._step_counts[id(p)] = self._step_count
            if m is not None:
                opt._master_weights[id(p)] = m

    def _flatten_state(self):
        flat = []
        for st in self._state:
            for k in sorted(st):
                flat.append(st[k])
        flat.extend(m for m in self._masters if m is not None)
        return flat

    def _unflatten_state(self, flat):
        pos = 0
        state, masters = [], []
        for st in self._state:
            d = {}
            for k in sorted(st):
                d[k] = flat[pos]
                pos += 1
            state.append(d)
        for m in self._masters:
            if m is None:
                masters.append(None)
            else:
                masters.append(flat[pos])
                pos += 1
        return state, masters

    def _nan_active(self) -> bool:
        """The sentinel state this step compiles/checks under: instance
        override first, else the global `_nancheck` slot (None-slot
        contract: off costs one attribute check)."""
        if self._nan_check is not None:
            return bool(self._nan_check)
        return _nancheck is not None

    def _build(self, batch_sig, nan_check=False):
        params, buffers = self._params, self._buffers
        model, opt = self._model, self._opt
        loss_fn = self._loss_fn
        outer = self

        # ZeRO offload: state leaves living in pinned host memory are
        # staged device-ward inside the program; the new state is staged
        # back host-ward eagerly in __call__ (reference group_sharded
        # offload=True semantics). Stage-out cannot live inside the
        # program: host-placement annotations on SPMD outputs don't
        # lower on the CPU test backend, and peak HBM is identical
        # either way (the state is resident during the update).
        # detect specifically the offload placement: ZeRO offload parks
        # state in "pinned_host". Comparing != "device" is wrong off-TPU —
        # the CPU backend's DEFAULT memory kind is "unpinned_host", which
        # made every stateful-optimizer step try (and fail) to stage
        # plain CPU state "device"-ward.
        host_shardings = [
            s.sharding if getattr(getattr(s, "sharding", None),
                                  "memory_kind", None) == "pinned_host"
            else None
            for s in self._flatten_state()]

        def step_fn(param_arrays, state_flat, buffer_arrays, lr, step, prng,
                    batch_arrays):
            if any(s is not None for s in host_shardings):
                state_flat = [
                    a if s is None else jax.device_put(
                        a, s.with_memory_kind("device"))
                    for a, s in zip(state_flat, host_shardings)]
            state, masters = outer._unflatten_state(state_flat)
            saved = [(t, t._data, t._grad_node) for t in params + buffers]
            try:
                for p, a in zip(params, param_arrays):
                    p._data = a
                    p._grad_node = None
                for b, a in zip(buffers, buffer_arrays):
                    b._data = a
                batch = [Tensor(a) for a in batch_arrays]
                with rng.rng_scope(prng), tape.enable_grad():
                    loss = loss_fn(model, *batch)
                grads = tape.grad(loss, params, allow_unused=True,
                                  retain_graph=False)
                pg = [(p, g) for p, g in zip(params, grads)]
                if opt._grad_clip is not None:
                    pg = opt._grad_clip(pg)
                new_params, new_state, new_masters = [], [], []
                for (p, g), arr, st, m in zip(pg, param_arrays, state, masters):
                    if g is None:
                        new_params.append(arr)
                        new_state.append(st)
                        new_masters.append(m)
                        continue
                    np_, ns_, nm_ = outer._param_update(
                        p, arr, g._data, st, m, lr, step)
                    new_params.append(np_)
                    new_state.append(ns_)
                    new_masters.append(nm_ if nm_ is not None else m)
                new_buffers = [b._data for b in buffers]
                flat_state = []
                for st in new_state:
                    for k in sorted(st):
                        flat_state.append(st[k])
                flat_state.extend(m for m in new_masters if m is not None)
                if nan_check:
                    # the sentinel's one extra output: a fused isfinite
                    # reduction over everything this step produced —
                    # checked as ONE host scalar, never per-tensor
                    finite = _numerics.finite_all(
                        [loss._data]
                        + [g._data for _, g in pg if g is not None]
                        + new_params + flat_state)
                    return (new_params, flat_state, new_buffers,
                            loss._data, finite)
                return new_params, flat_state, new_buffers, loss._data
            finally:
                for t, a, gn in saved:
                    t._data = a
                    t._grad_node = gn

        # donation suspended while the sentinel is armed: a failing step
        # is replayed against the pre-step params, which donation would
        # have invalidated
        donate = (0, 1, 2) if (self._donate and not nan_check) else ()
        return jax.jit(step_fn, donate_argnums=donate)

    def _place(self, x):
        # host-side scalars/batches join the params' mesh (replicated;
        # multihost-safe via env.put_replicated). An input ALREADY on
        # the mesh keeps its placement — a planned run's dp-sharded
        # batch (autoshard.shard_batch) must not be re-replicated, or
        # data parallelism would be compiled out of the step
        from ..distributed import env as env_mod

        e = env_mod.get_env()
        if e is None or e.mesh.size == 1:
            return x
        return env_mod.ensure_on_mesh(x, e.mesh)

    def _lowered_for(self, arrays, nan_check):
        """Trace + lower the step against the CURRENT params/state/batch
        placements — the lowering the exec cache compiles, and the one
        whose avals every later __call__ must match (lr/step/prng are
        runtime args; only their avals are fixed here)."""
        jitted = self._build(None, nan_check=nan_check)
        place = self._place
        return jitted.lower(
            [p._data for p in self._params],
            self._flatten_state(),
            [b._data for b in self._buffers],
            place(jnp.asarray(self._opt.get_lr(), jnp.float32)),
            place(jnp.asarray(self._step_count, jnp.int32)),
            # only the key's aval matters for lowering; a fixed key keeps
            # compilation free of global-PRNG side effects
            place(jax.random.key(0)),
            [place(a) for a in arrays],
        )

    def _cache_key(self, arrays, training, nan_check):
        """The executable-cache fingerprint: everything the traced
        program is a function of beyond the batch avals — model identity
        + config scalars, param/buffer/optimizer-state avals + shardings,
        values that get BAKED as constants (frozen params, ASP masks,
        per-param lr factors), optimizer + loss_fn identity, the
        donation/sentinel/training flags, and the mesh topology. Built
        only while the cache is enabled (key=None otherwise)."""
        from . import exec_cache as ec

        model, opt = self._model, self._opt
        params_spec, frozen = [], []
        for name, p in model.named_parameters():
            if p.stop_gradient:
                # closed over at trace time -> a program constant
                frozen.append((name, ec.array_digest(p._data)))
                continue
            attrs = getattr(p, "optimize_attr", None) or {}
            params_spec.append(
                (name, ec.array_spec(p._data),
                 float(attrs.get("learning_rate", 1.0)),
                 ec.freeze_attrs(getattr(p, "regularizer", None))))
        masks = getattr(opt, "_param_masks", None) or {}
        mask_spec = tuple(
            (i, ec.array_digest(masks[id(p)]))
            for i, p in enumerate(self._params) if id(p) in masks)
        # out-of-tree model/sublayer classes are invisible to the
        # package fingerprint — key their method bytecode explicitly so
        # an edited forward() can never serve a stale disk artifact
        layer_classes = {type(la) for la in (
            model.sublayers(include_self=True)
            if hasattr(model, "sublayers") else [model])}
        model_code = tuple(sorted(
            (fp for c in layer_classes if (fp := ec.fingerprint_class(c))),
            key=repr))
        return {
            "kind": "train_step",
            "model": type(model).__module__ + "." + type(model).__qualname__,
            "model_code": model_code,
            "config": ec.freeze_attrs(getattr(model, "config", None)),
            "params": tuple(params_spec),
            "frozen": tuple(frozen),
            "buffers": tuple((n, ec.array_spec(b._data))
                             for n, b in model.named_buffers()),
            "state": tuple(ec.array_spec(a) for a in self._flatten_state()),
            # id(p)-keyed runtime dicts are excluded: their keys are
            # per-process addresses (contents are keyed elsewhere —
            # state avals above, masks below, params by name)
            "opt": (type(opt).__module__ + "." + type(opt).__qualname__,
                    ec.fingerprint_class(type(opt)),
                    ec.freeze_attrs(opt, exclude=(
                        "_global_step", "_accumulators", "_step_counts",
                        "_master_weights", "_param_masks",
                        "_parameter_list",
                        # per-param scratch _param_update writes DURING
                        # tracing: keying them would make the key drift
                        # across a compile (the planner's meta sidecar
                        # re-keys after one) — their content is keyed
                        # per-param in params_spec already
                        "_current_param", "_current_reg")),
                    ec.freeze_attrs(getattr(opt, "_grad_clip", None))),
            "masks": mask_spec,
            "loss_fn": ec.fingerprint_callable(self._loss_fn),
            "donate": bool(self._donate),
            "nan_check": bool(nan_check),
            "training": bool(training),
            # full spec (not just shape/dtype): a batch committed to a
            # different placement is a different lowering, and the
            # stale-placement retry relies on the key seeing that
            "batch": tuple(ec.array_spec(a) for a in arrays),
            "mesh": ec.mesh_spec(),
        }

    def _get_compiled(self, batch):
        """Normalize batch to arrays and return (executable, arrays,
        nan_check) from the signature cache — shared by __call__ and
        memory_analysis so the analyzed executable is the one that
        actually runs. A per-instance miss routes through the process-
        wide exec cache (jit/exec_cache.py): AOT trace+lower+compile, or
        a deserialized on-disk artifact with zero fresh XLA compiles.
        ``nan_check`` is returned rather than re-read by the caller: it
        decides the executable's output arity, and the global slot may
        flip between two reads."""
        from . import exec_cache

        self._ensure_state()
        arrays = [b._data if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
        training = getattr(self._model, "training", True)
        nan_check = self._nan_active()
        sig = (tuple((tuple(a.shape), str(a.dtype)) for a in arrays),
               training, nan_check)
        fn = self._cache.get(sig)
        self._retraced = fn is None
        if fn is None:
            if _monitor is not None:
                _monitor.on_retrace(id(self), len(self._cache) + 1)
            key = (self._cache_key(arrays, training, nan_check)
                   if exec_cache.enabled() else None)
            fn = self._cache[sig] = exec_cache.get_or_compile(
                key, lambda: self._lowered_for(arrays, nan_check),
                label=f"train_step/{type(self._model).__name__}")
        return fn, arrays, nan_check

    def __call__(self, *batch):
        m = _monitor
        sp = _spans
        g = _goodput
        # span clock starts BEFORE _get_compiled: a fresh signature pays
        # trace + XLA compile (or a cache-tier load) inside it, and that
        # cost belongs to this call's compile span (and the goodput
        # ledger's compile bucket), not "other"
        t_dispatch = (time.perf_counter()
                      if sp is not None or g is not None else None)
        fn, arrays, nan_check = self._get_compiled(batch)
        if g is not None and self._retraced:
            g.charge("compile", time.perf_counter() - t_dispatch)
        lr = self._opt.get_lr()
        self._step_count += 1
        place = self._place
        # key split AFTER the span timestamp (it is a real device op —
        # its cost belongs in the dispatch span, not "other"); kept in a
        # local so a sentinel replay can reuse the exact key
        prng = rng.next_key()
        step_args = (
            [p._data for p in self._params],
            self._flatten_state(),
            [b._data for b in self._buffers],
            place(jnp.asarray(lr, jnp.float32)),
            place(jnp.asarray(self._step_count, jnp.int32)),
            place(prng),
            [place(a) for a in arrays],
        )
        try:
            outs = fn(*step_args)
        except Exception as e:
            # a mid-execution failure under donation has already consumed
            # the input buffers — retrying would mask the real error
            # behind a secondary "array deleted"; placement-mismatch
            # errors fail BEFORE donation, so live inputs are the test
            dead = any(
                getattr(a, "is_deleted", lambda: False)()
                for part in step_args[:3] for a in part)
            # only a stale-placement dispatch earns the retry: a device
            # OOM or runtime fault on a cached signature must surface
            # as-is, not cost a second compile + re-execution and a
            # needlessly emptied signature cache
            msg = str(e).lower()
            stale = any(t in msg for t in (
                "sharding", "placement", "incompatible device",
                "different input device", "memory kind", "committed"))
            if self._retraced or dead or not stale:
                raise
            # an AOT executable freezes the placements it was lowered
            # against; re-placed params or a mesh change since this
            # signature was cached surface here as a sharding mismatch.
            # jax.jit used to recompile transparently — restore that:
            # drop the stale per-instance entries (ALL are suspect once
            # placements moved) and retry once against current ones
            self._cache.clear()
            fn, _, nan_check = self._get_compiled(batch)
            outs = fn(*step_args)
        if nan_check:
            new_params, flat_state, new_buffers, loss, finite = outs
        else:
            new_params, flat_state, new_buffers, loss = outs
        if sp is not None:
            # one span per fn() call, categorized by what the wall time
            # actually was: trace+compile on a fresh signature, pure
            # dispatch (enqueue) on a cache hit — no nested double count
            if self._retraced:
                sp.record("jit/trace_compile", "compile", t_dispatch)
            else:
                sp.record("jit/step_dispatch", "dispatch", t_dispatch)
        if m is not None and self._donate and not nan_check:
            # donated buffers are dead after the call; every param rebinds
            m.on_donation_rebind(len(self._params))
        if nan_check:
            t_check = time.perf_counter()
            # ONE host scalar per step — the sentinel's whole healthy-path
            # cost, counted into the hapi/host_syncs guard counter
            ok = bool(finite)
            if m is not None:
                m.on_nan_check()
            if not ok:
                if m is not None:
                    m.on_nan_failure()
                # pre-step params are still bound (rebind happens below,
                # donation is off under the sentinel) — replay the batch
                # eagerly and name the first bad leaf
                leaf, kind = _numerics.isolate(self, arrays, prng, lr)
                if sp is not None:
                    sp.record("numerics/first_bad_step", "numerics",
                              t_check, args={"step": self._step_count,
                                             "leaf": leaf, "kind": kind})
                failed_step = self._step_count
                # a failed step never happened: params/state were not
                # rebound, so the counter must not advance either — a
                # skip-and-continue policy (resilience/numerics_policy)
                # retries the NEXT batch at the same step index, keeping
                # LR schedules and bias correction aligned with the
                # updates that actually landed
                self._step_count -= 1
                raise _numerics.NonFiniteError(failed_step, leaf, kind)
        t_rebind = time.perf_counter() if sp is not None else None
        for p, a in zip(self._params, new_params):
            p._data = a
            p._grad_node = None
            p.grad = None
        if getattr(self._opt, "_offload_state", False):
            flat_state = [
                a if getattr(a.sharding, "memory_kind", "device")
                != "device" else jax.device_put(
                    a, a.sharding.with_memory_kind("pinned_host"))
                for a in flat_state]
        self._state, self._masters = self._unflatten_state(flat_state)
        for b, a in zip(self._buffers, new_buffers):
            b._data = a
        self._sync_optimizer()
        if sp is not None:
            sp.record("jit/donation_rebind" if self._donate
                      else "jit/state_rebind", "dispatch", t_rebind)
        return Tensor(loss)

    # -- introspection --
    @property
    def compiled_count(self):
        return len(self._cache)

    def exec_cache_key(self, *batch):
        """The process-wide executable-cache key this batch signature
        compiles under (None while the cache is disabled) — the handle
        the sharding planner uses to file sidecar facts about the
        executable (`exec_cache.meta_put`) under the SAME invalidation
        lifetime as the executable itself."""
        from . import exec_cache

        if not exec_cache.enabled():
            return None
        self._ensure_state()
        arrays = [b._data if isinstance(b, Tensor) else jnp.asarray(b)
                  for b in batch]
        return self._cache_key(
            arrays, getattr(self._model, "training", True),
            self._nan_active())

    def memory_analysis(self, *batch):
        """XLA memory accounting of the compiled step for these batch
        shapes (``argument/output/temp/generated_code`` bytes, as reported
        by the executable). The HBM-footprint source of truth before a
        step has run (planning) and on backends that report no allocator
        stats (``device.memory_stats()`` is ``None`` on the CPU; the TPU
        reports ``peak_bytes_in_use``).
        Served from the same executable cache __call__ runs — an
        already-stepped signature is accounted for FREE (no second AOT
        compile), and so is a warm ``PT_EXEC_CACHE`` start: deserialized
        executables keep their ``memory_analysis``. For SPMD executables
        under a mesh the reported sizes are per-device."""
        fn, _arrays, _nan = self._get_compiled(batch)
        return fn.memory_analysis()


class AsyncStepper:
    """Bounded in-flight pipelining over a :class:`TrainStep`.

    Each ``__call__`` dispatches one compiled step and returns the loss as
    a LAZY device array (a ``Tensor`` whose buffer is a future — jax
    dispatch is asynchronous, so the host returns at enqueue). The stepper
    keeps at most ``max_in_flight`` un-fenced steps outstanding: past the
    bound it fences the OLDEST step's loss through a host transfer
    (``utils/timing.device_sync``) before dispatching further.

    Why a bound: params and optimizer state are donated, so in-flight
    steps chain through them without extra HBM — but each step's
    *undonated* outputs (the loss, plus any staged batch still live) hold
    device memory until fenced, and an unbounded host can race arbitrarily
    far ahead of a slow device (unbounded HBM + a uselessly deep dispatch
    queue). In steady state the (k−N)th step has already completed by the
    time step k is dispatched, so the fence costs ~0 host time; the bound
    only throttles when the host outruns the device by ≥ N steps — exactly
    when it should. docs/ASYNC_PIPELINE.md covers the HBM-vs-latency
    tradeoff of choosing N.

    Donation, retrace, and compile-counter semantics are the wrapped
    TrainStep's own — this class adds no step logic, only flow control.
    Telemetry (zero-overhead off): ``async/steps_in_flight`` gauge,
    ``async/bound_waits`` + ``async/bound_wait_ms`` when the bound blocks.
    """

    def __init__(self, train_step, max_in_flight=2):
        if max_in_flight < 1:
            raise ValueError(
                f"AsyncStepper: max_in_flight must be >= 1 "
                f"(got {max_in_flight})")
        self._step = train_step
        self._max = int(max_in_flight)
        self._inflight: deque = deque()
        # host-blocked seconds accumulated in fences (read by
        # benchmarks/host_overhead_bench.py and bench.py's A/B)
        self.host_blocked_s = 0.0

    def _fence(self, loss):
        """Block until `loss` has actually been computed (host transfer)."""
        from ..utils.timing import device_sync

        device_sync(loss._data if isinstance(loss, Tensor) else loss)

    def __call__(self, *batch):
        loss = self._step(*batch)
        self._inflight.append(loss)
        m = _monitor
        if len(self._inflight) > self._max:
            old = self._inflight.popleft()
            t0 = time.perf_counter()
            self._fence(old)
            waited = time.perf_counter() - t0
            self.host_blocked_s += waited
            if m is not None:
                m.on_async_bound_wait(waited * 1e3)
            sp = _spans
            if sp is not None:
                # outranks the nested device_sync span in attribution
                # (monitor/spans.py ATTRIBUTION_CATEGORIES priority)
                sp.record("async/bound_wait", "fence_wait", t0)
        if m is not None:
            m.on_async_inflight(len(self._inflight))
        return loss

    def drain(self):
        """Fence every in-flight step; returns the most recent loss (still
        lazy-typed, but guaranteed complete) or None if nothing is
        outstanding. Call before checkpointing, timing boundaries, or
        reading optimizer state snapshots."""
        last = self._inflight[-1] if self._inflight else None
        had_inflight = bool(self._inflight)
        t0 = time.perf_counter()
        while self._inflight:
            self._fence(self._inflight.popleft())
        self.host_blocked_s += time.perf_counter() - t0
        m = _monitor
        if m is not None:
            m.on_async_inflight(0)
        sp = _spans
        if sp is not None and had_inflight:
            sp.record("async/drain", "fence_wait", t0)
        return last

    @property
    def in_flight(self):
        return len(self._inflight)

    @property
    def max_in_flight(self):
        return self._max

    # introspection passthrough: callers treat this as a TrainStep
    @property
    def compiled_count(self):
        return self._step.compiled_count

    def memory_analysis(self, *batch):
        return self._step.memory_analysis(*batch)


_monitor_register(sys.modules[__name__])
_numerics._register(sys.modules[__name__])
