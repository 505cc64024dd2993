"""Unified runtime telemetry: counters, step-metrics sink, trace correlation.

What the profiler (`paddle_tpu/profiler`) does for *user code* — host event
scopes, op timelines — this subsystem does for the *runtime itself*: jit
retraces and compile wall-time, dispatch primitive-cache hits/misses,
sync-fence latency, collective traffic, PRNG key splits, autocast entries.
These are exactly the signals that were invisible when rounds 1–3 lost
bench truth to surprise recompiles.

Zero-overhead-when-off contract: instrumented modules (``ops/dispatch``,
``jit/train_step``, ``utils/timing``, ``distributed/collective``,
``framework/random``, ``amp/auto_cast``) each carry a module-global
``_monitor`` slot that is ``None`` unless :func:`enable` installed this
module into it. Their hot paths guard with ``if _monitor is not None`` —
when monitoring is off no monitor callable is ever invoked (asserted by
``tests/test_monitor.py``). Enablement: ``PT_MONITOR=1`` in the
environment, or :func:`enable` programmatically.

Emission path: :class:`StepLogger` writes one JSONL line per training step
(loss, ips, counter diff) — wired into ``hapi`` fit loops via
``hapi.callbacks.MonitorCallback`` and into ``bench.py``; sink path from
``PT_MONITOR_SINK``. ``tools/monitor_report.py`` joins a JSONL run with a
chrome trace from the profiler into one summary; the profiler also exports
these counters as chrome-trace ``ph:"C"`` counter events so they render on
the Perfetto timeline.
"""
from __future__ import annotations

import os
import sys

from .metrics import (  # noqa: F401
    Counter, Gauge, Histogram, Registry, diff_snapshots,
)
from .spans import SpanRecorder  # noqa: F401

__all__ = [
    "enable", "disable", "enabled", "counter", "gauge", "histogram",
    "snapshot", "diff", "reset", "StepLogger",
    "Counter", "Gauge", "Histogram", "Registry",
    "SpanRecorder", "spans", "record_span", "span_events", "export_spans",
    "watchpoint", "clear_watchpoints",
    "memory", "numerics", "live", "exporter", "INSTRUMENTED_MODULES",
    "goodput", "watchdog", "heartbeat",
]

# The canonical audit list for the zero-overhead contract: every module
# that carries a `_monitor` slot (and, where declared, `_spans` /
# `_nancheck` siblings). tests/test_memory_numerics.py asserts each is
# import-time-inert while PT_MONITOR / PT_NANCHECK / PT_MONITOR_MEM are
# unset — add new instrumentation sites HERE so the audit covers them.
INSTRUMENTED_MODULES = (
    "paddle_tpu.ops.dispatch",
    "paddle_tpu.jit.train_step",
    "paddle_tpu.jit.exec_cache",
    "paddle_tpu.utils.timing",
    "paddle_tpu.distributed.collective",
    "paddle_tpu.framework.random",
    "paddle_tpu.amp.auto_cast",
    "paddle_tpu.io.prefetch",
    "paddle_tpu.hapi.model",
    "paddle_tpu.serving.engine",
    "paddle_tpu.serving.scheduler",
    "paddle_tpu.serving.speculative",
    "paddle_tpu.serving.router",
    "paddle_tpu.ops.pallas.search",
    "paddle_tpu.resilience.checkpoint_manager",
    "paddle_tpu.resilience.resume",
    "paddle_tpu.resilience.numerics_policy",
    "paddle_tpu.autoshard.planner",
    "paddle_tpu.analysis.program_audit",
    "paddle_tpu.distributed.fleet.meta_parallel.parallel_layers.pp_layers",
    "paddle_tpu.monitor.goodput",
    "paddle_tpu.monitor.watchdog",
    "paddle_tpu.monitor.heartbeat",
)

_registry = Registry()
_enabled = False

# the flight recorder behind every module's `_spans` slot (monitor/spans.py);
# one process-wide ring so all lanes land on one timeline
_span_recorder = SpanRecorder()

# every instrumented module registers itself here (see _register); enable()
# installs this module into each site's `_monitor` slot, disable() clears it.
# Modules that also record spans declare a module-global `_spans` slot,
# wired to the ring recorder under the same enable/disable lifecycle.
_SITES: list = []

# hot-path metrics are pre-created so instrumentation pays one attribute
# load + method call, never a registry lookup
_c_op_apply = _registry.counter("dispatch/op_apply")
_c_prim = {kind: _registry.counter(f"dispatch/prim_cache_{kind}")
           for kind in ("hit", "miss", "uncacheable")}
_c_retraces = _registry.counter("jit/retraces")
_c_compiles = _registry.counter("jit/compiles")
_h_compile_ms = _registry.histogram("jit/compile_ms")
_g_cache_size = _registry.gauge("jit/signature_cache_size")
_c_rebinds = _registry.counter("jit/donation_rebinds")
_c_syncs = _registry.counter("sync/fences")
_h_sync_ms = _registry.histogram("sync/fence_ms")
_c_coll_bytes = _registry.counter("collective/bytes")
_c_key_splits = _registry.counter("rng/key_splits")
_c_autocast = _registry.counter("amp/autocast_enters")
# async-pipeline metrics (io/prefetch.py, jit/train_step.py AsyncStepper,
# hapi/model.py deferred loss materialization — docs/ASYNC_PIPELINE.md)
_c_prefetch_batches = _registry.counter("io/prefetch_batches")
_g_prefetch_depth = _registry.gauge("io/prefetch_depth")
_c_prefetch_starved = _registry.counter("io/prefetch_starvations")
_h_prefetch_wait_ms = _registry.histogram("io/prefetch_wait_ms")
_g_inflight = _registry.gauge("async/steps_in_flight")
_c_bound_waits = _registry.counter("async/bound_waits")
_h_bound_wait_ms = _registry.histogram("async/bound_wait_ms")
_c_host_syncs = _registry.counter("hapi/host_syncs")
# numerics sentinel (monitor/numerics.py via jit/train_step.py): one
# check = one extra host scalar fetch — it also counts into the
# hapi/host_syncs guard counter so the ≤1-extra-per-step bound is provable
_c_nan_checks = _registry.counter("numerics/checks")
_c_nan_failures = _registry.counter("numerics/failures")
# AOT executable cache (jit/exec_cache.py): hits span both tiers;
# deserialize/serialize time is the disk tier's cost, saved_ms the
# compile wall-time a disk hit avoided (the original build's measured
# compile_ms, carried inside the artifact)
_c_exec_hit = _registry.counter("jit/exec_cache_hit")
_c_exec_miss = _registry.counter("jit/exec_cache_miss")
_h_exec_deserialize_ms = _registry.histogram("jit/exec_cache_deserialize_ms")
_h_exec_serialize_ms = _registry.histogram("jit/exec_cache_serialize_ms")
_h_exec_saved_ms = _registry.histogram("jit/exec_cache_saved_ms")
# continuous-batching serving runtime (serving/engine.py — docs/SERVING.md):
# lane/block bookkeeping between shared decode steps. `evictions` counts
# finished-lane reclamations; `preemptions`/`requeues` the capacity-
# pressure evictions (recompute policy requeues every preempted request,
# so the two track together — both exist so a report reads either way)
_c_serve_admits = _registry.counter("serving/admits")
_c_serve_evictions = _registry.counter("serving/evictions")
_c_serve_preempt = _registry.counter("serving/preemptions")
_c_serve_requeue = _registry.counter("serving/requeues")
_c_serve_prefill = _registry.counter("serving/prefill_steps")
_c_serve_decode = _registry.counter("serving/decode_steps")
_g_serve_lanes = _registry.gauge("serving/lanes_occupied")
_g_serve_free_blocks = _registry.gauge("serving/free_blocks")
_h_serve_queue_wait = _registry.histogram("serving/queue_wait_ms")
# prefix-cache KV sharing (serving/kv_cache.py prefix index): per
# (re-)prefill token split — hit = context served by acquired shared
# blocks, miss = tokens actually prefilled — plus the pool's live
# shared / cold-LRU block census after the prefill
_c_serve_prefix_hit = _registry.counter("serving/prefix_hit_tokens")
_c_serve_prefix_miss = _registry.counter("serving/prefix_miss_tokens")
_g_serve_shared_blocks = _registry.gauge("serving/shared_blocks")
_g_serve_cold_blocks = _registry.gauge("serving/cold_blocks")
# int8 KV block pool (PT_SERVE_KV_INT8 — docs/SERVING.md "int8 KV"):
# quantize-on-write program launches + the real tokens they quantized,
# and the device bytes the K/V (+ scale) pools pin — bf16 engines never
# touch these
_c_kv_quant_writes = _registry.counter("serving/kv_quant_writes")
_c_kv_quant_tokens = _registry.counter("serving/kv_quant_tokens")
_g_kv_pool_bytes = _registry.gauge("serving/kv_pool_bytes")
# speculative decoding (serving/engine.py verify rounds + the
# serving/speculative.py drafter — docs/SERVING.md): decoded_tokens
# accumulates across plain decode AND verify rounds so
# tokens-per-decode-step = decoded / (decode_steps + verify_steps);
# proposed/accepted are post-trim (accepted/proposed IS the accept
# rate) and the per-round rate lands in the histogram
_c_serve_verify = _registry.counter("serving/verify_steps")
_c_serve_decoded = _registry.counter("serving/decoded_tokens")
_c_spec_proposed = _registry.counter("serving/spec_proposed_tokens")
_c_spec_accepted = _registry.counter("serving/spec_accepted_tokens")
_c_spec_bonus = _registry.counter("serving/spec_bonus_tokens")
_c_spec_draft_calls = _registry.counter("serving/spec_draft_calls")
_h_spec_accept = _registry.histogram("serving/spec_accept_rate")
# multi-replica serving router (serving/router.py — docs/SERVING.md):
# dispatch decisions (with the affinity hit/miss split the bench's
# affinity_hit_rate reads), drain re-dispatches after a replica death,
# and the dead-replica count; per-replica dispatch counters and
# lane/queue gauges land under router/<metric>/<replica>
_c_router_dispatch = _registry.counter("router/dispatches")
_c_router_aff_hit = _registry.counter("router/affinity_hits")
_c_router_aff_miss = _registry.counter("router/affinity_misses")
_c_router_redispatch = _registry.counter("router/redispatches")
_c_router_dead = _registry.counter("router/dead_replicas")
# Pallas kernel engagement + the search harness (ops/pallas/search.py —
# docs/KERNELS.md): every dispatch-time engagement decision is counted
# (engaged vs composite fallback, with a per-family breakdown counter),
# and a tuning run accounts its candidates (timed vs parity/compile
# rejects) plus the winning kernel-vs-composite ratio per family
_c_pallas_engaged = _registry.counter("pallas/engaged")
_c_pallas_fallback = _registry.counter("pallas/fallback_composite")
_c_search_timed = _registry.counter("search/candidates_timed")
_c_search_rejects = _registry.counter("search/rejects")
# resilience runtime (paddle_tpu/resilience — docs/RESILIENCE.md):
# checkpoint traffic + the NaN skip policy. `save_ms` is the BLOCKING
# cost per save (quiesce + host snapshot; file I/O overlaps training) —
# exactly the number the cadence planner budgets against
_c_res_saves = _registry.counter("resilience/saves")
_h_res_save_ms = _registry.histogram("resilience/save_ms")
_c_res_restores = _registry.counter("resilience/restores")
_c_res_crash_resumes = _registry.counter("resilience/crash_resumes")
_c_res_skipped = _registry.counter("resilience/skipped_batches")
# automatic sharding planner (paddle_tpu/autoshard — docs/AUTOSHARD.md):
# sweep accounting per candidate row + emitted plans; the winner gauge
# is the roofline estimate the plan committed to
_c_plan_candidates = _registry.counter("planner/candidates")
_c_plan_infeasible = _registry.counter("planner/infeasible")
_c_plan_errors = _registry.counter("planner/errors")
_c_plan_plans = _registry.counter("planner/plans")
_g_plan_winner_ms = _registry.gauge("planner/winner_est_step_ms")
# live telemetry plane (monitor/live.py + monitor/exporter.py —
# docs/OBSERVABILITY.md "Live telemetry plane"): SLO watchdog breaches.
# The sketches themselves live in monitor/live.py (they must work with
# the monitor disabled); only the breach count rides this registry.
_c_slo_breach = _registry.counter("monitor/slo_breach")
# compiled-program audit (analysis/program_audit.py, PT_PROGRAM_AUDIT=1
# — docs/STATIC_ANALYSIS.md): executables judged at the exec-cache
# chokepoint and invariant findings (per-rule breakdown under
# analysis/findings/<rule>)
_c_audit_programs = _registry.counter("analysis/audits")
_c_audit_findings = _registry.counter("analysis/findings")
# pipeline parallelism (fleet/meta_parallel pp_layers — ISSUE 15): the
# GPipe-in-XLA schedule's account per forward. The ppermute stage
# handoff is compiled into the one program, invisible to the eager
# collective counters, so the container reports it analytically —
# p2p_bytes also rides collective/bytes/pp so the planner's per-axis
# prediction has a measured twin; the gauge is the last schedule's
# fill/drain bubble fraction
_c_pipe_fwd = _registry.counter("pipeline/forwards")
_c_pipe_micro = _registry.counter("pipeline/microbatches")
_c_pipe_ticks = _registry.counter("pipeline/ticks")
_c_pipe_p2p = _registry.counter("pipeline/p2p_bytes")
_g_pipe_bubble = _registry.gauge("pipeline/bubble_frac")

# per-axis collective-bytes attribution (ISSUE 10 satellite): eager
# collectives know their group's mesh axes, so the aggregate
# collective/bytes counter splits into collective/bytes/<axis> the
# planner's cost model can be judged against. Multi-axis groups bill
# the fused label ("dp+mp", canonical AXIS_ORDER order) so the per-axis
# counters always sum to the aggregate.
_COLL_AXIS_ORDER = ("dp", "pp", "sharding", "sep", "mp")


# -- public metric access ----------------------------------------------------

def counter(name: str) -> Counter:
    """Get-or-create the process-wide counter ``name``
    (e.g. ``monitor.counter("jit/retraces")``)."""
    return _registry.counter(name)


def gauge(name: str) -> Gauge:
    return _registry.gauge(name)


def histogram(name: str) -> Histogram:
    """Get-or-create a histogram (e.g. ``monitor.histogram("sync/fence_ms")``)."""
    return _registry.histogram(name)


def snapshot() -> dict:
    """Typed snapshot ``{"counters", "gauges", "histograms"}`` of every
    live metric."""
    return _registry.snapshot()


def diff(prev: dict, cur: dict | None = None) -> dict:
    """Delta between ``prev`` and ``cur`` (default: a fresh snapshot)."""
    return diff_snapshots(prev, cur if cur is not None else snapshot())


def reset() -> None:
    """Zero every metric, drop recorded spans and armed watchpoints
    (registered objects stay live)."""
    _trainstep_cache_sizes.clear()
    _registry.reset()
    _span_recorder.clear()
    _watchpoints.clear()


# -- spans (monitor/spans.py) ------------------------------------------------

def spans() -> SpanRecorder:
    """The process-wide span ring (live regardless of enablement; the
    instrumented sites only *feed* it while enabled)."""
    return _span_recorder


def record_span(name, cat, t0, t1=None, lane=None, args=None) -> None:
    """Record one completed span — no-op unless the monitor is enabled
    (explicit emitters like StepLogger share the sites' off-is-free
    contract)."""
    if _enabled:
        _span_recorder.record(name, cat, t0, t1, lane=lane, args=args)


def span_events() -> list:
    """Retained spans as chrome-trace events (``ph:"X"`` + lane
    metadata) on the profiler's clock epoch."""
    return _span_recorder.chrome_events()


def export_spans(path: str) -> str:
    """Write the retained spans as a standalone chrome trace. For a trace
    merged with the op timeline and counter tracks, export through
    ``profiler.Profiler.export`` instead."""
    return _span_recorder.export_chrome(path)


# -- watchpoints -------------------------------------------------------------

# name -> {"ceiling", "message", "callback", "fired"}: armed by callers
# (bench.py arms jit/retraces after warmup), checked inline by the site
# callbacks below — so the warning fires live, mid-run, not in post-hoc
# report reading. Only consulted while enabled, and the common case
# (no watchpoints armed) is one falsy dict check.
_watchpoints: dict = {}

# the counters whose site callbacks call _check_watchpoint — arming
# anything else would silently never fire, so watchpoint() refuses it
WATCHABLE_COUNTERS = frozenset({
    "jit/retraces", "io/prefetch_starvations", "sync/fences",
    "async/bound_waits", "hapi/host_syncs",
})


def watchpoint(name: str, ceiling: float, message: str | None = None,
               callback=None) -> None:
    """Arm a one-shot alarm: the first time counter ``name`` exceeds
    ``ceiling``, print ``message`` to stderr (and invoke
    ``callback(name, value)`` if given). Re-arming replaces the old
    watchpoint. Only :data:`WATCHABLE_COUNTERS` are checked live by
    their site callbacks; any other name raises instead of silently
    never firing."""
    if name not in WATCHABLE_COUNTERS:
        raise ValueError(
            f"watchpoint: {name!r} is not checked live by any "
            f"instrumentation site; watchable counters: "
            f"{sorted(WATCHABLE_COUNTERS)}")
    _watchpoints[name] = {"ceiling": float(ceiling), "message": message,
                          "callback": callback, "fired": False}


def clear_watchpoints() -> None:
    _watchpoints.clear()


def _check_watchpoint(name: str, value: float) -> None:
    w = _watchpoints.get(name)
    if w is None or w["fired"] or value <= w["ceiling"]:
        return
    w["fired"] = True
    msg = w["message"] or (f"monitor watchpoint: {name} = {value} "
                           f"exceeded {w['ceiling']}")
    print(f"WARNING: {msg}", file=sys.stderr, flush=True)
    if w["callback"] is not None:
        try:
            w["callback"](name, value)
        except Exception:  # noqa: BLE001 — a watcher must not kill the run
            pass


# -- enablement --------------------------------------------------------------

def enabled() -> bool:
    return _enabled


def enable() -> None:
    """Install the instrumentation hooks (idempotent). Same effect as
    starting the process with ``PT_MONITOR=1``."""
    global _enabled
    if _enabled:
        return
    _enabled = True
    this = sys.modules[__name__]
    for mod in _SITES:
        mod._monitor = this
        if hasattr(mod, "_spans"):
            mod._spans = _span_recorder


def disable() -> None:
    """Uninstall every hook: instrumented hot paths go back to a single
    ``is None`` check with no monitor callables invoked."""
    global _enabled
    if not _enabled:
        return
    _enabled = False
    for mod in _SITES:
        mod._monitor = None
        if hasattr(mod, "_spans"):
            mod._spans = None


def _register(mod) -> None:
    """Called by each instrumented module at import: wires its ``_monitor``
    slot (and its ``_spans`` / ``_live`` slots, when the module declares
    them) to the current enablement state and keeps them in sync with
    later enable()/disable() calls. The ``_live`` slot is armed by
    :mod:`paddle_tpu.monitor.live`'s own enablement, independent of the
    monitor's (live SLO sketches must work with ``PT_MONITOR=0``)."""
    if mod not in _SITES:
        _SITES.append(mod)
    mod._monitor = sys.modules[__name__] if _enabled else None
    if hasattr(mod, "_spans"):
        mod._spans = _span_recorder if _enabled else None
    if hasattr(mod, "_live"):
        mod._live = live if live.enabled() else None
    if hasattr(mod, "_goodput"):
        from . import goodput

        mod._goodput = goodput._slot_value()


# -- site callbacks (invoked ONLY while enabled) -----------------------------

def on_op_apply(op_name: str) -> None:
    _c_op_apply.inc()


def on_prim_cache(kind: str) -> None:
    _c_prim[kind].inc()


# per-TrainStep-instance signature-cache sizes: the gauge is the SUM over
# live instances (a single per-instance value would be clobbered when a run
# holds several steps, e.g. train + eval)
_trainstep_cache_sizes: dict = {}


def on_retrace(owner_id: int, cache_size: int) -> None:
    _c_retraces.inc()
    _trainstep_cache_sizes[owner_id] = cache_size
    _g_cache_size.set(sum(_trainstep_cache_sizes.values()))
    if _watchpoints:
        _check_watchpoint("jit/retraces", _c_retraces.value)


def on_compile_ms(ms: float) -> None:
    """First dispatch of a fresh signature: trace + XLA compile wall-time
    (the call returns after enqueue, so device execution is excluded on
    async backends — this is host-side compile cost)."""
    _c_compiles.inc()
    _h_compile_ms.observe(ms)


def on_donation_rebind(n: int) -> None:
    _c_rebinds.inc(n)


def on_device_sync(ms: float) -> None:
    """One host-transfer-backed device fence (utils/timing.device_sync);
    its latency is the wait for the device plus one host fetch."""
    _c_syncs.inc()
    _h_sync_ms.observe(ms)
    if _watchpoints:
        _check_watchpoint("sync/fences", _c_syncs.value)


def on_collective(name: str, nbytes: int, axes=None) -> None:
    _registry.counter(f"collective/{name}").inc()
    if nbytes:
        _c_coll_bytes.inc(nbytes)
        if axes:
            label = "+".join(a for a in _COLL_AXIS_ORDER if a in axes) \
                or "+".join(sorted(axes))
            _registry.counter(f"collective/bytes/{label}").inc(nbytes)


def on_key_split() -> None:
    _c_key_splits.inc()


def on_autocast_enter() -> None:
    _c_autocast.inc()


def on_prefetch_put(depth: int) -> None:
    """Prefetch producer staged one batch device-ward; ``depth`` is the
    buffer fill level after the put."""
    _c_prefetch_batches.inc()
    _g_prefetch_depth.set(depth)


def on_prefetch_starved(wait_ms: float) -> None:
    """Consumer found the prefetch buffer empty and blocked ``wait_ms`` —
    the input pipeline, not the device, was the bottleneck for that step."""
    _c_prefetch_starved.inc()
    _h_prefetch_wait_ms.observe(wait_ms)
    if _watchpoints:
        _check_watchpoint("io/prefetch_starvations", _c_prefetch_starved.value)


def on_async_inflight(n: int) -> None:
    _g_inflight.set(n)


def on_async_bound_wait(ms: float) -> None:
    """AsyncStepper hit its in-flight bound and fenced the oldest step;
    ``ms`` is the host-blocked wait (≈0 in steady state when the device
    keeps up)."""
    _c_bound_waits.inc()
    _h_bound_wait_ms.observe(ms)
    if _watchpoints:
        _check_watchpoint("async/bound_waits", _c_bound_waits.value)


def on_host_sync(n: int = 1) -> None:
    """One deliberate host materialization of deferred training metrics
    (hapi fit's per-log-window loss fetch) — the guard metric for the
    ≤1-sync-per-window contract."""
    _c_host_syncs.inc(n)
    if _watchpoints:
        _check_watchpoint("hapi/host_syncs", _c_host_syncs.value)


def on_nan_check() -> None:
    """The numerics sentinel fetched its one finite-flag scalar for a
    step. Counts into ``hapi/host_syncs`` too: the fetch IS a deliberate
    host materialization, and the shared counter is how the
    ≤1-extra-fetch-per-step contract stays provable."""
    _c_nan_checks.inc()
    _c_host_syncs.inc()
    if _watchpoints:
        _check_watchpoint("hapi/host_syncs", _c_host_syncs.value)


def on_nan_failure() -> None:
    _c_nan_failures.inc()


def on_exec_cache_hit(tier: str, saved_ms: float | None = None) -> None:
    """The executable cache served a compiled executable without an XLA
    compile; ``tier`` is ``"mem"`` or ``"disk"``. ``saved_ms`` (disk
    hits) is the original build's compile wall-time the hit avoided."""
    _c_exec_hit.inc()
    if saved_ms:
        _h_exec_saved_ms.observe(saved_ms)


def on_exec_cache_miss() -> None:
    _c_exec_miss.inc()


def on_exec_cache_deserialize_ms(ms: float) -> None:
    _h_exec_deserialize_ms.observe(ms)


def on_exec_cache_serialize_ms(ms: float) -> None:
    _h_exec_serialize_ms.observe(ms)


def on_serving_admit(queue_wait_ms: float) -> None:
    """The scheduler moved a waiting request onto a free lane;
    ``queue_wait_ms`` is its submit→admit latency (the queue-pressure
    signal — TTFT is queue wait + prefill)."""
    _c_serve_admits.inc()
    _h_serve_queue_wait.observe(queue_wait_ms)


def on_serving_evict() -> None:
    """A finished lane was reclaimed (KV blocks + lane slot freed)."""
    _c_serve_evictions.inc()


def on_serving_preempt() -> None:
    """Capacity pressure evicted a running lane; the recompute policy
    requeues it at the waiting front, so requeues ride along."""
    _c_serve_preempt.inc()
    _c_serve_requeue.inc()


def on_serving_prefill(chunks: int) -> None:
    """One lane's (re-)prefill ran ``chunks`` compiled chunk calls."""
    _c_serve_prefill.inc(chunks)


def on_serving_decode(lanes_active: int, free_blocks: int) -> None:
    """One shared decode step advanced ``lanes_active`` lanes."""
    _c_serve_decode.inc()
    _c_serve_decoded.inc(lanes_active)
    _g_serve_lanes.set(lanes_active)
    _g_serve_free_blocks.set(free_blocks)


def on_serving_verify(lanes_active: int, free_blocks: int,
                      emitted_tokens: int) -> None:
    """One speculative verify step scored ``lanes_active`` lanes and
    emitted ``emitted_tokens`` (accepted prefixes + bonus tokens —
    ``>= lanes_active`` unless finishes truncated a prefix)."""
    _c_serve_verify.inc()
    _c_serve_decoded.inc(emitted_tokens)
    _g_serve_lanes.set(lanes_active)
    _g_serve_free_blocks.set(free_blocks)


def on_serving_spec(proposed: int, accepted: int, bonus: int) -> None:
    """One verify round's speculation account (post-trim draft tokens
    scored / accepted, bonus tokens emitted); the per-round accept rate
    feeds the ``serving/spec_accept_rate`` histogram."""
    if proposed:
        _c_spec_proposed.inc(proposed)
        _h_spec_accept.observe(accepted / proposed)
    if accepted:
        _c_spec_accepted.inc(accepted)
    if bonus:
        _c_spec_bonus.inc(bonus)


def on_spec_draft_call() -> None:
    """The drafter ran one propose() pass for a lane
    (serving/speculative.py)."""
    _c_spec_draft_calls.inc()


def on_serving_prefix(hit_tokens: int, miss_tokens: int,
                      shared_blocks: int, cold_blocks: int) -> None:
    """One lane's (re-)prefill consulted the prefix cache:
    ``hit_tokens`` of its context rode acquired shared blocks,
    ``miss_tokens`` went through the prefill program; the gauges are
    the pool's shared/cold block census afterwards."""
    if hit_tokens:
        _c_serve_prefix_hit.inc(hit_tokens)
    if miss_tokens:
        _c_serve_prefix_miss.inc(miss_tokens)
    _g_serve_shared_blocks.set(shared_blocks)
    _g_serve_cold_blocks.set(cold_blocks)


def on_serving_kv_quant(writes: int, tokens: int,
                        pool_bytes: int) -> None:
    """An int8-pool engine ran ``writes`` quantize-on-write program
    launches covering ``tokens`` real (non-pad) tokens; ``pool_bytes``
    is the static K/V + scale pool footprint (docs/SERVING.md
    "int8 KV")."""
    _c_kv_quant_writes.inc(writes)
    if tokens:
        _c_kv_quant_tokens.inc(tokens)
    _g_kv_pool_bytes.set(pool_bytes)


def on_router_dispatch(replica: int, affinity_hit: bool,
                       redispatch: bool = False) -> None:
    """The router routed one request to ``replica`` —
    ``affinity_hit`` when prefix coverage (not load) chose it,
    ``redispatch`` when this is a drained request restarting after a
    replica death."""
    _c_router_dispatch.inc()
    (_c_router_aff_hit if affinity_hit else _c_router_aff_miss).inc()
    _registry.counter(f"router/dispatches/{replica}").inc()
    if redispatch:
        _c_router_redispatch.inc()


def on_router_dead(replica: int) -> None:
    """A replica's ``step()`` raised: it is out of rotation and its
    requests drained back to the router queue."""
    _c_router_dead.inc()


def on_router_lanes(replica: int, occupied: int, queued: int) -> None:
    """Post-step load census for one replica: occupied lanes + queued
    (waiting) requests — the least-loaded dispatch rule's inputs."""
    _registry.gauge(f"router/lanes/{replica}").set(occupied)
    _registry.gauge(f"router/queued/{replica}").set(queued)


def on_pallas_engaged(family: str) -> None:
    """A kernel dispatch decision chose the Pallas kernel (a measured
    engagement row, or the flash crossover heuristic)."""
    _c_pallas_engaged.inc()
    _registry.counter(f"pallas/engaged/{family}").inc()


def on_pallas_fallback(family: str) -> None:
    """A kernel dispatch decision fell back to the XLA composite (no
    measurement, a measured loss, or an ineligible shape/mask)."""
    _c_pallas_fallback.inc()
    _registry.counter(f"pallas/fallback/{family}").inc()


def on_search_timed(family: str) -> None:
    """The search harness timed one candidate configuration."""
    _c_search_timed.inc()


def on_search_reject(family: str) -> None:
    """The search harness rejected a candidate (interpret-mode parity
    failure or a compile/run error) before or during timing."""
    _c_search_rejects.inc()


def on_search_best_ratio(family: str, ratio: float) -> None:
    """A search persisted a row; ``ratio`` is the winning candidate's
    composite/kernel time ratio (>1 = the kernel is faster)."""
    _registry.gauge(f"search/best_ratio/{family}").set(ratio)


def on_ckpt_save(blocked_ms: float) -> None:
    """The CheckpointManager started one checkpoint; ``blocked_ms`` is
    the training loop's blocking cost (quiesce + host snapshot — the
    async writer's file I/O is not in it)."""
    _c_res_saves.inc()
    _h_res_save_ms.observe(blocked_ms)


def on_ckpt_restore(crash_resume: bool = False) -> None:
    """Training state restored from a checkpoint; ``crash_resume`` marks
    a relaunch-after-failure restore (``PADDLE_RESTART_COUNT`` > 0) as
    opposed to an operator-requested warm start."""
    _c_res_restores.inc()
    if crash_resume:
        _c_res_crash_resumes.inc()


def on_nan_skip(n: int = 1) -> None:
    """The NaN policy dropped a poisoned batch and continued."""
    _c_res_skipped.inc(n)


def on_planner_candidate(fits: bool, error: bool = False) -> None:
    """The planner judged one (dp×mp, batch) candidate row."""
    _c_plan_candidates.inc()
    if error:
        _c_plan_errors.inc()
    elif not fits:
        _c_plan_infeasible.inc()


def on_program_audit(n_findings: int, rules=()) -> None:
    """The program auditor judged one compiled executable (fresh compile
    or sidecar re-report); ``rules`` are the finding rule ids."""
    _c_audit_programs.inc()
    if n_findings:
        _c_audit_findings.inc(n_findings)
    for r in rules:
        _registry.counter(f"analysis/findings/{r}").inc()


def on_pipeline_forward(pp: int, n_micro: int, ticks: int,
                        p2p_bytes: int, bubble: float = 0.0) -> None:
    """One pipelined forward dispatched its compiled GPipe schedule:
    ``ticks`` scan iterations over ``n_micro`` microbatches, moving
    ``p2p_bytes`` of stage state over the 'pp' axis (one
    collective-permute of the [pp, mb, ...] state array per tick).
    Same convention as every in-trace collective counter: under a
    compiled TrainStep this fires once per TRACE (the schedule shape
    per signature), not once per executed step — eager forwards count
    per call."""
    _c_pipe_fwd.inc()
    _c_pipe_micro.inc(n_micro)
    _c_pipe_ticks.inc(ticks)
    _g_pipe_bubble.set(bubble)
    if p2p_bytes:
        _c_pipe_p2p.inc(p2p_bytes)
        on_collective("ppermute", p2p_bytes, axes=("pp",))


def on_planner_plan(est_step_ms: float) -> None:
    """A plan was emitted; the gauge holds its winner's roofline
    step-time estimate (the number the hwbench ``shard_plan`` row
    later judges against a measurement)."""
    _c_plan_plans.inc()
    _g_plan_winner_ms.set(est_step_ms)


from . import memory  # noqa: E402  — device memory observatory
from . import numerics  # noqa: E402  — first-bad-step NaN isolation
from . import live  # noqa: E402  — streaming SLO sketches (must precede
#                                   _register calls so `_live` slots wire)
from . import exporter  # noqa: E402  — /metrics+/healthz+/statusz endpoint
from . import goodput  # noqa: E402  — wall-clock goodput ledger
from . import watchdog  # noqa: E402  — hang watchdog (step-deadline)
from . import heartbeat  # noqa: E402  — launcher fleet heartbeat plane
from .step_logger import StepLogger  # noqa: E402,F401

# PT_MONITOR=1 enables at import, before any instrumented module registers
# (later registrants are wired inside _register)
if os.environ.get("PT_MONITOR", "0") not in ("", "0"):
    enable()
# the sibling subsystems carry their own knobs: censuses are O(live
# arrays) and the sentinel costs one host fetch per step, so neither
# rides PT_MONITOR implicitly
if os.environ.get("PT_MONITOR_MEM", "0") not in ("", "0"):
    memory.enable()
if os.environ.get("PT_NANCHECK", "0") not in ("", "0"):
    numerics.enable()
# the live plane arms on any of its own knobs: explicit opt-in, a
# metrics port (a scraper wants data), or an SLO target (the watchdog
# needs the sketches). Import-time inert otherwise — no thread, no
# sketch, no callables in any hot path.
if (os.environ.get("PT_LIVE_TELEMETRY", "0") not in ("", "0")
        or os.environ.get("PT_METRICS_PORT")
        or os.environ.get("PT_SLO_TTFT_MS_P99")
        or os.environ.get("PT_SLO_TPOT_MS_P99")):
    live.enable()
if os.environ.get("PT_METRICS_PORT"):
    exporter.start()
