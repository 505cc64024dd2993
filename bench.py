"""Benchmark: Llama causal-LM training throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The metric is tokens/sec/chip for a compiled full train step (fwd+bwd+AdamW,
bf16 params with fp32 masters) on a ~0.44B-param Llama config (sized to one v5e chip) — the
single-chip proxy for BASELINE config 4. "vs_baseline" is model FLOPs
utilization (MFU) divided by the 0.45 north-star target from BASELINE.json,
so 1.0 means the 45%-MFU goal is met on this chip.
"""
from __future__ import annotations

import json
import os
import signal
import sys
import time

import numpy as np

# PT_BENCH_ASYNC A/B (docs/ASYNC_PIPELINE.md): unset = the default lazy
# loop (dispatch all steps, one final sync); "1"/"on" = AsyncStepper with
# a bounded in-flight window (depth PT_BENCH_ASYNC_DEPTH, default 2);
# "sync"/"0" = materialize the loss EVERY step — the worst-case host-in-
# the-critical-path baseline the async pipeline is measured against.
# A/B runs record under suffixed metric names so the measurement store
# keeps the three populations separate.
_ASYNC_KNOB = os.environ.get("PT_BENCH_ASYNC", "").lower()
_ASYNC_MODES = {"": "default", "1": "async", "on": "async", "async": "async",
                "0": "sync", "sync": "sync"}
if _ASYNC_KNOB not in _ASYNC_MODES:
    # fail loudly: a typo'd A/B arm must not silently record into the
    # unsuffixed headline population in PERF_MEASUREMENTS.json
    raise SystemExit(
        f"bench: unknown PT_BENCH_ASYNC={_ASYNC_KNOB!r} "
        f"(expected one of {sorted(k for k in _ASYNC_MODES if k)})")
_ASYNC_MODE = _ASYNC_MODES[_ASYNC_KNOB]

_METRIC = "llama_train_tokens_per_sec_per_chip" + {
    "default": "", "async": "_async", "sync": "_syncstep"}[_ASYNC_MODE]

def _emit(value, vs_baseline, **extra):
    """The one JSON line the driver parses. Exactly one call wins."""
    global _EMITTED
    if _EMITTED:
        return
    _EMITTED = True
    print(json.dumps({
        "metric": _METRIC,
        "value": value,
        "unit": "tokens/s",
        "vs_baseline": vs_baseline,
        **extra,
    }), flush=True)


_EMITTED = False


def _load_perf_guard():
    """tools/perf_guard.py as a module (tools/ is not a package; the
    guard stays pure-stdlib so the report boxes can run it too)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "perf_guard.py")
    spec = importlib.util.spec_from_file_location("perf_guard", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _guard_verdict(line: dict, on_cpu: bool, baseline) -> dict:
    """Judge this run's line against ``baseline``; on a CPU smoke the
    hardware comparison is skipped but the runtime-health checks (retrace
    storm, starvation, error) still gate.

    ``baseline`` must be the last-good record captured BEFORE this run
    persisted its own (main() does; None = no baseline) — re-reading the
    store here would hand back the run itself as "last good" and the
    drop gate would compare the number to itself."""
    guard = _load_perf_guard()
    verdict = guard.evaluate(line, baseline, hardware=not on_cpu)
    if not verdict["ok"]:
        print(guard.format_verdict(line["metric"], verdict),
              file=sys.stderr, flush=True)
    return verdict


# bf16 peak FLOPs/s per chip by TPU generation (public spec sheets)
_PEAK = {
    "v4": 275e12,
    "v5e": 197e12,
    "v5lite": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
}


def _peak_flops(device) -> float:
    """Peak of ``device`` from the table above; a device that is not in
    it is an error, not a default (an assumed peak is an invented MFU)."""
    kind = getattr(device, "device_kind", "").lower().replace(" ", "")
    for k, v in _PEAK.items():
        if k in kind:
            return v
    raise ValueError(
        f"bench: no bf16 peak known for device_kind "
        f"{getattr(device, 'device_kind', None)!r}; add it to _PEAK "
        f"with its source")


def build_headline_trainstep(on_cpu: bool):
    """The ONE headline model+step (also profiled by
    tools/profile_train_step.py — a profile must be attributable to the
    bench number, so the config lives in exactly one place).

    Returns (model, step, batch, seq)."""
    import paddle_tpu as pt
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if on_cpu:  # smoke-mode so local runs finish; real numbers need a chip
        cfg = LlamaConfig.tiny(
            use_parallel_cross_entropy=False,
            ce_chunk_size=int(os.environ.get("PT_BENCH_CE_CHUNK", "0")))
        batch, seq = 2, 64
    else:
        # sized for a single v5e chip (16G HBM): ~0.44B params, bf16 +
        # fp32 masters + Adam moments ≈ 5.7G, activations ≈ 5.6G at the
        # b8×s1024 default (11.3 GiB peak measured; b12 hits 14.1 and
        # regresses — see PERF.md batch sweep).
        # PT_BENCH_CE_CHUNK>0 switches the loss to the chunked CE (no
        # [N, V] fp32 logits) — the candidate MFU lever to A/B on
        # hardware (see PERF.md).
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1536, intermediate_size=4096,
            num_hidden_layers=12, num_attention_heads=12,
            max_position_embeddings=1024, dtype="bfloat16",
            use_parallel_cross_entropy=False,
            ce_chunk_size=int(os.environ.get("PT_BENCH_CE_CHUNK", "0")))
        # b8 measured MFU 0.647 vs 0.578 at b4 (+12%: 8192 rows fill the
        # MXU M dim; b10/b12 regress on HBM pressure) — PT_BENCH_BATCH to A/B
        batch, seq = int(os.environ.get("PT_BENCH_BATCH", "8")), 1024
    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        for p in model.parameters():
            p._data = p._data.astype("bfloat16")
    opt = pt.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        multi_precision=cfg.dtype == "bfloat16")
    step = TrainStep(model, opt, lambda m, i, l: m(i, l), donate=True)
    return model, step, batch, seq


def main():
    import jax

    import paddle_tpu as pt
    from paddle_tpu.framework.device import require_tpu
    from paddle_tpu.utils.xla_cache import enable_compilation_cache

    # One process, one look at the devices. The only CPU run is one the
    # caller asked for (JAX_PLATFORMS=cpu — the tier-1 rehearsal); with
    # anything else, no TPU is an error and nothing is measured.
    on_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if not on_cpu:
        require_tpu("bench")
    dev = jax.devices()[0]
    print(f"bench: platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={len(jax.devices())}", file=sys.stderr, flush=True)

    enable_compilation_cache()

    from paddle_tpu import monitor as _mon
    from paddle_tpu.monitor import memory as _memobs
    from paddle_tpu.monitor import numerics as _numerics

    if os.environ.get("PT_BENCH_MONITOR", "1") != "0":
        # runtime telemetry (retraces / compiles / sync fences) rides along
        # in the JSON line; the cost is off the hot path — compiled steps
        # bypass eager dispatch, so only tracing and sync fences count.
        # The memory observatory is NOT armed here: its per-step census
        # (a live-array walk inside log_step) would ride inside the
        # timed loop — opt in with PT_MONITOR_MEM=1; the `memory`
        # sub-object below takes one census AFTER the loop either way.
        _mon.enable()

    # Pre-flight: Mosaic-lower every Pallas kernel before the timed run
    # (jax.export — cheap, in-process; the described-topology compiles
    # in tests/test_chip_compile.py are the real compiler). A kernel
    # that does not lower fails the run: a bench that quietly measured
    # the composite instead would report a number for another program.
    from paddle_tpu.ops import pallas as _pallas

    _pallas.check_tpu_lowering()

    steps, warmup = (3, 1) if on_cpu else (10, 2)
    model, step, batch, seq = build_headline_trainstep(on_cpu)
    vocab = model.config.vocab_size

    ids = pt.to_tensor(np.random.randint(0, vocab, (batch, seq)))
    labels = pt.to_tensor(np.random.randint(0, vocab, (batch, seq)))

    # step-metrics JSONL sink (opt-in: the default bench writes no files);
    # per-step lines are async-dispatch timings, only the final loss syncs
    slog = None
    if _mon.enabled() and os.environ.get("PT_MONITOR", "0") not in ("", "0"):
        slog = _mon.StepLogger(
            os.environ.get("PT_MONITOR_SINK") or "bench_steps.jsonl",
            meta={"source": "bench.py", "backend": dev.platform,
                  "batch": batch, "seq": seq})

    stepper = step
    if _ASYNC_MODE == "async":
        from paddle_tpu.jit.train_step import AsyncStepper

        stepper = AsyncStepper(step, max_in_flight=int(
            os.environ.get("PT_BENCH_ASYNC_DEPTH", "2")))

    # goodput ledger over the whole bench (warmup + timed loop): the
    # line then says where the wall went — XLA compiles land in the
    # `compile` bucket via the TrainStep slot, everything outside the
    # bracketed step calls is `other` (monitor/goodput.py)
    from paddle_tpu.monitor import goodput as _gp

    gled = None
    if os.environ.get("PT_GOODPUT", "1") not in ("", "0"):
        _gp.reset_run()
        gled = _gp.Ledger()
        _gp.activate(gled)

    for _ in range(warmup):
        if gled is not None:
            gled.enter("productive_step")
        try:
            float(step(ids, labels).numpy())  # host transfer = real sync
        finally:
            if gled is not None:
                gled.exit()
    # post-warmup retrace baseline + live watchpoint: a retrace INSIDE the
    # timed loop means the throughput number includes an XLA compile — the
    # warning fires mid-run (tools/perf_guard.py re-checks it post-hoc)
    retrace_base = starved_base = None
    if _mon.enabled():
        _c0 = _mon.snapshot().get("counters", {})
        retrace_base = _c0.get("jit/retraces", 0)
        # warmup starvations (cold loader) must not gate the timed loop
        starved_base = _c0.get("io/prefetch_starvations", 0)
        _mon.watchpoint(
            "jit/retraces", retrace_base,
            message="bench: post-warmup retrace storm — a batch signature "
                    "changed inside the timed loop; this run's throughput "
                    "includes an XLA compile")
    # host_blocked: wall time the host spends inside step dispatch (+ the
    # per-step materialization in sync mode, + drain in async mode) — the
    # dispatch-gap number the PT_BENCH_ASYNC A/B compares
    host_blocked = 0.0
    t0 = time.perf_counter()
    for _ in range(steps):
        t_h = time.perf_counter()
        if gled is not None:
            gled.enter("productive_step")
        loss = stepper(ids, labels)
        if _ASYNC_MODE == "sync":
            float(loss.numpy())  # per-step host round-trip (the baseline)
        if gled is not None:
            gled.exit()
        host_blocked += time.perf_counter() - t_h
        if slog is not None:
            slog.log_step(num_samples=batch * seq)
    if _ASYNC_MODE == "async":
        t_h = time.perf_counter()
        stepper.drain()
        dt_drain = time.perf_counter() - t_h
        host_blocked += dt_drain
        if gled is not None:
            # the drain finishes dispatched steps: productive wall,
            # charged without bumping the ledger's step count
            gled.charge("productive_step", dt_drain)
    final_loss = float(loss.numpy())  # chained through params: syncs all
    dt = time.perf_counter() - t0
    assert np.isfinite(final_loss)

    tokens_per_sec = batch * seq * steps / dt
    flops_tok = model.flops_per_token(seq)
    # MFU is a statement about the chip: an explicit CPU run has none
    mfu = 0.0 if on_cpu else tokens_per_sec * flops_tok / _peak_flops(dev)
    extra = {"platform": dev.platform, "device_kind": dev.device_kind,
             "device_count": len(jax.devices()),
             "mfu": round(mfu, 4), "model_params_b": round(
        sum(int(np.prod(p.shape)) for p in model.parameters()) / 1e9, 3),
        "stepping": _ASYNC_MODE,
        "host_blocked_ms_per_step": round(host_blocked / steps * 1e3, 3),
        # the sweep config in the line: what this number measured, and
        # the guard's baseline filter (a b16 sweep record must not judge
        # a b8 run)
        "batch": batch, "seq": seq,
        "ce_chunk": model.config.ce_chunk_size}
    if _ASYNC_MODE == "async":
        extra["async_depth"] = stepper.max_in_flight
    if gled is not None:
        # wall-clock classification for the whole bench run (exact
        # telescoping; tools/perf_guard.py --goodput-drop gates the frac)
        gsnap = gled.snapshot()
        extra["goodput"] = gsnap
        extra["goodput_frac"] = round(gsnap["goodput_frac"], 4)
    # compiled-program audit account (PT_PROGRAM_AUDIT=1 — every fresh
    # compile above was judged at the exec-cache chokepoint): rides the
    # line AND the persisted record, so tools/perf_guard.py --audit can
    # fail a future line whose findings are new vs this baseline
    program_audit = None
    try:
        from paddle_tpu.analysis import program_audit as _pa

        if _pa.enabled():
            program_audit = _pa.report()
            extra["program_audit"] = program_audit
    except Exception:  # noqa: BLE001 — the audit must not break the line
        pass

    from paddle_tpu.utils import measurements as _meas

    # cold-vs-warm compile accounting: total XLA compile wall-time this
    # process paid (jit/compile_ms histogram — exec_cache hits pay none),
    # read once here so both the persisted record and the telemetry
    # sub-object carry the same number the perf guard's compile gate reads
    compile_ms_total = compile_count = None
    if _mon.enabled():
        _ch = _mon.snapshot().get("histograms", {}).get("jit/compile_ms")
        compile_ms_total = round(_ch["sum"], 1) if _ch else 0.0
        compile_count = _ch["count"] if _ch else 0

    # the guard's baseline MUST be read before this run's record lands in
    # the store — otherwise last_good returns the run itself and the
    # throughput gate compares the number to itself (always-pass) — and
    # at THIS run's sweep config, so A/B points at other batch/seq/chunk
    # settings are never a false baseline
    try:
        guard_baseline = _meas.last_good(_METRIC, match={
            "batch": batch, "seq": seq,
            "ce_chunk": model.config.ce_chunk_size})
    except Exception:  # noqa: BLE001
        guard_baseline = None

    # memory sub-object (cheap views first, so the persisted record can
    # carry peak HBM even if the expensive AOT accounting below times out)
    mem_obj = {"nan_check": _numerics.enabled()}
    try:
        led = _memobs.ledger()
        if led is not None:
            # observatory armed (PT_MONITOR_MEM=1): per-step censuses ran;
            # one more post-loop, then report the honest running peak
            led.census(tag="bench_end")
            mem_obj["peak_live_gib"] = round(led.peak_live_bytes / 2**30, 3)
        else:
            # one census AFTER the timed loop (never inside it): the
            # end-state live bytes, not a peak
            mem_obj["live_gib_end"] = round(
                _memobs.live_census()["live_bytes"] / 2**30, 3)
        cheap_peak = _memobs.device_peak_gib()
        if cheap_peak is not None:
            mem_obj["peak_hbm_gib"] = cheap_peak
    except Exception:  # noqa: BLE001 — memory views must not break the line
        pass

    if not on_cpu:
        # persist the hardware number the moment it exists
        rec_extra = {"mfu": round(mfu, 4),
                     "vs_baseline": round(mfu / 0.45, 4),
                     "batch": batch, "seq": seq,
                     "ce_chunk": model.config.ce_chunk_size,
                     "stepping": _ASYNC_MODE,
                     "host_blocked_ms_per_step":
                         extra["host_blocked_ms_per_step"],
                     "model_params_b": extra["model_params_b"],
                     "nan_check": _numerics.enabled()}
        if compile_ms_total is not None:
            # the guard's cold-start compile gate baselines on this; the
            # enabled flag lets it skip cache-on vs cache-off apples-to-
            # oranges comparisons (a cache-off run is not a regression)
            from paddle_tpu.jit import exec_cache as _ec0

            rec_extra["compile_ms_total"] = compile_ms_total
            rec_extra["exec_cache_enabled"] = _ec0.enabled()
        if mem_obj.get("peak_hbm_gib") is not None:
            rec_extra["peak_hbm_gib"] = mem_obj["peak_hbm_gib"]
        if program_audit is not None:
            rec_extra["program_audit"] = program_audit
        if extra.get("goodput_frac") is not None:
            rec_extra["goodput_frac"] = extra["goodput_frac"]
        try:
            _meas.record(_METRIC, round(tokens_per_sec, 2), "tokens/s",
                         extra=rec_extra)
        except Exception as e:  # noqa: BLE001
            print(f"bench: measurement persist failed: {e}",
                  file=sys.stderr, flush=True)
    # HBM accounting: the allocator's own peak where the backend reports
    # one (the TPU does), else XLA's executable accounting — served from
    # the same executable-cache entry the timed loop ran
    # (jit/exec_cache.py), so no second AOT compile.
    try:
        if mem_obj.get("peak_hbm_gib") is not None:
            extra["peak_hbm_gib"] = mem_obj["peak_hbm_gib"]
        elif not on_cpu:
            # args incl. donated params + temporaries = live HBM during
            # the step
            ma_rec = _memobs.executable_record(step, ids, labels,
                                               name="bench/headline")
            extra["peak_hbm_gib"] = round(ma_rec["peak_bytes"] / 2**30, 2)
            extra["hbm_args_gib"] = round(ma_rec["args_bytes"] / 2**30, 2)
            extra["hbm_temp_gib"] = round(ma_rec["temp_bytes"] / 2**30, 2)
            mem_obj["peak_hbm_gib"] = extra["peak_hbm_gib"]
            mem_obj["source"] = "xla_analysis"
            mem_obj["executable"] = ma_rec
            # back-fill the already-persisted record: the perf guard's
            # HBM gate needs a peak on the baseline
            _meas.annotate_last(
                _METRIC, {"peak_hbm_gib": extra["peak_hbm_gib"]},
                value=round(tokens_per_sec, 2))
    except Exception:
        pass
    if on_cpu:
        extra["note"] = "cpu smoke mode; not a TPU number"
    # runtime-health sub-object: a surprise retrace or a sync storm shows
    # up next to the ips it explains (BENCH_r*.json keeps both)
    try:
        snap = _mon.snapshot()
        c = snap.get("counters", {})
        tel = {"retraces": c.get("jit/retraces", 0),
               "compiles": c.get("jit/compiles", 0),
               "sync_count": c.get("sync/fences", 0),
               "steps": steps}
        if retrace_base is not None:
            tel["post_warmup_retraces"] = (
                c.get("jit/retraces", 0) - retrace_base)
        starved = c.get("io/prefetch_starvations", 0) - (starved_base or 0)
        if starved:
            tel["prefetch_starvations"] = starved
        h = snap.get("histograms", {}).get("sync/fence_ms")
        if h:
            tel["sync_ms_p50"] = h["p50"]
            tel["sync_ms_max"] = h["max"]
        # cold-vs-warm compile delta: total compile wall-time this process
        # paid — ~0 on a warm PT_EXEC_CACHE start, full XLA cost cold
        if compile_ms_total is not None:
            tel["compile_ms_total"] = compile_ms_total
            tel["compile_count"] = compile_count
        from paddle_tpu.jit import exec_cache as _ec

        if _ec.enabled():
            tel["exec_cache"] = _ec.stats()
        # per-step sink writes happen inside the timed loop: mark the
        # record so A/B comparisons don't conflate sink overhead with a
        # regression
        tel["sink_active"] = slog is not None
        # which attention path the step really took: a composite
        # fallback is a different program, so the line says so
        tel["pallas"] = {
            k[len("pallas/"):]: v for k, v in sorted(c.items())
            if k.startswith(("pallas/engaged", "pallas/fallback"))}
        nan_checks = c.get("numerics/checks", 0)
        if nan_checks:
            tel["nan_checks"] = nan_checks
        extra["telemetry"] = tel
    except Exception:  # noqa: BLE001 — telemetry must not break the line
        pass
    # device-memory sub-object rides next to telemetry: the peak the run
    # actually held, where the number came from, and the sentinel state
    extra["memory"] = mem_obj
    # regression-guard verdict rides along in the line (tools/perf_guard.py
    # is also a standalone CLI gate; embedding means BENCH_r*.json carries
    # the pass/fail next to the number it judges)
    try:
        extra["guard"] = _guard_verdict(
            {"metric": _METRIC, "value": round(tokens_per_sec, 2),
             "unit": "tokens/s", **extra}, on_cpu,
            baseline=guard_baseline)
    except Exception as e:  # noqa: BLE001 — the guard must not break the line
        print(f"bench: perf guard failed: {e}", file=sys.stderr, flush=True)
    if slog is not None:
        # run_end carries the guard verdict + memory account so
        # tools/monitor_report.py can render them from the JSONL alone
        slog.close(loss=final_loss,
                   tokens_per_sec=round(tokens_per_sec, 2),
                   host_blocked_ms_per_step=extra["host_blocked_ms_per_step"],
                   memory=mem_obj, guard=extra.get("guard"))
    if gled is not None:
        # after slog.close: the run_end line reads the active ledger
        _gp.deactivate(gled)
    _emit(round(tokens_per_sec, 2), round(mfu / 0.45, 4), **extra)


def _watchdog(seconds: int = 2700):
    """Guarantee a JSON line even if something hangs past the driver's
    patience: emit a structured failure and exit non-zero."""

    def _fire(signum, frame):
        _emit(0.0, 0.0, error=f"bench watchdog fired after {seconds}s")
        os._exit(3)

    signal.signal(signal.SIGALRM, _fire)
    signal.alarm(seconds)


if __name__ == "__main__":
    _watchdog()
    try:
        main()
    except BaseException as e:  # noqa: BLE001 — the JSON line must happen
        _emit(0.0, 0.0, error=f"{type(e).__name__}: {e}"[:500])
        raise
