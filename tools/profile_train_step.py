"""Capture an xplane trace of the compiled headline train step on the
live chip and print the MFU breakdown.

Usage: python tools/profile_train_step.py [--steps 5] [--outdir profiles/]
       [--smoke]   (tiny CPU sizes; without it the chip is required)

Captures `jax.profiler.trace` around the bench model's TrainStep, then
parses the xplane proto for per-op-category time (matmul / attention /
optimizer / other / host gaps) and appends the summary to
PERF_MEASUREMENTS.json. One command, one process holding the chip; run
via hwbench or standalone.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _trace_files(outdir):
    return set(glob.glob(os.path.join(
        outdir, "**", "*.trace.json.gz"), recursive=True))


def _breakdown_from_xplane(paths):
    """Best-effort xplane parse: per-op self-time grouped by name class,
    over exactly the trace files THIS run produced (repeat runs into the
    same outdir must not double-count)."""
    rows = {}
    for path in sorted(paths):
        with gzip.open(path, "rt") as f:
            trace = json.load(f)
        events = trace.get("traceEvents", [])
        # device lanes only: host thread slices would overcount wall time
        pid_names = {ev.get("pid"): ev.get("args", {}).get("name", "")
                     for ev in events
                     if ev.get("ph") == "M"
                     and ev.get("name") == "process_name"}
        device_pids = {pid for pid, name in pid_names.items()
                       if any(k in name for k in ("TPU", "/device",
                                                  "Device", "XLA Op"))}
        # within a device pid, keep the per-op lane only: module/step
        # lanes span whole steps and would double-count everything
        tid_names = {(ev.get("pid"), ev.get("tid")):
                     ev.get("args", {}).get("name", "")
                     for ev in events
                     if ev.get("ph") == "M"
                     and ev.get("name") == "thread_name"}
        op_tids = {key for key, name in tid_names.items()
                   if "XLA Ops" in name}
        for ev in events:
            if ev.get("ph") != "X" or "dur" not in ev:
                continue
            if device_pids and ev.get("pid") not in device_pids:
                continue
            if op_tids and (ev.get("pid"), ev.get("tid")) not in op_tids:
                continue
            name = ev.get("name", "")
            hlo_cat = (ev.get("args") or {}).get("hlo_category", "")
            low = (hlo_cat + " " + name).lower()
            if any(k in low for k in ("fusion", "dot", "conv", "matmul")):
                cat = "matmul/fusion"
            elif any(k in low for k in ("custom-call", "mosaic", "flash")):
                cat = "custom-call(pallas)"
            elif any(k in low for k in ("all-reduce", "all-gather",
                                        "collective", "permute")):
                cat = "collective"
            elif any(k in low for k in ("copy", "transpose", "reshape",
                                        "bitcast")):
                cat = "data-movement"
            else:
                cat = "other"
            rows[cat] = rows.get(cat, 0.0) + ev["dur"] / 1e6  # us -> s
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--outdir", default="profiles")
    ap.add_argument("--model", choices=("llama", "resnet"),
                    default="llama",
                    help="which bench step to profile (resnet: the "
                         "0.130-MFU cell)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU sizes (pipeline proof); without it "
                         "the profile needs the chip")
    args = ap.parse_args()

    import jax

    from bench import _peak_flops, build_headline_trainstep
    from paddle_tpu.framework.device import platform, require_tpu
    from paddle_tpu.utils.xla_cache import enable_compilation_cache

    enable_compilation_cache()
    on_cpu = args.smoke
    if not on_cpu:
        require_tpu("profile_train_step")
    print(f"profile_train_step: platform={platform()} "
          f"model={args.model}", flush=True)

    import paddle_tpu as pt

    # the EXACT bench model/step — the profile must be attributable to
    # the bench number
    if args.model == "resnet":
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks"))
        from baseline_configs import build_resnet_trainstep

        model, step, ids, labels, batch, seq = build_resnet_trainstep(
            on_cpu)  # (x, y, batch, hw) in the resnet case
        flops_per_unit = 3 * 4.1e9 if seq == 224 else 0.0  # per image
        bench_metric = "resnet50_train_imgs_per_sec_per_chip"
        profile_metric = "resnet50_train_profile_device_busy_frac"
        units_per_step = batch
        # data_format must be in the match: hwbench interleaves NCHW and
        # NHWC bench records at the same batch, and a busy fraction
        # computed against the other layout's step wall is misattributed
        fmt = os.environ.get("PT_RESNET_FORMAT", "NCHW")
        match = {"batch": batch, "data_format": fmt}
        extra_tags = {"model": "resnet", "data_format": fmt,
                      "batch": batch}
    else:
        model, step, batch, seq = build_headline_trainstep(on_cpu)
        vocab = model.config.vocab_size
        ids = pt.to_tensor(np.random.randint(0, vocab, (batch, seq)))
        labels = pt.to_tensor(np.random.randint(0, vocab, (batch, seq)))
        flops_per_unit = model.flops_per_token(seq)
        bench_metric = "llama_train_tokens_per_sec_per_chip"
        profile_metric = "llama_train_profile_device_busy_frac"
        units_per_step = batch * seq
        match = {"batch": batch, "seq": seq,
                 "ce_chunk": model.config.ce_chunk_size}
        extra_tags = {"model": "llama", "batch": batch, "seq": seq}

    # warm/compile outside the trace
    float(np.asarray(step(ids, labels).numpy()).sum())
    os.makedirs(args.outdir, exist_ok=True)
    before = _trace_files(args.outdir)
    # python/host tracers off: their ~1M events per few steps exhaust the
    # trace budget and truncate away the device per-op lane — the one
    # lane this tool exists to read
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
    except AttributeError:  # older jax: no options — trace anyway
        opts = None
    t0 = time.perf_counter()
    if opts is not None:
        jax.profiler.start_trace(args.outdir, profiler_options=opts)
    else:  # older jax predates the kwarg too — omit it entirely
        jax.profiler.start_trace(args.outdir)
    try:
        for _ in range(args.steps):
            loss = step(ids, labels)
        float(np.asarray(loss.numpy()).sum())  # transfer-backed sync
    finally:
        jax.profiler.stop_trace()
    wall = time.perf_counter() - t0
    tokens_per_sec = units_per_step * args.steps / wall
    mfu = (tokens_per_sec * flops_per_unit
           / _peak_flops(jax.devices()[0])) \
        if flops_per_unit and not on_cpu else 0.0
    print(f"traced {args.steps} steps in {wall:.3f}s "
          f"({tokens_per_sec:.0f} units/s, traced-wall mfu {mfu:.4f} — "
          f"profiler-inflated, informational only)", flush=True)

    rows = _breakdown_from_xplane(_trace_files(args.outdir) - before)
    if on_cpu:
        print("(CPU: no device lane in the trace — host-thread slices "
              "below overcount; the breakdown is meaningful on TPU)",
              flush=True)
    if rows:
        total = sum(rows.values())
        print("device-time breakdown (self time):", flush=True)
        for cat, secs in sorted(rows.items(), key=lambda kv: -kv[1]):
            print(f"  {cat:24s} {secs:8.4f}s  {secs / total:6.1%}",
                  flush=True)
        # the traced wall is profiler-inflated (trace IO, host tracer),
        # so busy-vs-traced-wall would understate 40x. The honest
        # denominator is the un-profiled bench step wall from the
        # last-good persisted headline measurement at the same config.
        device_s_per_step = total / args.steps
        print(f"  device time / step: {device_s_per_step * 1e3:.1f} ms",
              flush=True)
        device_busy = None
        try:
            from paddle_tpu.utils import measurements as _m

            lg = _m.last_good(bench_metric, match=match)
            if lg:
                bench_step_wall = units_per_step / lg["value"]
                device_busy = device_s_per_step / bench_step_wall
                print(f"  device busy vs bench step wall "
                      f"({bench_step_wall * 1e3:.1f} ms): "
                      f"{device_busy:.1%}", flush=True)
        except Exception:  # noqa: BLE001 — busy frac is optional
            pass
    else:
        device_busy = device_s_per_step = None
        print("no trace events parsed — breakdown unavailable "
              "(trace format drift?); NOT recording a busy fraction",
              flush=True)

    if not on_cpu:
        from paddle_tpu.utils import measurements as meas

        # persist the DEVICE-BUSY fraction, not traced-wall MFU: the
        # traced wall is profiler-inflated ~12-40x, so a metric named
        # "mfu" computed from it is junk data that contradicts its own
        # name (round-4 verdict weak #4). Throughput truth lives in the
        # bench metric; this record carries the profile breakdown.
        meas.record_or_warn(
            profile_metric,
            round(device_busy, 4) if device_busy is not None else -1.0,
            "fraction",
            extra={"note": "device-time/step over the last-good bench "
                           "step wall at the same config; -1 = no "
                           "matching bench record or no device lane",
                   "traced_wall_units_per_sec":
                       round(tokens_per_sec, 1),
                   "breakdown_s": ({k: round(v, 4)
                                    for k, v in rows.items()}
                                   if rows else None),
                   "device_s_per_step": (round(device_s_per_step, 4)
                                         if device_s_per_step is not None
                                         else None),
                   "steps": args.steps, "outdir": args.outdir,
                   **extra_tags})
    return 0


if __name__ == "__main__":
    sys.exit(main())
