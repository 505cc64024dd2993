"""One run of one cell of BENCHMARK.json on the chip.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

One process, no child. Without a TPU (or with fewer chips than the cell
asks for) it exits non-zero and prints no result. The last line of
standard output is the result object; earlier lines itemise set-up and
print every number compared beside its limit, which are also the result's
last key (``compared``) and the last lines of standard error.

    python3 benchmarks/chip/run.py --workload <name> --check-seeds 1,2,3 \
        [--control 1] --seconds <s>

is the builder's checking mode: the output comparison for a list of
seeds in one process (see chiplib/checkmode.py).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

# libtpu's log directory defaults to a fixed /tmp path; keep it under the
# run's own TMPDIR
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def run_cell(workload, seed, seconds, trace, *, files=None,
             require_chip=True, hooks=None, t_start=None, control=False,
             traffic_override=None):
    """Everything of a run but the argument parsing. ``files`` lets a
    test point at its own manifest and data directory; ``require_chip``
    False is for tests only and leaves every device metric unnamed;
    ``hooks`` lets a test break the timed path underneath."""
    from chiplib import common, manifest

    files = files or manifest.Files()
    man = files.load()
    cell = manifest.cell(man, workload)
    cfg = files.config(man, cell["config"])
    traffic = dict(files.traffic(cell["traffic"]), **(traffic_override or {}))
    arch = files.arch(cfg["arch"])
    device, devices = common.device_info(cell["chips"], require_chip)
    cache_dir = common.enable_compile_cache()
    events = common.JaxEvents()
    ctx = {
        "workload": workload, "seed": int(seed), "seconds": float(seconds),
        "trace": bool(trace), "config": cfg, "traffic": traffic,
        "limits": files.limits(workload), "device": device,
        "devices": devices, "events": events, "hooks": hooks or {},
        "setup_items": {"imports_s": time.perf_counter()
                        - (t_start or T_START)},
        "t_start": t_start or T_START, "control": bool(control),
        "files": files, "arch": arch,
        # what `correct` rests on, printed in every run's compare line
        "compared_with": {
            "reference_file": os.path.relpath(
                files.path("reference", cfg["reference"]), files.root),
            "arch_file": os.path.relpath(files.path("arch", cfg["arch"]),
                                    files.root)},
    }
    if traffic["kind"] == "train":
        from chiplib import train as job
    elif traffic["kind"] == "serve":
        from chiplib import serve as job
    else:
        raise SystemExit(f"traffic kind {traffic['kind']!r} has no runner")
    obs = job.run(ctx)
    common.note("setup", setup_s=obs["setup_s"], items=ctx["setup_items"],
                compile_cache_dir=cache_dir,
                cache_hits=events.counts["cache_hits"],
                cache_misses=events.counts["cache_misses"],
                backend_compiles=events.counts["backend_compiles"])
    on_chip = device["platform"] == "tpu"
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    if on_chip:
        from chiplib import peaks

        obs["peaks"] = peaks.peaks(device["kind"])
        reduced = None
        if trace and obs.get("trace"):
            from chiplib import trace as trace_mod

            reduced = trace_mod.reduce(obs["trace"],
                                       window=obs.get("trace_window"))
            obs["trace_reduced"] = reduced
        for name in manifest.metrics_for(man, workload, group):
            if name == "setup_s":
                continue
            value = manifest.metric_reader(name)(obs)
            if value is not None:
                metrics[name] = {"value": value,
                                 "unit": manifest.unit(man, name)}
        if not trace:
            metrics["setup_s"] = {"value": obs["setup_s"], "unit": "s"}
    result = {"correct": bool(obs["correct"]),
              "attempted": obs["attempted"], "failed": obs["failed"],
              "metrics": metrics,
              "device": dict(device,
                             memory_peak_bytes=obs["memory_peak_bytes"])}
    if not on_chip:
        result["rehearsal"] = ("platform %s: counts only, no device metric "
                               "is named" % device["platform"])
        result["counts"] = obs.get("counts", {})
    elif trace:
        if not reduced or reduced["busy_s"] <= 0:
            raise SystemExit("traced run: no operation ran on the device")
        result["device"].update(busy_s=reduced["busy_s"],
                                window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    # every number compared beside its limit, last in the line
    result["compared"] = {r["name"]: {"value": r["value"],
                                      "limit": r["limit"]}
                          for r in obs["compared"]}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--check-seeds", default="",
                    help="checking mode: comma-separated seeds compared "
                         "in one process; prints one line per seed")
    ap.add_argument("--sweep-rates", default="",
                    help="checking mode: comma-separated open-loop rates "
                         "(requests/s), one short run each, to find the "
                         "knee")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="checking mode: also read the lower-precision "
                         "control")
    args = ap.parse_args(argv)
    from chiplib import common

    if args.check_seeds or args.sweep_rates:
        from chiplib import checkmode

        return checkmode.main(args)
    result = run_cell(args.workload, args.seed, args.seconds, args.trace)
    common.emit(result)
    for name, r in result["compared"].items():  # standard error's last lines
        print(f"compared {name} {r['value']} limit {r['limit']}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
