"""Hardware-bench orchestrator.

Runs every benchmark in value order, each in its own subprocess, one
after the other, with a timeout, so one hang cannot cost the others.
Every successful run persists its numbers to ``PERF_MEASUREMENTS.json``
(see ``paddle_tpu/utils/measurements.py``) the moment they exist.

One process for each chip: this parent only starts the children that
hold the chip and reads their output. It never initialises a JAX
backend — there is no probe; a child that finds no TPU fails with its
own message and a non-zero code.

Usage: python tools/hwbench.py [--only headline,decode,bert,resnet,ernie]
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BENCHES = [
    # (name, argv, timeout_s, env) — round-5 value order (VERDICT r4
    # "Next round"): clean-tree headline + loss curve first, then 7B
    # geometry, then the ResNet layout A/B, then the rest.
    ("headline", [sys.executable, "bench.py"], 2700, None),
    # async-pipeline A/B (docs/ASYNC_PIPELINE.md): bounded in-flight
    # stepping vs per-step host sync. Each records under its own metric
    # suffix (…_async / …_syncstep) with host_blocked_ms_per_step, so the
    # host-sync-off-the-critical-path claim gets a hardware number.
    ("headline_async", [sys.executable, "bench.py"], 2700,
     {"PT_BENCH_ASYNC": "1"}),
    ("headline_syncstep", [sys.executable, "bench.py"], 2700,
     {"PT_BENCH_ASYNC": "sync"}),
    ("loss_curve", [sys.executable, "tools/loss_curve.py",
                    "--steps", "200"], 2700, None),
    ("llama7b", [sys.executable, "benchmarks/llama7b_geometry.py"],
     2400, None),
    ("resnet", [sys.executable, "benchmarks/baseline_configs.py",
                "--resnet-only"], 2400, None),
    ("resnet_nhwc", [sys.executable, "benchmarks/baseline_configs.py",
                     "--resnet-only"], 2400, {"PT_RESNET_FORMAT": "NHWC"}),
    ("resnet_profile", [sys.executable, "tools/profile_train_step.py",
                        "--model", "resnet"], 1800, None),
    ("decode", [sys.executable, "benchmarks/decode_bench.py"], 1800, None),
    ("decode_int8", [sys.executable, "benchmarks/decode_bench.py"],
     1800, {"PT_DECODE_INT8": "1"}),
    # continuous-batching serving runtime (docs/SERVING.md): smoke-sized
    # Poisson trace, timeboxed — tokens/s + p50/p99 TTFT vs the decode
    # HBM roofline; the guard's --ttft-growth gate judges the tail.
    # Spec pinned off: these two rows keep judging against their
    # pre-speculation baselines (spec/spec_k are guard config keys)
    ("serving", [sys.executable, "benchmarks/serving_bench.py"], 1800,
     {"PT_SERVE_BENCH_REQUESTS": "32", "PT_SERVE_SPEC": "0"}),
    # prefix-cache KV sharing (docs/SERVING.md): the same Poisson trace
    # with every prompt opening on one 64-token shared system prompt —
    # persists prefix_hit_rate + the cached-vs-cold TTFT A/B next to
    # the plain serving row; perf_guard --prefix-hit-drop pins the rate
    ("serving_prefix", [sys.executable, "benchmarks/serving_bench.py"],
     1800, {"PT_SERVE_BENCH_REQUESTS": "32",
            "PT_SERVE_BENCH_SHARED": "64", "PT_SERVE_SPEC": "0"}),
    # speculative decoding (docs/SERVING.md): the repetition-friendly
    # trace (tiled-motif prompts, spec_k=4) with the embedded spec-off
    # replay — persists accept_rate + tokens_per_decode_step and the
    # decode-rounds A/B; perf_guard --accept-drop pins the accept rate
    ("serving_spec", [sys.executable, "benchmarks/serving_bench.py"],
     1800, {"PT_SERVE_BENCH_REQUESTS": "32",
            "PT_SERVE_BENCH_SPEC_K": "4",
            "PT_SERVE_BENCH_SPEC_AB": "1"}),
    # multi-replica router (docs/SERVING.md "Replica router"): the
    # shared-prefix trace dispatched over 3 in-process replicas —
    # persists affinity_hit_rate + load_balance_spread next to the
    # single-engine rows (replicas is a guard config key, so they never
    # cross-judge); perf_guard --affinity-drop pins the hit rate
    ("serving_router", [sys.executable, "benchmarks/serving_bench.py"],
     1800, {"PT_SERVE_BENCH_REQUESTS": "32",
            "PT_SERVE_BENCH_SHARED": "64", "PT_SERVE_SPEC": "0",
            "PT_SERVE_BENCH_REPLICAS": "3"}),
    # int8 KV block pool (docs/SERVING.md "int8 KV"): the plain serving
    # trace with the pool quantized + the embedded bf16 replay — persists
    # kv_bytes_per_token / allocatable_tokens (the half-HBM capacity
    # claim) and the quantize-cost A/B; kv_int8 is a guard config key,
    # so this row never cross-judges the bf16 serving row
    ("serving_int8kv", [sys.executable, "benchmarks/serving_bench.py"],
     1800, {"PT_SERVE_BENCH_REQUESTS": "32", "PT_SERVE_SPEC": "0",
            "PT_SERVE_KV_INT8": "1", "PT_SERVE_BENCH_KV_AB": "1"}),
    # resilience soak (docs/RESILIENCE.md): fault-injected (crash +
    # poisoned batch) run through launcher relaunch + resume + NaN skip,
    # gated on loss slope / memory growth / the save-cost guard; the
    # persisted ckpt_save_ms_p50 anchors perf_guard --save-cost-growth
    ("soak", [sys.executable, "tools/soak.py", "--steps", "600"], 2400,
     None),
    ("bert", [sys.executable, "benchmarks/baseline_configs.py",
              "--bert-only"], 1800, None),
    ("ernie", [sys.executable, "benchmarks/ernie_bench.py"], 1800, None),
    ("longcontext", [sys.executable, "benchmarks/longcontext_bench.py"],
     2400, None),
    ("host_overhead", [sys.executable,
                       "benchmarks/host_overhead_bench.py"], 1200, None),
    ("flashtune", [sys.executable, "tools/flash_autotune.py"], 2400, None),
    # kernel search harness (docs/KERNELS.md): enumerate + parity-filter
    # + time the candidate spaces for every registered family (flash
    # blocks, head-batched flash) and persist the engagement rows the
    # runtime flips on — the timeboxed stage that settles the
    # disengaged-by-default kernel next chip-up
    ("kernel_search", [sys.executable, "tools/kernel_search.py"], 2400,
     None),
    # automatic sharding planner (docs/AUTOSHARD.md): timeboxed candidate
    # sweep — dp×mp×pp since ISSUE 15, so pipeline candidates are judged
    # and measured too — + a short measured run of the winner and
    # runner-up; persists the planned-vs-measured throughput delta (the
    # cost-model calibration number, incl. the bubble model's first
    # hardware anchor) and the (dp, mp, pp, batch) plan the guard's
    # --plan-drift gate pins for this topology
    ("shard_plan", [sys.executable, "tools/shard_plan.py", "bench"],
     2400, None),
    ("profile", [sys.executable, "tools/profile_train_step.py"], 1800,
     None),
    # ROADMAP A10: cold-vs-warm compile_ms_total on the chip + proof
    # the TPU's PJRT client supports serialize_executable (runs
    # bench.py twice)
    ("exec_cache_chip",
     [sys.executable, "tools/exec_cache_chip_probe.py"], 5400, None),
]


def _guard_check(name: str, stdout: str):
    """Run tools/perf_guard.py over a finished bench's stdout: judge the
    fresh line against the last-good record BEFORE the next bench runs,
    so a regression is called out while the chip is still up to re-measure.
    Returns True/False, or None when the output carries no bench line."""
    try:
        if ROOT not in sys.path:
            sys.path.insert(0, ROOT)
        from bench import _load_perf_guard

        guard = _load_perf_guard()
        fresh = guard.find_bench_line(stdout)
        if fresh is None:
            return None
        # bench.py embeds its own verdict (judged against the pre-record
        # baseline); for benches that don't embed, judge here — passing
        # `fresh` so the record the bench just persisted is not used as
        # its own baseline, and its sweep config so other-config A/B
        # points are skipped
        verdict = fresh.get("guard") or guard.evaluate(
            fresh, guard.last_good(guard._default_store(), fresh["metric"],
                                   fresh=fresh,
                                   match=guard.config_match(fresh)))
        ok = bool(verdict.get("ok"))
        if not ok:
            fails = [c["name"] for c in verdict.get("checks", [])
                     if not c.get("ok")]
            print(f"hwbench: {name} PERF GUARD FAILED "
                  f"({', '.join(fails) or 'unknown'})", flush=True)
        else:
            print(f"hwbench: {name} guard ok", flush=True)
        return ok
    except Exception as e:  # noqa: BLE001 — the guard must not stop the sweep
        print(f"hwbench: {name} guard errored: {e}", flush=True)
        return None


def _memory_status(name: str, stdout: str):
    """Peak-HBM + numerics-sentinel + goodput status from a finished
    bench's JSON line — printed per bench and returned for the summary,
    so memory and goodput regressions get the same while-the-chip-is-up
    visibility as throughput. (The benches themselves persist these
    fields into their PERF_MEASUREMENTS.json records — bench.py and
    soak.py carry ``goodput_frac`` in their extras, which anchors
    perf_guard --goodput-drop; this is the live readout.)"""
    try:
        if ROOT not in sys.path:
            sys.path.insert(0, ROOT)
        from bench import _load_perf_guard

        guard = _load_perf_guard()
        line = guard.find_bench_line(stdout)
        if line is None:
            return None
        out = {}
        hbm = guard.peak_hbm_of(line)
        if hbm is not None:
            out["peak_hbm_gib"] = hbm
        mem = line.get("memory") or {}
        if "nan_check" in mem:
            out["nan_check"] = mem["nan_check"]
        elif "nan_check" in line:
            out["nan_check"] = line["nan_check"]
        if line.get("goodput_frac") is not None:
            out["goodput_frac"] = line["goodput_frac"]
        if out:
            parts = []
            if "peak_hbm_gib" in out:
                parts.append(f"peak HBM {out['peak_hbm_gib']} GiB")
            if "nan_check" in out:
                parts.append("nan-check "
                             + ("armed" if out["nan_check"] else "off"))
            if "goodput_frac" in out:
                parts.append(f"goodput {out['goodput_frac']:.1%}")
            print(f"hwbench: {name} memory: {', '.join(parts)}", flush=True)
        return out or None
    except Exception as e:  # noqa: BLE001 — a readout, never a gate
        print(f"hwbench: {name} memory status errored: {e}", flush=True)
        return None


def main() -> int:
    only = None
    if "--only" in sys.argv:
        only = set(sys.argv[sys.argv.index("--only") + 1].split(","))
    results = {}
    for name, argv, timeout_s, extra_env in BENCHES:
        if only and name not in only:
            continue
        if not os.path.exists(os.path.join(ROOT, argv[1])):
            print(f"hwbench: {name}: script missing, skipped", flush=True)
            continue
        env = None
        if extra_env:
            env = dict(os.environ)
            env.update(extra_env)
        t0 = time.time()
        print(f"hwbench: running {name} ...", flush=True)
        try:
            # the child holds the chip; this parent stays off JAX
            proc = subprocess.run(argv, cwd=ROOT, capture_output=True,
                                  text=True, timeout=timeout_s, env=env)
            out = proc.stdout.strip().splitlines()
            results[name] = {"rc": proc.returncode,
                             "secs": round(time.time() - t0, 1),
                             "lines": out[-3:]}
            tail = (proc.stderr or "").strip().splitlines()[-3:]
            print(f"hwbench: {name} rc={proc.returncode} "
                  f"({results[name]['secs']}s)", flush=True)
            for ln in out[-3:]:
                print(f"  {ln}", flush=True)
            if proc.returncode == 0:
                results[name]["guard_ok"] = _guard_check(name, proc.stdout)
                mem = _memory_status(name, proc.stdout)
                if mem:
                    results[name]["memory"] = mem
            if proc.returncode != 0:
                for ln in tail:
                    print(f"  [stderr] {ln}", flush=True)
        except subprocess.TimeoutExpired:
            results[name] = {"rc": -1, "secs": timeout_s,
                             "lines": ["timeout"]}
            print(f"hwbench: {name} TIMED OUT after {timeout_s}s",
                  flush=True)
    summary = {"hwbench_summary": {
        k: v["rc"] for k, v in results.items()}}
    mem_map = {k: v["memory"] for k, v in results.items() if "memory" in v}
    if mem_map:
        summary["hwbench_memory"] = mem_map
    print(json.dumps(summary), flush=True)
    # a run in which nothing was measured must be retryable by exit code
    if not results or all(v["rc"] != 0 for v in results.values()):
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
