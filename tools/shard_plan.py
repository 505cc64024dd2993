#!/usr/bin/env python
"""Automatic sharding planner driver: plan → launch → resume hybrid
runs with zero hand-written PartitionSpecs (docs/AUTOSHARD.md).

    python tools/shard_plan.py plan --devices 8 --hbm-gb 16
    python tools/shard_plan.py plan --smoke          # tier-1 CPU proof
    python tools/shard_plan.py launch --plan shard_plan.json train.py args
    python tools/shard_plan.py resume --devices 2 --configs dp1xmp2 \
        --from ckpt_dir train.py args
    python tools/shard_plan.py bench                 # hwbench row

``plan`` enumerates every legal (dp × mp × pp, batch) candidate for
the device count (pipeline depth capped by the probe's stage-able
layer count and ``PT_AUTOSHARD_PP_MAX``), AOT-lowers each on a virtual
mesh (pp>1 candidates compile the GPipe-in-XLA PipelineLayer schedule;
no execution; with ``PT_EXEC_CACHE`` a repeat sweep pays ZERO fresh
XLA compiles — the JSON line's ``fresh_compiles`` proves it), applies
the HBM-fit hard constraint + the compute/comms roofline
(`paddle_tpu/autoshard/cost.py` — pipeline candidates carry the
``(pp−1)/n_micro`` bubble and the ppermute handoff wire term), and
writes the winner as a deterministic ``shard_plan.json`` — same
inputs, byte-identical file, now also recording ``pp``/``n_micro``/the
layer→stage assignment. Exit codes mirror memory_planner: 0 a winner
exists, 3 nothing fits, 2 setup error.

``launch`` starts the plan's run through `paddle_tpu.distributed.launch`
(the launcher stamps ``PT_SHARD_PLAN`` into every worker; scripts call
``autoshard.apply_plan`` and never name an axis). ``resume`` replans
(or takes ``--plan``) and relaunches with ``PT_SHARD_RESUME=<ckpt>`` so
the run continues from its newest complete checkpoint at the NEW
(dp × mp) — reshard-on-load (docs/RESILIENCE.md) does the conversion.

``bench`` is the hwbench row: a timeboxed sweep + a short measured run
of the winner (and the runner-up when one fits), persisting the
planned-vs-measured delta to PERF_MEASUREMENTS.json on hardware; CPU
runs are marked smoke and never enter the store.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD_FLAG = "_PT_SHARD_PLAN_CHILD"


def _cli():
    """`paddle_tpu.autoshard.cli` — probe args, smoke geometry, and the
    corrected-child re-exec shared with tools/memory_planner.py. Loaded
    BY FILE PATH: it is stdlib-pure, and a package import would pull
    jax into the parent process before the corrected-child re-exec."""
    import importlib.util

    path = os.path.join(ROOT, "paddle_tpu", "autoshard", "cli.py")
    spec = importlib.util.spec_from_file_location("_autoshard_cli", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _add_sweep_args(ap) -> None:
    ap.add_argument("--devices", type=int, default=8,
                    help="mesh size; a virtual CPU mesh of this many "
                         "devices is forced (default 8)")
    ap.add_argument("--hbm-gb", type=float, default=16.0,
                    help="per-device HBM budget in GiB (default 16 — one "
                         "v5e chip)")
    ap.add_argument("--configs", default=None,
                    help="comma list of mesh splits, e.g. "
                         "'dp8,dp4xmp2,dp2xpp2' (default: all power-of-2 "
                         "dp×mp×pp factorizations of --devices, pp capped "
                         "by the probe's --layers and PT_AUTOSHARD_PP_MAX)")
    ap.add_argument("--batches", default="8",
                    help="comma list of global batch sizes (default 8)")
    ap.add_argument("--out", default="shard_plan.json",
                    help="plan output path (default ./shard_plan.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny probe + 4 mesh candidates incl. a pp2 "
                         "pipeline (the tier-1 CPU pipeline proof, "
                         "kernel-search convention)")
    ap.add_argument("--exec-cache", default=None, metavar="DIR",
                    help="AOT executable cache dir for the candidate "
                         "compiles (default: inherit PT_EXEC_CACHE) — a "
                         "repeated sweep then pays zero fresh XLA compiles")
    _cli().add_probe_args(ap)


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="shard_plan",
        description="Plan, launch and resume hybrid (dp×mp) runs with "
                    "no hand-written PartitionSpecs.")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("plan", help="sweep candidates, emit shard_plan.json")
    _add_sweep_args(p)

    l = sub.add_parser("launch", help="launch a planned run")
    l.add_argument("--plan", default="shard_plan.json")
    l.add_argument("--log-dir", default="log")
    l.add_argument("--max-restart", type=int, default=3)
    l.add_argument("--nproc", type=int, default=1,
                   help="processes per host (SPMD default 1)")
    l.add_argument("script")
    l.add_argument("script_args", nargs=argparse.REMAINDER)

    r = sub.add_parser(
        "resume", help="replan for the CURRENT topology and resume a "
                       "checkpoint saved at another (dp×mp)")
    r.add_argument("--plan", default=None,
                   help="use this plan instead of replanning")
    r.add_argument("--from", dest="resume_from", required=True,
                   help="checkpoint dir of the run to resume")
    r.add_argument("--log-dir", default="log")
    r.add_argument("--max-restart", type=int, default=3)
    r.add_argument("--nproc", type=int, default=1)
    _add_sweep_args(r)
    r.add_argument("script")
    r.add_argument("script_args", nargs=argparse.REMAINDER)

    b = sub.add_parser("bench", help="hwbench row: planned vs measured")
    _add_sweep_args(b)
    b.add_argument("--steps", type=int, default=8,
                   help="measured steps per judged candidate (default 8)")
    return ap


# -- plan --------------------------------------------------------------------

def _reexec_child(args, argv, force_cpu: bool = True,
                  timeout: int = 1800) -> int:
    return _cli().reexec_virtual_child(
        __file__, "shard_plan", argv, args.devices, _CHILD_FLAG,
        exec_cache=getattr(args, "exec_cache", None), force_cpu=force_cpu,
        timeout=timeout)


def _render_rows(rows, hbm_gb: float, devices: int) -> str:
    out = [f"== shard planner: budget {hbm_gb:.2f} GiB/device, "
           f"{devices} devices =="]
    hdr = (f"{'config':<18}{'per-dev peak':>14}{'comms MiB':>11}"
           f"{'est ms':>9}{'est tok/s':>12}  verdict")
    out.append(hdr)
    out.append("-" * len(hdr))
    gib = 2**30
    for r in rows:
        if "error" in r:
            out.append(f"{r['label']:<18}{'—':>14}{'—':>11}{'—':>9}"
                       f"{'—':>12}  ERROR ({r['error'][:40]})")
            continue
        comms = (r.get("collectives") or {}).get("total_wire_bytes", 0)
        est = r.get("est_step_ms")
        tps = r.get("est_tokens_per_sec")
        out.append(
            f"{r['label']:<18}"
            f"{r['peak_bytes'] / gib:>11.3f} GiB"
            f"{comms / 2**20:>11.2f}"
            f"{est if est is not None else '—':>9}"
            f"{tps if tps is not None else '—':>12}"
            f"  {'FITS' if r.get('fits') else 'DOES NOT FIT'}")
    return "\n".join(out)


def cmd_plan(args, argv) -> int:
    if args.smoke:
        _cli().apply_smoke(args)
    args.out = os.path.abspath(args.out)
    if os.environ.get(_CHILD_FLAG) != "1":
        # the child runs with cwd=ROOT — pin the out path to the
        # INVOKING directory before re-exec (argparse last-wins)
        return _reexec_child(args, list(argv) + ["--out", args.out])

    import jax

    if len(jax.devices()) < args.devices:
        print(f"shard_plan: need {args.devices} devices, have "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from paddle_tpu import autoshard
    from paddle_tpu.jit import exec_cache

    spec = autoshard.ProbeSpec(
        vocab=args.vocab, hidden=args.hidden,
        intermediate=args.intermediate, layers=args.layers,
        heads=args.heads, seq=args.seq,
        moe_experts=getattr(args, "moe_experts", 0) or 0)
    try:
        plan, rows = autoshard.make_plan(
            args.devices, args.hbm_gb, spec=spec,
            configs=args.configs, batches=args.batches)
    except ValueError as e:
        print(f"shard_plan: {e}", file=sys.stderr)
        return 2
    print(_render_rows(rows, args.hbm_gb, args.devices), flush=True)
    stats = exec_cache.stats() if exec_cache.enabled() else None
    line = {"shard_plan": {
        "devices": args.devices, "hbm_gb": args.hbm_gb,
        "candidates": len(rows),
        "feasible": sum(1 for r in rows if r.get("fits")),
        # the exec-cache-warm acceptance number: misses == fresh XLA
        # compiles this sweep paid (0 on a warm repeat)
        "fresh_compiles": stats["misses"] if stats else None,
        "exec_cache": bool(stats),
    }}
    if plan is None:
        print("shard_plan: no candidate fits the HBM budget — not "
              "emitting a plan", flush=True)
        print(json.dumps(line), flush=True)
        return 3
    plan.save(args.out)
    line["shard_plan"].update(plan.summary())
    line["shard_plan"]["out"] = args.out
    print(f"winner: {plan.winner} -> {args.out} "
          f"(digest {plan.digest()})", flush=True)
    if stats is not None:
        print(f"exec cache: {stats['disk_hits']} disk hit(s), "
              f"{stats['mem_hits']} mem hit(s), {stats['misses']} "
              f"miss(es) ({stats['dir']})", flush=True)
    print(json.dumps(line), flush=True)
    return 0


# -- launch / resume ---------------------------------------------------------

def _launch(plan_path: str, args, resume_from: str | None = None) -> int:
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--shard_plan", os.path.abspath(plan_path),
           "--log_dir", args.log_dir,
           "--max_restart", str(args.max_restart),
           "--nproc_per_node", str(args.nproc),
           args.script] + list(args.script_args)
    env = dict(os.environ)
    if resume_from is not None:
        env["PT_SHARD_RESUME"] = os.path.abspath(resume_from)
    print("shard_plan: exec " + " ".join(cmd), flush=True)
    return subprocess.call(cmd, env=env, cwd=os.getcwd())


def cmd_launch(args) -> int:
    if not os.path.exists(args.plan):
        print(f"shard_plan: no plan at {args.plan!r} — run "
              f"`shard_plan.py plan` first", file=sys.stderr)
        return 2
    return _launch(args.plan, args)


def cmd_resume(args, argv) -> int:
    args.out = os.path.abspath(args.out)
    plan_path = args.plan
    if plan_path is None:
        # replan for the topology we are resuming INTO; the checkpoint
        # reshards on load, so the saved (dp×mp) does not constrain it
        plan_path = args.out
        plan_argv = ["plan"] + _sweep_argv(args)
        rc = main(plan_argv)
        if rc != 0:
            return rc
    if not os.path.exists(plan_path):
        print(f"shard_plan: no plan at {plan_path!r}", file=sys.stderr)
        return 2
    return _launch(plan_path, args, resume_from=args.resume_from)


def _sweep_argv(args) -> list:
    out = ["--devices", str(args.devices), "--hbm-gb", str(args.hbm_gb),
           "--batches", str(args.batches), "--out", args.out,
           "--hidden", str(args.hidden), "--layers", str(args.layers),
           "--heads", str(args.heads), "--seq", str(args.seq),
           "--vocab", str(args.vocab),
           "--intermediate", str(args.intermediate)]
    if args.configs:
        out += ["--configs", args.configs]
    if args.smoke:
        out += ["--smoke"]
    if getattr(args, "exec_cache", None):
        out += ["--exec-cache", args.exec_cache]
    return out


# -- bench (the hwbench row) -------------------------------------------------

def cmd_bench(args, argv) -> int:
    """Plan on the virtual mesh, then measure the winner (and the
    runner-up when one fits) for a few real steps — the planned-vs-
    measured delta is the number that calibrates the cost model."""
    if args.smoke:
        _cli().apply_smoke(args)
    if os.environ.get(_CHILD_FLAG) != "1":
        # This parent starts the child that holds the chip, so it never
        # initialises a JAX backend itself (no probe). --smoke measures
        # on the virtual CPU mesh (marked, never a baseline); anything
        # else needs the chip, and the child fails without one.
        # Timeout: inside hwbench's 2400 s row timebox, with headroom.
        return _reexec_child(args, argv, force_cpu=args.smoke,
                             timeout=2100)

    import jax

    sys.path.insert(0, ROOT)
    from paddle_tpu import autoshard
    from paddle_tpu.framework.device import platform, require_tpu

    if not args.smoke:
        require_tpu("shard_plan bench")
        args.devices = len(jax.devices())
    backend = platform()
    spec = autoshard.ProbeSpec(
        vocab=args.vocab, hidden=args.hidden,
        intermediate=args.intermediate, layers=args.layers,
        heads=args.heads, seq=args.seq,
        moe_experts=getattr(args, "moe_experts", 0) or 0)
    plan, rows = autoshard.make_plan(
        args.devices, args.hbm_gb, spec=spec,
        configs=args.configs, batches=args.batches)
    if plan is None:
        print(json.dumps({"metric": "shard_plan_planned_vs_measured",
                          "value": 0.0, "error": "no feasible candidate"}),
              flush=True)
        return 3
    ranked = autoshard.rank_candidates(rows)
    judged = []
    for row in ranked[:2]:
        cand = {"dp": row["dp"], "mp": row["mp"], "batch": row["batch"]}
        measured = _measure_candidate(cand, spec, steps=args.steps)
        judged.append({**cand, "label": row["label"],
                       "est_tokens_per_sec": row.get("est_tokens_per_sec"),
                       "measured_tokens_per_sec": measured})
    winner = judged[0]
    planned_first = (len(judged) < 2
                     or (winner["measured_tokens_per_sec"] or 0)
                     >= (judged[1]["measured_tokens_per_sec"] or 0))
    line = {
        "metric": "shard_plan_planned_vs_measured",
        "value": winner["measured_tokens_per_sec"],
        "unit": "tokens/s",
        "devices": args.devices,
        "shard_plan": plan.summary(),
        "judged": judged,
        "planned_winner_measured_best": bool(planned_first),
    }
    if backend == "cpu":
        # smoke runs never enter the store — PERF_MEASUREMENTS.json is
        # the hardware record (serving_bench convention)
        line["note"] = "cpu smoke mode; not a TPU number"
    else:
        try:
            from paddle_tpu.utils import measurements as _meas

            _meas.record_rec_or_warn(dict(line), backend=backend)
        except Exception as e:  # noqa: BLE001 — persistence is
            # best-effort after a successful measurement
            print(f"shard_plan: persist failed: {e}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def _measure_candidate(cand: dict, spec, steps: int = 8) -> float | None:
    """Short measured run of one candidate on the live backend: tokens/s
    over ``steps`` timed steps (1 warmup, each window ended by a
    device_sync fence). The probe comes from
    the SAME builder the planning sweep lowered (`autoshard.build_probe`
    — dp-sharded batch included), so the measured program is the one
    the plan's memory/comms account described."""
    from paddle_tpu.autoshard import build_probe
    from paddle_tpu.distributed import env as env_mod
    from paddle_tpu.utils.timing import device_sync

    try:
        try:
            step, ids, _model = build_probe(cand, spec)
            loss = step(ids, ids)  # warmup: trace+compile
            device_sync(loss._data)
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = step(ids, ids)
            device_sync(loss._data)
            dt = time.perf_counter() - t0
            return round(cand["batch"] * spec.seq * steps / dt, 2)
        finally:
            env_mod.reset_env()
    except Exception as e:  # noqa: BLE001 — one candidate's failure must
        # not kill the row; the delta is simply not judged for it
        print(f"shard_plan: measure failed for {cand}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return None


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    args = build_argparser().parse_args(argv)
    if args.cmd == "plan":
        return cmd_plan(args, argv)
    if args.cmd == "launch":
        return cmd_launch(args)
    if args.cmd == "resume":
        return cmd_resume(args, argv)
    if args.cmd == "bench":
        return cmd_bench(args, argv)
    return 2


if __name__ == "__main__":
    sys.exit(main())
