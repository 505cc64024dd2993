#!/usr/bin/env python
"""OOM preflight planner: fits/doesn't-fit per sharding/batch config,
from lowering-only cost data — no execution, nothing paid per candidate
beyond the AOT compile.

    python tools/memory_planner.py --hbm-gb 16
    python tools/memory_planner.py --hbm-gb 16 --devices 8 \
        --configs dp8,dp4xmp2,dp2xmp4 --batches 4,8 --hidden 512 --layers 4

For each candidate (dp × mp × pp mesh split, batch size — the pp
column rides the planner's shared enumeration, capped by the probe's
``--layers`` stage depth and ``PT_AUTOSHARD_PP_MAX``) the planner
builds the model under that mesh, AOT-compiles the full train step
(fwd+bwd+optimizer — `jit/train_step.py`; pp>1 candidates compile the
pipeline-staged probe), and reads XLA's own executable memory
accounting (`monitor/memory.py:executable_record`;
per-device for SPMD executables) against the ``--hbm-gb`` budget. A
long compile on the chip that would end in an OOM becomes a table row
instead (PAPERS: *GSPMD*, *Memory-efficient array redistribution* — the
sharding choice IS the memory plan).

The number judged is ``args + temp`` bytes per device: parameters,
optimizer state, batch, and every XLA temporary live during the step —
the high-water mark that has to fit. Host-side RAM is used to
materialize parameters for lowering; the device never runs.

With ``PT_EXEC_CACHE=<dir>`` in the environment (or ``--exec-cache``),
candidate executables come from the AOT executable cache
(``paddle_tpu/jit/exec_cache.py``): a repeated sweep — the planner's
normal usage — deserializes every already-seen candidate instead of
recompiling it, and each row says which (``exec_cache: hit|miss``).

Exit code: 0 when at least one candidate fits, 3 when none do, 2 on
setup errors — so a driver can gate a launch on the verdict.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _autoshard_mod(name):
    """Load `paddle_tpu/autoshard/<name>.py` BY FILE PATH — these
    modules are stdlib-pure, and a package import would pull the whole
    jax-backed paddle_tpu __init__ into the parent process the
    corrected-child re-exec exists to keep light (CLI/arg errors must
    surface before any backend initializes)."""
    import importlib.util

    path = os.path.join(ROOT, "paddle_tpu", "autoshard", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"_autoshard_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _candidates_mod():
    """`paddle_tpu.autoshard.candidates` — the planner's enumeration is
    the ONE code path (ISSUE 10 satellite: this tool's private copies
    moved there)."""
    return _autoshard_mod("candidates")


def parse_mesh(token: str) -> dict:
    """``dp4xmp2`` -> {"dp": 4, "mp": 2} (either axis optional)."""
    return _candidates_mod().parse_mesh(token)


def default_meshes(n_devices: int) -> list:
    """(dp, mp) factorizations of the device count, dp-heavy first."""
    return _candidates_mod().default_meshes(n_devices)


def candidates(args, n_devices: int) -> list:
    c = _candidates_mod()
    # the pp column rides the shared enumeration (ISSUE 15): default
    # sweeps include pipeline candidates up to the probe's stage-able
    # depth (--layers), bounded by PT_AUTOSHARD_PP_MAX
    return c.enumerate_candidates(
        n_devices, args.configs, str(args.batches),
        pp_max=c.pp_cap(args.layers), stage_depth=args.layers)


def plan_one(cand: dict, args) -> dict:
    """One candidate: mesh init -> model -> AOT compile -> per-device
    memory record -> verdict — via the sharding planner's shared
    child-lowering API (`paddle_tpu/autoshard/lowering.py`, where this
    function's body moved). Tears the mesh down before returning."""
    sys.path.insert(0, ROOT)
    from paddle_tpu.autoshard.lowering import ProbeSpec, lower_candidate

    return lower_candidate(cand, ProbeSpec.from_args(args),
                           hbm_gb=args.hbm_gb)


def render(rows: list, hbm_gb: float, n_devices: int) -> str:
    out = [f"== memory planner: budget {hbm_gb:.2f} GiB/device, "
           f"{n_devices} devices =="]
    hdr = (f"{'config':<18}{'per-dev peak':>14}{'args':>10}{'temp':>10}"
           f"{'out':>10}  verdict")
    out.append(hdr)
    out.append("-" * len(hdr))
    for r in rows:
        if "error" in r:
            out.append(f"{r['label']:<18}{'—':>14}{'—':>10}{'—':>10}"
                       f"{'—':>10}  ERROR ({r['error'][:40]})")
            continue
        gib = 2**30
        out.append(
            f"{r['label']:<18}"
            f"{r['peak_bytes'] / gib:>11.3f} GiB"
            f"{r['args_bytes'] / gib:>10.3f}"
            f"{r['temp_bytes'] / gib:>10.3f}"
            f"{r['output_bytes'] / gib:>10.3f}"
            f"  {'FITS' if r['fits'] else 'DOES NOT FIT'}")
    n_fit = sum(1 for r in rows if r.get("fits"))
    out.append(f"verdict: {n_fit}/{len(rows)} candidate config(s) fit in "
               f"{hbm_gb:.2f} GiB/device")
    return "\n".join(out)


def plan(args, n_devices: int) -> list:
    rows = []
    for cand in candidates(args, n_devices):
        try:
            rows.append(plan_one(cand, args))
        except Exception as e:  # noqa: BLE001 — one broken candidate
            # must not hide the others' verdicts
            rows.append({"label": _candidates_mod().candidate_label(cand),
                         **cand, "error": f"{type(e).__name__}: {e}"})
    return rows


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="Fits/doesn't-fit preflight over sharding/batch "
                    "candidates from lowering-only memory accounting.")
    ap.add_argument("--hbm-gb", type=float, default=16.0,
                    help="per-device HBM budget in GiB (default 16 — one "
                         "v5e chip)")
    ap.add_argument("--devices", type=int, default=8,
                    help="mesh size; a virtual CPU mesh of this many "
                         "devices is forced (default 8)")
    ap.add_argument("--configs", default=None,
                    help="comma list of mesh splits, e.g. "
                         "'dp8,dp4xmp2,dp2xmp4' (default: all power-of-2 "
                         "dp×mp factorizations of --devices)")
    ap.add_argument("--batches", default="8",
                    help="comma list of global batch sizes (default 8)")
    # probe dims shared with tools/shard_plan.py (one sweep, two tools)
    _autoshard_mod("cli").add_probe_args(ap)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + 3 mesh candidates (CI smoke)")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line with the rows as well")
    ap.add_argument("--exec-cache", default=None, metavar="DIR",
                    help="AOT executable cache dir for the candidate "
                         "compiles (default: inherit PT_EXEC_CACHE) — a "
                         "repeated sweep then deserializes instead of "
                         "recompiling every (dp×mp, batch) candidate")
    return ap


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    _cli = _autoshard_mod("cli")
    if args.smoke:
        _cli.apply_smoke(args)

    # the planner needs its virtual mesh BEFORE jax initializes a
    # backend, so it re-execs in a child whose environment asks for it
    # (shared with shard_plan: autoshard/cli.py — PT_EXEC_CACHE rides
    # into the child so repeated sweeps pay XLA compilation once per
    # candidate signature EVER, not once per invocation)
    if os.environ.get("_PT_PLANNER_CHILD") != "1":
        return _cli.reexec_virtual_child(
            __file__, "memory_planner",
            argv if argv is not None else sys.argv[1:],
            args.devices, "_PT_PLANNER_CHILD",
            exec_cache=args.exec_cache)

    import jax

    n = len(jax.devices())
    if n < args.devices:
        print(f"memory_planner: need {args.devices} devices, have {n}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    try:
        rows = plan(args, args.devices)
    except ValueError as e:
        # bad --configs tokens / factorizations: name the problem, rc 2
        msg = str(e)
        print(msg if msg.startswith("memory_planner:")
              else f"memory_planner: {msg}", file=sys.stderr)
        return 2
    print(render(rows, args.hbm_gb, args.devices), flush=True)
    cache_stats = None
    try:
        from paddle_tpu.jit import exec_cache

        if exec_cache.enabled():
            cache_stats = exec_cache.stats()
            print(f"exec cache: {cache_stats['disk_hits']} disk hit(s), "
                  f"{cache_stats['mem_hits']} mem hit(s), "
                  f"{cache_stats['misses']} miss(es), "
                  f"{cache_stats['compile_ms_saved']:.0f} compile-ms "
                  f"saved ({cache_stats['dir']})", flush=True)
    except Exception:  # noqa: BLE001 — stats must not break the verdict
        pass
    if args.json:
        obj = {"memory_planner": {
            "hbm_gb": args.hbm_gb, "devices": args.devices,
            "rows": rows}}
        if cache_stats is not None:
            obj["memory_planner"]["exec_cache"] = cache_stats
        print(json.dumps(obj), flush=True)
    if not rows:
        return 2
    return 0 if any(r.get("fits") for r in rows) else 3


if __name__ == "__main__":
    sys.exit(main())
