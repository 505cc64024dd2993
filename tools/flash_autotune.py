"""Run the flash-attention block-size autotuner on the live backend.

Usage: python tools/flash_autotune.py [--iters 20] [--shapes bh,sq,sk,d,causal ...]

Writes `paddle_tpu/ops/pallas/flash_tune.json` (block choices + kernel-vs-
composite ratios with device provenance) and records a summary metric to
PERF_MEASUREMENTS.json. Run whenever a chip is reachable (hwbench stage).
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--shapes", nargs="*", default=None,
                    help="bh,sq,sk,d,causal tuples")
    args = ap.parse_args()

    from paddle_tpu.framework.device import require_tpu
    from paddle_tpu.utils.xla_cache import enable_compilation_cache

    require_tpu("flash_autotune")  # wall-clock tuning needs the chip
    enable_compilation_cache()

    from paddle_tpu.ops.pallas import autotune

    if args.shapes:
        shapes = []
        for s in args.shapes:
            bh, sq, sk, d, causal = s.split(",")
            shapes.append((int(bh), int(sq), int(sk), int(d),
                           causal.lower() in ("1", "true", "c")))
    else:
        shapes = autotune.STANDARD_SHAPES

    entries = []
    for bh, sq, sk, d, causal in shapes:
        print(f"tuning bh={bh} s={sq}x{sk} d={d} causal={causal}",
              flush=True)
        entries.append(autotune.tune_shape(bh, sq, sk, d, causal,
                                           iters=args.iters))

    for bh, sq, sk, d, causal, p_drop in autotune.VARIANT_SHAPES:
        print(f"variant bh={bh} s={sq}x{sk} d={d} causal={causal} "
              f"dropout={p_drop}", flush=True)
        try:
            entries.append(autotune.tune_variant_ratio(
                bh, sq, sk, d, causal, p_drop, iters=args.iters))
        except Exception as e:  # noqa: BLE001 — variants must not
            print(f"  variant failed: {e}", flush=True)  # cost the base rows

    from paddle_tpu.utils import measurements as meas

    base = [e for e in entries if not e.get("dropout")]
    wins = sum(1 for e in base if e.get("ratio_fwd_bwd", 0) > 1.0)
    meas.record_or_warn(
        "flash_autotune_shapes_kernel_wins", float(wins), "shapes",
        extra={"tuned": len(base), "variants": len(entries) - len(base),
               "entries": {
                   autotune._key(e["sq"], e["sk"], e["d"], e["causal"],
                                 e.get("dropout", 0.0)):
                   e.get("ratio_fwd_bwd") for e in entries}})
    print(f"flash_autotune: {wins}/{len(base)} base shapes favor the "
          f"kernel (+{len(entries) - len(base)} variant rows); cache at "
          f"paddle_tpu/ops/pallas/flash_tune.json", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
