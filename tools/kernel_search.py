#!/usr/bin/env python
"""Run the kernel search harness (ops/pallas/search.py) on the live backend.

    python tools/kernel_search.py [--families a,b] [--iters 20]
    python tools/kernel_search.py --smoke          # CPU pipeline proof

Hardware run: for every registered family (flash blocks, head-batched
flash — default: all), enumerate the candidate space,
interpret-parity-filter every candidate, time the survivors with the
two-fori-loop discipline, and persist the best row (device + commit
provenance) to ``paddle_tpu/ops/pallas/kernel_tune.json``. Engagement
flips happen ONLY through those rows (measured-faster-than-composite);
a summary metric lands in PERF_MEASUREMENTS.json. Run whenever a chip
is reachable (hwbench ``kernel_search`` stage).

``--smoke`` proves the full pipeline (enumerate -> parity filter ->
timing path) on CPU in interpret mode at tiny shapes: rows go to a
TEMPORARY table (unless --table/PT_KERNEL_TUNE_PATH overrides) and are
stamped backend=cpu/interpret=true, which ``search.engaged`` refuses —
a smoke run can never flip an engagement. Tier-1 runs it
(tests/test_kernel_search.py).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CPU interpret-mode pipeline proof at tiny "
                         "shapes; never produces engagement rows")
    ap.add_argument("--families", default=None,
                    help="comma-separated family names (default: all "
                         "registered)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--table", default=None,
                    help="tune-table path override (also "
                         "PT_KERNEL_TUNE_PATH)")
    args = ap.parse_args()

    if args.smoke:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if args.table:
        os.environ["PT_KERNEL_TUNE_PATH"] = args.table
    elif args.smoke and not os.environ.get("PT_KERNEL_TUNE_PATH"):
        # a smoke run must not dirty the committed table
        os.environ["PT_KERNEL_TUNE_PATH"] = os.path.join(
            tempfile.mkdtemp(prefix="kernel_search_smoke_"),
            "kernel_tune.json")

    from paddle_tpu.framework.device import platform, require_tpu
    from paddle_tpu.utils.xla_cache import enable_compilation_cache

    enable_compilation_cache()
    smoke = args.smoke
    if not smoke:
        # wall-clock search on a CPU is meaningless: --smoke is the
        # pipeline proof, anything else needs the chip
        require_tpu("kernel_search")
    print(f"kernel_search: platform={platform()} smoke={smoke}",
          file=sys.stderr, flush=True)

    import paddle_tpu.ops.pallas  # noqa: F401 — registers the families
    from paddle_tpu.ops.pallas import search

    if args.families:
        names = args.families.split(",")
    elif smoke:
        names = sorted(search.FAMILIES)
    else:
        # hardware default: the families with NO rows yet. The flash
        # family's block search is already served by the (earlier)
        # hwbench flashtune stage — re-searching it here would spend
        # the timebox twice; pass --families flash to force it.
        names = [n for n in sorted(search.FAMILIES) if n != "flash"]
    iters = 2 if smoke else args.iters
    entries = []
    failures = []
    for name in names:
        fam = search.FAMILIES.get(name)
        if fam is None:
            print(f"kernel_search: unknown family {name!r} (have "
                  f"{sorted(search.FAMILIES)})", file=sys.stderr,
                  flush=True)
            return 2
        try:
            entries.extend(search.search_family(fam, iters=iters,
                                                smoke=smoke))
        except Exception as e:  # noqa: BLE001 — one family must not
            failures.append((name, f"{type(e).__name__}: {e}"))
            print(f"kernel_search: family {name} failed: {e}",
                  file=sys.stderr, flush=True)  # cost the others

    engaged = [e for e in entries if e.get("ratio", 0) > 1.0]
    rec = {
        "metric": "kernel_search_shapes",
        "value": float(len(entries)),
        "unit": "shapes",
        "families": names,
        "engaged_shapes": len(engaged),
        "rows": {f"{e['family']}:{e['key']}": e.get("ratio")
                 for e in entries},
        "table": search.table_path(),
        "failures": dict(failures),
    }
    if smoke:
        rec["note"] = "cpu smoke mode; not a TPU number"
    else:
        from paddle_tpu.utils import measurements as _meas

        _meas.record_rec_or_warn(rec)
    print(json.dumps(rec), flush=True)
    if not entries:
        return 3  # nothing searched — retryable
    return 1 if failures else 0  # partial rows persisted either way


if __name__ == "__main__":
    sys.exit(main())
