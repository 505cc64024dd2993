"""The builder's checking mode: the output comparison for a list of
seeds in ONE process (one start-up, one compile of each program), with
the lower-precision control beside it when asked. Not used by the
driver's runs.

    python3 benchmarks/chip/run.py --workload <cell> --check-seeds 1,2,3 \
        --control 1 --seconds 8
    python3 benchmarks/chip/run.py --workload <open-loop cell> \
        --sweep-rates 2,2.5,3 --seconds 30     # the knee sweep
"""
from __future__ import annotations

import time


def main(args):
    import run as runner
    from chiplib import common

    seeds = [int(s) for s in args.check_seeds.split(",") if s]
    rates = [float(r) for r in args.sweep_rates.split(",") if r]
    # a sweep looks for the knee, not for faults: it compares one request
    runs = ([(args.seed + i, {"rate_rps": r, "check_requests": 1})
             for i, r in enumerate(rates)]
            if rates else [(s, None) for s in seeds])
    ok = True
    for seed, override in runs:
        t = time.perf_counter()
        result = runner.run_cell(args.workload, seed, args.seconds, 0,
                                 t_start=t, control=bool(args.control),
                                 traffic_override=override)
        common.note("checked", seed=seed, override=override,
                    correct=result["correct"],
                    seconds=time.perf_counter() - t,
                    metrics=result["metrics"])
        ok = ok and result["correct"]
    common.emit({"checked": [r[0] for r in runs], "all_correct": ok})
    return 0 if ok else 1
