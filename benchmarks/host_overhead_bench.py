"""Host-overhead (dispatch-gap) benchmark: sync vs async stepping, on CPU.

The async-pipeline win on hardware is keeping the host's per-step blocking
fetch out of the device's critical path (docs/ASYNC_PIPELINE.md). This
benchmark makes the mechanism CI-measurable without a chip: on any
backend, a loop that materializes the loss every step
("sync") blocks the host for the step's remaining compute plus a transfer,
every step — while the AsyncStepper loop only blocks when its in-flight
bound is hit, and the dispatch of step k+1 (plus all the host-side
bookkeeping around it) overlaps step k's execution.

Measured quantity: **host-blocked ms/step** — time the host spends waiting
on device results (the per-step `.numpy()` in sync mode; bound-fences +
final drain in async mode). Dispatch/bookkeeping time is reported
separately (``loop_ms_per_step``). The structural invariant this asserts —
async host-blocked < sync host-blocked — holds on every backend: the sync
loop serializes [dispatch → compute → transfer] while the async loop
overlaps dispatch with compute and pays one transfer per run, not per step.

Prints ONE JSON line. Exit 0 when the async loop wins (the default-tier
smoke test asserts the same via :func:`run`).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _build(hidden, depth):
    import paddle_tpu as pt
    from paddle_tpu.jit.train_step import TrainStep

    pt.seed(0)
    layers = []
    for _ in range(depth):
        layers += [pt.nn.Linear(hidden, hidden), pt.nn.ReLU()]
    layers += [pt.nn.Linear(hidden, 1)]
    net = pt.nn.Sequential(*layers)
    opt = pt.optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters())
    step = TrainStep(net, opt, lambda m, x, y: ((m(x) - y) ** 2).mean())
    return step


def _prep_batch(rng, batch, hidden):
    """Per-step host-side input work (the stand-in for decode/augment/
    tokenize): synthesize and normalize a batch. Both loops pay this
    identically; only the async loop can overlap it with device compute."""
    import paddle_tpu as pt

    x = rng.standard_normal((batch, hidden)).astype(np.float32)
    x = (x - x.mean(axis=1, keepdims=True)) / (x.std(axis=1, keepdims=True)
                                               + 1e-6)
    y = rng.standard_normal((batch, 1)).astype(np.float32)
    return pt.to_tensor(x), pt.to_tensor(y)


def run(steps=40, max_in_flight=4, hidden=256, depth=4, batch=256):
    """Measure both loop disciplines on fresh TrainSteps.

    host-blocked = time waiting on DEVICE results only (the per-step
    `.numpy()` in sync mode; bound-fences + final drain in async mode).
    Batch prep is identical host work in both loops and is excluded from
    the blocked number — the async win is that prep/dispatch of step k+1
    overlaps step k's compute, shrinking the fence wait; the sync loop
    pays the full remaining compute + a transfer every step.
    """
    from paddle_tpu.jit.train_step import AsyncStepper

    # -- sync loop: loss materialized every step ----------------------------
    step = _build(hidden, depth)
    rng = np.random.RandomState(0)
    x, y = _prep_batch(rng, batch, hidden)
    for _ in range(3):  # warmup: compile + first dispatches
        float(step(x, y).numpy())
    sync_blocked = 0.0
    t0 = time.perf_counter()
    for _ in range(steps):
        x, y = _prep_batch(rng, batch, hidden)
        loss = step(x, y)
        t_b = time.perf_counter()
        float(loss.numpy())
        sync_blocked += time.perf_counter() - t_b
    sync_wall = time.perf_counter() - t0

    # -- async loop: bounded in-flight, deferred sync -----------------------
    step = _build(hidden, depth)
    rng = np.random.RandomState(0)
    x, y = _prep_batch(rng, batch, hidden)
    for _ in range(3):
        float(step(x, y).numpy())
    stepper = AsyncStepper(step, max_in_flight=max_in_flight)
    t0 = time.perf_counter()
    for _ in range(steps):
        x, y = _prep_batch(rng, batch, hidden)
        loss = stepper(x, y)
    last = stepper.drain()
    async_wall = time.perf_counter() - t0
    assert np.isfinite(float(last.numpy()))

    res = {
        "metric": "host_blocked_ms_per_step",
        "unit": "ms",
        "steps": steps,
        "max_in_flight": max_in_flight,
        "sync_host_blocked_ms_per_step": round(sync_blocked / steps * 1e3, 3),
        "async_host_blocked_ms_per_step": round(
            stepper.host_blocked_s / steps * 1e3, 3),
        "sync_wall_ms_per_step": round(sync_wall / steps * 1e3, 3),
        "async_wall_ms_per_step": round(async_wall / steps * 1e3, 3),
    }
    res["async_wins"] = (res["async_host_blocked_ms_per_step"]
                         < res["sync_host_blocked_ms_per_step"])
    return res


def main():
    import jax

    res = run(steps=int(os.environ.get("PT_HOSTBENCH_STEPS", "40")))
    res["backend"] = jax.default_backend()
    if res["backend"] != "cpu":
        # PERF_MEASUREMENTS.json is the hardware record — CPU smoke runs
        # stay out of it (same convention as bench.py)
        try:
            from paddle_tpu.utils import measurements as _meas

            _meas.record("host_blocked_ms_per_step_async",
                         res["async_host_blocked_ms_per_step"], "ms",
                         extra={k: v for k, v in res.items()
                                if k not in ("metric", "unit")})
        except Exception as e:  # noqa: BLE001 — persistence is best-effort
            print(f"host_overhead_bench: persist failed: {e}",
                  file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0 if res["async_wins"] else 1


if __name__ == "__main__":
    sys.exit(main())
