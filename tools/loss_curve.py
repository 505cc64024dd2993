"""Hardware loss-curve artifact: does the TPU numeric path LEARN?

BASELINE's metric is throughput AND loss parity, but every round-4 TPU
record was throughput-only — nothing persisted showed the bf16 + Pallas
flash + donated-buffer headline step converging on the chip (CPU tests
converge, but bf16 matmuls and the flash kernel are exactly what CPU
tests don't cover). This runs the EXACT headline train step
(`bench.py:build_headline_trainstep` — same config the MFU number comes
from) for N steps on a fixed synthetic corpus with a learnable
structure, and persists the full loss series.

Pass criterion recorded with the data: mean(last 10%) < 0.8 * mean(first
10%) and the final loss is finite. Synthetic data is drawn once from a
fixed-seed Zipf-ish unigram + repeated n-gram templates so the model has
real structure to learn (pure-uniform random tokens plateau at
ln(vocab)).

Usage: python tools/loss_curve.py [--steps 200] [--smoke]
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _corpus(vocab, n_tokens, seed=0):
    """Zipf unigrams + planted 8-gram templates: learnable structure."""
    rng = np.random.RandomState(seed)
    base = rng.zipf(1.3, n_tokens).astype(np.int64) % vocab
    templates = [rng.randint(0, vocab, 8) for _ in range(32)]
    i = 0
    while i + 8 < n_tokens:
        if rng.rand() < 0.3:
            base[i:i + 8] = templates[rng.randint(32)]
            i += 8
        else:
            i += 1
    return base


def main() -> int:
    from paddle_tpu.framework.device import require_tpu
    from paddle_tpu.utils.xla_cache import enable_compilation_cache

    enable_compilation_cache()
    smoke = "--smoke" in sys.argv
    steps = 200
    if "--steps" in sys.argv:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])
    if not smoke:
        require_tpu("loss_curve")
    if smoke:
        steps = min(steps, 30)
    print(f"loss_curve: smoke={smoke} steps={steps}", flush=True)

    from bench import build_headline_trainstep

    import paddle_tpu as pt

    model, step, batch, seq = build_headline_trainstep(on_cpu=smoke)
    vocab = model.config.vocab_size
    corpus = _corpus(vocab, batch * seq * steps + steps + 1)

    losses = []
    t0 = time.perf_counter()
    for s in range(steps):
        lo = s * batch * seq
        chunk = corpus[lo:lo + batch * seq + 1]
        ids = pt.to_tensor(chunk[:-1].reshape(batch, seq))
        labels = pt.to_tensor(chunk[1:].reshape(batch, seq))
        loss = step(ids, labels)
        # per-step host read IS the sync; decode-style enqueue-ack
        # artifacts cannot fake a loss series
        losses.append(float(np.asarray(loss.numpy())))
        if s % 20 == 0 or s == steps - 1:
            print(f"  step {s:4d} loss {losses[-1]:.4f}", flush=True)
    wall = time.perf_counter() - t0

    head = float(np.mean(losses[:max(1, steps // 10)]))
    tail = float(np.mean(losses[-max(1, steps // 10):]))
    ok = np.isfinite(losses).all() and tail < 0.8 * head
    rec = {
        "metric": "llama_train_loss_curve",
        "value": round(tail, 4),
        "unit": "loss",
        "steps": steps, "batch": batch, "seq": seq,
        "loss_first10pct": round(head, 4),
        "loss_last10pct": round(tail, 4),
        "converging": bool(ok),
        "losses": [round(x, 4) for x in losses],
        "wall_s": round(wall, 1),
    }
    # memory + numerics provenance: peak HBM and sentinel status ride in
    # the persisted record like throughput does (allocator stats where
    # the backend reports them — the TPU does — else XLA's executable
    # accounting; best-effort: it must not cost the loss series)
    from paddle_tpu.monitor import memory as _memobs
    from paddle_tpu.monitor import numerics as _numerics

    rec["nan_check"] = _numerics.enabled()
    rec["losses_finite"] = bool(np.isfinite(losses).all())
    try:
        peak = _memobs.device_peak_gib()
        if peak is None:
            mrec = _memobs.executable_record(
                step, ids, labels, name="loss_curve/headline")
            peak = round(mrec["peak_bytes"] / 2**30, 3)
        rec["peak_hbm_gib"] = peak
    except Exception as e:  # noqa: BLE001
        print(f"loss_curve: memory accounting unavailable: {e}",
              file=sys.stderr, flush=True)
    if smoke:
        rec["note"] = "cpu smoke; the hardware artifact needs the chip"
    else:
        from paddle_tpu.utils import measurements as meas

        meas.record_rec_or_warn(rec)
    line = {k: v for k, v in rec.items() if k != "losses"}
    print(json.dumps(line), flush=True)
    return 0 if (ok or smoke) else 3


if __name__ == "__main__":
    sys.exit(main())
