"""Long-context end-to-end training throughput on one chip (SURVEY §5.7).

Trains the headline Llama architecture at seq 4096/8192/16384 with the
Pallas flash kernel engaged (batch scaled down to hold tokens/step at
8192 while batch > 1; from seq 16384 on, batch floors at 1 and
tokens/step = seq) and prints one JSON line per seq. This is the model-level
long-context evidence on top of the kernel-level autotune table: the
flash kernel's O(seq) memory is what lets the full train step fit at
seq >= 8192, where the composite's s*s score materialization would not.

Run: python benchmarks/longcontext_bench.py [--smoke] [--seqs 4096,8192]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_seq(seq: int, smoke: bool):
    import jax
    import paddle_tpu as pt
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    pt.seed(0)
    if smoke:
        cfg = LlamaConfig.tiny()
        batch, steps, warmup = 1, 2, 1
        seq = min(seq, 128)
    else:
        # headline architecture (bench.py), position table stretched to
        # seq; batch keeps tokens/step at 8192 while batch > 1 so HBM
        # headroom goes to the longer context, not more rows (from seq
        # 16384 the floor of batch=1 makes tokens/step = seq)
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1536, intermediate_size=4096,
            num_hidden_layers=12, num_attention_heads=12,
            max_position_embeddings=seq, dtype="bfloat16",
            use_parallel_cross_entropy=False,
            ce_chunk_size=int(os.environ.get("PT_BENCH_CE_CHUNK", "0")))
        batch, steps, warmup = max(8192 // seq, 1), 10, 2
    model = LlamaForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        for p in model.parameters():
            p._data = p._data.astype("bfloat16")
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=cfg.dtype == "bfloat16")
    step = TrainStep(model, opt, lambda m, i, l: m(i, l), donate=True)

    rng = np.random.RandomState(0)
    ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = pt.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))

    for _ in range(warmup):
        float(np.asarray(step(ids, labels).numpy()).sum())
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids, labels)
    final = float(np.asarray(loss.numpy()).sum())
    dt = time.perf_counter() - t0
    assert np.isfinite(final)

    tokens_per_sec = batch * seq * steps / dt
    out = {"metric": "llama_longcontext_train_tokens_per_sec_per_chip",
           "value": round(tokens_per_sec, 1), "unit": "tokens/s",
           "seq": seq, "batch": batch, "final_loss": round(final, 3)}
    if not smoke:
        from bench import _peak_flops

        out["mfu"] = round(
            tokens_per_sec * model.flops_per_token(seq)
            / _peak_flops(jax.devices()[0]), 4)
        from paddle_tpu.utils import measurements as _meas

        _meas.record_rec_or_warn(out)
    print(json.dumps(out), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seqs", default="4096,8192,16384")
    args = ap.parse_args()

    from paddle_tpu.framework.device import require_tpu
    from paddle_tpu.utils.xla_cache import enable_compilation_cache

    enable_compilation_cache()
    smoke = args.smoke
    if not smoke:
        require_tpu("longcontext_bench")

    # same pre-flight as bench.py: a kernel that cannot lower fails the
    # run
    from paddle_tpu.ops import pallas as _pallas

    _pallas.check_tpu_lowering()

    for seq in (int(s) for s in args.seqs.split(",")):
        bench_seq(seq, smoke)
        if smoke:  # every smoke seq clamps to the same tiny config
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
