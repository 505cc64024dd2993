"""BASELINE config 5 single-chip proxy: ERNIE joint-pretraining throughput.

The real config-5 target (ERNIE-3.0 10B, semi-auto shard + pipeline on
v5p-32) needs a pod; the proxy here is a scaled ERNIE (same architecture:
shared trunk + NLU/NLG task branches, joint MLM+LM loss) sized to one v5e
chip, trained with the same whole-step-compiled TrainStep the pipe path
uses per stage.  Reference contract: BASELINE.md config 5.

Run: python benchmarks/ernie_bench.py [--smoke]
Prints one JSON line: {"metric": "ernie_pretrain_tokens_per_sec_per_chip"}.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    from bench import _peak_flops
    from paddle_tpu.framework.device import platform, require_tpu
    from paddle_tpu.utils.xla_cache import enable_compilation_cache

    enable_compilation_cache()
    smoke = "--smoke" in sys.argv
    if not smoke:
        require_tpu("ernie_bench")
    print(f"ernie_bench: platform={platform()} smoke={smoke}",
          file=sys.stderr, flush=True)

    import paddle_tpu as pt
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models import ErnieConfig, ErnieForPretraining

    pt.seed(0)
    if smoke:
        cfg = ErnieConfig.tiny()
        batch, seq, steps, warmup = 2, 32, 2, 1
    else:
        # ~0.4B proxy of the 10B shape (trunk 16x1536/12h, task 4x512),
        # bf16 + fp32 masters; fits one v5e chip at b4 x s1024
        cfg = ErnieConfig(
            vocab_size=40000, hidden_size=1536, num_hidden_layers=16,
            num_attention_heads=12, intermediate_size=4096,
            task_hidden_size=512, num_task_layers=4,
            num_task_attention_heads=8, task_intermediate_size=2048,
            max_position_embeddings=1024, dtype="bfloat16",
            hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1)
        batch = int(os.environ.get("PT_ERNIE_BATCH", "4"))
        seq, steps, warmup = 1024, 10, 2
    model = ErnieForPretraining(cfg)
    if cfg.dtype == "bfloat16":
        for p in model.parameters():
            p._data = p._data.astype("bfloat16")
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=cfg.dtype == "bfloat16")

    def compute(m, ids, mlm_labels, lm_labels):
        return m(ids, mlm_labels=mlm_labels, lm_labels=lm_labels)

    step = TrainStep(model, opt, compute, donate=True)
    rng = np.random.RandomState(0)
    ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    mlm_labels = pt.to_tensor(np.where(rng.rand(batch, seq) < 0.15,
                                       ids.numpy(), -100))
    lm_labels = pt.to_tensor(ids.numpy())

    for _ in range(warmup):
        float(np.asarray(step(ids, mlm_labels, lm_labels).numpy()))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids, mlm_labels, lm_labels)
    final = float(np.asarray(loss.numpy()))
    dt = time.perf_counter() - t0
    assert np.isfinite(final)
    tps = batch * seq * steps / dt
    rec = {"metric": "ernie_pretrain_tokens_per_sec_per_chip",
           "value": round(tps, 1), "unit": "tokens/s",
           "final_loss": round(final, 3),
           "params_b": round(sum(int(np.prod(p.shape))
                                 for p in model.parameters()) / 1e9, 3)}
    if smoke:
        rec["note"] = "cpu smoke mode; not a TPU number"
    else:
        rec["mfu"] = round(tps * model.flops_per_token(seq)
                           / _peak_flops(jax.devices()[0]), 4)
        from paddle_tpu.utils import measurements as _meas

        _meas.record_rec_or_warn(rec)
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
