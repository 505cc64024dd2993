"""MFU at Llama-2-7B GEOMETRY (BASELINE config 4 names 7B; round-4's
headline was 0.44B-shaped).

A 16 GiB chip cannot hold all of 7B + Adam + masters, but MFU is set by
the per-layer matmul shapes, not the layer count — so this benches a
2-layer stack with the exact 7B layer geometry (hidden 4096, 32 heads,
head_dim 128, ffn 11008, vocab 32000; reference Llama-2-7B config) and
persists `llama7b_geometry_tokens_per_sec_per_chip`. If MFU holds ≥0.6
here, the 0.44B headline claim generalizes to 7B shapes; if it drops,
that is the finding.

Memory at the default (2 layers + tied-size embed/lm_head ≈ 0.67B
params): bf16 params 1.3G + fp32 masters 2.7G + moments 5.3G ≈ 9.3G,
leaving ~6G for activations at b4×s1024 (flash kernel engaged at
s1024/d128 per flash_tune.json).

Usage: python benchmarks/llama7b_geometry.py [--smoke]
Knobs: PT_7B_LAYERS (2), PT_7B_BATCH (4), PT_7B_CE_CHUNK (4096 — the
[4096-row, 32000-vocab] fp32 logits would be 0.5G/microstep otherwise).
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax

    from bench import _peak_flops
    from paddle_tpu.framework.device import require_tpu
    from paddle_tpu.utils.xla_cache import enable_compilation_cache

    enable_compilation_cache()
    smoke = "--smoke" in sys.argv
    if not smoke:
        require_tpu("llama7b_geometry")
    print(f"llama7b_geometry: smoke={smoke}", flush=True)

    import paddle_tpu as pt
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    if smoke:
        layers, batch, seq, steps, warmup = 1, 1, 64, 2, 1
        vocab, hidden, heads, ffn = 1024, 256, 4, 704
    else:
        layers = int(os.environ.get("PT_7B_LAYERS", "2"))
        batch = int(os.environ.get("PT_7B_BATCH", "4"))
        seq, steps, warmup = 1024, 10, 2
        vocab, hidden, heads, ffn = 32000, 4096, 32, 11008
    cfg = LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=ffn,
        num_hidden_layers=layers, num_attention_heads=heads,
        max_position_embeddings=seq, dtype="bfloat16",
        use_parallel_cross_entropy=False,
        ce_chunk_size=int(os.environ.get("PT_7B_CE_CHUNK", "4096")))
    pt.seed(0)
    model = LlamaForCausalLM(cfg)
    for p in model.parameters():
        p._data = p._data.astype("bfloat16")
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True)
    step = TrainStep(model, opt, lambda m, i, l: m(i, l), donate=True)

    rng = np.random.RandomState(0)

    def batch_ids(i):
        return (pt.to_tensor(rng.randint(0, vocab, (batch, seq))),
                pt.to_tensor(rng.randint(0, vocab, (batch, seq))))

    for i in range(warmup):
        loss = step(*batch_ids(i))
    _ = float(np.asarray(loss.numpy()))  # transfer-backed sync
    t0 = time.perf_counter()
    for i in range(steps):
        loss = step(*batch_ids(i))
    final = float(np.asarray(loss.numpy()))  # sync
    dt = time.perf_counter() - t0
    tps = batch * seq * steps / dt
    # MFU is a statement about the chip: a CPU smoke has none
    mfu = 0.0 if smoke else (tps * model.flops_per_token(seq)
                             / _peak_flops(jax.devices()[0]))
    rec = {"metric": "llama7b_geometry_tokens_per_sec_per_chip",
           "value": round(tps, 1), "unit": "tokens/s",
           "mfu": round(mfu, 4), "layers": layers, "batch": batch,
           "seq": seq, "hidden": hidden, "heads": heads, "ffn": ffn,
           "model_params_b": round(n_params / 1e9, 3),
           "final_loss": round(final, 4)}
    if smoke:
        rec["note"] = "cpu smoke at shrunken geometry; not a TPU number"
    else:
        from paddle_tpu.utils import measurements as meas

        meas.record_rec_or_warn(rec)
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
