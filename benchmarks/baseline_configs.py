"""BASELINE configs 2 and 3, measured end-to-end on one chip.

- config 2: ResNet50 (ImageNet shapes), compiled whole-step training
  (`TrainStep` — the static/@to_static path's engine), imgs/sec/chip.
- config 3: BERT-base masked-LM, AMP O2 (bf16 params + fp32 masters),
  flash-attention kernel engaged (head_dim 64), tokens/sec/chip.

Secondary to `bench.py` (the driver's headline metric stays the Llama
MFU); prints one JSON line per config for PERF.md. Run:
    python benchmarks/baseline_configs.py [--smoke]
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sync(x):
    return float(np.asarray(x.numpy()).sum())


def build_resnet_trainstep(smoke):
    """The ONE ResNet50 model+step (shared with
    tools/profile_train_step.py --model resnet — a profile must be
    attributable to the bench number). Returns (model, step, x, y,
    batch, hw)."""
    import paddle_tpu as pt
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.vision.models import resnet50

    pt.seed(0)
    if smoke:
        batch, hw, depth_kw = 4, 32, {"num_classes": 10}
    else:
        # b256 measured 2084 imgs/s vs 1984 at b128 (2026-07-31,
        # pre-PR-1 tree, not reproduced). PT_RESNET_BATCH to sweep
        batch = int(os.environ.get("PT_RESNET_BATCH", "256"))
        hw, depth_kw = 224, {}
    # PT_RESNET_FORMAT=NHWC: channel-last end-to-end — the round-5
    # layout A/B against the 0.130-MFU NCHW measurement
    fmt = os.environ.get("PT_RESNET_FORMAT", "NCHW")
    depth_kw["data_format"] = fmt
    model = resnet50(**depth_kw)
    model = pt.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                parameters=model.parameters(),
                                multi_precision=True)
    loss_fn = pt.nn.CrossEntropyLoss()

    def compute(m, x, y):
        return loss_fn(m(x), y)

    step = TrainStep(model, opt, compute, donate=True)
    shape = (batch, 3, hw, hw) if fmt == "NCHW" else (batch, hw, hw, 3)
    x = pt.to_tensor((np.random.randn(*shape) * 0.1).astype(np.float32))
    x = x.astype("bfloat16")
    y = pt.to_tensor(np.random.randint(
        0, model.num_classes, (batch, 1)).astype(np.int64))
    return model, step, x, y, batch, hw


def bench_resnet50(smoke):
    import jax

    if smoke:
        steps, warmup = 2, 1
    else:
        steps, warmup = 10, 2
    model, step, x, y, batch, hw = build_resnet_trainstep(smoke)

    for _ in range(warmup):
        _sync(step(x, y))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, y)
    final = _sync(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final)
    imgs_per_sec = batch * steps / dt
    # ResNet50@224 fwd ~= 4.1 GFLOP/img (MACs x2); training ~= 3x fwd
    flops_img = 3 * 4.1e9 if hw == 224 else None
    out = {"metric": "resnet50_train_imgs_per_sec_per_chip",
           "value": round(imgs_per_sec, 1), "unit": "imgs/s",
           "batch": batch, "final_loss": round(final, 3),
           "data_format": os.environ.get("PT_RESNET_FORMAT", "NCHW")}
    if flops_img:
        from bench import _peak_flops  # same chip peak table

        out["mfu"] = round(imgs_per_sec * flops_img
                           / _peak_flops(jax.devices()[0]), 4)
    if not smoke:
        from paddle_tpu.utils import measurements as _meas

        _meas.record_rec_or_warn(out)
    print(json.dumps(out), flush=True)
    return out


def bench_bert_mlm(smoke):
    import jax
    import paddle_tpu as pt
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models import BertConfig, BertForMaskedLM

    pt.seed(0)
    if smoke:
        cfg = BertConfig.tiny()
        batch, seq, steps, warmup = 2, 32, 2, 1
    else:
        # reference-default attn dropout 0.1: the Pallas kernel now runs
        # dropout IN-KERNEL (counter-hash mask, flash_attention.py), so
        # the honest config no longer forces the composite path
        cfg = BertConfig(max_position_embeddings=512, dtype="bfloat16")
        batch = int(os.environ.get("PT_BERT_BATCH", "64"))
        seq, steps, warmup = 512, 10, 2
    model = BertForMaskedLM(cfg)
    model = pt.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True)

    def compute(m, ids, labels):
        return m(ids, labels=labels)

    step = TrainStep(model, opt, compute, donate=True)
    rng = np.random.RandomState(0)
    ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)))
    labels = pt.to_tensor(np.where(rng.rand(batch, seq) < 0.15,
                                   ids.numpy(), -100))

    for _ in range(warmup):
        _sync(step(ids, labels))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids, labels)
    final = _sync(loss)
    dt = time.perf_counter() - t0
    assert np.isfinite(final)
    tokens_per_sec = batch * seq * steps / dt
    # 6*N per token (N = params excl. embeddings-as-lookup is close enough
    # to N_total for BERT-base) + attention matmul term 12*s*h per layer
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops_tok = 6 * n_params + cfg.num_hidden_layers * 12 * seq * cfg.hidden_size
    out = {"metric": "bert_base_mlm_tokens_per_sec_per_chip",
           "value": round(tokens_per_sec, 1), "unit": "tokens/s",
           "batch": batch, "final_loss": round(final, 3),
           "params_m": round(n_params / 1e6, 1)}
    if not smoke:
        from bench import _peak_flops

        out["mfu"] = round(tokens_per_sec * flops_tok
                           / _peak_flops(jax.devices()[0]), 4)
        from paddle_tpu.utils import measurements as _meas

        _meas.record_rec_or_warn(out)
    print(json.dumps(out), flush=True)
    return out


def main():
    from paddle_tpu.framework.device import platform, require_tpu
    from paddle_tpu.utils.xla_cache import enable_compilation_cache

    enable_compilation_cache()
    smoke = "--smoke" in sys.argv
    if not smoke:
        require_tpu("baseline_configs")
    print(f"baseline_configs: platform={platform()} smoke={smoke}",
          file=sys.stderr, flush=True)

    # same pre-flight as bench.py: a kernel that cannot lower fails the
    # run
    from paddle_tpu.ops import pallas as _pallas

    _pallas.check_tpu_lowering()

    if "--bert-only" not in sys.argv:
        bench_resnet50(smoke)
    if "--resnet-only" not in sys.argv:
        bench_bert_mlm(smoke)


if __name__ == "__main__":
    main()
