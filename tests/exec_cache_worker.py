"""Subprocess body for the two-process exec-cache warm-start proof
(tests/test_exec_cache.py).

Runs a small deterministic TrainStep for two steps with the AOT
executable cache armed (``PT_EXEC_CACHE`` in the environment, set by the
parent) and the monitor on, then prints ONE JSON line with the losses,
the post-step parameter digest, and the monitor/cache counters — the
parent asserts a cold process compiles+serializes and a warm process
deserializes with zero fresh XLA compiles and bitwise-identical numbers.
"""
import hashlib
import json
import os
import sys

import jax

# a test worker runs on the CPU whatever its environment says
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import numpy as np  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu import monitor, nn  # noqa: E402
from paddle_tpu.jit import exec_cache  # noqa: E402
from paddle_tpu.jit.train_step import TrainStep  # noqa: E402


class TinyModel(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(8, 16)
        self.fc2 = nn.Linear(16, 8)

    def forward(self, x):
        return self.fc2(nn.functional.relu(self.fc1(x)))


def main():
    monitor.enable()
    pt.seed(1234)
    np.random.seed(1234)
    model = TinyModel()
    opt = pt.optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    step = TrainStep(model, opt, lambda m, x, y: ((m(x) - y) ** 2).mean())
    x = pt.to_tensor(np.random.RandomState(7).randn(4, 8).astype("float32"))
    y = pt.to_tensor(np.random.RandomState(8).randn(4, 8).astype("float32"))
    losses = [float(step(x, y).numpy()) for _ in range(2)]
    # bitwise digest of every post-step param: the cold-vs-warm identity
    # proof must cover the executable's real outputs, not a rounded loss
    h = hashlib.sha256()
    for p in model.parameters():
        h.update(np.asarray(p.numpy()).tobytes())
    snap = monitor.snapshot()
    print(json.dumps({
        "losses": losses,
        "param_digest": h.hexdigest(),
        "counters": snap.get("counters", {}),
        "exec_cache": exec_cache.stats(),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
