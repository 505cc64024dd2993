"""Time the linear-attention layers' STATE PATH alone, at the served sizes
(Kimi-Linear-48B-A3B's: 64 lanes, 32 heads of 128 x 128, 20 layers, 5
positions a verify round), a round's 20 layers in ONE jitted program:

- ``xla_verify``: the order the round programs had before PR 48 — every
  layer read (``kda_wy`` + ``kda_read``), the lanes' acceptance, then the
  masked one-pass update (``kda_apply``) a layer;
- ``xla_decode``: a plain round's ``kda_step`` a layer;
- ``traversal``: a copy kernel over the state kernel's grid (a lane's 2 MB
  a step, in place): what reading and writing every state once costs;
- ``kernel_verify`` / ``kernel_decode``: ``ops/pallas/kda_state.py`` —
  5 owed + 5 read; 5 owed + 1 read + 1 own.

    chiprun -- python tools/bench_kda_state.py --out chiprun_out/kda.json
    JAX_PLATFORMS=cpu python tools/bench_kda_state.py --smoke   # tiny, CPU

Timed as ``tools/bench_row_read.py`` times: 2 warm calls, then ``--calls``
dispatched back to back over the host clock and one fence; ``ms`` is a
ROUND's (all layers). ``least_ms``: every state read once and written
once at the published HBM rate. ``gap``: the kernel's outputs and state
against the step recurrence, position by position, on this device. The
times RANK forms; they do not price them: here every form's small
operands are program arguments in HBM, where a step program's live in
fast memory, and in the cell's traced rounds the same kernel and the same
XLA order read 6-8 ms a round less (PERF.md section 6, PR 48).
Without a TPU it raises, unless ``--smoke`` asks for tiny shapes on the
CPU (interpret mode: no time means anything there).
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

HBM_RATE = 819e9  # TPU v5e, published (benchmarks/chip/chiplib/peaks.py)


def _inputs(lanes, T, H, d, seed):
    """(q, k, v, g, beta) [lanes, T, H, ..] float32 as ``kda_conv`` /
    ``kda_gates`` give them: unit keys, log-decays <= 0."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    n = functools.partial(jax.random.normal, shape=(lanes, T, H, d))
    return (unit(n(ks[0])) * d ** -0.5, unit(n(ks[1])), n(ks[2]),
            -0.3 * jax.nn.softplus(n(ks[3])),
            jax.nn.sigmoid(jax.random.normal(ks[4], (lanes, T, H))))


def _positions_first(now):
    """(q, k, v, g) [T, lanes, ..] as the kernel takes them, and beta."""
    import jax.numpy as jnp

    return (*(jnp.swapaxes(a, 0, 1) + 0 for a in now[:4]), now[4])


def _forms(layers, lanes, H, d):
    """{name: (jitted program, its operands after the states)}; every
    program takes and returns the ``layers`` state arrays (donated)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.models import linear_latent_moe as M
    from paddle_tpu.ops.pallas import kda_state

    hf = lambda a: jnp.swapaxes(a, 1, 2)  # noqa: E731

    def xla_verify(states, now, n_keep):
        q, k, v, g, beta = (hf(a) for a in now)
        keep = (jnp.arange(q.shape[2])[None, :]
                < n_keep[:, None])[:, None, :, None]
        kept = [M.kda_read(M.kda_wy(q, k, v, g, beta), S) for S in states]
        return [M.kda_apply(S, k, jnp.where(keep, g, 0.0),
                            jnp.where(keep, u, 0.0))
                for S, (_, u) in zip(states, kept)], sum(o for o, _ in kept)

    def xla_decode(states, now):
        out = [M.kda_step(S, *(a[:, 0] for a in now)) for S in states]
        return [S for S, _ in out], sum(o for _, o in out)

    def copy(s_ref, o_ref):
        o_ref[...] = s_ref[...]

    def traversal(states):
        block = pl.BlockSpec((1, H, d, d), lambda i: (i, 0, 0, 0))
        return [pl.pallas_call(
            copy, grid=(lanes,), in_specs=[block], out_specs=block,
            out_shape=jax.ShapeDtypeStruct(S.shape, S.dtype),
            input_output_aliases={0: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=32 << 20),
            interpret=jax.default_backend() != "tpu")(S)
            for S in states], None

    def kernel_verify(states, pend, n_owed, now):
        outs = []
        for li, S in enumerate(states):
            o, S, pend = kda_state.state_round(S, pend, li, n_owed, *now,
                                               own=False)
            outs.append((o, S))
        return [S for _, S in outs], pend, sum(o for o, _ in outs)

    def kernel_decode(states, pend, n_owed, now):
        out = [kda_state.state_round(S, pend, li, n_owed, *now, own=True)
               for li, S in enumerate(states)]
        return [S for _, S in out], sum(o for o, _ in out)

    round5, round1 = _inputs(lanes, 5, H, d, 1), _inputs(lanes, 1, H, d, 2)
    first5, first1 = _positions_first(round5), _positions_first(round1)
    owed = _inputs(lanes, 5, H, d, 3)
    n = jnp.asarray([1 + i % 5 for i in range(lanes)], jnp.int32)

    def pend():  # every layer's (k, g, u) of a round before
        return jnp.broadcast_to(
            jnp.stack([owed[1], owed[3], 0.1 * owed[2]], axis=1),
            (layers, lanes, 3, 5, H, d)) + 0

    jit = functools.partial(jax.jit, donate_argnums=(0,))
    return {
        "xla_verify": (jit(xla_verify), (round5, n)),
        "xla_decode": (jit(xla_decode), (round1,)),
        "traversal": (jit(traversal), ()),
        "kernel_verify": (jax.jit(kernel_verify, donate_argnums=(0, 1)),
                          (pend(), n, first5)),
        "kernel_decode": (jit(kernel_decode), (pend(), n, first1)),
    }


def _time(fn, states, rest, calls):
    """ms a call; the states (and a donated pending pool) threaded
    through."""
    from paddle_tpu.utils.timing import device_sync  # the repo's fence

    rest = list(rest)

    def call(states):
        out = fn(states, *rest)
        if len(out) == 3:  # the verify kernel hands its pending on
            rest[0] = out[1]
        return out[0], out[-1]

    for _ in range(2):
        states, o = call(states)
        device_sync(states)
    t = time.perf_counter()
    for _ in range(calls):
        states, o = call(states)
    device_sync((states, o))
    return (time.perf_counter() - t) / calls * 1e3, states


def _gap(lanes, H, d):
    """The kernel against ``kda_step`` position by position: 5 owed (1-5
    of them kept) then 5 read, and a plain round; the widest absolute gaps
    of outputs and state, and the values' size."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models import linear_latent_moe as M
    from paddle_tpu.ops.pallas import kda_state

    def steps(S, now, n):
        outs = []
        for t in range(now[0].shape[1]):
            S2, o = M.kda_step(S, *(a[:, t] for a in now))
            S = jnp.where((t < n)[:, None, None, None], S2, S)
            outs.append(o)
        return jnp.stack(outs, 1), S

    S0 = jnp.asarray(np.random.RandomState(0).normal(
        0, 1, (lanes, H, d, d)), jnp.float32)
    owed, now5 = _inputs(lanes, 5, H, d, 3), _inputs(lanes, 5, H, d, 1)
    now1 = _inputs(lanes, 1, H, d, 2)
    n = jnp.asarray([i % 6 for i in range(lanes)], jnp.int32)
    every = jnp.full((lanes,), 9)
    zero = jnp.zeros((2, lanes, 3, 5, H, d), jnp.float32)
    pf = _positions_first
    _, _, pend = kda_state.state_round(S0 + 0, zero, 1, 0 * n, *pf(owed),
                                       own=False)
    _, S_c = steps(S0, owed, n)
    o5, S5, _ = kda_state.state_round(S0 + 0, pend + 0, 1, n, *pf(now5),
                                      own=False)
    o1, S1 = kda_state.state_round(S0 + 0, pend, 1, n, *pf(now1), own=True)
    want5, want1 = steps(S_c, now5, every), steps(S_c, now1, every)

    def gap(a, b):
        if a.shape != b.shape:
            a = jnp.swapaxes(a, 0, 1)
        return float(jnp.max(jnp.abs(a - b)))

    return {"verify_out": gap(o5, want5[0]), "verify_state": gap(S5, S_c),
            "decode_out": gap(o1, want1[0]),
            "decode_state": gap(S1, want1[1]),
            "out_max": float(jnp.max(jnp.abs(want5[0]))),
            "state_max": float(jnp.max(jnp.abs(S_c)))}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--layers", type=int, default=20)
    ap.add_argument("--forms", default="")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import jax.numpy as jnp

    from paddle_tpu.framework.device import on_tpu

    if not on_tpu() and not args.smoke:
        raise SystemExit("bench_kda_state: no TPU (use --smoke on the CPU)")
    lanes, H, d, layers = (3, 4, 16, 2) if args.smoke \
        else (64, 32, 128, args.layers)
    least = 2 * layers * lanes * H * d * d * 4 / HBM_RATE * 1e3
    lines = []

    def emit(**line):
        lines.append(line)
        print(json.dumps(line), flush=True)

    forms = _forms(layers, lanes, H, d)
    for name in [f for f in args.forms.split(",") if f] or forms:
        fn, rest = forms[name]
        states = [jnp.full((lanes, H, d, d), 0.01 * (i + 1), jnp.float32)
                  for i in range(layers)]
        ms, states = _time(fn, states, rest, args.calls)
        emit(form=name, lanes=lanes, heads=H, d=d, layers=layers,
             ms=round(ms, 4), least_ms=round(least, 4),
             pct=round(100 * least / ms, 1),
             finite=bool(jnp.isfinite(states[0]).all()))
    emit(gap=_gap(lanes, H, d))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
