"""A training cell: the program's ``jit.TrainStep`` on seeded batches fed
through ``io/prefetch``, timed over fenced steps, and held to the plain
reference over its first three steps."""
from __future__ import annotations

import gc
import time

import numpy as np

from . import common, modelbuild
from . import trace as trace_mod
from . import weights as W

CHECK_STEPS = 3
# The fence is jax.block_until_ready: PR 21's fence pair on the v5e showed
# it and utils/timing.device_sync agree (115.0-116.4 vs 115.3-115.5 ms), so
# the lint rule that prefers device_sync (PTL002, ROADMAP C7) is switched
# off on those lines rather than the measured path changed.


def batches(seed, traffic, replicas, vocab):
    """The run's distinct batches, seeded, all rows different: int32
    [rows, seq + 1]; inputs are [:, :-1], labels [:, 1:]."""
    rng = np.random.default_rng([int(seed), 0x7A11])
    rows = traffic["batch_per_replica"] * replicas
    return [rng.integers(0, vocab, (rows, traffic["seq_len"] + 1),
                         dtype=np.int32)
            for _ in range(traffic["distinct_batches"])]


def feed(tokens):
    """Endless (ids, labels) over the prepared batches, in order."""
    i = 0
    while True:
        t = tokens[i % len(tokens)]
        yield t[:, :-1], t[:, 1:]
        i += 1


def init_mesh(devices):
    """One chip, one replica: the program's mesh over the first device."""
    from paddle_tpu.distributed import env as env_mod

    env_mod.init_mesh(dp=1, devices=list(devices[:1]))
    return 1


def build_step(arch, cfg, traffic, seed, hooks):
    """The ONE object the check drives and the window times: model,
    optimizer and compiled step with its state."""
    import paddle_tpu as pt
    from paddle_tpu.jit.train_step import TrainStep

    layers = cfg["num_hidden_layers"]["train"]
    model, specs, keys, params = modelbuild.build(
        arch, cfg, layers, traffic["seq_len"], seed,
        **cfg.get("model_flags", {}))
    o = cfg["train"]
    opt = pt.optimizer.AdamW(
        learning_rate=o["learning_rate"], beta1=o["beta1"],
        beta2=o["beta2"], epsilon=o["epsilon"],
        weight_decay=o["weight_decay"], parameters=model.parameters(),
        multi_precision=o["multi_precision"])
    step = TrainStep(model, opt, lambda m, i, l: m(i, l), donate=True)
    step = hooks.get("wrap_step", lambda s: s)(step)
    return model, step, specs, keys, params


def _sq_norms(arrays):
    import jax.numpy as jnp

    return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for a in arrays]


def change_norms(leaves, keys, specs, dtype):
    """Per leaf, the norm of (leaf - its seeded initial value); the
    initial value is made again from its key, one leaf at a time."""
    import jax.numpy as jnp

    out = []
    for i, (_, _, shape, kind) in enumerate(specs):
        init = W.make_leaf(keys[i], shape, kind, dtype)
        out.append(jnp.sqrt(jnp.sum(jnp.square(
            leaves[i].astype(jnp.float32) - init.astype(jnp.float32)))))
    return out


def program_first_steps(step, params, specs, keys, cfg, it):
    """Drives the step through CHECK_STEPS batches of the window's own
    feed. Returns losses, the per-leaf norm of the first gradient as the
    optimizer got it (AdamW's first moment after one step is
    (1 - beta1) g) and the per-leaf norm of the parameters' change."""
    import jax
    import jax.numpy as jnp

    inner = getattr(step, "inner", step)
    b1 = cfg["train"]["beta1"]
    dtype = cfg["model"]["torch_dtype"]
    losses, gnorm, dnorms = [], None, []
    moved = jax.jit(lambda leaves, keys: change_norms(leaves, keys, specs,
                                                      dtype))
    for k in range(CHECK_STEPS):
        ids, labels = next(it)
        loss = step(ids, labels)
        losses.append(loss._data)
        index = {id(p): i for i, p in enumerate(inner._params)}
        if k == 0:
            moments = [inner._state[index[id(p)]]["moment1"]
                       for p in params]
            gnorm = jax.jit(_sq_norms)(moments)
        masters = [inner._masters[index[id(p)]]
                   if inner._masters[index[id(p)]] is not None else p._data
                   for p in params]
        dnorms.append(moved(masters, jnp.asarray(keys)))
    losses, gnorm, dnorms = jax.device_get((losses, gnorm, dnorms))
    return ([float(x) for x in losses],
            [float(x) / (1.0 - b1) for x in gnorm],
            [float(x) for x in dnorms[-1]],
            [[float(x) for x in d] for d in dnorms])


def reference_first_steps(ref, cfg, specs, keys, tokens, quant=False):
    """The plain reference over the same three batches: float32,
    precision 'highest', its own AdamW."""
    import jax
    import jax.numpy as jnp
    params = modelbuild.reference_params(cfg, specs, keys)
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    mom, var = zeros(params), zeros(params)
    stepfn = jax.jit(
        lambda p, a, b, ids, labels, n: ref.train_step(
            p, a, b, ids, labels, n, m=cfg["model"], o=cfg["train"],
            quant=quant),
        donate_argnums=(0, 1, 2))
    def flat(tree):
        return [tree[name] if li < 0 else tree["layers"][li][name]
                for li, name, _, _ in specs]

    dtype = cfg["model"]["torch_dtype"]
    moved = jax.jit(lambda leaves, keys: change_norms(leaves, keys, specs,
                                                      dtype))
    losses, gnorm, dnorms = [], None, []
    with jax.default_matmul_precision("highest"):
        for k in range(CHECK_STEPS):
            t = jnp.asarray(tokens[k])
            params, mom, var, loss, g = stepfn(
                params, mom, var, t[:, :-1], t[:, 1:], jnp.float32(k + 1))
            losses.append(float(loss))
            if k == 0:
                gnorm = jax.device_get(flat(g))
            dnorms.append(jax.device_get(moved(flat(params),
                                               jnp.asarray(keys))))
    del mom, var, params
    return (losses, [float(x) for x in gnorm],
            [float(x) for x in dnorms[-1]],
            [[float(x) for x in d] for d in dnorms])


def leaf_gaps(prog, ref):
    """Per leaf: the gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger."""
    med = float(np.median(ref))
    return [abs(a - b) / max(b, med) for a, b in zip(prog, ref)]


def worst_leaf(prog, ref):
    return max(leaf_gaps(prog, ref))


def compare(prog, ref, limits):
    """[{name, value, limit, ok}] — each number beside its limit."""
    p_loss, p_g, p_d = prog[:3]
    r_loss, r_g, r_d = ref[:3]
    rows = [
        ("loss_abs_gap", max(abs(a - b) for a, b in zip(p_loss, r_loss))),
        ("grad_norm_rel_gap", worst_leaf(p_g, r_g)),
        ("update_norm_rel_gap", worst_leaf(p_d, r_d)),
    ]
    return [{"name": n, "value": v, "limit": limits[n],
             "ok": bool(v <= limits[n])} for n, v in rows]


def run(ctx):
    import jax

    from paddle_tpu import monitor
    from paddle_tpu.io.prefetch import DevicePrefetchIterator

    cfg, traffic, hooks = ctx["config"], ctx["traffic"], ctx["hooks"]
    devices, events = ctx["devices"], ctx["events"]
    items = ctx["setup_items"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    t = time.perf_counter()
    replicas = init_mesh(devices)
    monitor.enable()  # set-up only: engagement and retrace counters
    model, step, specs, keys, params = build_step(ctx["arch"], cfg, traffic,
                                                  seed, hooks)
    jax.block_until_ready([p._data for p in params])  # ptlint: disable=PTL002
    items["model_and_weights_s"] = time.perf_counter() - t
    items["bytes_in_use_after_model"] = common.bytes_in_use(devices)

    t = time.perf_counter()
    tokens = batches(seed, traffic, replicas, cfg["model"]["vocab_size"])
    it = DevicePrefetchIterator(feed(tokens),
                                depth=traffic["prefetch_depth"])
    rows, seq = tokens[0].shape[0], traffic["seq_len"]
    items["batch_prep_s"] = time.perf_counter() - t

    t = time.perf_counter()
    prog = program_first_steps(step, params, specs, keys, cfg, it)
    items["compile_and_checked_steps_s"] = time.perf_counter() - t
    t = time.perf_counter()
    for _ in range(traffic["unmeasured_steps"]):
        loss = step(*next(it))
    jax.block_until_ready(loss._data)  # ptlint: disable=PTL002
    items["unmeasured_steps_s"] = time.perf_counter() - t
    counters = monitor.snapshot()["counters"]
    monitor.disable()  # the window runs with no recorder
    flash = {"engaged": counters.get("pallas/engaged/flash", 0),
             "fallback": counters.get("pallas/fallback/flash", 0)}
    gc.collect()
    gc.freeze()
    compiles0 = events.compiles()
    setup_s = time.perf_counter() - ctx["t_start"]

    # -- the window ---------------------------------------------------------
    every = 1 if ctx["trace"] else traffic["fence_every"]
    step_ms, n = [], 0
    traced = traced_dir = None
    ann = jax.profiler.TraceAnnotation
    if ctx["trace"]:
        import os

        tdir = common.trace_dir(ctx["workload"], seed)
        os.makedirs(tdir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=opts)
        traced = tdir
    t0 = time.perf_counter()
    t_end = t_prev = t0
    while True:
        if ctx["trace"]:
            with ann("bench/batch_prep"):
                batch = next(it)
            with ann("bench/train_step"):
                loss = step(*batch)
                jax.block_until_ready(loss._data)  # ptlint: disable=PTL002
        else:
            loss = step(*next(it))
        n += 1
        if n % every == 0:
            if not ctx["trace"]:
                jax.block_until_ready(loss._data)  # ptlint: disable=PTL002
            t_end = time.perf_counter()
            step_ms.append((t_end - t_prev) * 1e3 / every)
            t_prev = t_end
            if traced and (n >= traffic["traced_steps"]
                           or t_end - t0 >= seconds):
                jax.profiler.stop_trace()
                traced_dir, traced = traced, None
            if t_end - t0 >= seconds:
                break
    window_s = t_end - t0
    it.close()
    in_window_compiles = events.compiles() - compiles0
    peak = common.memory_peak(devices)
    gc.unfreeze()

    obs = {"job": "train", "steps": n, "tokens": n * rows * seq,
           "window_s": window_s, "step_ms": step_ms, "rows": rows,
           "seq": seq, "chips": len(devices), "setup_s": setup_s,
           "model": cfg["model"], "layers": cfg["num_hidden_layers"]["train"],
           "arch": ctx["arch"],
           "device_kind": ctx["device"]["kind"], "traced_steps":
           min(n, traffic["traced_steps"]) if ctx["trace"] else 0}
    if ctx["trace"]:
        obs["trace"] = trace_mod.load(trace_mod.find_xplane(traced_dir))

    # -- the reference, with the chip to itself -------------------------------
    t = time.perf_counter()
    del model, step, params, it, loss
    from paddle_tpu.distributed import env as env_mod

    env_mod.reset_env()
    common.drop_program_state()
    left = common.bytes_in_use(devices)
    ref = ctx["files"].reference(cfg["reference"])
    refv = reference_first_steps(ref, cfg, specs, keys, tokens)
    rows_cmp = compare(prog, refv, ctx["limits"])
    if ctx.get("control"):
        ctrl = reference_first_steps(ref, cfg, specs, keys, tokens,
                                     quant=True)
        common.note("control", numbers=compare(ctrl, refv, ctx["limits"]),
                    losses=ctrl[0])
    checks = [
        {"name": "in_window_compiles", "value": in_window_compiles,
         "limit": 0, "ok": in_window_compiles == 0},
        {"name": "flash_fallbacks", "value": flash["fallback"], "limit": 0,
         "ok": flash["engaged"] > 0 and flash["fallback"] == 0},
    ]
    names = [f"{name}{li if li >= 0 else ''}" for li, name, _, _ in specs]
    common.note("leaf_gaps", grad=dict(zip(names, leaf_gaps(prog[1],
                                                            refv[1]))),
                update=dict(zip(names, leaf_gaps(prog[2], refv[2]))),
                grad_norm_reference=dict(zip(names, refv[1])),
                update_norm_by_step={"program": prog[3],
                                     "reference": refv[3]})
    common.note("compare", numbers=rows_cmp + checks, **ctx["compared_with"],
                program={"losses": prog[0]}, reference={"losses": refv[0]},
                flash=flash, bytes_left_before_reference=left,
                reference_s=time.perf_counter() - t)
    obs["correct"] = all(r["ok"] for r in rows_cmp + checks)
    obs["compared"] = rows_cmp + checks
    obs["attempted"], obs["failed"] = n, 0
    obs["memory_peak_bytes"] = peak
    return obs
