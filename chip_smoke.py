"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip: device, train, serve
    python chip_smoke.py --chips 4    # ONLY the sharded-vs-single training
                                      # comparison, on four chips
    python chip_smoke.py --rehearse [--chips 4]
                                      # the same code on the CPU at tiny
                                      # size (Pallas in interpret mode;
                                      # --chips 4 on 4 virtual devices)

One process drives the chip(s): nothing here starts a child. Each phase
prints one JSON line; the LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it. Any phase that raises or fails a
check prints ``"ok": false`` there and the exit code is 1. Without
``--rehearse`` a platform other than ``tpu`` fails in the first phase.

Model: ``LlamaConfig.llama2_7b`` widths (hidden 4096, 32 heads x 128,
FFN 11008, vocab 32000, bf16), never cut. DEPTH is cut to what one 16 GB
chip holds, and every phase prints its cut: training at 2 layers, b4 x
s1024, AdamW with fp32 masters (benchmarks/llama7b_geometry.py's
sizing, ~9.3 GB of state); serving at 8 layers (3.8 GB of weights, held
twice while ``generate()``'s stacked copy lives beside the Layer's own)
with a 2.1 GB KV pool for 8 lanes x 2048 tokens. Weights are random,
from a fixed seed. Every timing printed is a smoke reading, not a
benchmark.

The compile cache follows utils/xla_cache.py (``JAX_COMPILATION_CACHE_DIR``
if set, else ``<repo>/.jax_cache``). ``PT_EXEC_CACHE`` is left OFF: its
disk key hashes file mtimes, which change on every copy of the tree, so
it could never hit in a fresh machine.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

REAL = dict(
    model=dict(),  # llama2_7b's own widths
    train_layers=2, batch=4, seq=1024,
    serve_layers=8, max_pos=2048,
    # (prompt_len, new_tokens) — few distinct shapes, because
    # generate() compiles one program per shape
    shapes=((64, 32), (192, 48), (512, 64)), shared=128, motif=8,
    row_bucket=640, state_drop_bytes=1 << 30,
)
TINY = dict(
    model=dict(vocab_size=512, hidden_size=64, intermediate_size=128,
               num_attention_heads=4),
    train_layers=2, batch=2, seq=64,
    serve_layers=2, max_pos=256,
    shapes=((24, 8), (48, 12), (96, 16)), shared=32, motif=4,
    row_bucket=128, state_drop_bytes=1 << 30,
)

# A batched program may flip a greedy near-tie that a per-request one
# does not (bf16: 8 significant bits, so one ulp of a logit x is at most
# 2^-7 |x|). A differing token is accepted only when, in BOTH logit rows
# recomputed for that position, both candidates sit within 4 ulps of the
# row's largest logit.
TIE_TOL = 2.0 ** -5
# The same three AdamW steps on two layouts reduce in different orders.
# Stated bound on the (fp32) loss: one bf16 unit roundoff, absolute, on
# a loss of ~10; the v5e run showed 2e-5.
LOSS_TOL = 2.0 ** -8


def emit(obj):
    print(json.dumps(obj), flush=True)


class Checks:
    """Collects a phase's failed checks so its line can name them all."""

    def __init__(self):
        self.failed = []

    def need(self, cond, what):
        if not cond:
            self.failed.append(what)
        return bool(cond)


class JaxEvents:
    """Counts XLA backend compiles and persistent-cache hits/misses from
    JAX's own monitoring events (every jit in the process, the engine's
    AOT programs and generate() alike)."""

    def __init__(self):
        import jax.monitoring as jm

        self.counts = {"backend_compiles": 0, "cache_hits": 0,
                       "cache_misses": 0}
        jm.register_event_listener(self._on_event)
        jm.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.counts["cache_misses"] += 1

    def _on_duration(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.counts["backend_compiles"] += 1

    def __getitem__(self, key):
        return self.counts[key]


# -- phase 1: device ----------------------------------------------------------

def phase_device(args):
    import jax
    import jaxlib

    from paddle_tpu.utils.xla_cache import enable_compilation_cache

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a version string, never a gate
        libtpu = "unknown"
    line = {"phase": "device", **device,
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu, "rehearsal": bool(args.rehearse),
            "compile_cache_dir": enable_compilation_cache(),
            "exec_cache": os.environ.get("PT_EXEC_CACHE") or "off"}
    c = Checks()
    if not args.rehearse:
        c.need(device["platform"] == "tpu",
               f"platform is {device['platform']!r}, not 'tpu', and no "
               f"--rehearse was asked for")
    c.need(device["count"] >= args.chips,
           f"{args.chips} device(s) needed, {device['count']} visible")
    line["ok"] = not c.failed
    if c.failed:
        line["failed"] = c.failed
    emit(line)
    return device, not c.failed


# -- model + requests ---------------------------------------------------------

def build_model(size, layers, seed, **cfg_kw):
    """The bench's construction (bench.build_headline_trainstep) at
    Llama-2-7B widths: fp32 init from the seed, cast to bf16."""
    import paddle_tpu as pt
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    kw = dict(size["model"])
    kw.update(num_hidden_layers=layers, dtype="bfloat16",
              max_position_embeddings=size["max_pos"])
    kw.update(cfg_kw)
    cfg = LlamaConfig.llama2_7b(**kw)
    pt.seed(seed)
    model = LlamaForCausalLM(cfg)
    for p in model.parameters():
        p._data = p._data.astype("bfloat16")
    return model


def describe(cfg):
    return {"hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
            "head_dim": cfg.hidden_size // cfg.num_attention_heads,
            "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size,
            "layers": cfg.num_hidden_layers, "layers_cut_from": 32,
            "dtype": cfg.dtype}


def mem_stat(key):
    """``device.memory_stats()[key]`` of device 0, or "not reported"
    (the CPU backend reports none)."""
    import jax

    return (jax.devices()[0].memory_stats() or {}).get(key, "not reported")


# -- phase 2: train -----------------------------------------------------------

def make_trainstep(model):
    import paddle_tpu as pt
    from paddle_tpu.jit.train_step import TrainStep

    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True)
    return TrainStep(model, opt, lambda m, i, l: m(i, l), donate=True)


def train_batch(size, vocab, seed):
    import numpy as np

    import paddle_tpu as pt

    rng = np.random.RandomState(seed)
    shape = (size["batch"], size["seq"])
    return (pt.to_tensor(rng.randint(0, vocab, shape)),
            pt.to_tensor(rng.randint(0, vocab, shape)))


def phase_train(args, size, events):
    import jax

    from paddle_tpu import monitor
    from paddle_tpu.utils.timing import device_sync

    c = Checks()
    model = build_model(size, size["train_layers"], seed=args.seed,
                        use_parallel_cross_entropy=False)
    step = make_trainstep(model)
    ids, labels = train_batch(size, model.config.vocab_size, args.seed)

    def counters():
        return monitor.snapshot()["counters"]

    t0 = time.perf_counter()
    loss = step(ids, labels)  # warm-up: trace + compile + first run
    jax.block_until_ready(loss._data)
    compile_s = time.perf_counter() - t0
    base = counters()

    losses, bur_ms, sync_ms = [], [], []
    for _ in range(3):  # the three checked steps, block_until_ready
        t0 = time.perf_counter()
        loss = step(ids, labels)
        jax.block_until_ready(loss._data)
        bur_ms.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(loss._data))
    for _ in range(3):  # ROADMAP A10: the same step, host-fetch fence
        t0 = time.perf_counter()
        loss = step(ids, labels)
        device_sync(loss._data)
        sync_ms.append((time.perf_counter() - t0) * 1e3)
    end = counters()

    def delta(name):
        return end.get(name, 0) - base.get(name, 0)

    c.need(all(x == x and abs(x) != float("inf") for x in losses),
           f"non-finite loss {losses}")
    c.need(losses[2] < losses[0],
           f"loss did not fall over 3 steps on a repeated batch: {losses}")
    c.need(delta("jit/retraces") == 0,
           f"{delta('jit/retraces')} retrace(s) after warm-up")
    engaged = end.get("pallas/engaged/flash", 0)
    fallback = end.get("pallas/fallback/flash", 0)
    c.need(engaged > 0 and fallback == 0,
           f"flash kernel engaged {engaged}x, fell back to the composite "
           f"{fallback}x (a fallback at causal s{size['seq']} is a "
           f"failure here)")
    emit({"phase": "train", "ok": not c.failed, "failed": c.failed,
          "model": describe(model.config), "batch": size["batch"],
          "seq": size["seq"], "optimizer": "AdamW, fp32 masters",
          "losses": [round(x, 4) for x in losses],
          "flash": {"engaged": engaged, "fallback": fallback},
          "retraces_after_warmup": delta("jit/retraces"),
          "compile_s": round(compile_s, 1),
          "step_ms_block_until_ready": [round(x, 2) for x in bur_ms],
          "step_ms_device_sync": [round(x, 2) for x in sync_ms],
          "peak_bytes_in_use": mem_stat("peak_bytes_in_use"),
          "backend_compiles": events["backend_compiles"],
          "note": "smoke, not a benchmark"})
    return not c.failed


def drop_state(size):
    """The train phase leaves ~9 GB on the device. Everything that held
    it was local to phase_train; what is left are caches: the exec
    cache's in-memory tier (only when PT_EXEC_CACHE is on), the
    generation-params memo, JAX's own jit caches."""
    import jax

    from paddle_tpu.jit import exec_cache

    exec_cache.clear()
    jax.clear_caches()
    gc.collect()
    left = mem_stat("bytes_in_use")
    ok = not isinstance(left, int) or left < size["state_drop_bytes"]
    emit({"phase": "drop_train_state", "ok": ok, "bytes_in_use": left})
    return ok


# -- phase 3: serve -----------------------------------------------------------

def make_requests(size, vocab, seed):
    """Eight (prompt, new_tokens): two open with the same ``shared``
    tokens (prefix sharing), one is a tiled motif (something for the
    n-gram drafter to look up), lengths span the shapes above."""
    import numpy as np

    rng = np.random.RandomState(seed + 1)

    def rand(n):
        return rng.randint(0, vocab, (n,)).astype(np.int32)

    (n0, k0), (n1, k1), (n2, k2) = size["shapes"]
    shared = rand(size["shared"])
    motif = rand(size["motif"])
    return [
        (rand(n0), k0),
        (np.concatenate([shared, rand(n1 - shared.size)]), k1),
        (np.concatenate([shared, rand(n1 - shared.size)]), k1),
        (np.tile(motif, n2 // motif.size), k2),
        (rand(n2), k2),
        (rand(n0), k0),
        (rand(n1), k1),
        (rand(n2), k2),
    ]


def run_engine(model, requests, events, **cfg_kw):
    """warmup(), then the requests submitted over a few step()s, then
    drain. Returns (tokens by request index, facts, failed checks)."""
    from paddle_tpu import monitor
    from paddle_tpu.serving import ServingConfig, ServingEngine

    c = Checks()

    def compiles():
        return monitor.snapshot()["counters"].get("jit/compiles", 0)

    n0 = compiles()
    eng = ServingEngine(model, ServingConfig(**cfg_kw))
    t0 = time.perf_counter()
    eng.warmup()
    warm_s = time.perf_counter() - t0
    n_programs = compiles() - n0
    b0 = events["backend_compiles"]
    handles = []
    t0 = time.perf_counter()
    for i, (prompt, new) in enumerate(requests):
        handles.append(eng.submit(prompt, max_new_tokens=new,
                                  request_id=f"r{i}"))
        if i % 3 == 2:  # arrivals spread over a few engine steps
            eng.step()
    out = eng.run()
    wall_s = time.perf_counter() - t0
    st = eng.stats()
    c.need(len(out) == len(requests)
           and all(h.finished for h in handles),
           f"{len(out)}/{len(requests)} requests finished")
    c.need(n_programs == 3 and compiles() - n0 == 3,
           f"{compiles() - n0} programs compiled for this engine, not 3")
    c.need(events["backend_compiles"] == b0,
           f"{events['backend_compiles'] - b0} XLA compile(s) while "
           f"serving (after warmup)")
    c.need(st["prefix_hit_tokens"] > 0, "zero prefix-hit tokens")
    c.need(st["spec_accepted_tokens"] > 0, "zero accepted draft tokens")
    facts = {
        "read_path": "row gather",
        "kv_int8": st["kv_int8"], "programs_compiled": n_programs,
        "compiles_while_serving": events["backend_compiles"] - b0,
        "warmup_s": round(warm_s, 1), "serve_wall_s": round(wall_s, 2),
        "lanes": st["lanes"], "max_seq_len": st["max_seq_len"],
        "kv_pool_bytes": st["kv_pool_bytes"],
        "prefix_hit_tokens": st["prefix_hit_tokens"],
        "spec_proposed_tokens": st["spec_proposed_tokens"],
        "spec_accepted_tokens": st["spec_accepted_tokens"],
        "decode_steps": st["decode_steps"],
        "verify_steps": st["verify_steps"],
        "prefill_chunks": st["prefill_chunks"],
    }
    tokens = [out.get(f"r{i}") for i in range(len(requests))]
    return tokens, facts, c.failed


def reference(model, requests, kv_int8):
    """Per-request ``models.generation.generate()`` on the same device."""
    import numpy as np

    from paddle_tpu.models import generate

    return [np.asarray(generate(model, prompt[None, :], max_new_tokens=new,
                                kv_int8=kv_int8).numpy())[0]
            for prompt, new in requests]


class LogitRows:
    """The two logit rows for one position, recomputed on the device
    from the context both token streams share: one through generate()'s
    forward (dense cache, the context left-padded into one bucket so
    every position shares one compile), one through the engine's pool
    forward (block pool, right-padded) — `_prefill_chunk` with the
    argmax taken off."""

    def __init__(self, model, bucket, kv_int8):
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models import generation as G
        from paddle_tpu.serving.families import dense_gqa as E

        self.bucket = bucket
        cfg = G._GenCfg(model.config)
        self.params = G._collect_params(model)
        layers = self.params["ln1"].shape[0]
        nkv = cfg.num_key_value_heads
        d = cfg.hidden_size // cfg.num_attention_heads
        dt = jnp.dtype(cfg.dtype)
        block = 16
        nblk = bucket // block
        # the bucket's blocks 1..nblk as the engine's read operand: live
        # rows of the family's width, and each position's write block
        from paddle_tpu.serving.engine import fit_rows, pack_rows

        w, tile, cap = fit_rows((E.ROW_BLOCKS, E.PREFILL_TILE), 1, nblk)
        self.read = tuple(jnp.asarray(a) for a in pack_rows(
            [(0, list(range(1, nblk + 1)), 0, bucket)], 1, bucket, block,
            w, cap)[:2])

        @jax.jit
        def ref_row(params, ids, n):
            cache = jnp.zeros((layers, 1, bucket, nkv, d), dt)
            logits, _, _ = G._forward(
                params, ids, cache, cache, jnp.asarray(bucket), cfg,
                key_pad=jnp.reshape(bucket - n, (1,)), kv_int8=kv_int8)
            return logits[0]

        @jax.jit
        def eng_row(params, ids, n):
            # as DenseGQAFamily.make_pools lays them out: the bf16 pool
            # merges the heads into its last axis
            pool = jnp.zeros((layers, nblk + 1, block, nkv, d), jnp.int8) \
                if kv_int8 else jnp.zeros(
                    (layers, nblk + 1, block, nkv * d), dt)
            scale = (jnp.zeros((layers, nblk + 1, block, nkv),
                               jnp.float32) if kv_int8 else None)
            pos = jnp.arange(bucket, dtype=jnp.int32)[None, :]
            x, *_ = E._pool_forward(params, pool, pool, scale, scale,
                                    self.read, ids, pos,
                                    jnp.reshape(n, (1,)), cfg, tile=tile)
            x = G._rms(x, params["norm"], cfg.rms_norm_eps)
            h = jax.lax.dynamic_index_in_dim(x, n - 1, axis=1,
                                             keepdims=False)
            return G._mm(h, params["lm_head"]).astype(jnp.float32)[0]

        self._ref, self._eng = ref_row, eng_row

    def __call__(self, context):
        import jax.numpy as jnp
        import numpy as np

        n = int(context.size)
        left = np.zeros((1, self.bucket), np.int32)
        left[0, self.bucket - n:] = context
        right = np.zeros((1, self.bucket), np.int32)
        right[0, :n] = context
        return (np.asarray(self._ref(self.params, jnp.asarray(left),
                                     jnp.int32(n))),
                np.asarray(self._eng(self.params, jnp.asarray(right),
                                     jnp.int32(n))))


def hold_to(name, got, want, requests, rows):
    """Token identity, or — at the first differing position — a near-tie
    within TIE_TOL in both recomputed logit rows. Never skipped."""
    import numpy as np

    c = Checks()
    identical, ties = 0, []
    for i, ((prompt, _new), a, b) in enumerate(zip(requests, got, want)):
        if a is None or b is None or a.shape != b.shape:
            c.need(False, f"{name} r{i}: missing or mis-shaped output")
            continue
        diff = np.nonzero(a != b)[0]
        if not diff.size:
            identical += 1
            continue
        p = int(diff[0])
        ref_row, eng_row = rows(np.concatenate([prompt, b[:p]]))
        gaps = []
        for row in (ref_row, eng_row):
            top = float(row.max())
            tol = TIE_TOL * float(np.abs(row).max())
            gap = max(top - float(row[a[p]]), top - float(row[b[p]]))
            gaps.append(round(gap, 5))
            c.need(gap <= tol,
                   f"{name} r{i} differs at position {p} "
                   f"({int(a[p])} vs {int(b[p])}) and it is no near-tie: "
                   f"gap {gap:.4f} > tol {tol:.4f}")
        ties.append({"request": i, "position": p,
                     "tokens": [int(a[p]), int(b[p])], "gaps": gaps,
                     "tol": round(TIE_TOL * float(np.abs(ref_row).max()),
                                  5)})
    return {"identical": identical, "of": len(requests),
            "near_ties": ties}, c.failed


def phase_serve(args, size, events):
    c = Checks()
    model = build_model(size, size["serve_layers"], seed=args.seed + 7,
                        use_parallel_cross_entropy=False)
    model.eval()
    requests = make_requests(size, model.config.vocab_size, args.seed)
    rows = {kv: LogitRows(model, size["row_bucket"], kv_int8=kv)
            for kv in (False, True)}
    line = {"phase": "serve", "model": describe(model.config),
            "requests": [(int(p.size), n) for p, n in requests],
            "tie_tolerance": f"{TIE_TOL} x max|logit| (4 bf16 ulps)"}

    ref = reference(model, requests, kv_int8=False)
    default, facts, failed = run_engine(model, requests, events)
    cmp_, failed2 = hold_to("default vs generate()", default, ref,
                            requests, rows[False])
    line["default"] = {**facts, "held_to": "generate()", **cmp_}
    c.failed += failed + failed2

    ref8 = reference(model, requests, kv_int8=True)
    int8, facts, failed = run_engine(model, requests, events,
                                     kv_int8=True)
    cmp_, failed2 = hold_to("kv_int8 vs generate(kv_int8=True)", int8,
                            ref8, requests, rows[True])
    line["kv_int8"] = {**facts, "held_to": "generate(kv_int8=True)",
                       **cmp_}
    c.failed += failed + failed2

    line.update(ok=not c.failed, failed=c.failed,
                peak_bytes_in_use=mem_stat("peak_bytes_in_use"),
                note="smoke, not a benchmark")
    emit(line)
    return not c.failed


# -- phase 4: four chips ------------------------------------------------------

def sharded_facts(model, mesh_devices):
    """Where the mp-sharded parameters really sit."""
    c = Checks()
    n_sharded = 0
    per_device = {}
    for name, p in model.named_parameters():
        a = p._data
        spec = getattr(a.sharding, "spec", ())
        axes = [ax for part in spec if part is not None
                for ax in (part if isinstance(part, tuple) else (part,))]
        for s in a.addressable_shards:
            per_device[s.device.id] = (per_device.get(s.device.id, 0)
                                       + s.data.nbytes)
        if "mp" not in axes:
            continue
        n_sharded += 1
        shards = a.addressable_shards
        c.need(len({s.device.id for s in shards}) == len(mesh_devices),
               f"{name}: shards on {len({s.device.id for s in shards})} "
               f"devices, not {len(mesh_devices)}")
        # mp=2 halves it; dp=2 replicates the half
        c.need(all(s.data.nbytes * 2 == a.nbytes for s in shards),
               f"{name}: a shard is not half of the array")
    c.need(n_sharded > 0, "no parameter is sharded over 'mp'")
    sizes = sorted(per_device.values())
    c.need(len(sizes) == len(mesh_devices)
           and sizes[-1] <= 1.01 * sizes[0],
           f"parameter bytes per device are uneven: {per_device}")
    return {"mp_sharded_params": n_sharded,
            "param_bytes_per_device": per_device}, c.failed


def three_steps(model, ids, labels):
    import jax

    step = make_trainstep(model)
    losses = []
    t0 = time.perf_counter()
    for _ in range(3):
        loss = step(ids, labels)
        jax.block_until_ready(loss._data)
        losses.append(float(loss._data))
    return step, losses, time.perf_counter() - t0


def phase_multichip(args, size):
    import jax

    from paddle_tpu.analysis import program_audit
    from paddle_tpu.autoshard.hlo_costs import parse_collectives
    from paddle_tpu.distributed import env as env_mod
    from paddle_tpu.distributed import fleet

    c = Checks()
    devices = jax.devices()[:4]
    kw = dict(sequence_parallel=True, use_parallel_cross_entropy=True)

    # the path dryrun_multichip drives on virtual devices: fleet.init
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    model = build_model(size, size["train_layers"], seed=args.seed, **kw)
    ids, labels = train_batch(size, model.config.vocab_size, args.seed)
    placement, failed = sharded_facts(model, devices)
    c.failed += failed
    step, sharded, wall4 = three_steps(model, ids, labels)
    facts = program_audit.audit_train_step(step, ids, labels)["facts"]
    entry, _, _ = step._get_compiled((ids, labels))
    colls = parse_collectives(entry.compiled.as_text(), facts["degrees"])
    mp_ops = sorted({x["op"] for x in colls
                     if "mp" in x["axis"].split("+")})
    c.need(facts["dp_collectives"] > 0,
           "dp=2 but the step has no collective over 'dp'")
    c.need(any(op in mp_ops for op in
               ("all-reduce", "all-gather", "reduce-scatter")),
           f"no mp all-reduce/all-gather/reduce-scatter in the compiled "
           f"HLO (mp ops: {mp_ops})")
    del step, model, entry
    env_mod.reset_env()
    jax.clear_caches()
    gc.collect()

    # the same three steps, same seed and batch, on one device
    env_mod.init_mesh(dp=1, devices=jax.devices()[:1])
    model = build_model(size, size["train_layers"], seed=args.seed, **kw)
    ids, labels = train_batch(size, model.config.vocab_size, args.seed)
    step, single, wall1 = three_steps(model, ids, labels)
    del step, model
    env_mod.reset_env()

    worst = max(abs(a - b) for a, b in zip(sharded, single))
    c.need(worst <= LOSS_TOL,
           f"sharded and single-device losses differ by {worst:.4f} > "
           f"{LOSS_TOL}: {sharded} vs {single}")
    emit({"phase": "multichip", "ok": not c.failed, "failed": c.failed,
          "mesh": {"dp": 2, "mp": 2, "pp": 1}, "sequence_parallel": True,
          "parallel_cross_entropy": True,
          "batch": size["batch"], "seq": size["seq"],
          "losses_sharded": [round(x, 4) for x in sharded],
          "losses_single": [round(x, 4) for x in single],
          "max_loss_diff": round(worst, 5), "loss_tolerance": LOSS_TOL,
          **placement,
          "dp_collectives": facts["dp_collectives"],
          "mp_collective_ops": mp_ops, "collectives": facts["collectives"],
          "wall_s_3_steps_incl_compile": {"sharded": round(wall4, 1),
                                          "single": round(wall1, 1)},
          "note": "smoke, not a benchmark"})
    return not c.failed


# -- main ---------------------------------------------------------------------

def run(args):
    """Phases in order; returns (device, ok)."""
    if args.rehearse:
        # asked for, never inferred: the CPU, and as many virtual
        # devices as the run needs chips
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(
            f"--xla_force_host_platform_device_count={args.chips}")
        os.environ["XLA_FLAGS"] = " ".join(flags)
    device, ok = phase_device(args)
    if not ok:
        return device, False

    from paddle_tpu import monitor

    monitor.enable()  # retrace / compile / engaged-fallback counters
    size = TINY if args.rehearse else REAL
    if args.rehearse:
        # the CPU has no Mosaic: the flash kernel runs in interpret mode
        from paddle_tpu.ops.pallas import flash_attention

        flash_attention.register(platform="cpu", interpret=True)
    events = JaxEvents()
    if args.chips == 4:
        ok = phase_multichip(args, size)
    else:
        ok = phase_train(args, size, events)
        ok = drop_state(size) and ok
        ok = phase_serve(args, size, events) and ok
    emit({"phase": "compile_cache", "hits": events["cache_hits"],
          "misses": events["cache_misses"],
          "backend_compiles": events["backend_compiles"]})
    return device, ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run ONLY the sharded-vs-single training "
                         "comparison, on four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, Pallas in interpret mode")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device, ok = None, False
    t0 = time.perf_counter()
    try:
        device, ok = run(args)
    except BaseException:  # noqa: BLE001 — reported below, exit code 1
        traceback.print_exc()
        ok = False
    print(f"chip_smoke: {time.perf_counter() - t0:.0f}s", file=sys.stderr,
          flush=True)
    emit({"ok": bool(ok), "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
