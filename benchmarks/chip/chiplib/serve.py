"""A serving cell: the program's ``ServingEngine`` under seeded traffic,
open loop (requests due at fixed times, timed from when they were due)
or closed backlog (the queue never drains), and the served tokens held to
the plain reference once the window has closed."""
from __future__ import annotations

import gc
import itertools
import time

import numpy as np

from . import common, modelbuild
from . import trace as trace_mod
from . import traffic as traffic_mod


def build_engine(arch, cfg, seed, hooks):
    from paddle_tpu.serving import ServingConfig, ServingEngine

    s = cfg["serve"]
    layers = cfg["num_hidden_layers"]["serve"]
    model, specs, keys, _ = modelbuild.build(
        arch, cfg, layers, s["max_seq_len"], seed,
        **cfg.get("model_flags", {}))
    model.eval()
    # only what a deployer must choose is pinned; chunk, paged, spec,
    # spec_k and prefix_cache stay the program's defaults
    engine = ServingEngine(model, ServingConfig(
        max_lanes=s["max_lanes"], max_seq_len=s["max_seq_len"],
        num_blocks=s.get("num_blocks")))
    engine = hooks.get("wrap_engine", lambda e: e)(engine)
    return model, engine, specs, keys


def _drain(engine):
    while engine.has_work():
        engine.step()
    engine.pop_finished()


def warm(engine, fills, vocab, seed, lanes):
    """Every program runs once (prefill, decode, verify), then the
    set-up fills go through the engine so their blocks are cached."""
    rng = np.random.default_rng([int(seed), 0x3A23])
    motif = rng.integers(0, vocab, 8, dtype=np.int32)
    for i in range(min(lanes, 4)):
        # a tiled motif gives the drafter something to propose, so the
        # verify program runs too
        engine.submit(np.tile(motif, 6 + i), max_new_tokens=12,
                      request_id=f"warm{i}")
    _drain(engine)
    for i, prompt in enumerate(fills):
        engine.submit(prompt, max_new_tokens=1, request_id=f"fill{i}")
        _drain(engine)


class Recorder:
    """A traced run: the benchmark's own span around ``engine.step`` with
    what the round did, and the profiler on from ``start_s`` seconds into
    the window to its end. The profiler is stopped only after the window:
    stopping it writes the trace and stalls the host for seconds, which
    inside an open-loop window reads as a queue (PR 23: 4 s of generator
    lateness). A whole window of trace is too large to bring back."""

    def __init__(self, engine, traced_dir, start_s):
        self.engine, self.rounds = engine, []
        self.dir, self.start_s = traced_dir, start_s
        self.t_first = None
        self.tracing = False

    def _profiler(self, now):
        import jax

        if self.t_first is None:
            self.t_first = now
        if not self.tracing and now - self.t_first >= self.start_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.tracing = True

    def stop(self):
        import jax

        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing = False

    def step(self):
        import jax

        e = self.engine
        self._profiler(time.perf_counter())
        before = dict(e.counters)
        live = sum(r.pool_len for r in e.scheduler.running())
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/engine_step"):
            e.step()
        dur = time.perf_counter() - t
        d = {k: e.counters[k] - before[k] for k in
             ("prefill_chunks", "decode_steps", "verify_steps",
              "decoded_tokens")}
        self.rounds.append({"ms": dur * 1e3, "live_kv_tokens": live,
                            "lanes": len(e.scheduler.running()),
                            "traced": self.tracing, **d})


def _emitted(handles):
    return sum(len(h.output) for h in handles)


class Watch:
    """What the window's loop reads after every step, at the cost of a
    few dictionary lengths: the longest step (a stall of the host or the
    chip shows here and nowhere else in an untraced run) and how full the
    K/V pool was: blocks that running requests hold (live), and those
    plus the unreferenced blocks the prefix index still keeps (held)."""

    def __init__(self, engine):
        self.pool = engine.scheduler.pool
        self.longest_s = 0.0
        self.steps = self.live_sum = self.live_peak = self.held_peak = 0

    def step(self, dur):
        if dur > self.longest_s:
            self.longest_s = dur
        live = self.pool.used_count
        held = live + self.pool.cold_count
        self.steps += 1
        self.live_sum += live
        if live > self.live_peak:
            self.live_peak = live
        if held > self.held_peak:
            self.held_peak = held

    def facts(self):
        cap = self.pool.capacity
        return {"longest_step_ms": self.longest_s * 1e3,
                "pool_blocks": cap,
                "pool_live_mean_pct": 100.0 * self.live_sum
                / max(1, self.steps) / cap,
                "pool_live_peak_pct": 100.0 * self.live_peak / cap,
                "pool_held_peak_pct": 100.0 * self.held_peak / cap}


def open_loop(engine, reqs, stepper, watch):
    """Requests submitted when due; runs until all have finished."""
    handles, late = [], []
    n, i = len(reqs), 0
    waiting = engine.scheduler.waiting
    max_waiting = 0
    t0 = time.perf_counter()
    while i < n or engine.has_work():
        max_waiting = max(max_waiting, len(waiting))
        now = time.perf_counter() - t0
        while i < n and reqs[i]["due"] <= now:
            handles.append(engine.submit(
                reqs[i]["prompt"], max_new_tokens=reqs[i]["out"],
                request_id=f"w{i}"))
            late.append((now - reqs[i]["due"]) * 1e3)
            i += 1
        if engine.has_work():
            t = time.perf_counter()
            stepper()
            watch.step(time.perf_counter() - t)
        else:
            time.sleep(min(max(reqs[i]["due"] - now, 0.0), 0.002))
    t_end = time.perf_counter()
    engine.pop_finished()
    return t0, t_end, handles, late, max_waiting


def backlog(engine, reqs, seconds, ramp_s, min_waiting, stepper, fresh,
            watch):
    """A queue that never drains: at least ``min_waiting`` requests wait
    at every step. ``ramp_s`` seconds are served before the window opens,
    so it starts with every lane busy and out of step."""
    handles = []
    stream = itertools.cycle(range(len(reqs)))
    serial = itertools.count()

    def top_up():
        while len(engine.scheduler.waiting) < min_waiting:
            r, k = reqs[next(stream)], next(serial)
            # the shapes repeat cycle after cycle, the token ids never
            # do: nothing is shared, so nothing may hit the prefix cache
            handles.append(engine.submit(
                fresh(r["prompt_len"], k), max_new_tokens=r["out"],
                request_id=f"b{k}"))

    t_ramp = time.perf_counter()
    while time.perf_counter() - t_ramp < ramp_s:
        top_up()
        engine.step()
    engine.pop_finished()
    done_before = {id(h) for h in handles if h.finished}
    base = _emitted(handles)
    counters0 = dict(engine.counters)
    t0 = t_end = time.perf_counter()
    while True:
        top_up()
        t_step = t_end
        stepper()
        t_end = time.perf_counter()
        watch.step(t_end - t_step)
        if t_end - t0 >= seconds:
            break
        if len(engine._finished) > 64:
            engine.pop_finished()
    tokens = _emitted(handles) - base
    engine.pop_finished()
    in_window = [h for h in handles if id(h) not in done_before]
    return t0, t_end, in_window, tokens, counters0


def request_facts(handles, reqs_by_id, t0):
    out = []
    for h in handles:
        r = reqs_by_id.get(h.request_id, {})
        due = t0 + r["due"] if r.get("due") is not None else h.t_submit
        n = len(h.output)
        fact = {"id": h.request_id, "cls": r.get("cls"),
                "prompt_len": int(h.prompt.size), "out": n,
                "finished": bool(h.finished),
                "planned_cached": r.get("cached_len"),
                "cached": int(h.ttft_cached_tokens or 0),
                "queue_ms": h.queue_ms, "prefill_ms": h.prefill_ms,
                "preemptions": h.preemptions,
                "spec_rounds": h.spec_rounds,
                "accepted": h.accepted_tokens}
        if h.t_first is not None:
            fact["ttft_ms"] = (h.t_first - due) * 1e3
        if h.finished and n > 1:
            fact["tpot_ms"] = (h.t_done - h.t_first) * 1e3 / (n - 1)
        out.append(fact)
    return out


# -- the comparison ------------------------------------------------------------

def pick_sample(handles, seed, k):
    """k finished requests drawn from the seed, the longest among them."""
    done = [h for h in handles if h.finished and len(h.output) > 0]
    if not done:
        return []
    done.sort(key=lambda h: h.request_id)
    longest = max(done, key=lambda h: (h.prompt.size + len(h.output),
                                       h.request_id))
    rest = [h for h in done if h is not longest]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    pick = [rest[i] for i in rng.permutation(len(rest))[: max(0, k - 1)]]
    return [longest] + pick


def _bucket(n, step, cap):
    return min(cap, -(-n // step) * step)


def reference_gaps(ref, cfg, specs, keys, samples, control=False):
    """For each sampled request: the reference's logits at every served
    position, from ONE float32 forward over prompt + served tokens, and
    the gap by which the served token's logit lies below the reference's
    best. The stack runs layer by layer, so one layer's float32 weights
    are alive at a time; each layer's leaves are the ones its
    architecture's specs list for it, and the jitted walk compiles once
    per distinct set of them (two kinds of layer: two programs).
    ``control``: the same positions through the lower-precision
    reference, and the gap of ITS first choice."""
    import jax
    import jax.numpy as jnp

    m = cfg["model"]
    cap = cfg["serve"]["max_seq_len"]
    names_of = {}
    for i, (li, name, _, _) in enumerate(specs):
        names_of.setdefault(li, {})[name] = i

    def leaves(li):
        return {name: modelbuild.reference_leaf(cfg, specs[i], keys[i])
                for name, i in names_of[li].items()}

    seqs = []
    for prompt, served in samples:
        full = np.concatenate([prompt, served])[:-1]  # last token unfed
        T = _bucket(full.size, 512, cap)
        ids = np.zeros(T, np.int32)
        ids[: full.size] = full
        seqs.append({"ids": jnp.asarray(ids), "T": T,
                     "first": prompt.size - 1, "served": served})
    with jax.default_matmul_precision("highest"):
        top = leaves(-1)
        chains = [False, True] if control else [False]
        xs = {q: [top["embed"][s["ids"]] for s in seqs] for q in chains}
        fwd = {q: jax.jit(lambda x, lw, li, q=q: ref.layer_forward(
            x, lw, li=li, m=m, quant=q)) for q in chains}
        for li in sorted(k for k in names_of if k >= 0):
            lw = leaves(li)
            for q in chains:
                xs[q] = [fwd[q](x, lw, jnp.int32(li)) for x in xs[q]]
            del lw

        def gaps_at(x, xq, rows, served, top):
            """At K positions: how far the served token's logit lies
            below the reference's best, and how far the control's own
            first choice does. Shapes are the buckets T and K alone, so
            a new seed compiles nothing new."""
            logits = ref.head_logits(x[rows], top, m=m, quant=False)
            best = jnp.max(logits, -1)

            def below(tok):
                return best - jnp.take_along_axis(logits, tok[:, None],
                                                  -1)[:, 0]

            if xq is None:
                return below(served), None
            lq = ref.head_logits(xq[rows], top, m=m, quant=True)
            return below(served), below(jnp.argmax(lq, -1))

        gaps_at = jax.jit(gaps_at)
        out = []
        for si, s in enumerate(seqs):
            n = len(s["served"])
            K = _bucket(n, 256, 4096)
            rows = np.minimum(s["first"] + np.arange(K), s["T"] - 1)
            served = np.zeros(K, np.int32)
            served[:n] = s["served"]
            gap, cgap = gaps_at(xs[False][si],
                                xs[True][si] if control else None,
                                jnp.asarray(rows), jnp.asarray(served), top)
            rec = {"gaps": np.asarray(gap)[:n], "n": n}
            if control:
                rec["control_gaps"] = np.asarray(cgap)[:n]
            out.append(rec)
    return out


def run(ctx):
    import jax

    cfg, mix, hooks = ctx["config"], ctx["traffic"], ctx["hooks"]
    devices, events = ctx["devices"], ctx["events"]
    items = ctx["setup_items"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    vocab = cfg["model"]["vocab_size"]
    lanes = cfg["serve"]["max_lanes"]

    t = time.perf_counter()
    from paddle_tpu.distributed import env as env_mod

    env_mod.init_mesh(dp=1, devices=list(devices[:1]))
    model, engine, specs, keys = build_engine(ctx["arch"], cfg, seed, hooks)
    jax.block_until_ready(jax.tree_util.tree_leaves(engine._params))  # ptlint: disable=PTL002
    items["model_weights_engine_s"] = time.perf_counter() - t
    items["bytes_in_use_after_engine"] = common.bytes_in_use(devices)
    items["bytes_peak_after_engine"] = common.memory_peak(devices)
    t = time.perf_counter()
    reqs, fills = traffic_mod.schedule(mix, seed, seconds, vocab)
    items["traffic_s"] = time.perf_counter() - t
    t = time.perf_counter()
    engine.warmup()
    items["engine_programs_s"] = time.perf_counter() - t
    t = time.perf_counter()
    warm(engine, fills, vocab, seed, lanes)
    items["warm_requests_and_session_fill_s"] = time.perf_counter() - t
    items["session_fill_tokens"] = int(sum(f.size for f in fills))

    rec = None
    if ctx["trace"]:
        import os

        traced_dir = common.trace_dir(ctx["workload"], seed)
        os.makedirs(traced_dir, exist_ok=True)
        rec = Recorder(engine, traced_dir,
                       max(0.0, seconds - mix["traced_seconds"]))
    stepper = rec.step if rec else engine.step
    gc.collect()
    gc.freeze()

    items["bytes_in_use_at_window"] = common.bytes_in_use(devices)
    compiles0 = events.compiles()
    watch = Watch(engine)
    if mix["loop"] == "backlog":
        # the ramp is served before the window opens and counts as set-up
        t_ramp = time.perf_counter()
        t0, t_end, handles, tokens, counters0 = backlog(
            engine, reqs, seconds, mix["ramp_s"],
            mix["min_waiting_per_lane"] * lanes, stepper,
            lambda n, k: traffic_mod._tokens(seed, vocab, n, 9, k), watch)
        items["ramp_s"] = t0 - t_ramp
        setup_s = t0 - ctx["t_start"]
        late, by_id = [], {}
    else:
        setup_s = time.perf_counter() - ctx["t_start"]
        counters0 = dict(engine.counters)
        t0, t_end, handles, late, max_waiting = open_loop(
            engine, reqs, stepper, watch)
        tokens = _emitted(handles)
        by_id = {f"w{i}": r for i, r in enumerate(reqs)}
    if rec:
        rec.stop()
    window_s = t_end - t0
    in_window_compiles = events.compiles() - compiles0
    counters = {k: engine.counters[k] - counters0[k] for k in counters0}
    peak = common.memory_peak(devices)
    gc.unfreeze()
    facts = request_facts(handles, by_id, t0)
    stats = engine.stats()
    unfinished = [f for f in facts if not f["finished"]]
    wrong_len = [f for f, h in zip(facts, handles)
                 if f["finished"] and f["out"] != h.max_new_tokens]
    failed = len(wrong_len) + (len(unfinished)
                               if mix["loop"] == "open" else 0)

    obs = {"job": "serve", "loop": mix["loop"], "window_s": window_s,
           "tokens_out": tokens, "requests": facts, "late_ms": late,
           "counters": counters, "rounds": rec.rounds if rec else [],
           "setup_s": setup_s, "model": cfg["model"], "lanes": lanes,
           "arch": ctx["arch"],
           "layers": cfg["num_hidden_layers"]["serve"],
           "slo": mix.get("slo"), "device_kind": ctx["device"]["kind"],
           "read_path": ("pallas " + stats["paged_family"]
                         if stats["paged_attention"] else "dense gather")}
    if ctx["trace"]:
        obs["trace"] = trace_mod.load(trace_mod.find_xplane(rec.dir))
        # how much of each round's host span the device was busy: shows
        # whether spans and device events pair up (decode_step_roofline)
        busy = trace_mod.busy_in_spans(obs["trace"], "bench/engine_step")
        traced = [r for r in rec.rounds if r["traced"]]
        n = min(len(busy), len(traced))
        share = [b / (r["ms"] / 1e3) for r, b in
                 zip(traced[len(traced) - n:], busy[len(busy) - n:])
                 if not r["prefill_chunks"]
                 and r["decode_steps"] + r["verify_steps"]]
        if share:
            common.note("round_device_share", spans=len(busy),
                        traced_rounds=len(traced), pure_rounds=len(share),
                        p10=common.quantile(share, 0.1),
                        p50=common.quantile(share, 0.5),
                        p90=common.quantile(share, 0.9))
    by_cls = {}
    for f in facts:
        if "ttft_ms" in f:
            by_cls.setdefault(f["cls"], []).append(f["ttft_ms"])
    if mix["loop"] == "open":
        common.note("ttft_by_class", classes={
            c: {"n": len(v), "p50": common.quantile(v, 0.5),
                "p90": common.quantile(v, 0.9), "max": max(v)}
            for c, v in by_cls.items()})
    if mix["loop"] == "open":
        # how the per-request gaps lie around the median that is judged:
        # a median on a sparse stretch moves with the smallest change
        tpot = [f["tpot_ms"] for f in facts if "tpot_ms" in f]
        if tpot:
            gaps = [f["out"] - 1 for f in facts if "tpot_ms" in f]
            common.note("tpot_by_request", n=len(tpot),
                        mean=sum(tpot) / len(tpot),
                        mean_over_tokens=sum(
                            t * g for t, g in zip(tpot, gaps)) / sum(gaps),
                        **{"p%d" % (100 * q): common.quantile(tpot, q)
                           for q in (0.1, 0.25, 0.4, 0.5, 0.6, 0.75, 0.9)})
        common.note("open_loop", rate_rps=mix["rate_rps"],
                    order_seed=mix.get("order_seed"),
                    last_due_s=reqs[-1]["due"],
                    drain_s=window_s - reqs[-1]["due"],
                    max_waiting=max_waiting,
                    late_ms_p99=common.quantile(late, 0.99))
    common.note("window", window_s=window_s, tokens_out=tokens,
                requests=len(facts), finished=sum(f["finished"]
                                                  for f in facts),
                counters=counters, read_path=obs["read_path"],
                kv_pool_bytes=stats["kv_pool_bytes"], **watch.facts())

    # -- the reference, with the chip to itself -------------------------------
    t = time.perf_counter()
    sample = [(np.asarray(h.prompt), np.asarray(h.output, np.int32))
              for h in pick_sample(handles, seed, mix["check_requests"])]
    del model, engine, handles, stepper, rec, watch
    env_mod.reset_env()
    common.drop_program_state()
    left = common.bytes_in_use(devices)
    ref = ctx["files"].reference(cfg["reference"])
    gaps = reference_gaps(ref, cfg, specs, keys, sample,
                          control=ctx.get("control", False))
    widest = max((float(g["gaps"].max()) for g in gaps), default=None)
    limit = ctx["limits"]["served_logit_gap"]
    numbers = [
        {"name": "served_logit_gap", "value": widest, "limit": limit,
         "ok": widest is not None and widest <= limit},
        {"name": "failed_requests", "value": failed, "limit": 0,
         "ok": failed == 0},
        {"name": "in_window_compiles", "value": in_window_compiles,
         "limit": 0, "ok": in_window_compiles == 0},
    ]
    extra = {}
    if ctx.get("control"):
        extra["control_gap"] = max(float(g["control_gaps"].max())
                                   for g in gaps)
    common.note("compare", numbers=numbers, **ctx["compared_with"],
                sampled_requests=len(sample),
                served_tokens_compared=int(sum(g["n"] for g in gaps)),
                longest_sequence=int(max((p.size + s.size
                                          for p, s in sample), default=0)),
                bytes_left_before_reference=left,
                reference_s=time.perf_counter() - t, **extra)
    obs["correct"] = all(r["ok"] for r in numbers)
    obs["attempted"], obs["failed"] = len(facts), failed
    obs["memory_peak_bytes"] = peak
    obs["compare"] = {"widest": widest, **extra}
    obs["compared"] = numbers
    return obs
