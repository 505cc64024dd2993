"""Request-level serving throughput + latency for the continuous-
batching engine (`paddle_tpu/serving`), judged against the decode HBM
roofline (`benchmarks/decode_bench.py`'s byte model).

Replays a SEEDED Poisson arrival trace (exponential inter-arrivals,
uniform prompt/output lengths — same seed, same trace, every run) and
reports aggregate ``tokens/s`` plus p50/p99 time-to-first-token and
per-token decode latency in the standard one-JSON-line format.

Run: python benchmarks/serving_bench.py [--smoke]
Prints one JSON line: {"metric": "serving_tokens_per_sec", ...} with
``tokens_per_sec`` / ``ttft_ms_p50`` / ``ttft_ms_p99`` / ``tpot_ms_*``
plus the prefix-cache readout: ``prefix_hit_rate`` (cached fraction of
all (re-)prefilled context tokens) and the cached-vs-cold TTFT A/B
(``ttft_ms_p50_cached`` / ``ttft_ms_p50_cold`` — requests whose
admission hit the prefix cache vs requests that prefilled everything).

Knobs (seeded defaults; --smoke pins the small trace explicitly):
  PT_SERVE_BENCH_REQUESTS (64; smoke 8)    trace length
  PT_SERVE_BENCH_RATE     (4.0; smoke 50)  Poisson arrival rate, req/s
  PT_SERVE_BENCH_SEED     (0)    trace seed
  PT_SERVE_BENCH_SHARED   (0)    shared-system-prompt trace mode: every
                                 prompt opens with the SAME seeded
                                 N-token prefix (hwbench's
                                 ``serving_prefix`` row sets 64), so
                                 the prefix cache turns all but the
                                 first prefill of it into hits
  PT_SERVE_BENCH_SPEC_K   (0)    speculative-decoding trace mode
                                 (hwbench's ``serving_spec`` row sets
                                 4): the engine runs with spec_k=N and
                                 every prompt becomes a seeded tiled
                                 motif (repetition-friendly — the
                                 prompt-lookup drafter's win
                                 condition), so ``accept_rate`` /
                                 ``tokens_per_decode_step`` measure a
                                 workload speculation can actually
                                 serve
  PT_SERVE_BENCH_SPEC_AB  (0)    =1 replays the same trace once more
                                 with speculation off on a fresh
                                 engine and embeds the A/B
                                 (``spec_off`` sub-object: decode
                                 rounds + tokens/s the plain decode
                                 path needed)
  PT_SERVE_BENCH_REPLICAS (0)    multi-replica router mode (hwbench's
                                 ``serving_router`` row sets 3): the
                                 trace replays through a
                                 ``RouterEngine`` over N in-process
                                 replicas instead of one engine — the
                                 line gains ``replicas`` /
                                 ``affinity_hit_rate`` /
                                 ``dispatches_per_replica`` /
                                 ``load_balance_spread`` /
                                 ``redispatched`` (perf_guard's
                                 ``--affinity-drop`` gate judges the
                                 hit rate)
  PT_SERVE_BENCH_KV_AB    (0)    =1 (with PT_SERVE_KV_INT8=1, hwbench's
                                 ``serving_int8kv`` row) replays the
                                 same trace once more through a fresh
                                 engine whose pool stores the model
                                 dtype and embeds the A/B (``kv_bf16``
                                 sub-object: tokens/s, TTFT p50, pool
                                 bytes, allocatable_tokens, peak-HBM —
                                 the capacity line's denominator)
  PT_SERVE_*                     engine geometry (docs/SERVING.md)
  PT_SERVE_PREFIX_CACHE=0        share-nothing pool A/B
  PT_SERVE_SPEC=0                speculation off (plain decode) A/B
  PT_SERVE_KV_INT8=1             int8 KV block pool (half-HBM KV) A/B
  PT_DECODE_INT8=1               weight-only int8 decode A/B
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load_decode_bench():
    """The HBM roofline helpers live in decode_bench (the ONE byte model
    both decode benches are judged against) — load by path, benchmarks/
    is not a package."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "decode_bench.py")
    spec = importlib.util.spec_from_file_location("decode_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build_trace(n, rate, vocab, prompt_rng, new_rng, seed=0,
                shared_prefix=0, motif=0):
    """Seeded Poisson trace: ``[(arrival_s, prompt_ids, max_new)]``,
    arrival-sorted by construction. Deterministic for a (seed, n, rate,
    length-range, shared-prefix, motif) tuple — the replayable-input
    contract the scheduler property tests lean on. ``shared_prefix`` > 0
    is the shared-system-prompt mode: one seeded prefix of that many
    tokens opens EVERY prompt (per-request lengths still draw from
    ``prompt_rng`` for the unique suffix). ``motif`` > 0 is the
    repetition-friendly mode (PT_SERVE_BENCH_SPEC_K): each prompt is a
    per-request seeded ``motif``-token pattern tiled to its drawn
    length — the structure (code, quoted context, lists) prompt-lookup
    speculation exists for."""
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, vocab, size=(int(shared_prefix),)) \
        .astype(np.int32)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    trace = []
    for i in range(n):
        plen = int(rng.randint(prompt_rng[0], prompt_rng[1] + 1))
        new = int(rng.randint(new_rng[0], new_rng[1] + 1))
        if motif:
            pat = rng.randint(0, vocab, size=(int(motif),))
            prompt = np.tile(pat, -(-plen // int(motif)))[:plen] \
                .astype(np.int32)
        else:
            prompt = rng.randint(0, vocab, size=(plen,)).astype(np.int32)
        if shared_prefix:
            prompt = np.concatenate([prefix, prompt])
        trace.append((float(arrivals[i]), prompt, new))
    return trace


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if values else None


def kv_byte_model(cfg, num_blocks, block_size, kv_el_bytes, scale_bytes):
    """The serving-KV byte model — ONE place the bench line and the
    capacity tests (tests/test_serving_kv_int8.py) read the same
    arithmetic. Per-token KV bytes follow the POOL's storage dtype
    (``kv_el_bytes`` is the pool array's own itemsize, not an assumed
    2-byte element) plus ``scale_bytes`` per (position, kv_head) — the
    fp32 amax scales `quantize_kv` stores alongside an int8 pool.

    ``allocatable_tokens`` divides the UNQUANTIZED pool's byte budget
    (the configured ``num_blocks`` at the model dtype — "equal
    PT_SERVE_BLOCKS byte budget") by the actual per-token cost: the
    bf16 pool lands exactly on ``num_blocks * block_size``, the int8
    pool on ``2d/(d+4)`` times that (1.94x at head_dim=128 — the
    capacity claim ISSUE 18 gates at >= 1.9x).

    Returns ``(kv_bytes_per_token, allocatable_tokens)``."""
    nkv = cfg.num_key_value_heads or cfg.num_attention_heads
    head_dim = cfg.hidden_size // cfg.num_attention_heads
    base_el = 2 if cfg.dtype == "bfloat16" else 4
    per_tok = 2 * cfg.num_hidden_layers * nkv \
        * (head_dim * kv_el_bytes + scale_bytes)
    budget = (num_blocks * block_size
              * 2 * cfg.num_hidden_layers * nkv * head_dim * base_el)
    return per_tok, budget // per_tok


def main():
    import jax

    from paddle_tpu.framework.device import platform, require_tpu
    from paddle_tpu.utils.xla_cache import enable_compilation_cache

    enable_compilation_cache()
    smoke = "--smoke" in sys.argv
    if not smoke:
        require_tpu("serving_bench")
    print(f"serving_bench: platform={platform()} smoke={smoke}",
          file=sys.stderr, flush=True)

    import paddle_tpu as pt
    from paddle_tpu import monitor as _mon
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (
        RouterConfig, RouterEngine, ServingConfig, ServingEngine,
    )

    from paddle_tpu.monitor import live as _live

    if os.environ.get("PT_BENCH_MONITOR", "1") != "0":
        # same telemetry ride-along as bench.py: compile wall-time and
        # the serving/* counters land in the JSON line's telemetry
        _mon.enable()
        # the live plane rides along too: streaming sketches + the SLO
        # watchdog (PT_SLO_* targets) feed the line's `slo` sub-object,
        # and sketch-vs-exact p99 agreement is self-reported
        _live.enable()
        _live.reset()

    pt.seed(0)
    # documented defaults (module docstring): 64 requests at 4.0/s;
    # --smoke pins its small trace explicitly (8 at 50/s), env overrides
    # either way
    n_req_env = os.environ.get("PT_SERVE_BENCH_REQUESTS")
    rate_env = os.environ.get("PT_SERVE_BENCH_RATE")
    shared = int(os.environ.get("PT_SERVE_BENCH_SHARED", "0") or 0)
    # speculative trace mode (docs/SERVING.md): PT_SERVE_BENCH_SPEC_K=N
    # pins the engine's draft depth AND makes the prompts repetitive
    # (tiled seeded motifs) so prompt-lookup acceptance is measurable
    spec_k_env = int(os.environ.get("PT_SERVE_BENCH_SPEC_K", "0") or 0)
    spec_kw = {"spec": True, "spec_k": spec_k_env} if spec_k_env else {}
    motif = 4 if spec_k_env else 0
    # multi-replica router mode (docs/SERVING.md "Replica router"):
    # PT_SERVE_BENCH_REPLICAS=N replays the SAME trace through a
    # RouterEngine over N in-process replicas — prefix-affinity dispatch
    # on, so the shared-prefix trace (PT_SERVE_BENCH_SHARED) measures
    # what affinity is worth
    replicas = int(os.environ.get("PT_SERVE_BENCH_REPLICAS", "0") or 0)
    if smoke:
        cfg = LlamaConfig.tiny()
        n_req = int(n_req_env) if n_req_env else 8
        rate = float(rate_env) if rate_env else 50.0
        prompt_rng, new_rng = (3, 12), (4, 12)
        if spec_k_env:  # longer outputs give speculation room to help
            new_rng = (12, 24)
        make_cfg = lambda **kw: ServingConfig(  # noqa: E731
            max_lanes=int(os.environ.get("PT_SERVE_LANES", "4")),
            block_size=int(os.environ.get("PT_SERVE_BLOCK", "4")),
            prefill_chunk=int(
                os.environ.get("PT_SERVE_PREFILL_CHUNK", "8")),
            max_seq_len=int(os.environ.get("PT_SERVE_MAX_LEN",
                                           "48" if spec_k_env
                                           else "32")),
            **{**spec_kw, **kw})
        serve_cfg = make_cfg()
    else:
        # the headline-bench decode model (~0.44B, one v5e chip)
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=1536, intermediate_size=4096,
            num_hidden_layers=12, num_attention_heads=12,
            max_position_embeddings=2048, dtype="bfloat16",
            use_parallel_cross_entropy=False)
        n_req = int(n_req_env) if n_req_env else 64
        rate = float(rate_env) if rate_env else 4.0
        prompt_rng, new_rng = (64, 192), (64, 256)
        make_cfg = lambda **kw: ServingConfig(  # noqa: E731
            max_seq_len=int(os.environ.get("PT_SERVE_MAX_LEN", "512")),
            **{**spec_kw, **kw})
        serve_cfg = make_cfg()
    seed = int(os.environ.get("PT_SERVE_BENCH_SEED", "0"))
    if shared and (serve_cfg.max_seq_len is None or
                   shared + prompt_rng[1] + new_rng[1]
                   > serve_cfg.max_seq_len):
        raise SystemExit(
            f"PT_SERVE_BENCH_SHARED={shared} would exceed max_seq_len "
            f"{serve_cfg.max_seq_len} with prompts up to "
            f"{prompt_rng[1]} + {new_rng[1]} new tokens — raise "
            f"PT_SERVE_MAX_LEN or shrink the shared prefix")

    model = LlamaForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        for p in model.parameters():
            p._data = p._data.astype("bfloat16")
    model.eval()

    trace = build_trace(n_req, rate, cfg.vocab_size, prompt_rng, new_rng,
                        seed=seed, shared_prefix=shared, motif=motif)

    def replay(engine):
        """Submit each request when its arrival time passes, step the
        engine whenever it has work. Request timestamps (TTFT,
        per-token) come from the engine's own perf_counter clock; each
        decode round ends in a host fetch (the emitted token IS
        fetched), so every stamp follows finished device work."""
        reqs = []
        t0 = time.perf_counter()
        i = 0
        while i < len(trace) or engine.has_work():
            now = time.perf_counter() - t0
            while i < len(trace) and trace[i][0] <= now:
                _, prompt, new = trace[i]
                reqs.append(engine.submit(prompt, max_new_tokens=new))
                i += 1
            if engine.has_work():
                engine.step()
            elif i < len(trace):
                time.sleep(min(trace[i][0] - now, 0.02))
        return reqs, time.perf_counter() - t0

    if replicas > 1:
        engine = RouterEngine(
            model, serve_cfg, RouterConfig(replicas=replicas,
                                           mode="inproc"))
    else:
        engine = ServingEngine(model, serve_cfg)
    engine.warmup()  # compiles (or exec-cache-loads) outside the clock
    reqs, wall = replay(engine)
    # snapshot the monitor AND the exec-cache account NOW: the optional
    # spec-off A/B engine below must not leak its counters or cache
    # traffic into the main run's telemetry
    try:
        mon_snap = _mon.snapshot()
    except Exception:  # noqa: BLE001 — telemetry must not break the run
        mon_snap = None
    try:
        from paddle_tpu.jit import exec_cache as _ec_snap_mod

        ec_snap = (_ec_snap_mod.stats()
                   if _ec_snap_mod.enabled() else None)
    except Exception:  # noqa: BLE001
        ec_snap = None
    # live-plane snapshot NOW for the same reason: the A/B engines
    # below would keep feeding the shared sketches/watchdog
    try:
        live_snap = _live.snapshot() if _live.enabled() else None
        live_sketches = (_live.merged_sketches()
                         if _live.enabled() else {})
    except Exception:  # noqa: BLE001
        live_snap, live_sketches = None, {}

    stats = engine.stats()
    tokens = sum(len(r.output) for r in reqs)
    tps = tokens / wall if wall > 0 else 0.0
    ttft = [(r.t_first - r.t_submit) * 1e3 for r in reqs
            if r.t_first is not None]
    tpot = [(r.t_done - r.t_first) * 1e3 / (len(r.output) - 1)
            for r in reqs if r.t_done is not None and len(r.output) > 1]
    # prefix-cache readout: hit rate over every (re-)prefilled context
    # token, and the cached-vs-cold TTFT A/B — grouped by the FIRST
    # admission's cache credit (the prefill that set t_first; a later
    # recompute hit must not relabel a cold-TTFT request as cached)
    hit, miss = stats["prefix_hit_tokens"], stats["prefix_miss_tokens"]
    hit_rate = hit / (hit + miss) if (hit + miss) else 0.0
    ttft_cached = [(r.t_first - r.t_submit) * 1e3 for r in reqs
                   if r.t_first is not None and r.ttft_cached_tokens]
    ttft_cold = [(r.t_first - r.t_submit) * 1e3 for r in reqs
                 if r.t_first is not None and not r.ttft_cached_tokens]

    # per-request latency attribution (docs/SERVING.md): the engine's
    # telescoping clock bills every wall-ms of a request's life to
    # exactly one of {queue, prefill, decode, preempted}, so the phase
    # means sum to the measured end-to-end latency — phase_sum_vs_total
    # self-reports that identity (the acceptance bound is 5%), and
    # queue_share is what perf_guard --queue-share-growth judges
    fins = [r for r in reqs if r.t_done is not None]
    attribution = None
    if fins:
        def _mean(xs):
            return sum(xs) / len(xs)

        q_mean = _mean([r.queue_ms for r in fins])
        p_mean = _mean([r.prefill_ms for r in fins])
        d_mean = _mean([r.decode_ms for r in fins])
        pre_mean = _mean([r.preempted_ms for r in fins])
        total_mean = _mean([(r.t_done - r.t_submit) * 1e3 for r in fins])
        phase_sum = q_mean + p_mean + d_mean + pre_mean
        attribution = {
            "queue_ms_mean": round(q_mean, 3),
            "prefill_ms_mean": round(p_mean, 3),
            "decode_ms_mean": round(d_mean, 3),
            "preempted_ms_mean": round(pre_mean, 3),
            "total_ms_mean": round(total_mean, 3),
            "phase_sum_vs_total": (round(phase_sum / total_mean, 4)
                                   if total_mean > 0 else None),
            "queue_share": (round(q_mean / total_mean, 4)
                            if total_mean > 0 else None),
            "queue_ms_p99": round(percentile(
                [r.queue_ms for r in fins], 99), 3),
            "prefill_refunded_tokens": sum(
                r.prefill_refunded_tokens for r in fins),
            "spec_rounds": sum(r.spec_rounds for r in fins),
            "accepted_tokens": sum(r.accepted_tokens for r in fins),
        }

    # HBM roofline (decode_bench's byte model on the decode phase): per
    # step the chip reads every matmul weight once (lanes share the
    # read) + each live lane's KV prefix, writes one KV token per
    # layer/lane. kv_read_tokens is the engine's live-token count — the
    # bytes a perfectly ragged read would move (since PR 28 the counters
    # cover prefill chunks' reads too, a few percent of a decode-heavy
    # run); the engine gathers kv_gathered_tokens (rows and tiles pad),
    # and kv_dense_read_tokens is every lane's whole table.
    db = _load_decode_bench()
    # byte-size facts from the engine's OWN param arrays — re-running
    # _collect_params would materialize a duplicate full weight copy
    # (~GBs held live in a bench whose point is HBM headroom)
    params = engine._params
    embed_nbytes = params["embed"].nbytes
    # decode_rounds = plain decode steps + speculative verify steps:
    # every round reads the matmul weights exactly once either way —
    # fewer rounds for the same tokens IS speculation's byte saving
    rounds = stats["decode_rounds"]
    lane_rows = (stats["decoded_tokens"] / max(rounds, 1))
    embed_row_bytes = lane_rows * cfg.hidden_size \
        * params["embed"].dtype.itemsize
    param_bytes = sum(
        x.nbytes for x in jax.tree_util.tree_leaves(params)
    ) - embed_nbytes + embed_row_bytes
    # KV bytes from the pool's ACTUAL itemsize (+ scale bytes), not an
    # assumed 2-byte element — before int8 KV landed this line billed
    # every pool as bf16; worker-mode routers hold no local pool, so
    # they derive the itemsize from the config they dispatched
    kv_int8 = bool(stats.get("kv_int8", False))
    kpool = getattr(engine, "_pools", (None,))[0]
    kv_el_bytes = (int(kpool.dtype.itemsize) if kpool is not None
                   else 1 if kv_int8
                   else 2 if cfg.dtype == "bfloat16" else 4)
    scale_bytes = 4 if kv_int8 else 0  # one fp32 amax per (pos, kv_head)
    tok_kv_bytes, allocatable = kv_byte_model(
        cfg, stats["num_blocks"], stats["block_size"], kv_el_bytes,
        scale_bytes)
    decode_bytes = (rounds * param_bytes
                    + stats["kv_read_tokens"] * tok_kv_bytes
                    + stats["decoded_tokens"] * tok_kv_bytes)
    decode_wall = stats["dispatch_s"] + stats["fetch_s"] or 1e-9
    achieved_gbps = decode_bytes / decode_wall / 1e9
    peak = db._peak_hbm_gbps(jax.devices()[0])

    rec = {"metric": "serving_tokens_per_sec",
           "value": round(tps, 1), "unit": "tokens/s",
           "tokens_per_sec": round(tps, 1),
           "decode_tokens_per_sec": round(
               stats["decoded_tokens"] / decode_wall, 1),
           "ttft_ms_p50": round(percentile(ttft, 50), 2) if ttft else None,
           "ttft_ms_p99": round(percentile(ttft, 99), 2) if ttft else None,
           "tpot_ms_p50": round(percentile(tpot, 50), 3) if tpot else None,
           "tpot_ms_p99": round(percentile(tpot, 99), 3) if tpot else None,
           "attribution": attribution,
           "requests": len(reqs),
           "completed": stats["finished"],
           "generated_tokens": tokens,
           "arrival_rate_per_s": rate,
           "trace_seed": seed,
           "lanes": stats["lanes"],
           "block_size": stats["block_size"],
           "num_blocks": stats["num_blocks"],
           "prefill_chunk": stats["prefill_chunk"],
           "preemptions": stats["preemptions"],
           "decode_steps": stats["decode_steps"],
           "verify_steps": stats["verify_steps"],
           "decode_rounds": rounds,
           "prefill_chunks": stats["prefill_chunks"],
           # speculative decoding readout (docs/SERVING.md): accept_rate
           # = accepted/proposed draft tokens (post-trim), and the
           # tokens-per-round multiplier speculation bought; spec-off
           # lines omit accept_rate so perf_guard's --accept-drop gate
           # skips them
           "spec": bool(stats["spec"]),
           "spec_k": stats["spec_k"],
           "tokens_per_decode_step": round(
               stats["decoded_tokens"] / rounds, 3) if rounds else None,
           "prefix_cache": bool(stats["prefix_cache"]),
           "shared_prefix_tokens": shared,
           "prefix_hit_rate": round(hit_rate, 4),
           "prefix_hit_tokens": hit,
           "prefix_miss_tokens": miss,
           "ttft_ms_p50_cached": (round(percentile(ttft_cached, 50), 2)
                                  if ttft_cached else None),
           "ttft_ms_p50_cold": (round(percentile(ttft_cold, 50), 2)
                                if ttft_cold else None),
           "hbm_gb_per_s": round(achieved_gbps, 1),
           "hbm_model_bytes_per_step": int(
               decode_bytes / max(rounds, 1)),
           "hbm_peak_gb_per_s": peak,
           "hbm_util": (round(achieved_gbps / peak, 4) if peak else None),
           "int8_weights": serve_cfg.int8_weights,
           # int8-KV capacity line (docs/SERVING.md "int8 KV"):
           # kv_bytes_per_token follows the pool's own itemsize (+ fp32
           # scale bytes); allocatable_tokens is what the UNQUANTIZED
           # pool's byte budget buys at that rate — int8 reports ~1.94x
           # bf16's at head_dim=128 (the >=1.9x acceptance gate)
           "kv_int8": kv_int8,
           "kv_bytes_per_token": int(tok_kv_bytes),
           "allocatable_tokens": int(allocatable),
           "kv_pool_bytes": stats.get("kv_pool_bytes"),
           "replicas": replicas if replicas > 1 else 1}
    if replicas > 1:
        # router readout: affinity hit rate is the --affinity-drop
        # gate's input; load_balance_spread = (max-min)/total dispatches
        # (0 = perfectly even, 1 = one replica took everything)
        disp = stats["dispatches_per_replica"]
        rec["affinity"] = bool(stats["affinity"])
        rec["affinity_hit_rate"] = round(stats["affinity_hit_rate"], 4)
        rec["dispatches_per_replica"] = disp
        rec["load_balance_spread"] = round(
            (max(disp) - min(disp)) / max(sum(disp), 1), 4)
        rec["redispatched"] = stats["router"]["redispatches"]
        rec["dead_replicas"] = stats["router"]["dead_replicas"]
    # SLO readout (docs/OBSERVABILITY.md "Live telemetry plane"): the
    # streaming-sketch view of the SAME run next to the exact-numpy
    # percentiles above — targets + breach count feed perf_guard's
    # --slo-breach gate, and sketch_err_pct self-reports the sketch's
    # honesty (must sit within one log-bucket width, ~5%, of exact)
    rec["slo_ttft_ms_p99"] = (float(os.environ["PT_SLO_TTFT_MS_P99"])
                              if os.environ.get("PT_SLO_TTFT_MS_P99")
                              else None)
    rec["slo_tpot_ms_p99"] = (float(os.environ["PT_SLO_TPOT_MS_P99"])
                              if os.environ.get("PT_SLO_TPOT_MS_P99")
                              else None)
    if live_snap is not None:
        lslo = live_snap["slo"]
        worst = lslo["worst_burn"]
        sk_ttft = live_sketches.get("ttft_ms")
        sketch_p99 = (round(sk_ttft.quantile(0.99), 3)
                      if sk_ttft is not None and sk_ttft.count else None)
        err_pct = None
        if ttft and sketch_p99 is not None:
            # nearest-rank exact, matching the sketch's own rank rule —
            # numpy's interpolated p99 differs by whole samples at
            # small n, which is not sketch error
            xs = sorted(ttft)
            exact_p99 = xs[min(len(xs) - 1,
                               max(0, -(-99 * len(xs) // 100) - 1))]
            if exact_p99:
                err_pct = round(
                    abs(sketch_p99 - exact_p99) / exact_p99 * 100, 3)
        rec["slo"] = {
            "targets": lslo["targets"],
            "breaches": lslo["breaches"],
            "worst_burn": (round(max(worst.values()), 3)
                           if worst else 0.0),
            "burn_windows": {"fast_steps": lslo["fast_window_steps"],
                             "slow_steps": lslo["slow_window_steps"]},
            "sketch_p99_ttft_ms": sketch_p99,
            "sketch_err_pct": err_pct,
        }
    if stats["spec"]:
        prop = stats["spec_proposed_tokens"]
        rec["accept_rate"] = round(
            stats["spec_accepted_tokens"] / prop, 4) if prop else 0.0
        rec["spec_proposed_tokens"] = prop
        rec["spec_accepted_tokens"] = stats["spec_accepted_tokens"]
        rec["spec_bonus_tokens"] = stats["spec_bonus_tokens"]
    if stats["spec"] and os.environ.get(
            "PT_SERVE_BENCH_SPEC_AB", "0") == "1":
        # spec-on vs spec-off A/B: the SAME trace through a fresh
        # plain-decode engine — the decode-rounds delta is the claim
        # ("one verify round advances several tokens"), the tokens/s
        # delta is what it was worth end to end on this box
        eng_off = ServingEngine(model, make_cfg(spec=False))
        eng_off.warmup()
        reqs_off, wall_off = replay(eng_off)
        st_off = eng_off.stats()
        toks_off = sum(len(r.output) for r in reqs_off)
        rec["spec_off"] = {
            "tokens_per_sec": round(toks_off / wall_off, 1)
            if wall_off > 0 else 0.0,
            "decode_rounds": st_off["decode_rounds"],
            "decode_tokens_per_sec": round(
                st_off["decoded_tokens"]
                / (st_off["dispatch_s"] + st_off["fetch_s"] or 1e-9), 1),
        }
    if kv_int8 and os.environ.get("PT_SERVE_BENCH_KV_AB", "0") == "1":
        # int8-vs-bf16 KV A/B (hwbench's serving_int8kv row): the SAME
        # trace through a fresh engine whose pool stores the model
        # dtype — the allocatable_tokens delta is the HBM-capacity
        # claim, the tokens/s + TTFT delta is what quantize-on-write /
        # dequant-on-read cost end to end on this box
        eng_bf = ServingEngine(model, make_cfg(kv_int8=False))
        eng_bf.warmup()
        reqs_bf, wall_bf = replay(eng_bf)
        st_bf = eng_bf.stats()
        toks_bf = sum(len(r.output) for r in reqs_bf)
        ttft_bf = [(r.t_first - r.t_submit) * 1e3 for r in reqs_bf
                   if r.t_first is not None]
        tok_bf, alloc_bf = kv_byte_model(
            cfg, st_bf["num_blocks"], st_bf["block_size"],
            int(eng_bf._pools[0].dtype.itemsize), 0)
        rec["kv_bf16"] = {
            "tokens_per_sec": round(toks_bf / wall_bf, 1)
            if wall_bf > 0 else 0.0,
            "ttft_ms_p50": (round(percentile(ttft_bf, 50), 2)
                            if ttft_bf else None),
            "kv_bytes_per_token": int(tok_bf),
            "allocatable_tokens": int(alloc_bf),
            "kv_pool_bytes": st_bf["kv_pool_bytes"],
        }
        try:
            from paddle_tpu.monitor import memory as _memobs

            pk = _memobs.device_peak_gib()
            if pk is not None:
                rec["kv_bf16"]["peak_hbm_gib"] = pk
        except Exception:  # noqa: BLE001 — a readout must not break the line
            pass
    try:
        from paddle_tpu.ops.pallas import search as _ksearch

        # {family: engaged} for the guard's engagement-regression gate
        rec["kernels"] = _ksearch.engagement_report()
    except Exception:  # noqa: BLE001 — a readout must not break the line
        pass
    # runtime telemetry rides along like bench.py's line: compile cost
    # actually paid + exec-cache traffic (the warm-server-start proof)
    try:
        from paddle_tpu import monitor as _mon
        from paddle_tpu.jit import exec_cache as _ec

        tel = {}
        snap = mon_snap if mon_snap is not None else _mon.snapshot()
        _ch = snap["histograms"].get("jit/compile_ms")
        tel["compile_ms_total"] = round(_ch["sum"], 1) if _ch else 0.0
        # top-level too (→ the persisted record's extra): perf_guard's
        # --compile-growth gate reads baseline extra.compile_ms_total,
        # and exec_cache_enabled keeps cache-on/off runs from
        # false-judging each other — same shape as bench.py's record
        rec["compile_ms_total"] = tel["compile_ms_total"]
        rec["exec_cache_enabled"] = _ec.enabled()
        serv = {k.split("/", 1)[1]: v
                for k, v in snap["counters"].items()
                if k.startswith("serving/") and v}
        if serv:
            tel["serving"] = serv
        rout = {k.split("/", 1)[1]: v
                for k, v in snap["counters"].items()
                if k.startswith("router/") and v}
        if rout:
            tel["router"] = rout
        if _ec.enabled():
            tel["exec_cache"] = ec_snap if ec_snap is not None \
                else _ec.stats()
        rec["telemetry"] = tel
    except Exception:  # noqa: BLE001 — telemetry must not break the line
        pass
    if smoke:
        rec["note"] = "cpu smoke mode; not a TPU number"
    else:
        from paddle_tpu.utils import measurements as _meas

        _meas.record_rec_or_warn(rec)
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
