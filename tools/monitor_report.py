#!/usr/bin/env python
"""Join a monitor StepLogger JSONL run with a profiler chrome trace into
one summary table.

    python tools/monitor_report.py run.jsonl [--trace trace.json] [--top 10]
    python tools/monitor_report.py run.jsonl --trace trace.json --spans
    python tools/monitor_report.py run.jsonl --bench bench.log
    python tools/monitor_report.py run.jsonl --metrics metrics.txt

Sections: run overview (steps, wall, loss, ips), counter totals, the async
pipeline (prefetch staging/starvation, AsyncStepper bound waits, hapi host
syncs, host_blocked_ms_per_step), the AOT executable cache (hit rate,
compile-ms saved/paid, tier + serialization latencies — from the
`jit/exec_cache_*` metrics or a bench line's `telemetry.exec_cache`),
the Pallas kernel account (`pallas/*` engagement + `search/*` harness
counters, and a bench line's `kernels` engagement map —
docs/KERNELS.md), device memory (peak HBM / live-census
peaks from the memory observatory, per-executable breakdown), the perf
guard verdict (the `guard` sub-object bench.py embeds — rendered from the
run_end line, or from a bench log via `--bench`), retrace timeline (which
step retraced — the recompile smoking gun), sync-fence latency
percentiles, and — when a chrome trace from
`paddle_tpu.profiler.Profiler.export` (or `monitor.export_spans`) is
given — the top dispatched ops and the monitor counter tracks found on
the timeline, so one report correlates the JSONL run with the trace.

The "SLO / live windows" section renders the live telemetry plane
(``paddle_tpu/monitor/live.py`` — docs/OBSERVABILITY.md): streaming
sketch percentiles (TTFT/TPOT/queue-wait/accept-rate), the armed
``PT_SLO_*`` targets, fast/slow burn-rate state, and the breach count —
from the run_end line's ``live`` snapshot, or from a SAVED ``/metrics``
exposition (``--metrics FILE``, e.g. ``curl :9100/metrics > f``) where
it also derives the per-replica dispatch share the router's
``{replica=}`` labels carry.

`--spans` adds the host-blocked-time attribution pass: the flight
recorder's `ph:"X"` spans (`paddle_tpu/monitor/spans.py`) are decomposed
per StepLogger step window into {sync, fence_wait, prefetch_starvation,
compile, dispatch, other} by a priority sweep (nested spans — a
device_sync inside an AsyncStepper fence — count once, under the outer
category), which is exactly the breakdown that explains a bench line's
`host_blocked_ms_per_step`.

Pure stdlib: runs anywhere the artifacts land, no jax import.
"""
from __future__ import annotations

import argparse
import json
import sys

# attribution buckets in priority order (an overlapping slice counts under
# the earliest matching category) — mirrors
# paddle_tpu/monitor/spans.py:ATTRIBUTION_CATEGORIES, restated here so the
# tool stays stdlib-only with no package import
ATTRIBUTION_CATEGORIES = (
    "fence_wait", "prefetch_starvation", "compile", "dispatch", "sync",
)


def load_jsonl(path):
    """(step_lines, begin, end) from a StepLogger file; tolerates junk
    lines (a crashed run must still be reportable)."""
    steps, begin, end = [], None, None
    with open(path) as f:
        for raw in f:
            raw = raw.strip()
            if not raw:
                continue
            try:
                line = json.loads(raw)
            except ValueError:
                continue
            if not isinstance(line, dict):
                continue
            if "step" in line:
                steps.append(line)
            elif line.get("event") == "run_begin" and begin is None:
                begin = line
            elif line.get("event") == "run_end":
                end = line  # last one wins (appended runs)
    return steps, begin, end


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{n} B"
        n /= 1024.0


def _table(rows, widths):
    out = []
    for row in rows:
        out.append("".join(
            f"{str(c):<{w}}" if i == 0 else f"{str(c):>{w}}"
            for i, (c, w) in enumerate(zip(row, widths))))
    return out


def _counter_totals(steps, end):
    if end and end.get("totals", {}).get("counters"):
        return dict(end["totals"]["counters"])
    totals = {}
    for s in steps:
        for k, v in s.get("counters", {}).items():
            totals[k] = totals.get(k, 0) + v
    return totals


def _fmt_gib(n_bytes):
    return f"{n_bytes / 2**30:.3f} GiB"


def find_bench_line(text):
    """tools/perf_guard.py:find_bench_line — THE one scanner for bench
    lines (its contract) — loaded from the sibling file so the scan rule
    cannot drift between the guard, hwbench, and this report. Still
    stdlib-only: tools/ is not a package, so load by path."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "perf_guard.py")
    spec = importlib.util.spec_from_file_location("perf_guard", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.find_bench_line(text)


def render_guard(guard, out, source=""):
    """The perf-guard verdict sub-object (`tools/perf_guard.py` schema:
    {ok, checks: [{name, ok, detail}], compared, baseline?})."""
    out.append("")
    out.append(f"-- perf guard{source} --")
    for c in guard.get("checks", []):
        mark = "ok  " if c.get("ok") else "FAIL"
        out.append(f"  [{mark}] {c.get('name', '?'):<12} "
                   f"{c.get('detail', '')}")
    base = guard.get("baseline")
    if base:
        out.append(f"  baseline: {base.get('value')} "
                   f"@ {base.get('commit', '?')} ({base.get('timestamp')})")
    elif not guard.get("compared"):
        out.append("  (no hardware baseline compared)")
    out.append("verdict: " + ("PASS" if guard.get("ok")
                              else "REGRESSION — do not trust/land "
                                   "this number"))


def render_exec_cache(out, totals=None, hists=None, bench_tel=None,
                      source=""):
    """The AOT executable cache's account (``jit/exec_cache_*`` counters
    and histograms from a monitor run, and/or the ``telemetry.exec_cache``
    stats sub-object a bench line carries): hit rate and the compile
    wall-time the cache saved."""
    totals, hists = totals or {}, hists or {}
    tel = bench_tel or {}
    ec = tel.get("exec_cache") or {}
    hits = totals.get("jit/exec_cache_hit", 0) or (
        ec.get("mem_hits", 0) + ec.get("disk_hits", 0))
    misses = totals.get("jit/exec_cache_miss", 0) or ec.get("misses", 0)
    # a cache-off monitor run still carries compile_ms_total — the
    # cold-vs-warm A/B needs the cost line even with zero cache traffic
    if not (hits or misses or ec or "compile_ms_total" in tel):
        return
    out.append("")
    out.append(f"-- exec cache (AOT executables){source} --")
    line = f"hits {hits}   misses {misses}"
    if hits or misses:
        line += f"   hit rate {hits / (hits + misses):.2f}"
    out.append(line)
    if ec:
        out.append(f"  tiers: mem {ec.get('mem_hits', 0)}   "
                   f"disk {ec.get('disk_hits', 0)}   "
                   f"serialized {ec.get('serialized', 0)}   "
                   f"errors {ec.get('errors', 0)}"
                   + (f"   dir {ec['dir']}" if ec.get("dir") else ""))
    saved = hists.get("jit/exec_cache_saved_ms")
    saved_ms = (saved["sum"] if saved
                else ec.get("compile_ms_saved") or 0.0)
    if saved_ms:
        out.append(f"compile ms saved (warm hits): {saved_ms:.0f}")
    if "compile_ms_total" in tel:
        out.append(f"compile ms paid this run: {tel['compile_ms_total']}"
                   + (f" ({tel.get('compile_count')} compile(s))"
                      if tel.get("compile_count") is not None else ""))
    for name, label in (("jit/exec_cache_deserialize_ms", "deserialize"),
                        ("jit/exec_cache_serialize_ms", "serialize")):
        h = hists.get(name)
        if h:
            out.append(f"  {label} ms: p50 {h['p50']}   max {h['max']} "
                       f"({h['count']} file(s))")


def render_serving(out, totals=None, hists=None, gauges=None, source=""):
    """The continuous-batching engine's account (``serving/*`` counters
    from ``paddle_tpu/serving/engine.py`` — docs/SERVING.md): lane
    traffic (admits / finished-lane evictions / capacity preemptions),
    prefill-vs-decode step mix, and the queue-wait histogram (TTFT's
    scheduler-side component)."""
    totals, hists, gauges = totals or {}, hists or {}, gauges or {}
    if not any(k.startswith("serving/") for k in
               (*totals, *hists, *gauges)):
        return
    out.append("")
    out.append(f"-- serving (continuous batching){source} --")
    admits = totals.get("serving/admits", 0)
    evictions = totals.get("serving/evictions", 0)
    preempts = totals.get("serving/preemptions", 0)
    out.append(f"admits {admits}   evictions (finished) {evictions}   "
               f"preemptions {preempts} "
               f"(requeued {totals.get('serving/requeues', 0)})")
    pre = totals.get("serving/prefill_steps", 0)
    dec = totals.get("serving/decode_steps", 0)
    ver = totals.get("serving/verify_steps", 0)
    line = f"prefill chunks {pre}   decode steps {dec}"
    if ver:
        line += f"   verify steps {ver}"
    if dec or ver:
        line += f"   ({pre / (dec + ver):.2f} prefill/decode ratio)"
    out.append(line)
    hit = totals.get("serving/prefix_hit_tokens", 0)
    miss = totals.get("serving/prefix_miss_tokens", 0)
    if hit or miss:
        out.append(f"prefix cache: {hit} cached + {miss} prefilled "
                   f"context tokens ({hit / (hit + miss):.0%} hit rate)")
    # speculative decoding (serving/speculative.py — docs/SERVING.md):
    # accept rate over proposed draft tokens + the tokens-per-round
    # multiplier the verify step bought
    prop = totals.get("serving/spec_proposed_tokens", 0)
    acc = totals.get("serving/spec_accepted_tokens", 0)
    bon = totals.get("serving/spec_bonus_tokens", 0)
    if prop or ver:
        line = f"speculative: {prop} proposed"
        if prop:
            line += f"   {acc} accepted ({acc / prop:.0%} accept rate)"
        line += f"   {bon} bonus"
        out.append(line)
        decoded = totals.get("serving/decoded_tokens", 0)
        if decoded and (dec + ver):
            out.append(f"tokens per decode step: "
                       f"{decoded / (dec + ver):.2f} "
                       f"({decoded} tokens / {dec + ver} rounds)")
        h = (hists or {}).get("serving/spec_accept_rate")
        if h:
            out.append(f"  accept rate per round: p50 {h['p50']}   "
                       f"p95 {h['p95']}   max {h['max']} "
                       f"({h['count']} round(s))")
    # int8 KV pool (docs/SERVING.md "int8 KV"): quantize-on-write
    # totals + the pool's resident bytes — counters only move when
    # kv_int8 is on, so a bf16 run renders nothing here
    qw = totals.get("serving/kv_quant_writes", 0)
    qt = totals.get("serving/kv_quant_tokens", 0)
    pool_b = gauges.get("serving/kv_pool_bytes")
    if qw or qt or pool_b:
        line = "kv pool: int8" if (qw or qt) else "kv pool:"
        if pool_b is not None:
            line += f"   {pool_b / 2**20:.1f} MiB resident"
        line += (f"   {qw} quantizing write(s)   "
                 f"{qt} token(s) quantized")
        out.append(line)
    lanes = gauges.get("serving/lanes_occupied")
    blocks = gauges.get("serving/free_blocks")
    shared = gauges.get("serving/shared_blocks")
    cold = gauges.get("serving/cold_blocks")
    if any(v is not None for v in (lanes, blocks, shared, cold)):
        parts = []
        if lanes is not None:
            parts.append(f"lanes occupied (last): {lanes:g}")
        if blocks is not None:
            parts.append(f"free KV blocks (last): {blocks:g}")
        if shared is not None:
            parts.append(f"shared (last): {shared:g}")
        if cold is not None:
            parts.append(f"cold-cached (last): {cold:g}")
        out.append("   ".join(parts))
    w = hists.get("serving/queue_wait_ms")
    if w:
        out.append(f"queue wait ms: p50 {w['p50']}   p95 {w['p95']}   "
                   f"max {w['max']} ({w['count']} admit(s))")


def render_router(out, totals=None, gauges=None, source=""):
    """The multi-replica router's account (``router/*`` counters from
    ``paddle_tpu/serving/router.py`` — docs/SERVING.md "Replica
    router"): dispatch volume with the affinity hit/miss split,
    drain traffic (redispatches after a replica death), and the
    per-replica dispatch + lane-occupancy spread."""
    totals, gauges = totals or {}, gauges or {}
    if not any(k.startswith("router/") for k in (*totals, *gauges)):
        return
    out.append("")
    out.append(f"-- serving router (replica dispatch){source} --")
    disp = totals.get("router/dispatches", 0)
    hits = totals.get("router/affinity_hits", 0)
    misses = totals.get("router/affinity_misses", 0)
    line = f"dispatches {disp}"
    if hits or misses:
        line += (f"   affinity hits {hits} / misses {misses} "
                 f"({hits / (hits + misses):.0%} hit rate)")
    out.append(line)
    redisp = totals.get("router/redispatches", 0)
    dead = totals.get("router/dead_replicas", 0)
    if redisp or dead:
        out.append(f"dead replicas {dead}   redispatched (drained) "
                   f"requests {redisp}")
    per = sorted((k.rsplit("/", 1)[1], v) for k, v in totals.items()
                 if k.startswith("router/dispatches/"))
    for idx, n in per:
        parts = [f"  replica {idx:<3} dispatches {n}"]
        lanes = gauges.get(f"router/lanes/{idx}")
        queued = gauges.get(f"router/queued/{idx}")
        if lanes is not None:
            parts.append(f"lanes (last) {lanes:g}")
        if queued is not None:
            parts.append(f"queued (last) {queued:g}")
        out.append("   ".join(parts))


def render_slo(out, live=None, source=""):
    """The live telemetry plane's account (the run_end line's ``live``
    sub-object — ``monitor/live.py:snapshot()``): streaming sketch
    percentiles per metric, the armed SLO targets, burn-rate state
    (fast/slow windows), and the breach count."""
    if not live:
        return
    out.append("")
    out.append(f"-- SLO / live windows{source} --")
    slo = live.get("slo") or {}
    out.append(f"engine steps {live.get('steps', 0)}   windows: fast "
               f"{slo.get('fast_window_steps', '?')} / slow "
               f"{slo.get('slow_window_steps', '?')} steps")
    targets = {k: v for k, v in (slo.get("targets") or {}).items() if v}
    if targets:
        out.append("targets: " + "   ".join(
            f"{k} {v:g} ms" for k, v in sorted(targets.items())))
    else:
        out.append("targets: none armed (PT_SLO_TTFT_MS_P99 / "
                   "PT_SLO_TPOT_MS_P99)")
    line = f"breaches: {slo.get('breaches', 0)}"
    if slo.get("fleet_breaches") is not None:
        line += f"   fleet total: {slo['fleet_breaches']}"
    out.append(line)
    last = slo.get("last_burn") or {}
    worst = slo.get("worst_burn") or {}
    for metric in sorted(set(last) | set(worst)):
        lb = last.get(metric) or {}
        out.append(f"  {metric}: burn fast {lb.get('fast', '-')} / "
                   f"slow {lb.get('slow', '-')}   worst "
                   f"{worst.get(metric, '-')} "
                   f"(fires at {slo.get('burn_fast_threshold', 14)}/"
                   f"{slo.get('burn_slow_threshold', 6)})")
    sketches = live.get("sketches") or {}
    if sketches:
        rows = [("metric", "count", "p50", "p90", "p99")]
        for name, s in sorted(sketches.items()):
            rows.append((name, s.get("count", 0), s.get("p50", "-"),
                         s.get("p90", "-"), s.get("p99", "-")))
        out.extend(_table(rows, (18, 8, 12, 12, 12)))
    if live.get("replicas_remote"):
        out.append("remote replicas merged: "
                   + ", ".join(str(r) for r in live["replicas_remote"]))


def parse_openmetrics(text):
    """``{name: [(labels_dict, value)]}`` from a saved ``/metrics``
    exposition (``monitor/exporter.py`` format). Comment/TYPE/EOF lines
    are skipped; unparseable lines are tolerated (a truncated scrape
    must still be reportable)."""
    series = {}
    for ln in text.splitlines():
        ln = ln.strip()
        if not ln or ln.startswith("#"):
            continue
        head, _, val = ln.rpartition(" ")
        try:
            value = float(val)
        except ValueError:
            continue
        name, labels = head, {}
        if "{" in head and head.endswith("}"):
            name, _, lab = head.partition("{")
            for part in lab[:-1].split(","):
                k, eq, v = part.partition("=")
                if eq:
                    labels[k.strip()] = v.strip().strip('"')
        series.setdefault(name, []).append((labels, value))
    return series


def render_metrics_file(series, out, source=""):
    """The SLO/live view of a saved ``/metrics`` exposition: live sketch
    summaries, targets + burn state, breach total, and the per-replica
    dispatch share from the router's ``{replica=}`` labels."""
    out.append("")
    out.append(f"-- SLO / live windows (/metrics){source} --")

    def _one(name, default=None):
        samples = series.get(name) or []
        return samples[0][1] if samples else default

    breaches = _one("pt_slo_breaches_total")
    if breaches is not None:
        out.append(f"breaches: {breaches:g}")
    targets = series.get("pt_slo_target_ms") or []
    if targets:
        out.append("targets: " + "   ".join(
            f"{lb.get('metric', '?')} {v:g} ms"
            for lb, v in sorted(targets,
                                key=lambda s: s[0].get("metric", ""))))
    burns = series.get("pt_slo_burn_rate") or []
    if burns:
        by_metric = {}
        for lb, v in burns:
            by_metric.setdefault(lb.get("metric", "?"), {})[
                lb.get("window", "?")] = v
        for metric in sorted(by_metric):
            w = by_metric[metric]
            out.append(f"  {metric}: burn fast {w.get('fast', '-')} / "
                       f"slow {w.get('slow', '-')}")
    live_names = sorted(
        n[:-len("_count")] for n in series
        if n.startswith("pt_live_") and n.endswith("_count"))
    if live_names:
        rows = [("metric", "count", "p50", "p90", "p99")]
        for base in live_names:
            q = {lb.get("quantile"): v
                 for lb, v in series.get(base, [])}
            rows.append((base[len("pt_live_"):],
                         f"{_one(base + '_count', 0):g}",
                         q.get("0.5", "-"), q.get("0.9", "-"),
                         q.get("0.99", "-")))
        out.extend(_table(rows, (18, 8, 12, 12, 12)))
    disp = [(lb.get("replica", "?"), v) for lb, v in
            series.get("pt_router_dispatches_total", [])
            if lb.get("replica") is not None]
    total_disp = sum(v for _, v in disp)
    if disp and total_disp:
        out.append("dispatch share:")
        for idx, v in sorted(disp):
            out.append(f"  replica {idx:<3} {v:g} "
                       f"({v / total_disp:.0%})")


def render_kernels(out, totals=None, gauges=None, bench_kernels=None,
                   source=""):
    """The Pallas kernel account (``pallas/*`` engagement counters and
    ``search/*`` harness counters from ``ops/pallas/search.py`` —
    docs/KERNELS.md): how often dispatch chose a kernel vs the XLA
    composite (per family), and what the last search run did."""
    totals, gauges = totals or {}, gauges or {}
    have = any(k.startswith(("pallas/", "search/"))
               for k in (*totals, *gauges))
    if not have and not bench_kernels:
        return
    out.append("")
    out.append(f"-- pallas kernels (engagement + search){source} --")
    eng = totals.get("pallas/engaged", 0)
    fb = totals.get("pallas/fallback_composite", 0)
    if eng or fb:
        line = f"engaged {eng}   composite fallbacks {fb}"
        if eng or fb:
            line += f"   (engage rate {eng / (eng + fb):.2f})"
        out.append(line)
        fams = sorted({k.rsplit("/", 1)[1] for k in totals
                       if k.startswith(("pallas/engaged/",
                                        "pallas/fallback/"))})
        for fam in fams:
            fe = totals.get(f"pallas/engaged/{fam}", 0)
            ff = totals.get(f"pallas/fallback/{fam}", 0)
            out.append(f"  {fam:<20} engaged {fe}   composite {ff}")
    timed = totals.get("search/candidates_timed", 0)
    rejects = totals.get("search/rejects", 0)
    if timed or rejects:
        out.append(f"search: candidates timed {timed}   rejects "
                   f"{rejects} (parity/compile pre-filter)")
    for name in sorted(gauges):
        if name.startswith("search/best_ratio/"):
            fam = name.split("search/best_ratio/", 1)[1]
            out.append(f"  best ratio {fam}: {gauges[name]:g} "
                       f"(>1 = kernel faster than composite)")
    if bench_kernels:
        line = ", ".join(f"{k}={'engaged' if v else 'composite'}"
                         for k, v in sorted(bench_kernels.items()))
        out.append(f"bench engagement: {line}")


def render_planner(out, totals=None, gauges=None, source=""):
    """The sharding planner's account (``planner/*`` counters from
    ``paddle_tpu/autoshard/planner.py`` — docs/AUTOSHARD.md) plus the
    per-axis collective-bytes split the cost model is judged against."""
    totals = totals or {}
    gauges = gauges or {}
    axis_bytes = {k.rsplit("/", 1)[1]: v for k, v in totals.items()
                  if k.startswith("collective/bytes/")}
    if not (axis_bytes
            or any(k.startswith("planner/") for k in totals)):
        return
    out.append("")
    out.append(f"-- sharding planner{source} --")
    cand = totals.get("planner/candidates", 0)
    if cand:
        out.append(f"candidates judged: {cand}   infeasible "
                   f"{totals.get('planner/infeasible', 0)}   errors "
                   f"{totals.get('planner/errors', 0)}   plans emitted "
                   f"{totals.get('planner/plans', 0)}")
        w = gauges.get("planner/winner_est_step_ms")
        if w is not None:
            out.append(f"winner roofline est: {w:g} ms/step")
    if axis_bytes:
        total = totals.get("collective/bytes", sum(axis_bytes.values()))
        parts = "   ".join(f"{ax} {_fmt_bytes(v)}"
                           for ax, v in sorted(axis_bytes.items()))
        out.append(f"collective bytes by axis: {parts}"
                   + (f"   (aggregate {_fmt_bytes(total)})"
                      if total else ""))


def render_pipeline(out, totals=None, gauges=None, source=""):
    """The pipeline-parallel account (``pipeline/*`` counters from
    ``fleet/meta_parallel/.../pp_layers.py`` — ISSUE 15): schedule
    shape (microbatches, ticks), the fill/drain bubble fraction, and
    the analytically-attributed ppermute handoff bytes (the compiled
    stage ring is invisible to the eager collective counters)."""
    totals, gauges = totals or {}, gauges or {}
    if not any(k.startswith("pipeline/") for k in totals):
        return
    out.append("")
    out.append(f"-- pipeline (pp stages){source} --")
    fwd = totals.get("pipeline/forwards", 0)
    micro = totals.get("pipeline/microbatches", 0)
    ticks = totals.get("pipeline/ticks", 0)
    out.append(f"pipelined forwards {fwd}   microbatches {micro}   "
               f"schedule ticks {ticks}")
    bub = gauges.get("pipeline/bubble_frac")
    if bub is not None:
        out.append(f"bubble: {bub * 100:.1f}% of ticks "
                   f"(fill/drain — shrink with more microbatches)")
    p2p = totals.get("pipeline/p2p_bytes", 0)
    if p2p:
        out.append(f"p2p handoff: {_fmt_bytes(p2p)} "
                   f"(also attributed to collective/bytes/pp)")


def render_resilience(out, totals=None, hists=None, end=None, source=""):
    """The resilience runtime's account (``resilience/*`` counters from
    ``paddle_tpu/resilience`` — docs/RESILIENCE.md): checkpoint traffic
    (saves + the blocking-cost histogram the cadence planner budgets
    against), restores split by crash resumes, NaN batches skipped, and
    the last COMPLETE checkpoint step the run_end line names (what a
    relaunch will resume from)."""
    totals, hists, end = totals or {}, hists or {}, end or {}
    ckpt_step = end.get("last_checkpoint_step")
    if not any(k.startswith("resilience/") for k in (*totals, *hists)) \
            and ckpt_step is None:
        return
    out.append("")
    out.append(f"-- resilience (checkpoints + NaN policy){source} --")
    saves = totals.get("resilience/saves", 0)
    restores = totals.get("resilience/restores", 0)
    crash = totals.get("resilience/crash_resumes", 0)
    out.append(f"saves {saves}   restores {restores} "
               f"(crash resumes {crash})")
    w = hists.get("resilience/save_ms")
    if w:
        out.append(f"  save blocking ms: p50 {w['p50']}   p95 {w['p95']}   "
                   f"max {w['max']} ({w['count']} save(s))")
    skipped = totals.get("resilience/skipped_batches", 0)
    if skipped:
        out.append(f"NaN batches skipped: {skipped} (params/LR/step "
                   f"untouched per skip)")
    if ckpt_step is not None:
        out.append(f"last complete checkpoint: step {ckpt_step}"
                   + (" — what a relaunch resumes from"
                      if end.get("error") else ""))


GOODPUT_BUCKETS = (
    "productive_step", "compile", "checkpoint_save_blocking",
    "nan_replay_or_skip", "restore_resume", "input_wait", "other",
)

_GOODPUT_VERDICTS = {
    "productive_step": "healthy: productive stepping dominates the wall",
    "compile": "compile-bound: XLA compiles ate the wall — warm the "
               "exec cache (PT_EXEC_CACHE) or check for retrace churn",
    "checkpoint_save_blocking": "checkpoint-bound: blocking save cost "
                                "dominates — raise PT_CKPT_OVERHEAD_PCT "
                                "or check the save path's throughput",
    "nan_replay_or_skip": "numerics-bound: NaN replay/skip cycles ate "
                          "the wall — the data or LR is poisoning steps",
    "restore_resume": "restore-bound: checkpoint restore dominates "
                      "(expected only on short relaunched runs)",
    "input_wait": "input-bound: the loader starved fit — raise prefetch "
                  "depth / loader workers",
    "other": "mostly unclassified wall (host bookkeeping between "
             "ledgered regions)",
}


def render_goodput(out, gp, source=""):
    """The goodput ledger's "where did the time go" account
    (``monitor/goodput.py`` — docs/OBSERVABILITY.md "Training goodput
    plane"): every wall-clock second of the run classified into the
    telescoping buckets, the goodput fraction, and a verdict naming
    the dominant non-productive bucket."""
    if not gp or not isinstance(gp, dict):
        return
    buckets = gp.get("buckets") or {}
    wall = gp.get("wall_s")
    if wall is None:
        wall = sum(v for v in buckets.values()
                   if isinstance(v, (int, float)))
    out.append("")
    out.append(f"-- goodput (where did the time go){source} --")
    line = f"wall: {wall:.3f} s"
    if gp.get("steps") is not None:
        line += f"   steps: {gp['steps']}"
    if gp.get("nan_steps"):
        line += f"   nan steps: {gp['nan_steps']}"
    out.append(line)
    if buckets and wall > 0:
        rows = []
        for name in GOODPUT_BUCKETS:
            if name not in buckets:
                continue
            s = buckets[name]
            rows.append((name, f"{s:.3f} s", f"{s / wall * 100:5.1f}%"))
        for name in sorted(set(buckets) - set(GOODPUT_BUCKETS)):
            s = buckets[name]
            rows.append((name, f"{s:.3f} s", f"{s / wall * 100:5.1f}%"))
        out.extend(_table(rows, (26, 14, 10)))
        ssum = sum(buckets.values())
        out.append(f"buckets sum: {ssum:.3f} s "
                   + ("(telescopes exactly)" if ssum == wall
                      else f"vs wall {wall:.3f} s — LEDGER BROKEN"))
    frac = gp.get("goodput_frac")
    if frac is None and wall and buckets.get("productive_step") is not None:
        frac = buckets["productive_step"] / wall
    if frac is not None:
        out.append(f"goodput_frac: {frac:.4f} "
                   f"({frac * 100:.1f}% of wall was productive stepping)")
    if buckets and wall > 0:
        dom = max(buckets, key=lambda b: buckets[b])
        if buckets[dom] > 0.2 * wall and dom in _GOODPUT_VERDICTS:
            out.append(f"verdict: {_GOODPUT_VERDICTS[dom]}")


def _load_heartbeat_mod():
    """``paddle_tpu/monitor/heartbeat.py`` loaded by path (its
    module-level imports are stdlib-only by contract) so the fleet
    section's parsing + detectors cannot drift from the launcher's —
    and this tool stays importable with no jax on the box."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "paddle_tpu", "monitor", "heartbeat.py")
    spec = importlib.util.spec_from_file_location("pt_heartbeat", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_fleet(path):
    """A fleet view from either a ``fleet.json`` snapshot (the
    launcher's scraped artifact) or a heartbeat DIRECTORY (re-run the
    detectors offline over the raw JSONL — a postmortem needs no live
    launcher). Returns the ``FleetMonitor.status()`` dict shape."""
    import os

    if os.path.isdir(path):
        hb = _load_heartbeat_mod()
        by_rank = hb.read_heartbeats(path)
        workers = {}
        last_ts = {}
        for rank, lines in sorted(by_rank.items()):
            if not lines:
                continue
            newest = lines[-1]
            workers[str(rank)] = {
                k: newest.get(k) for k in
                ("step", "loss", "step_ms", "goodput", "metrics_port")}
            last_ts[rank] = newest.get("ts") or 0.0
        steps = [w["step"] for w in workers.values()
                 if w.get("step") is not None]
        now = max(last_ts.values()) if last_ts else 0.0
        return {
            "nprocs": len(by_rank) or None,
            "workers": workers,
            "fleet": {"min_step": min(steps) if steps else None,
                      "max_step": max(steps) if steps else None,
                      "step_ms": None},
            "verdicts": {
                "straggler": hb.detect_straggler(by_rank),
                "desync": hb.detect_desync(by_rank),
                # offline: judge silence against the newest beat anywhere
                # in the fleet, not this tool's wall clock
                "silent": hb.detect_silent(by_rank, now=now),
            },
            "postmortem": None,
            "offline": True,
        }
    with open(path) as f:
        return json.load(f)


def render_fleet(out, fleet, source=""):
    """The launcher fleet view (``FleetMonitor.status()`` — per-worker
    table, merged step_ms, and the three latched detector verdicts:
    straggler / dp desync / silent worker, each naming its rank)."""
    if not fleet:
        return
    out.append("")
    off = " [offline re-detect]" if fleet.get("offline") else ""
    out.append(f"-- fleet (launcher workers){source}{off} --")
    workers = fleet.get("workers") or {}
    fl = fleet.get("fleet") or {}
    head = f"workers reporting: {len(workers)}"
    if fleet.get("nprocs"):
        head += f" / {fleet['nprocs']}"
    if fl.get("min_step") is not None:
        head += (f"   step span: {fl['min_step']}..{fl['max_step']}"
                 + (f" (skew {fl['max_step'] - fl['min_step']})"
                    if fl["max_step"] != fl["min_step"] else ""))
    out.append(head)
    if workers:
        rows = [("rank", "step", "step_ms", "loss", "age_s", "gp%")]
        for rank in sorted(workers, key=lambda r: int(r)):
            w = workers[rank] or {}
            gp = w.get("goodput") or {}
            tot = sum(v for v in gp.values()
                      if isinstance(v, (int, float))) if gp else 0.0
            gpp = (f"{gp.get('productive_step', 0.0) / tot * 100:.0f}"
                   if tot > 0 else "-")
            rows.append((rank, w.get("step", "-"),
                         w.get("step_ms", "-"),
                         (f"{w['loss']:.4f}"
                          if isinstance(w.get("loss"), (int, float))
                          else "-"),
                         w.get("age_s", "-"), gpp))
        out.extend(_table(rows, (6, 8, 10, 12, 9, 6)))
    sk = fl.get("step_ms")
    if sk:
        out.append(f"fleet step_ms (merged sketch): p50 {sk.get('p50')}   "
                   f"p90 {sk.get('p90')}   p99 {sk.get('p99')} "
                   f"({sk.get('count')} step(s))")
    verdicts = fleet.get("verdicts") or {}
    strag = verdicts.get("straggler")
    if strag:
        out.append(f"STRAGGLER: rank {strag.get('rank')} at step "
                   f"{strag.get('step')} — {strag.get('step_ms')} ms vs "
                   f"fleet median {strag.get('fleet_median_ms')} ms "
                   f"(threshold {strag.get('factor')}x)")
    desync = verdicts.get("desync")
    if desync:
        out.append(f"DP DESYNC: ranks {desync.get('ranks')} at step "
                   f"{desync.get('step')} — loss spread "
                   f"{desync.get('spread'):.6g} (rel "
                   f"{desync.get('rel_spread'):.3g} > tol "
                   f"{desync.get('tol'):.3g}); same-step losses must "
                   f"match across dp replicas")
    silent = verdicts.get("silent")
    if silent:
        out.append(f"SILENT WORKER: rank {silent.get('rank')} — no "
                   f"heartbeat for {silent.get('silent_s')}s (timeout "
                   f"{silent.get('timeout_s')}s, last step "
                   f"{silent.get('last_step')})")
    if fleet.get("postmortem"):
        out.append(f"postmortem: {fleet['postmortem']}")
    if not (strag or desync or silent):
        out.append("verdicts: none latched (fleet healthy)")


def render_memory(mem, out, steps=(), source=""):
    """The memory observatory's account: run-level peaks (+ sentinel
    state) and the per-step live-census trajectory when step lines
    carry `memory` sub-objects."""
    out.append("")
    out.append(f"-- device memory{source} --")
    peak = mem.get("peak_hbm_gib")
    if peak is not None:
        out.append(f"peak HBM: {peak:.3f} GiB"
                   + (f"   (source: {mem['source']})"
                      if mem.get("source") else ""))
    for key, label in (("peak_live_bytes", "peak live bytes (census)"),
                       ("peak_backend_bytes", "peak bytes (allocator)")):
        if mem.get(key):
            out.append(f"{label}: {_fmt_gib(mem[key])}")
    if mem.get("peak_live_gib") is not None and "peak_live_bytes" not in mem:
        out.append(f"peak live (census): {mem['peak_live_gib']:.3f} GiB")
    if mem.get("censuses"):
        out.append(f"censuses: {mem['censuses']}")
    if "nan_check" in mem:
        out.append(f"numerics sentinel: "
                   f"{'armed' if mem['nan_check'] else 'off'}")
    execs = mem.get("executables") or (
        [mem["executable"]] if mem.get("executable") else [])
    if execs:
        out.append("per-executable (args / temp / out -> peak):")
        for e in execs:
            out.append(f"  {e.get('name', '?'):<28}"
                       f"{_fmt_gib(e.get('args_bytes', 0)):>12} /"
                       f"{_fmt_gib(e.get('temp_bytes', 0)):>12} /"
                       f"{_fmt_gib(e.get('output_bytes', 0)):>12} -> "
                       f"{_fmt_gib(e.get('peak_bytes', 0))}"
                       + ("  (per-shard)" if e.get("per_shard") else ""))
    # per-step live-census trajectory from the step lines
    series = [(s["step"], s["memory"]) for s in steps
              if isinstance(s.get("memory"), dict)]
    if series:
        live = [m.get("live_bytes", 0) for _, m in series]
        peaks = [m.get("peak_live_bytes", 0) for _, m in series]
        hi_step = max(series, key=lambda sm: sm[1].get("live_bytes", 0))
        out.append(f"step census: {len(series)} step(s)   "
                   f"live min {_fmt_gib(min(live))}   "
                   f"max {_fmt_gib(max(live))} (step {hi_step[0]})   "
                   f"run peak {_fmt_gib(max(peaks))}")


# -- span attribution --------------------------------------------------------

def _merge_intervals(iv):
    """Union of (lo, hi) intervals as a sorted, disjoint list."""
    out = []
    for lo, hi in sorted(iv):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def _measure(iv):
    return sum(hi - lo for lo, hi in iv)


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv
            if b > lo and a < hi]


def _subtract(iv, claimed):
    """`iv` minus `claimed` (both merged/disjoint, sorted)."""
    out = []
    for lo, hi in iv:
        cur = lo
        for c0, c1 in claimed:
            if c1 <= cur or c0 >= hi:
                continue
            if c0 > cur:
                out.append((cur, c0))
            cur = max(cur, c1)
            if cur >= hi:
                break
        if cur < hi:
            out.append((cur, hi))
    return out


def load_spans(trace_path):
    """(step_windows, intervals_by_cat) from a chrome trace's ``ph:"X"``
    span events, in trace-clock milliseconds. ``step_windows`` are the
    StepLogger step-marker spans; ``intervals_by_cat`` holds the
    attribution-bucket spans."""
    with open(trace_path) as f:
        trace = json.load(f)
    steps, by_cat = [], {c: [] for c in ATTRIBUTION_CATEGORIES}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        cat = ev.get("cat")
        try:
            t0 = float(ev["ts"]) / 1e3
            t1 = t0 + float(ev.get("dur", 0)) / 1e3
        except (KeyError, TypeError, ValueError):
            continue
        if cat == "step":
            steps.append((ev.get("name", "step/?"), t0, t1))
        elif cat in by_cat:
            by_cat[cat].append((t0, t1))
    steps.sort(key=lambda s: s[1])
    return steps, {c: _merge_intervals(v) for c, v in by_cat.items()}


def attribute_spans(steps, by_cat):
    """Decompose each step window into the attribution buckets.

    Priority sweep: categories claim time in ATTRIBUTION_CATEGORIES
    order, so a slice covered by several nested spans counts exactly
    once — bucket sums can never exceed the window. Without step markers
    the whole span extent is one window. Returns
    ``{"per_step": [...], "totals": {...}, "wall_ms": float}``.
    """
    if not steps:
        allspans = [iv for v in by_cat.values() for iv in v]
        if not allspans:
            return {"per_step": [], "totals": {}, "wall_ms": 0.0}
        lo = min(a for a, _ in allspans)
        hi = max(b for _, b in allspans)
        steps = [("run", lo, hi)]
    per_step = []
    totals = {c: 0.0 for c in ATTRIBUTION_CATEGORIES}
    wall = 0.0
    for name, lo, hi in steps:
        dur = hi - lo
        wall += dur
        claimed = []
        row = {"step": name, "dur_ms": dur}
        for cat in ATTRIBUTION_CATEGORIES:
            take = _subtract(_clip(by_cat.get(cat, []), lo, hi), claimed)
            got = _measure(take)
            row[cat] = got
            totals[cat] += got
            if take:
                claimed = _merge_intervals(claimed + take)
        row["other"] = max(0.0, dur - sum(row[c]
                                          for c in ATTRIBUTION_CATEGORIES))
        per_step.append(row)
    totals["other"] = max(0.0, wall - sum(totals.values()))
    return {"per_step": per_step, "totals": totals, "wall_ms": wall}


_VERDICTS = {
    "prefetch_starvation": "input-bound: the loader starved the step — "
                           "raise prefetch depth / loader workers",
    "fence_wait": "device-bound: the host out-ran the device to the "
                  "in-flight bound (healthy pipelining; the device is "
                  "the limiter)",
    "sync": "sync-bound: metric materializations dominate — check "
            "log_freq or a per-step .numpy() in a callback",
    "compile": "compile-bound: retrace storm — check for shape churn",
    "dispatch": "dispatch-bound: host-side enqueue cost dominates",
    "other": "mostly unattributed host time (python bookkeeping between "
             "instrumented regions)",
}


def render_attribution(att, out):
    out.append("")
    out.append("-- span attribution (host wall decomposition) --")
    totals, wall = att["totals"], att["wall_ms"]
    if not totals or wall <= 0:
        out.append("no spans found (was PT_MONITOR=1 set for the run?)")
        return
    n = len(att["per_step"])
    out.append(f"windows: {n}   wall: {wall:.3f} ms")
    rows = []
    for cat in (*ATTRIBUTION_CATEGORIES, "other"):
        ms = totals.get(cat, 0.0)
        rows.append((cat, f"{ms:.3f} ms", f"{ms / wall * 100:5.1f}%"))
    out.extend(_table(rows, (24, 16, 10)))
    attributed = wall - totals.get("other", 0.0)
    out.append(f"attributed: {attributed / wall * 100:.1f}% of "
               f"host wall across {n} window(s)")
    # the dominant category is the verdict
    dom = max(totals, key=lambda c: totals[c])
    if totals[dom] > 0.2 * wall:
        out.append(f"verdict: {_VERDICTS[dom]}")
    worst = [r for r in att["per_step"]
             if r["dur_ms"] > 0 and r["step"] != "run"]
    if worst:
        w = max(worst, key=lambda r: r["dur_ms"] - r["other"])
        parts = ", ".join(
            f"{c} {w[c]:.2f}ms" for c in ATTRIBUTION_CATEGORIES if w[c] > 0)
        if parts:
            out.append(f"worst window: {w['step']} "
                       f"(dur {w['dur_ms']:.2f}ms: {parts})")


# -- per-request serving journeys ---------------------------------------------

def load_request_spans(events_or_path):
    """``serving/request`` finish spans (cat ``serving_finish``) — each
    one is a whole request journey with the telescoping latency
    attribution in its args (docs/SERVING.md). Accepts a chrome-trace
    event list, a chrome-trace path, or a ``serving_blackbox.json``
    artifact path (its ``spans`` list uses the raw recorder tuple
    shape)."""
    if isinstance(events_or_path, str):
        with open(events_or_path) as f:
            data = json.load(f)
        if isinstance(data, dict) and "spans" in data:  # blackbox artifact
            return [dict(sp.get("args") or {}) for sp in data["spans"]
                    if sp.get("cat") == "serving_finish"]
        events = (data or {}).get("traceEvents", [])
    else:
        events = events_or_path or []
    return [dict(ev.get("args") or {}) for ev in events
            if ev.get("ph") == "X" and ev.get("cat") == "serving_finish"]


def render_requests(journeys, out, top=10, source=""):
    """Slowest-N request journeys, each decomposed into the phase
    buckets the engine billed (queue/prefill/decode/preempted — they sum
    to the request's end-to-end latency)."""
    if not journeys:
        return

    def _ms(v):
        return f"{v:.1f}" if isinstance(v, (int, float)) else "-"

    out.append("")
    out.append(f"-- requests (slowest {min(top, len(journeys))} of "
               f"{len(journeys)} journeys, ms){source} --")
    ordered = sorted(journeys,
                     key=lambda j: -(j.get("total_ms") or 0.0))
    rows = [("request", "total", "queue", "prefill", "decode",
             "preempted", "tokens", "pre", "spec")]
    for j in ordered[:top]:
        rows.append((j.get("trace_id") or j.get("request", "?"),
                     _ms(j.get("total_ms")), _ms(j.get("queue_ms")),
                     _ms(j.get("prefill_ms")), _ms(j.get("decode_ms")),
                     _ms(j.get("preempted_ms")), j.get("tokens", "-"),
                     j.get("preemptions", 0), j.get("spec_rounds", 0)))
    out.extend(_table(rows, (10, 10, 9, 9, 9, 11, 8, 5, 6)))
    tot = [j["total_ms"] for j in journeys
           if isinstance(j.get("total_ms"), (int, float))]
    qs = [j.get("queue_ms", 0.0) for j in journeys
          if isinstance(j.get("total_ms"), (int, float))]
    if tot:
        mean_t = sum(tot) / len(tot)
        line = (f"{len(journeys)} finished: total_ms mean "
                f"{mean_t:.1f}   max {max(tot):.1f}")
        if mean_t > 0:
            line += f"   queue share {sum(qs) / sum(tot):.1%}"
        out.append(line)


def render_request_attribution(att, out, source=""):
    """serving_bench's ``attribution`` sub-object: per-phase latency
    means that telescope to the measured end-to-end request latency
    (``phase_sum_vs_total`` ~ 1.0 is the engine's accounting proof)."""
    if not att:
        return
    out.append("")
    out.append(f"-- request attribution (phase means, ms){source} --")
    rows = []
    for key in ("queue_ms_mean", "prefill_ms_mean", "decode_ms_mean",
                "preempted_ms_mean", "total_ms_mean", "queue_ms_p99"):
        if att.get(key) is not None:
            rows.append((key, att[key]))
    out.extend(_table(rows, (24, 14)))
    if att.get("queue_share") is not None:
        out.append(f"queue share: {att['queue_share']:.1%} of request "
                   f"latency spent waiting for a lane")
    if att.get("phase_sum_vs_total") is not None:
        out.append(f"phase sum vs total: {att['phase_sum_vs_total']} "
                   f"(1.0 = the buckets telescope exactly)")
    extras = []
    for key in ("prefill_refunded_tokens", "spec_rounds",
                "accepted_tokens"):
        if att.get(key):
            extras.append(f"{key} {att[key]}")
    if extras:
        out.append("   ".join(extras))


def render(jsonl_path, trace_path=None, top=10, spans=False,
           bench_path=None, metrics_path=None, fleet_path=None):
    steps, begin, end = load_jsonl(jsonl_path)
    out = [f"== monitor run: {jsonl_path} =="]
    if begin:
        meta = begin.get("meta") or {}
        if meta:
            out.append("meta: " + ", ".join(
                f"{k}={v}" for k, v in meta.items() if v is not None))

    # -- run overview --
    n = len(steps)
    out.append("")
    out.append("-- run --")
    wall = (end or {}).get("wall_s")
    if wall is None and n:
        wall = sum(s.get("dur_ms", 0) for s in steps) / 1e3
    out.append(f"steps: {n}   wall: {wall:.3f} s" if wall is not None
               else f"steps: {n}")
    if n:
        durs = [s["dur_ms"] for s in steps if "dur_ms" in s]
        if durs:
            out.append(f"step dur_ms: mean {sum(durs) / len(durs):.3f}   "
                       f"min {min(durs):.3f}   max {max(durs):.3f}")
        losses = [(s["step"], s["loss"]) for s in steps if "loss" in s]
        if losses:
            out.append(f"loss: first {losses[0][1]:.6f} (step {losses[0][0]})"
                       f" -> last {losses[-1][1]:.6f} (step {losses[-1][0]})")
        elif end and end.get("loss") is not None:
            out.append(f"final loss: {end['loss']:.6f}")
        ips = [s["ips"] for s in steps if s.get("ips")]
        if ips:
            out.append(f"ips: mean {sum(ips) / len(ips):.2f}   "
                       f"max {max(ips):.2f}")

    # -- counter totals --
    totals = _counter_totals(steps, end)
    if totals:
        out.append("")
        out.append("-- counters (run total) --")
        rows = []
        for name in sorted(totals, key=lambda k: (-totals[k], k)):
            val = totals[name]
            rows.append((name, _fmt_bytes(val) if name.endswith("bytes")
                         else val))
        out.extend(_table(rows, (44, 16)))

    # -- async pipeline (PR 2 instrumentation: prefetch, AsyncStepper,
    #    hapi deferred syncs) --
    hists = (end or {}).get("totals", {}).get("histograms", {})
    gauges = (end or {}).get("totals", {}).get("gauges", {})
    pipe = []
    if totals.get("io/prefetch_batches") or totals.get(
            "io/prefetch_starvations"):
        staged = totals.get("io/prefetch_batches", 0)
        starved = totals.get("io/prefetch_starvations", 0)
        line = (f"prefetch: staged {staged}   starvations {starved}")
        if staged:
            line += f"   starvation rate {starved / staged:.3f}/batch"
        pipe.append(line)
        w = hists.get("io/prefetch_wait_ms")
        if w:
            pipe.append(f"  starved wait ms: p50 {w['p50']}   "
                        f"p95 {w['p95']}   max {w['max']}")
        depth = gauges.get("io/prefetch_depth")
        if depth is not None:
            pipe.append(f"  buffer depth (last): {depth:g}")
    if totals.get("async/bound_waits") or "async/steps_in_flight" in gauges:
        waits = totals.get("async/bound_waits", 0)
        line = f"async: bound waits {waits}"
        if n:
            line += f" over {n} steps ({waits / n:.2f}/step)"
        pipe.append(line)
        w = hists.get("async/bound_wait_ms")
        if w:
            pipe.append(f"  bound wait ms: p50 {w['p50']}   "
                        f"p95 {w['p95']}   max {w['max']}")
    if totals.get("hapi/host_syncs"):
        syncs = totals["hapi/host_syncs"]
        line = f"hapi host syncs: {syncs}"
        if n:
            line += (f"   ({n / syncs:.1f} steps/sync — the "
                     f"≤ 1-per-log-window guard)")
        pipe.append(line)
    hb = (end or {}).get("host_blocked_ms_per_step")
    if hb is None:
        hbs = [s["host_blocked_ms_per_step"] for s in steps
               if "host_blocked_ms_per_step" in s]
        hb = hbs[-1] if hbs else None
    if hb is not None:
        pipe.append(f"host_blocked_ms_per_step: {hb}")
    if pipe:
        out.append("")
        out.append("-- async pipeline --")
        out.extend(pipe)

    # -- exec cache (jit/exec_cache_* from the run's counters) --
    render_exec_cache(out, totals=totals,
                      hists=(end or {}).get("totals", {})
                      .get("histograms", {}))

    # -- serving runtime (serving/* from the continuous-batching engine) --
    render_serving(out, totals=totals,
                   hists=(end or {}).get("totals", {}).get("histograms", {}),
                   gauges=(end or {}).get("totals", {}).get("gauges", {}))

    # -- replica router (router/* from the multi-replica dispatcher) --
    render_router(out, totals=totals,
                  gauges=(end or {}).get("totals", {}).get("gauges", {}))

    # -- SLO / live windows (the run_end line's live snapshot) --
    render_slo(out, live=(end or {}).get("live"))

    # -- SLO / live windows from a saved /metrics exposition --
    if metrics_path:
        try:
            series = parse_openmetrics(open(metrics_path).read())
        except OSError as e:
            out.append("")
            out.append(f"unreadable metrics file: {e}")
        else:
            render_metrics_file(series, out,
                                source=f" {metrics_path}")

    # -- pallas kernels (pallas/* + search/* from the search harness) --
    render_kernels(out, totals=totals,
                   gauges=(end or {}).get("totals", {}).get("gauges", {}))

    # -- sharding planner (planner/* + collective/bytes/<axis>) --
    render_planner(out, totals=totals,
                   gauges=(end or {}).get("totals", {}).get("gauges", {}))

    # -- pipeline parallelism (pipeline/* schedule + ppermute account) --
    render_pipeline(out, totals=totals,
                    gauges=(end or {}).get("totals", {}).get("gauges", {}))

    # -- resilience runtime (resilience/* + run_end last_checkpoint_step) --
    render_resilience(out, totals=totals,
                      hists=(end or {}).get("totals", {})
                      .get("histograms", {}),
                      end=end)

    # -- goodput ledger (run_end's goodput sub-object — where did the
    #    wall-clock go) --
    render_goodput(out, (end or {}).get("goodput"))

    # -- fleet (--fleet: a launcher fleet.json snapshot or the raw
    #    heartbeat directory, detectors re-run offline) --
    if fleet_path:
        try:
            fleet = load_fleet(fleet_path)
        except (OSError, ValueError) as e:
            out.append("")
            out.append(f"unreadable fleet source: {e}")
        else:
            render_fleet(out, fleet, source=f" {fleet_path}")

    # -- device memory (observatory run_end sub-object and/or per-step
    #    censuses) --
    mem = (end or {}).get("memory")
    has_step_mem = any(isinstance(s.get("memory"), dict) for s in steps)
    if mem or has_step_mem:
        render_memory(mem or {}, out, steps=steps)

    # -- perf guard verdict (bench.py embeds it in run_end) --
    guard = (end or {}).get("guard")
    if guard:
        render_guard(guard, out)

    # -- bench line join (--bench): guard + memory from a bench log --
    if bench_path:
        read_ok = True
        try:
            line = find_bench_line(open(bench_path).read())
        except OSError as e:
            line = None
            read_ok = False
            out.append("")
            out.append(f"unreadable bench log: {e}")
        if line is not None:
            out.append("")
            out.append(f"-- bench line: {bench_path} --")
            out.append(f"{line.get('metric')}: {line.get('value')} "
                       f"{line.get('unit', '')}"
                       + (f"   mfu {line['mfu']}" if line.get("mfu")
                          else ""))
            mem_b = dict(line.get("memory") or {})
            if line.get("peak_hbm_gib") is not None:
                mem_b.setdefault("peak_hbm_gib", line["peak_hbm_gib"])
            if mem_b:
                render_memory(mem_b, out, source=" (bench)")
            tel_b = line.get("telemetry") or {}
            if tel_b.get("exec_cache") or "compile_ms_total" in tel_b:
                render_exec_cache(out, bench_tel=tel_b, source=" (bench)")
            if tel_b.get("serving"):
                # serving_bench embeds the counters prefix-stripped
                render_serving(
                    out, totals={f"serving/{k}": v
                                 for k, v in tel_b["serving"].items()},
                    source=" (bench)")
            if tel_b.get("router"):
                # serving_bench embeds the router counters the same way
                render_router(
                    out, totals={f"router/{k}": v
                                 for k, v in tel_b["router"].items()},
                    source=" (bench)")
            if line.get("attribution"):
                render_request_attribution(line["attribution"], out,
                                           source=" (bench)")
            if line.get("goodput"):
                render_goodput(out, line["goodput"], source=" (bench)")
            if line.get("kernels"):
                render_kernels(out, bench_kernels=line["kernels"],
                               source=" (bench)")
            if line.get("guard"):
                render_guard(line["guard"], out, source=" (bench)")
        elif read_ok:
            out.append("")
            out.append(f"no bench JSON line found in {bench_path!r}")

    # -- retrace timeline --
    retraces = [(s["step"], s["counters"]["jit/retraces"]) for s in steps
                if s.get("counters", {}).get("jit/retraces")]
    out.append("")
    out.append("-- retrace timeline --")
    if retraces:
        out.append("  ".join(f"step {st}: +{k}" for st, k in retraces))
        if len(retraces) > 1:
            out.append(f"WARNING: {len(retraces)} steps retraced — check "
                       f"for shape churn (each retrace is an XLA compile)")
    else:
        out.append("no retraces inside the logged window")

    # -- sync latency --
    hists = (end or {}).get("totals", {}).get("histograms", {})
    sync = hists.get("sync/fence_ms")
    if sync:
        out.append("")
        out.append("-- sync fence latency (ms) --")
        out.extend(_table(
            [("count", sync["count"]), ("mean", sync["mean"]),
             ("p50", sync["p50"]), ("p95", sync["p95"]),
             ("max", sync["max"])], (10, 14)))
    compile_h = hists.get("jit/compile_ms")
    if compile_h:
        out.append("")
        out.append("-- compile wall-time (ms) --")
        out.extend(_table(
            [("count", compile_h["count"]), ("mean", compile_h["mean"]),
             ("max", compile_h["max"])], (10, 14)))

    # -- chrome trace join --
    if trace_path:
        out.append("")
        out.append(f"-- chrome trace: {trace_path} --")
        try:
            with open(trace_path) as f:
                trace = json.load(f)
            events = trace.get("traceEvents", [])
        except (OSError, ValueError) as e:
            events = None
            out.append(f"unreadable trace: {e}")
        if events is not None:
            op_counts = {}
            for ev in events:
                if ev.get("cat") in ("op", "op_dispatch"):
                    name = ev.get("name", "?")
                    op_counts[name] = op_counts.get(name, 0) + 1
            counter_tracks = sorted({
                ev.get("name", "?") for ev in events if ev.get("ph") == "C"})
            out.append(f"events: {len(events)}   "
                       f"counter tracks: {len(counter_tracks)}")
            if op_counts:
                out.append(f"top {top} dispatched ops:")
                rows = sorted(op_counts.items(),
                              key=lambda kv: (-kv[1], kv[0]))[:top]
                out.extend(_table(rows, (44, 10)))
            if counter_tracks:
                out.append("counter tracks: " + ", ".join(counter_tracks))
            lanes = sorted({
                (ev.get("args") or {}).get("name", "?") for ev in events
                if ev.get("ph") == "M"
                and ev.get("name") == "thread_name"})
            if lanes:
                out.append("span lanes: " + ", ".join(lanes))
            # per-request journeys: the engine's serving/request finish
            # spans carry the whole telescoped attribution per request
            render_requests(load_request_spans(events), out, top=top)

    # -- span attribution --
    if spans:
        span_src = spans if isinstance(spans, str) else trace_path
        if not span_src:
            out.append("")
            out.append("--spans needs a trace (pass --trace, or "
                       "--spans PATH)")
        else:
            try:
                st, by_cat = load_spans(span_src)
                render_attribution(attribute_spans(st, by_cat), out)
            except (OSError, ValueError) as e:
                out.append("")
                out.append(f"unreadable span trace: {e}")

    return "\n".join(out)


def _selftest():
    """Render a fully synthesized run (StepLogger JSONL + spans chrome
    trace + bench line) and assert every section the serving-trace stack
    depends on actually renders — the tier-1 smoke for this tool (pure
    stdlib: no jax, no engine, no fixture files to go stale)."""
    import os
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        jsonl = os.path.join(td, "run.jsonl")
        with open(jsonl, "w") as f:
            for line in (
                {"event": "run_begin", "ts": 0.0, "pid": 1,
                 "monitor_enabled": True, "meta": {"source": "selftest"}},
                {"step": 1, "ts": 0.1, "dur_ms": 10.0, "loss": 2.5,
                 "ips": 100.0, "counters": {"jit/retraces": 1}},
                {"step": 2, "ts": 0.2, "dur_ms": 9.0, "loss": 2.4,
                 "ips": 110.0},
                {"event": "run_end", "ts": 0.3, "steps": 2, "wall_s": 0.02,
                 "goodput": {"wall_s": 10.0,
                             "buckets": {"productive_step": 8.0,
                                         "compile": 1.5,
                                         "checkpoint_save_blocking": 0.25,
                                         "nan_replay_or_skip": 0.0,
                                         "restore_resume": 0.0,
                                         "input_wait": 0.25,
                                         "other": 0.0},
                             "goodput_frac": 0.8, "steps": 2,
                             "nan_steps": 0},
                 "totals": {"counters": {
                     "serving/admits": 2, "serving/evictions": 2,
                     "serving/prefill_steps": 4, "serving/decode_steps": 9,
                     "serving/prefix_hit_tokens": 16,
                     "serving/prefix_miss_tokens": 48},
                     "histograms": {}, "gauges": {}},
                 "live": {"steps": 9, "sketches": {
                     "ttft_ms": {"count": 2, "sum": 52.0, "p50": 12.3,
                                 "p90": 40.1, "p99": 40.1}},
                     "slo": {"targets": {"ttft_ms_p99": 25.0,
                                         "tpot_ms_p99": None},
                             "breaches": 1,
                             "worst_burn": {"ttft_ms": 50.0},
                             "last_burn": {"ttft_ms": {"fast": 50.0,
                                                       "slow": 11.1}},
                             "fast_window_steps": 12,
                             "slow_window_steps": 120,
                             "burn_fast_threshold": 14.0,
                             "burn_slow_threshold": 6.0}}},
            ):
                f.write(json.dumps(line) + "\n")
        trace = os.path.join(td, "trace.json")

        def _req(i, total, queue, prefill, decode, preempted, pre=0):
            return {"ph": "X", "name": "serving/request",
                    "cat": "serving_finish", "pid": 1,
                    "tid": f"req/r{i}", "ts": i * 1000.0, "dur": total * 1e3,
                    "args": {"request": i, "trace_id": f"r{i}",
                             "tokens": 8, "preemptions": pre,
                             "total_ms": total, "queue_ms": queue,
                             "prefill_ms": prefill, "decode_ms": decode,
                             "preempted_ms": preempted,
                             "prefill_refunded_tokens": 0,
                             "spec_rounds": 0, "accepted_tokens": 0}}

        with open(trace, "w") as f:
            json.dump({"traceEvents": [
                {"ph": "M", "name": "thread_name", "pid": 1, "tid": "steps",
                 "args": {"name": "steps"}},
                {"ph": "X", "name": "step/1", "cat": "step", "pid": 1,
                 "tid": "steps", "ts": 0.0, "dur": 10000.0},
                {"ph": "X", "name": "sync/fence", "cat": "sync", "pid": 1,
                 "tid": "host", "ts": 2000.0, "dur": 3000.0},
                _req(1, 40.0, 5.0, 10.0, 25.0, 0.0),
                _req(2, 90.0, 20.0, 10.0, 40.0, 20.0, pre=1),
            ]}, f)
        bench = os.path.join(td, "bench.log")
        with open(bench, "w") as f:
            f.write(json.dumps({
                "metric": "serving_tokens_per_sec", "value": 123.4,
                "unit": "tokens/s", "ttft_ms_p50": 12.0,
                "attribution": {
                    "queue_ms_mean": 12.5, "prefill_ms_mean": 10.0,
                    "decode_ms_mean": 32.5, "preempted_ms_mean": 10.0,
                    "total_ms_mean": 65.0, "phase_sum_vs_total": 1.0,
                    "queue_share": 0.1923, "queue_ms_p99": 20.0,
                    "prefill_refunded_tokens": 4, "spec_rounds": 3,
                    "accepted_tokens": 5},
                "goodput": {"wall_s": 5.0,
                            "buckets": {"productive_step": 4.0,
                                        "compile": 1.0},
                            "goodput_frac": 0.8, "steps": 4},
                "telemetry": {"serving": {"admits": 2, "evictions": 2,
                                          "prefill_steps": 4,
                                          "decode_steps": 9}}}) + "\n")
        metrics_file = os.path.join(td, "metrics.txt")
        with open(metrics_file, "w") as f:
            f.write("\n".join((
                "# TYPE pt_router_dispatches counter",
                'pt_router_dispatches_total{replica="0"} 6',
                'pt_router_dispatches_total{replica="1"} 2',
                "# TYPE pt_live_ttft_ms summary",
                'pt_live_ttft_ms{quantile="0.5"} 12.3',
                'pt_live_ttft_ms{quantile="0.9"} 40.1',
                'pt_live_ttft_ms{quantile="0.99"} 40.1',
                "pt_live_ttft_ms_count 2",
                "pt_live_ttft_ms_sum 52.0",
                "# TYPE pt_slo_breaches counter",
                "pt_slo_breaches_total 1",
                "# TYPE pt_slo_target_ms gauge",
                'pt_slo_target_ms{metric="ttft_ms"} 25.0',
                "# TYPE pt_slo_burn_rate gauge",
                'pt_slo_burn_rate{metric="ttft_ms",window="fast"} 50.0',
                'pt_slo_burn_rate{metric="ttft_ms",window="slow"} 11.1',
                "# EOF", "")))
        # fleet fixture: 3 workers' heartbeat JSONL with an injected
        # straggler (rank 2 at step 2: 50ms vs fleet median 5ms) and a
        # dp desync (rank 2's step-3 loss diverges) — the offline
        # detectors in load_fleet() must latch + name both
        hb_dir = os.path.join(td, "heartbeats")
        os.makedirs(hb_dir)
        beats = {
            0: [(1, 5.0, 2.50), (2, 5.0, 2.40), (3, 5.0, 2.30)],
            1: [(1, 5.0, 2.50), (2, 5.0, 2.40), (3, 5.0, 2.30)],
            2: [(1, 5.0, 2.50), (2, 50.0, 2.40), (3, 5.0, 9.99)],
        }
        for rank, rows in beats.items():
            with open(os.path.join(hb_dir,
                                   f"heartbeat.{rank}.jsonl"), "w") as f:
                for step, ms, loss in rows:
                    f.write(json.dumps(
                        {"rank": rank, "step": step, "ts": 100.0 + step,
                         "step_ms": ms, "loss": loss,
                         "goodput": {"productive_step": 4.0,
                                     "compile": 1.0}}) + "\n")
        report = render(jsonl, trace_path=trace, top=5, spans=True,
                        bench_path=bench, metrics_path=metrics_file,
                        fleet_path=hb_dir)
        needed = (
            "-- run --",
            "-- counters (run total) --",
            "-- serving (continuous batching) --",
            "-- SLO / live windows --",
            "-- SLO / live windows (/metrics)",
            "-- goodput (where did the time go) --",
            "-- fleet (launcher workers)",
            "-- bench line:",
            "-- serving (continuous batching) (bench) --",
            "-- request attribution (phase means, ms) (bench) --",
            "-- goodput (where did the time go) (bench) --",
            "-- requests (slowest 2 of 2 journeys, ms) --",
            "-- retrace timeline --",
            "-- span attribution (host wall decomposition) --",
        )
        missing = [m for m in needed if m not in report]
        # the run_end live snapshot's SLO state must land in the text
        slo_ok = ("breaches: 1" in report
                  and "ttft_ms 25 ms" in report
                  and "replica 0" in report and "(75%)" in report)
        # the slowest journey must lead the requests table
        order_ok = report.find("r2") < report.find("r1") \
            or "r2" not in report
        # goodput: the exact-telescope proof + fraction must render
        gp_ok = ("goodput_frac: 0.8000" in report
                 and "(telescopes exactly)" in report)
        # fleet: both injected verdicts must latch and name rank 2
        fleet_ok = ("STRAGGLER: rank 2 at step 2" in report
                    and "DP DESYNC: ranks [0, 2] at step 3" in report)
        if missing or not order_ok or not slo_ok or not gp_ok \
                or not fleet_ok:
            print(report)
            print(f"selftest FAILED: missing={missing} "
                  f"order_ok={order_ok} slo_ok={slo_ok} "
                  f"gp_ok={gp_ok} fleet_ok={fleet_ok}",
                  file=sys.stderr)
            return 1
        print(f"monitor_report selftest ok "
              f"({len(report.splitlines())} lines, "
              f"{len(needed)} sections present)")
        return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Summarize a monitor JSONL run, optionally joined "
                    "with a profiler chrome trace.")
    ap.add_argument("jsonl", nargs="?", default=None,
                    help="StepLogger JSONL file")
    ap.add_argument("--trace", default=None,
                    help="chrome trace JSON from profiler.export or "
                         "monitor.export_spans")
    ap.add_argument("--top", type=int, default=10,
                    help="top-N ops from the trace (default 10)")
    ap.add_argument("--spans", nargs="?", const=True, default=False,
                    metavar="TRACE",
                    help="attribute host wall time per step into "
                         "{sync, fence_wait, prefetch_starvation, compile, "
                         "dispatch, other} from the flight-recorder spans "
                         "(in --trace, or in the given file)")
    ap.add_argument("--bench", default=None, metavar="LOG",
                    help="bench log/JSON line: render its guard verdict "
                         "and memory sub-object next to the run")
    ap.add_argument("--metrics", default=None, metavar="FILE",
                    help="saved /metrics OpenMetrics exposition "
                         "(monitor/exporter.py): render its SLO/live "
                         "view incl. per-replica dispatch share")
    ap.add_argument("--fleet", default=None, metavar="DIR-or-JSON",
                    help="launcher fleet view: a fleet.json snapshot, "
                         "or the PT_HEARTBEAT_DIR itself (straggler / "
                         "dp-desync / silent-worker detectors re-run "
                         "offline over the raw heartbeat JSONL)")
    ap.add_argument("--selftest", action="store_true",
                    help="render a synthesized run and assert every "
                         "section appears (tier-1 smoke; no jsonl needed)")
    args = ap.parse_args(argv)
    if args.selftest:
        return _selftest()
    if args.jsonl is None:
        ap.error("jsonl is required (or pass --selftest)")
    report = render(args.jsonl, trace_path=args.trace, top=args.top,
                    spans=args.spans, bench_path=args.bench,
                    metrics_path=args.metrics, fleet_path=args.fleet)
    print(report)
    return report


if __name__ == "__main__":
    _rc = main()
    sys.exit(_rc if isinstance(_rc, int) else 0)
