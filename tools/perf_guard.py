#!/usr/bin/env python
"""Perf regression guard: fresh bench line vs the last-good hardware record.

    python tools/perf_guard.py fresh.json [--store PERF_MEASUREMENTS.json]
    some_bench | tail -1 | python tools/perf_guard.py -

``fresh.json`` holds a bench's one-line JSON (the last parseable object
with a ``metric`` key wins, so a whole bench log can be piped in; ``-``
reads stdin). The guard compares it against the most recent real-hardware
record for the same metric in the measurement store
(``PERF_MEASUREMENTS.json`` — see ``paddle_tpu/utils/measurements.py``)
and exits nonzero with a human-readable verdict when the run regressed:

- throughput below last-good by more than ``--throughput-drop`` (10%)
- MFU below last-good by more than ``--mfu-drop`` (10%)
- peak HBM above last-good by more than ``--hbm-growth`` (10%): the step
  got hungrier — the config that fit yesterday may OOM tomorrow
  (``peak_hbm_gib`` from the line or its ``memory`` sub-object, vs the
  baseline record's ``extra.peak_hbm_gib``)
- cold-start compile wall-time above last-good by more than
  ``--compile-growth`` (50%) **and** ``--compile-slack-ms`` (2000 ms)
  absolute: ``telemetry.compile_ms_total`` vs the baseline record's
  ``extra.compile_ms_total``. This is the executable-cache regression
  gate (``jit/exec_cache.py``): a warm ``PT_EXEC_CACHE`` run pays ~0
  compile ms, so a cache that stops hitting (key churn, serialization
  break) fails the bench the same way a throughput drop does; the
  absolute slack keeps sub-second compile noise from tripping it
- serving p99 time-to-first-token above last-good by more than
  ``--ttft-growth`` (25%): ``ttft_ms_p99`` from a
  ``benchmarks/serving_bench.py`` line vs the baseline record's
  ``extra.ttft_ms_p99`` — the tail-latency gate; the aggregate tokens/s
  drop is the same ``--throughput-drop`` check every metric gets
- serving ``prefix_hit_rate`` below last-good by more than
  ``--prefix-hit-drop`` (25%): the shared-prompt trace stopped sharing
  KV blocks (chain-key churn or a publish regression in
  ``serving/kv_cache.py``'s prefix index) — the cached-TTFT win
  evaporated even when this run's tail happens to pass. Skipped when
  either side lacks the field or the baseline rate is 0
- serving speculative ``accept_rate`` below last-good by more than
  ``--accept-drop`` (25%): the drafter stopped matching the workload
  (``serving/speculative.py`` regression or a verify-step acceptance
  bug) — the tokens-per-decode-step multiplier evaporated. Spec-off
  lines never carry the field, so they skip; ``spec``/``spec_k`` are
  sweep-config keys, so spec and plain serving rows never cross-judge
- serving router ``affinity_hit_rate`` below last-good by more than
  ``--affinity-drop`` (25%): the multi-replica router stopped routing
  same-prefix requests to the replica that already holds their KV
  blocks (affinity-index churn or a dispatch regression in
  ``serving/router.py``) — every replica re-prefills the shared prompt
  and the scale-out win evaporated. Single-engine lines never carry
  the field, so they skip; ``replicas`` is a sweep-config key, so
  routed and single-engine rows never cross-judge
- ``goodput_frac`` below last-good by more than ``--goodput-drop``
  (10%): the run's goodput ledger (``monitor/goodput.py`` — the
  wall-clock share spent in ``productive_step``; ``bench.py`` and
  ``tools/soak.py`` lines carry it) says the same workload now burns
  its wall somewhere unproductive — compile storm, checkpoint stalls,
  or input waits; the line's ``goodput`` buckets name which. Skipped
  when either side lacks the field or the baseline is 0
- a changed sharding plan (``--plan-drift``): a fresh hardware line
  whose ``shard_plan`` sub-object (from ``tools/shard_plan.py``) names
  a different (dp, mp, pp, batch) than the last-good record's
  ``extra.shard_plan`` for the SAME device count (pre-PP records read
  as pp=1 baselines) — a silently-changed
  cost model must not flip production sharding without a human reading
  this verdict. Missing baselines, missing plan fields, other
  topologies, and CPU smokes skip the check
- a fresh SLO breach (``--slo-breach``): a fresh hardware line whose
  ``slo`` sub-object (``serving_bench`` with the live telemetry plane
  armed — docs/OBSERVABILITY.md) reports ``breaches > 0`` when the
  last-good record's ``extra.slo`` had zero — the burn-rate watchdog
  fired on a trace that used to meet its ``PT_SLO_*`` targets. The
  target values are sweep-config keys, so lines judged against
  different targets never cross-compare; lines or baselines without
  the sub-object (live plane off, pre-SLO records) skip, CPU smokes
  skip with the rest
- a new compiled-program audit finding (``--audit``): a fresh hardware
  line whose ``program_audit`` sub-object (``analysis/program_audit.py``,
  armed by ``PT_PROGRAM_AUDIT=1``) reports a (rule, label) finding
  absent from the last-good record's ``extra.program_audit`` —
  replicated-dp compute, dropped donation, host callbacks, or retrace
  churn appeared since the baseline. Lines or baselines without the
  sub-object skip the check; CPU smokes skip with the rest of the
  hardware comparisons
- a Pallas kernel family engaged in the last-good record but running on
  the composite in the fresh line (``kernels`` sub-object — the
  ``{family: engaged}`` map benches embed from
  ``ops.pallas.search.engagement_report``): a lost engagement means the
  tune table stopped matching (device change, key churn, a deleted
  row) and the measured win silently evaporated. Families absent from
  the fresh line are wildcards; CPU smokes skip the check
- any post-warmup retrace (``telemetry.post_warmup_retraces`` > 0): a
  shape changed inside the timed loop, so the number includes an XLA
  compile and the next run won't reproduce it
- prefetch starvation rate above ``--max-starvation-rate``: the loader,
  not the device, bounded the measurement
- a zero/absent value or an embedded ``error`` field (the bench died)

CPU smoke lines (an explicit CPU run) skip the hardware comparisons — a laptop
number vs a TPU record is not a regression — but still fail on retrace
storms and errors. ``bench.py`` embeds this module's verdict in its JSON
line (``"guard"`` sub-object) and ``tools/hwbench.py`` prints it per
bench, so a silent regression can't land in the measurement store
unnoticed.

Pure stdlib: runs anywhere the artifacts land, no jax import.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_THRESHOLDS = {
    # fractional drop vs last-good before the check fails
    "throughput_drop": 0.10,
    "mfu_drop": 0.10,
    # any retrace after warmup is a storm: the timed loop recompiled
    "max_post_warmup_retraces": 0,
    # starvations per timed step before the run counts as input-bound
    "max_starvation_rate": 0.25,
    # fractional peak-HBM growth vs last-good before the check fails
    "hbm_growth": 0.10,
    # cold-start compile wall-time vs last-good: fails only past BOTH the
    # fractional growth and the absolute slack (compile time is noisy at
    # small absolute values; a lost exec-cache warm start is neither)
    "compile_growth": 0.50,
    "compile_slack_ms": 2000.0,
    # serving gate: fractional p99 time-to-first-token growth vs the
    # last-good record before the check fails (serving_bench lines carry
    # ttft_ms_p99; the aggregate tokens/s drop rides the generic
    # throughput check — the metric's value IS tokens/s)
    "ttft_growth": 0.25,
    # request-attribution gate: fractional growth of serving_bench's
    # attribution.queue_share (mean queue-wait fraction of end-to-end
    # request latency) vs the last-good record before the check fails —
    # a grown queue share means requests wait longer for lanes at the
    # SAME workload (scheduler regression, slower prefill backing up
    # admissions, or shrunk effective pool). Only fails past BOTH the
    # fractional growth and the absolute slack (tiny shares are noisy:
    # 0.01 → 0.02 is not a regression); skips when either side lacks
    # the attribution sub-object or the baseline share is 0
    "queue_share_growth": 0.25,
    "queue_share_slack": 0.05,
    # prefix-cache gate: fractional drop of serving_bench's
    # prefix_hit_rate vs the last-good record before the check fails —
    # a collapsed hit rate means the shared-prompt workload stopped
    # sharing (chain-key churn, publish regression, or cold-LRU
    # thrash) and the TTFT win silently evaporated. Skips when either
    # side lacks the field or the baseline rate is 0 (a trace with no
    # shared prefix pins nothing), and on CPU smokes with the rest
    "prefix_hit_drop": 0.25,
    # speculative-decoding gate: fractional drop of serving_bench's
    # accept_rate (accepted/proposed draft tokens) vs the last-good
    # record before the check fails — a collapsed accept rate means the
    # drafter stopped matching the workload (drafter regression, trace
    # change, or a verify-step acceptance bug) and the
    # tokens-per-decode-step win silently evaporated. Skips when either
    # side lacks the field (spec-off lines never carry it) or the
    # baseline rate is 0, and on CPU smokes with the rest
    "accept_drop": 0.25,
    # replica-router gate: fractional drop of serving_bench's
    # affinity_hit_rate (router dispatches that landed on a replica
    # already holding the prompt's prefix blocks) vs the last-good
    # record before the check fails — a collapsed hit rate means every
    # replica re-prefills the shared prompt (affinity-index churn or a
    # dispatch regression) and the multi-replica TTFT win silently
    # evaporated. Skips when either side lacks the field (single-engine
    # lines never carry it) or the baseline rate is 0, and on CPU
    # smokes with the rest
    "affinity_drop": 0.25,
    # resilience gate: fractional growth of the blocking checkpoint-save
    # cost (tools/soak.py lines carry ckpt_save_ms_p50 — the quiesce +
    # host-snapshot time the cadence planner budgets against) vs the
    # last-good record, past an absolute slack (small-model saves are
    # noisy at single-digit ms)
    "save_cost_growth": 0.50,
    "save_cost_slack_ms": 250.0,
    # goodput gate (--goodput-drop): fractional drop of the line's
    # goodput_frac (wall-clock share spent in productive_step — the
    # run's goodput ledger, monitor/goodput.py; bench.py and
    # tools/soak.py lines carry it) vs the last-good record before the
    # check fails — a collapsed goodput fraction means the same
    # workload now burns its wall somewhere unproductive (compile
    # storm, checkpoint stalls, input waits). Skips when either side
    # lacks the field or the baseline is 0, and on CPU smokes with
    # the rest
    "goodput_drop": 0.10,
    # sharding-plan drift gate: on by default; --no-plan-drift disables
    "plan_drift": True,
    # program-audit gate (--audit / --no-audit): a fresh hardware line
    # whose program_audit sub-object (analysis/program_audit.py,
    # PT_PROGRAM_AUDIT=1) reports findings ABSENT from the last-good
    # record fails — a compiled-invariant break (replicated dp, dropped
    # donation, host callbacks, retrace churn) must not land silently.
    # CPU smokes and baselines without the sub-object skip, matching the
    # --ttft-growth convention
    "audit": True,
    # SLO-breach gate (--slo-breach / --no-slo-breach): a fresh
    # hardware line whose slo sub-object (serving_bench with the live
    # plane armed) counts breaches > 0 fails when the last-good record
    # breached zero times at the SAME PT_SLO_* targets — a latency
    # regression crossed the burn-rate watchdog's line, not just a
    # percentile wiggle. Both-sides-have-the-sub-object required;
    # baselines that already breached ride forward (fixing the SLO is
    # a separate act from regressing it)
    "slo_breach": True,
}


def peak_hbm_of(line: dict) -> float | None:
    """``peak_hbm_gib`` from a bench line (top level or the ``memory``
    sub-object) — the one accessor both the gate and the report use."""
    v = line.get("peak_hbm_gib")
    if v is None:
        v = (line.get("memory") or {}).get("peak_hbm_gib")
    return v


def _default_store() -> str:
    override = os.environ.get("PT_MEASUREMENTS_PATH")
    if override:
        return override
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(here), "PERF_MEASUREMENTS.json")


def find_bench_line(text: str) -> dict | None:
    """Last parseable JSON object with a ``metric`` key in ``text`` —
    tolerates a bench's full stdout log. The ONE scanner for bench lines
    (the CLI and tools/hwbench.py both call it, so the format can't
    drift between them)."""
    found = None
    for raw in text.splitlines():
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        try:
            obj = json.loads(raw)
        except ValueError:
            continue
        if isinstance(obj, dict) and "metric" in obj:
            found = obj
    return found


def load_fresh(path: str) -> dict:
    """:func:`find_bench_line` over a file (``-`` = stdin)."""
    text = sys.stdin.read() if path == "-" else open(path).read()
    found = find_bench_line(text)
    if found is None:
        raise ValueError(f"no bench JSON line (object with a 'metric' "
                         f"key) found in {path!r}")
    return found


# sweep knobs that change what the number measures: a baseline is only
# comparable at the same config (CLAUDE.md PT_BENCH_BATCH / ce-chunk A/Bs
# persist under the SAME metric name). The serving keys pin the bench's
# offered load + engine geometry (int8_weights also rides decode_bench
# lines): a 64-request trace legitimately queues deeper than a
# 32-request one, so judging p99 TTFT across them would false-fail (or
# mask) the gate. Keys a baseline record predates are wildcards — see
# last_good.
CONFIG_KEYS = ("batch", "seq", "ce_chunk",
               "requests", "arrival_rate_per_s", "lanes", "block_size",
               "int8_weights", "kv_int8", "devices", "pp",
               "shared_prefix_tokens", "prefix_cache", "spec", "spec_k",
               "replicas", "slo_ttft_ms_p99", "slo_tpot_ms_p99")

# keys whose ABSENCE from an old record means the knob's default, not a
# wildcard: records persisted before the prefix cache existed WERE
# shared=0 / cache-on runs, so a fresh shared-prefix line must not
# judge itself against them (a 64-token-longer-prompt workload), while
# a fresh plain line keeps its pre-PR baselines. Likewise records from
# before speculative decoding were plain-decode (spec-off) runs: a
# fresh spec-on line gets no pre-spec baseline, a fresh spec-off line
# keeps its history
# ... and pp: records persisted before the planner's pipeline axis
# existed WERE pp=1 runs, so a fresh pp>1 row never judges itself
# against them while pp=1 rows keep their pre-PP baselines
# ... and replicas: records persisted before the multi-replica router
# existed WERE single-engine (replicas=1) runs, so a fresh routed row
# never judges itself against them while single-engine rows keep their
# pre-router baselines
# ... and kv_int8: records persisted before the int8 KV pool existed
# WERE bf16-pool runs — an int8 line reads half the KV bytes per
# decode step, so letting it judge (or be judged by) a bf16 baseline
# would cross-compare different byte models
CONFIG_KEY_DEFAULTS = {"shared_prefix_tokens": 0, "prefix_cache": True,
                       "spec": False, "spec_k": 0, "pp": 1,
                       "replicas": 1, "kv_int8": False,
                       # absent = no SLO target armed (pre-live-plane
                       # records and target-off runs are the same config)
                       "slo_ttft_ms_p99": None, "slo_tpot_ms_p99": None}


def config_match(fresh: dict) -> dict:
    """The sweep-config filter a fresh line implies: ``{key: value}`` for
    every :data:`CONFIG_KEYS` entry the line carries."""
    return {k: fresh[k] for k in CONFIG_KEYS if k in fresh}


def last_good(store_path: str, metric: str, fresh: dict | None = None,
              match: dict | None = None) -> dict | None:
    """Most recent real-hardware record for ``metric`` — the stdlib twin
    of ``utils/measurements.last_good`` (this tool must run with no
    package import, e.g. on a box that only has the artifacts).

    Benches persist their number BEFORE the guard runs, so when judging a line that may
    already be in the store pass it as ``fresh``: the newest records
    whose value matches it are skipped — comparing a run to itself would
    make the gate always-pass. ``match`` filters on the record's
    ``extra`` fields (e.g. ``{"batch": 8, "seq": 1024}``) so A/B sweep
    points at other configs are skipped instead of becoming a false
    baseline."""
    try:
        with open(store_path) as f:
            data = json.load(f)
        records = data.get("records", [])
    except (OSError, ValueError):
        return None
    skipping_self = fresh is not None
    for rec in reversed(records):
        if not (isinstance(rec, dict) and rec.get("metric") == metric
                and rec.get("backend") not in (None, "cpu", "unknown")):
            continue
        ex = rec.get("extra") or {}
        # a key ABSENT from a record's extra is a wildcard, not a
        # mismatch: records persisted before a config knob existed
        # (e.g. pre-serving decode lines without int8_weights) must
        # stay eligible baselines for the gates they anchored —
        # except CONFIG_KEY_DEFAULTS keys, where absence means the
        # knob's default value (pre-knob behavior)
        if match and any(
                (ex[k] if k in ex else CONFIG_KEY_DEFAULTS.get(k, v))
                != v for k, v in match.items()):
            continue
        if skipping_self and rec.get("value") == fresh.get("value"):
            continue
        # past the newest self-matching records, stop skipping: an older
        # record that happens to share the value is a real baseline
        skipping_self = False
        return rec
    return None


def _is_cpu_smoke(fresh: dict) -> bool:
    note = str(fresh.get("note", ""))
    return "cpu smoke" in note or fresh.get("platform") == "cpu"


def evaluate(fresh: dict, baseline: dict | None, thresholds: dict | None
             = None, hardware: bool | None = None) -> dict:
    """Check a fresh bench line; returns the verdict dict.

    ``hardware=False`` (default: inferred from the line's CPU-smoke
    markers) skips the throughput/MFU comparison — the runtime-health
    checks (error, retrace storm, starvation) always apply.
    """
    th = dict(DEFAULT_THRESHOLDS)
    if thresholds:
        th.update(thresholds)
    if hardware is None:
        hardware = not _is_cpu_smoke(fresh)
    checks = []

    def check(name, ok, detail):
        checks.append({"name": name, "ok": bool(ok), "detail": detail})

    err = fresh.get("error")
    value = fresh.get("value") or 0.0
    check("emitted", err is None and value > 0,
          f"error: {err}" if err is not None else f"value {value}")

    tel = fresh.get("telemetry") or {}
    pwr = tel.get("post_warmup_retraces")
    if pwr is not None:
        check("retraces", pwr <= th["max_post_warmup_retraces"],
              f"{pwr} post-warmup retrace(s)" + (
                  " — the timed loop recompiled (shape churn); the "
                  "number includes an XLA compile" if pwr else ""))
    starved = tel.get("prefetch_starvations")
    steps = tel.get("steps")
    if starved is not None and steps:
        rate = starved / steps
        check("starvation", rate <= th["max_starvation_rate"],
              f"{starved} starvation(s) / {steps} steps = {rate:.2f} "
              f"(max {th['max_starvation_rate']})")

    compared = False
    if hardware and baseline is not None and baseline.get("value"):
        compared = True
        base_v = baseline["value"]
        drop = 1.0 - value / base_v
        check("throughput", drop <= th["throughput_drop"],
              f"{value:.2f} vs last-good {base_v:.2f} "
              f"({'-' if drop > 0 else '+'}{abs(drop) * 100:.1f}%, "
              f"max drop {th['throughput_drop'] * 100:.0f}%)")
        mfu = fresh.get("mfu")
        base_mfu = (baseline.get("extra") or {}).get("mfu")
        if mfu and base_mfu:
            mdrop = 1.0 - mfu / base_mfu
            check("mfu", mdrop <= th["mfu_drop"],
                  f"{mfu:.4f} vs last-good {base_mfu:.4f} "
                  f"({'-' if mdrop > 0 else '+'}{abs(mdrop) * 100:.1f}%)")
        cms = tel.get("compile_ms_total")
        base_cms = (baseline.get("extra") or {}).get("compile_ms_total")
        # cache-on vs cache-off is an A/B dimension like batch size: a
        # run without PT_EXEC_CACHE judged against a warm-cache 0 ms
        # baseline would false-fail, so mismatched states skip the gate
        base_ec = (baseline.get("extra") or {}).get("exec_cache_enabled")
        if base_ec is not None and bool(base_ec) != bool(
                tel.get("exec_cache")):
            cms = None
        # presence check, not truthiness: 0.0 is the HEALTHY warm-cache
        # baseline, and the zero→huge cold start is exactly the
        # regression this gate exists to catch (growth is undefined
        # there — gate on the absolute slack alone)
        if cms is not None and base_cms is not None:
            over = cms - base_cms
            if base_cms > 0:
                growth = cms / base_cms - 1.0
                failed = (growth > th["compile_growth"]
                          and over > th["compile_slack_ms"])
                detail = (f"{cms:.0f} ms vs last-good {base_cms:.0f} ms "
                          f"({'+' if growth > 0 else '-'}"
                          f"{abs(growth) * 100:.1f}%, max growth "
                          f"{th['compile_growth'] * 100:.0f}% past "
                          f"{th['compile_slack_ms']:.0f} ms slack)")
            else:
                failed = over > th["compile_slack_ms"]
                detail = (f"{cms:.0f} ms vs last-good 0 ms (warm-cache "
                          f"baseline; max {th['compile_slack_ms']:.0f} ms "
                          f"slack)")
            check("compile_ms", not failed, detail
                  + (" — exec cache stopped saving compiles "
                     "(jit/exec_cache.py key churn or a dead disk tier?)"
                     if failed else ""))
        ttft = fresh.get("ttft_ms_p99")
        base_ttft = (baseline.get("extra") or {}).get("ttft_ms_p99")
        if ttft and base_ttft:
            tgrowth = ttft / base_ttft - 1.0
            check("ttft_p99", tgrowth <= th["ttft_growth"],
                  f"{ttft:.1f} ms vs last-good {base_ttft:.1f} ms "
                  f"({'+' if tgrowth > 0 else '-'}"
                  f"{abs(tgrowth) * 100:.1f}%, max growth "
                  f"{th['ttft_growth'] * 100:.0f}%)"
                  + (" — tail latency regressed (scheduler queueing or "
                     "prefill got slower)" if tgrowth > th["ttft_growth"]
                     else ""))
        qs = (fresh.get("attribution") or {}).get("queue_share")
        base_qs = ((baseline.get("extra") or {}).get("attribution")
                   or {}).get("queue_share")
        if qs is not None and base_qs:
            qgrowth = qs / base_qs - 1.0
            qover = qs - base_qs
            qfail = (qgrowth > th["queue_share_growth"]
                     and qover > th["queue_share_slack"])
            check("queue_share", not qfail,
                  f"queue share {qs:.3f} vs last-good {base_qs:.3f} "
                  f"({'+' if qgrowth > 0 else '-'}"
                  f"{abs(qgrowth) * 100:.1f}%, max growth "
                  f"{th['queue_share_growth'] * 100:.0f}% past "
                  f"{th['queue_share_slack']:.2f} absolute slack)"
                  + (" — requests wait longer for lanes at the same "
                     "workload (scheduler regression, slower prefill, "
                     "or a shrunk effective pool?)" if qfail else ""))
        phr = fresh.get("prefix_hit_rate")
        base_phr = (baseline.get("extra") or {}).get("prefix_hit_rate")
        if phr is not None and base_phr:
            pdrop = 1.0 - phr / base_phr
            check("prefix_hit", pdrop <= th["prefix_hit_drop"],
                  f"hit rate {phr:.3f} vs last-good {base_phr:.3f} "
                  f"({'-' if pdrop > 0 else '+'}{abs(pdrop) * 100:.1f}%,"
                  f" max drop {th['prefix_hit_drop'] * 100:.0f}%)"
                  + (" — prefix sharing collapsed (chain-key churn, a "
                     "publish regression, or cold-LRU thrash?)"
                     if pdrop > th["prefix_hit_drop"] else ""))
        ar = fresh.get("accept_rate")
        base_ar = (baseline.get("extra") or {}).get("accept_rate")
        if ar is not None and base_ar:
            adrop = 1.0 - ar / base_ar
            check("accept_rate", adrop <= th["accept_drop"],
                  f"accept rate {ar:.3f} vs last-good {base_ar:.3f} "
                  f"({'-' if adrop > 0 else '+'}{abs(adrop) * 100:.1f}%,"
                  f" max drop {th['accept_drop'] * 100:.0f}%)"
                  + (" — speculation stopped accepting (drafter "
                     "regression, workload change, or a verify-step "
                     "acceptance bug?)"
                     if adrop > th["accept_drop"] else ""))
        ahr = fresh.get("affinity_hit_rate")
        base_ahr = (baseline.get("extra") or {}).get("affinity_hit_rate")
        if ahr is not None and base_ahr:
            hdrop = 1.0 - ahr / base_ahr
            check("affinity_hit", hdrop <= th["affinity_drop"],
                  f"affinity hit rate {ahr:.3f} vs last-good "
                  f"{base_ahr:.3f} "
                  f"({'-' if hdrop > 0 else '+'}{abs(hdrop) * 100:.1f}%,"
                  f" max drop {th['affinity_drop'] * 100:.0f}%)"
                  + (" — prefix-affinity dispatch collapsed (affinity-"
                     "index churn or a router dispatch regression?)"
                     if hdrop > th["affinity_drop"] else ""))
        sms = fresh.get("ckpt_save_ms_p50")
        base_sms = (baseline.get("extra") or {}).get("ckpt_save_ms_p50")
        if sms is not None and base_sms:
            sgrowth = sms / base_sms - 1.0
            sover = sms - base_sms
            sfail = (sgrowth > th["save_cost_growth"]
                     and sover > th["save_cost_slack_ms"])
            check("ckpt_save_ms", not sfail,
                  f"{sms:.1f} ms vs last-good {base_sms:.1f} ms "
                  f"({'+' if sgrowth > 0 else '-'}"
                  f"{abs(sgrowth) * 100:.1f}%, max growth "
                  f"{th['save_cost_growth'] * 100:.0f}% past "
                  f"{th['save_cost_slack_ms']:.0f} ms slack)"
                  + (" — checkpointing got more expensive (the cadence "
                     "planner will save less often for the same "
                     "overhead budget)" if sfail else ""))
        gf = fresh.get("goodput_frac")
        base_gf = (baseline.get("extra") or {}).get("goodput_frac")
        if gf is not None and base_gf:
            gdrop = 1.0 - gf / base_gf
            check("goodput_frac", gdrop <= th["goodput_drop"],
                  f"goodput {gf:.3f} vs last-good {base_gf:.3f} "
                  f"({'-' if gdrop > 0 else '+'}{abs(gdrop) * 100:.1f}%,"
                  f" max drop {th['goodput_drop'] * 100:.0f}%)"
                  + (" — the run's wall-clock went unproductive "
                     "(compile storm, checkpoint stalls, or input "
                     "waits — read the goodput buckets in the line)"
                     if gdrop > th["goodput_drop"] else ""))
        plan = fresh.get("shard_plan")
        base_plan = (baseline.get("extra") or {}).get("shard_plan")
        if (th.get("plan_drift") and isinstance(plan, dict)
                and isinstance(base_plan, dict)
                and plan.get("devices") == base_plan.get("devices")):
            # pp default 1: records from before the planner's pipeline
            # axis existed were pp=1 plans, not wildcards
            def _axis(p, k):
                return p.get(k, 1 if k == "pp" else None)

            drift = [k for k in ("dp", "mp", "pp", "batch")
                     if _axis(plan, k) != _axis(base_plan, k)]
            check("plan_drift", not drift,
                  (f"planned dp{plan.get('dp')}×mp{plan.get('mp')}"
                   f"×pp{_axis(plan, 'pp')} "
                   f"b{plan.get('batch')} matches last-good"
                   if not drift else
                   f"plan changed for the same topology "
                   f"({plan.get('devices')} devices): "
                   + ", ".join(f"{k} {_axis(base_plan, k)}→{_axis(plan, k)}"
                               for k in drift)
                   + " — the cost model flipped production sharding; "
                     "re-measure both configs before trusting it"))
        pa = fresh.get("program_audit")
        base_pa = (baseline.get("extra") or {}).get("program_audit")
        if (th.get("audit") and isinstance(pa, dict)
                and isinstance(base_pa, dict)):
            # a finding is "new" when its (rule, label) pair is absent
            # from the last-good record — known/accepted findings ride
            # the baseline forward, fresh invariant breaks fail
            known = {(f.get("rule"), f.get("label"))
                     for f in base_pa.get("findings", [])
                     if isinstance(f, dict)}
            new = [f for f in pa.get("findings", [])
                   if isinstance(f, dict)
                   and (f.get("rule"), f.get("label")) not in known]
            check("program_audit", not new,
                  ("no new compiled-program findings "
                   f"({len(pa.get('findings', []))} total, all in "
                   "baseline)" if not new else
                   "new compiled-program finding(s) vs last-good: "
                   + "; ".join(
                       f"{f.get('rule')} {f.get('name')} "
                       f"[{f.get('label')}]" for f in new)
                   + " — a program invariant broke since the baseline "
                     "(see analysis/program_audit.py)"))
        slo = fresh.get("slo")
        base_slo = (baseline.get("extra") or {}).get("slo")
        if (th.get("slo_breach") and isinstance(slo, dict)
                and isinstance(base_slo, dict)):
            # config-key matching already pinned the PT_SLO_* targets,
            # so both sides judged the same line in the sand; a
            # baseline that already breached rides forward (fixing an
            # SLO is a separate act from regressing into one)
            breaches = int(slo.get("breaches") or 0)
            base_breaches = int(base_slo.get("breaches") or 0)
            regressed = breaches > 0 and base_breaches == 0
            check("slo_breach", not regressed,
                  (f"{breaches} breach(es), last-good had "
                   f"{base_breaches}"
                   + (" — the burn-rate watchdog fired on a trace that "
                      "used to meet its SLO targets (worst burn "
                      f"{slo.get('worst_burn')}; see "
                      "docs/OBSERVABILITY.md)" if regressed else "")))
        kern = fresh.get("kernels")
        base_kern = (baseline.get("extra") or {}).get("kernels")
        if kern is not None and base_kern:
            # engaged in the baseline but composite now -> regression;
            # a family the fresh line doesn't report is a wildcard
            # (that bench simply didn't exercise it this run)
            lost = sorted(k for k, v in base_kern.items()
                          if v and kern.get(k) is False)
            check("kernel_engagement", not lost,
                  ("all engaged kernel families still engaged"
                   if not lost else
                   f"engaged in last-good but composite now: "
                   f"{', '.join(lost)} — tune-table row no longer "
                   f"matches (device change or key churn?)"))
        hbm = peak_hbm_of(fresh)
        base_hbm = (baseline.get("extra") or {}).get("peak_hbm_gib")
        if hbm and base_hbm:
            growth = hbm / base_hbm - 1.0
            check("peak_hbm", growth <= th["hbm_growth"],
                  f"{hbm:.2f} GiB vs last-good {base_hbm:.2f} GiB "
                  f"({'+' if growth > 0 else '-'}{abs(growth) * 100:.1f}%, "
                  f"max growth {th['hbm_growth'] * 100:.0f}%)")
    elif not hardware:
        check("hardware", True,
              "cpu smoke line — throughput not compared to the TPU record")

    verdict = {
        "ok": all(c["ok"] for c in checks),
        "checks": checks,
        "compared": compared,
    }
    if baseline is not None:
        verdict["baseline"] = {
            "value": baseline.get("value"),
            "commit": baseline.get("commit"),
            "timestamp": baseline.get("timestamp"),
        }
    return verdict


def format_verdict(metric: str, verdict: dict) -> str:
    lines = [f"== perf guard: {metric} =="]
    for c in verdict["checks"]:
        mark = "ok  " if c["ok"] else "FAIL"
        lines.append(f"  [{mark}] {c['name']:<12} {c['detail']}")
    base = verdict.get("baseline")
    if base:
        lines.append(f"  baseline: {base['value']} "
                     f"@ {base.get('commit', '?')} ({base.get('timestamp')})")
    elif verdict["compared"] is False:
        lines.append("  no last-good hardware baseline in the store")
    lines.append("verdict: " + (
        "PASS" if verdict["ok"]
        else "REGRESSION — do not trust/land this number"))
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Gate a fresh bench JSON line against the last-good "
                    "record in PERF_MEASUREMENTS.json.")
    ap.add_argument("fresh", help="file with the bench JSON line ('-' = "
                                  "stdin; a full bench log is fine)")
    ap.add_argument("--store", default=None,
                    help="measurement store (default: repo-root "
                         "PERF_MEASUREMENTS.json, or $PT_MEASUREMENTS_PATH)")
    ap.add_argument("--throughput-drop", type=float,
                    default=DEFAULT_THRESHOLDS["throughput_drop"],
                    help="max fractional throughput drop (default 0.10)")
    ap.add_argument("--mfu-drop", type=float,
                    default=DEFAULT_THRESHOLDS["mfu_drop"],
                    help="max fractional MFU drop (default 0.10)")
    ap.add_argument("--max-starvation-rate", type=float,
                    default=DEFAULT_THRESHOLDS["max_starvation_rate"],
                    help="max prefetch starvations per step (default 0.25)")
    ap.add_argument("--hbm-growth", type=float,
                    default=DEFAULT_THRESHOLDS["hbm_growth"],
                    help="max fractional peak-HBM growth (default 0.10)")
    ap.add_argument("--compile-growth", type=float,
                    default=DEFAULT_THRESHOLDS["compile_growth"],
                    help="max fractional compile wall-time growth vs "
                         "last-good (default 0.50; only fails past "
                         "--compile-slack-ms absolute)")
    ap.add_argument("--compile-slack-ms", type=float,
                    default=DEFAULT_THRESHOLDS["compile_slack_ms"],
                    help="absolute compile-ms headroom before the growth "
                         "gate can fail (default 2000)")
    ap.add_argument("--ttft-growth", type=float,
                    default=DEFAULT_THRESHOLDS["ttft_growth"],
                    help="max fractional p99 TTFT growth vs last-good "
                         "for serving bench lines (default 0.25)")
    ap.add_argument("--queue-share-growth", type=float,
                    default=DEFAULT_THRESHOLDS["queue_share_growth"],
                    help="max fractional growth of the serving bench's "
                         "attribution.queue_share vs last-good (default "
                         "0.25; only fails past --queue-share-slack, "
                         "skipped when either side lacks the "
                         "attribution sub-object)")
    ap.add_argument("--queue-share-slack", type=float,
                    default=DEFAULT_THRESHOLDS["queue_share_slack"],
                    help="absolute queue-share headroom before the "
                         "growth gate can fail (default 0.05)")
    ap.add_argument("--prefix-hit-drop", type=float,
                    default=DEFAULT_THRESHOLDS["prefix_hit_drop"],
                    help="max fractional prefix_hit_rate drop vs "
                         "last-good for serving bench lines (default "
                         "0.25; skipped when the baseline rate is 0)")
    ap.add_argument("--accept-drop", type=float,
                    default=DEFAULT_THRESHOLDS["accept_drop"],
                    help="max fractional speculative accept_rate drop "
                         "vs last-good for serving bench lines (default "
                         "0.25; skipped when either side lacks the "
                         "field or the baseline rate is 0)")
    ap.add_argument("--affinity-drop", type=float,
                    default=DEFAULT_THRESHOLDS["affinity_drop"],
                    help="max fractional router affinity_hit_rate drop "
                         "vs last-good for serving bench lines (default "
                         "0.25; skipped when either side lacks the "
                         "field or the baseline rate is 0)")
    ap.add_argument("--save-cost-growth", type=float,
                    default=DEFAULT_THRESHOLDS["save_cost_growth"],
                    help="max fractional checkpoint-save blocking-cost "
                         "growth vs last-good for soak lines (default "
                         "0.50; only fails past --save-cost-slack-ms)")
    ap.add_argument("--save-cost-slack-ms", type=float,
                    default=DEFAULT_THRESHOLDS["save_cost_slack_ms"],
                    help="absolute save-cost headroom before the growth "
                         "gate can fail (default 250)")
    ap.add_argument("--goodput-drop", type=float,
                    default=DEFAULT_THRESHOLDS["goodput_drop"],
                    help="max fractional goodput_frac drop vs last-good "
                         "for lines carrying the goodput ledger "
                         "(default 0.10; skipped when either side lacks "
                         "the field)")
    ap.add_argument("--plan-drift", dest="plan_drift",
                    action="store_true", default=True,
                    help="fail a hardware line whose shard_plan differs "
                         "from the last-good record's for the same "
                         "topology (default on)")
    ap.add_argument("--no-plan-drift", dest="plan_drift",
                    action="store_false",
                    help="disable the sharding-plan drift gate")
    ap.add_argument("--audit", dest="audit", action="store_true",
                    default=True,
                    help="fail a hardware line whose program_audit "
                         "sub-object reports findings absent from the "
                         "last-good record (default on; skips when "
                         "either side lacks the sub-object)")
    ap.add_argument("--no-audit", dest="audit", action="store_false",
                    help="disable the program-audit gate")
    ap.add_argument("--slo-breach", dest="slo_breach",
                    action="store_true", default=True,
                    help="fail a hardware line whose slo sub-object "
                         "counts breaches when the last-good record "
                         "(same PT_SLO_* targets) breached zero times "
                         "(default on; skips when either side lacks "
                         "the sub-object)")
    ap.add_argument("--no-slo-breach", dest="slo_breach",
                    action="store_false",
                    help="disable the SLO-breach gate")
    ap.add_argument("--require-baseline", action="store_true",
                    help="fail when the store has no last-good hardware "
                         "record for the metric")
    ap.add_argument("--hardware", choices=("auto", "yes", "no"),
                    default="auto",
                    help="treat the fresh line as a hardware number "
                         "(default: infer from its cpu-smoke markers)")
    args = ap.parse_args(argv)
    try:
        fresh = load_fresh(args.fresh)
    except (OSError, ValueError) as e:
        print(f"perf_guard: {e}", file=sys.stderr)
        return 2
    store = args.store or _default_store()
    # pass the fresh line so its own already-persisted record (benches
    # write the store before the guard runs) is never its baseline, and
    # its sweep config so other-config A/B points are skipped
    baseline = last_good(store, fresh["metric"], fresh=fresh,
                         match=config_match(fresh))
    hardware = {"auto": None, "yes": True, "no": False}[args.hardware]
    verdict = evaluate(
        fresh, baseline,
        thresholds={"throughput_drop": args.throughput_drop,
                    "mfu_drop": args.mfu_drop,
                    "max_starvation_rate": args.max_starvation_rate,
                    "hbm_growth": args.hbm_growth,
                    "compile_growth": args.compile_growth,
                    "compile_slack_ms": args.compile_slack_ms,
                    "ttft_growth": args.ttft_growth,
                    "queue_share_growth": args.queue_share_growth,
                    "queue_share_slack": args.queue_share_slack,
                    "prefix_hit_drop": args.prefix_hit_drop,
                    "accept_drop": args.accept_drop,
                    "affinity_drop": args.affinity_drop,
                    "save_cost_growth": args.save_cost_growth,
                    "save_cost_slack_ms": args.save_cost_slack_ms,
                    "goodput_drop": args.goodput_drop,
                    "plan_drift": args.plan_drift,
                    "audit": args.audit,
                    "slo_breach": args.slo_breach},
        hardware=hardware)
    if args.require_baseline and baseline is None:
        verdict["ok"] = False
        verdict["checks"].append({
            "name": "baseline", "ok": False,
            "detail": f"no hardware record for {fresh['metric']!r} "
                      f"in {store}"})
    print(format_verdict(fresh["metric"], verdict))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
