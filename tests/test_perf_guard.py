"""Perf regression guard (`tools/perf_guard.py`) tests.

The tier-1 smoke from the issue: the guard flags a synthetic 20%
throughput drop and a post-warmup retrace against a last-good
`PERF_MEASUREMENTS.json` record, passes on the unmodified record, and the
CPU-smoke `bench.py` JSON line still parses with the new ``guard``
sub-object — all synthetic, no TPU.
"""
import importlib.util
import json
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, *relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def guard():
    return _load("perf_guard", "tools", "perf_guard.py")


_METRIC = "llama_train_tokens_per_sec_per_chip"


@pytest.fixture
def store(tmp_path):
    path = str(tmp_path / "PERF_MEASUREMENTS.json")
    with open(path, "w") as f:
        json.dump({"records": [
            {"metric": "other_metric", "value": 1.0, "unit": "u",
             "backend": "tpu", "device": "TPU v5 lite"},
            {"metric": _METRIC, "value": 40000.0, "unit": "tokens/s",
             "backend": "cpu", "device": "cpu"},  # smoke: never last-good
            {"metric": _METRIC, "value": 40000.0, "unit": "tokens/s",
             "backend": "tpu", "device": "TPU v5 lite",
             "commit": "abc1234", "timestamp": "2026-08-01T00:00:00Z",
             "extra": {"mfu": 0.6}},
        ]}, f)
    return path


def _fresh(value=40000.0, mfu=0.6, **tel):
    telemetry = {"retraces": 1, "compiles": 1, "steps": 10,
                 "post_warmup_retraces": 0}
    telemetry.update(tel)
    return {"metric": _METRIC, "value": value, "unit": "tokens/s",
            "mfu": mfu, "telemetry": telemetry}


class TestEvaluate:
    def test_passes_on_unmodified_record(self, guard, store):
        base = guard.last_good(store, _METRIC)
        assert base["value"] == 40000.0 and base["backend"] == "tpu"
        v = guard.evaluate(_fresh(), base, hardware=True)
        assert v["ok"] and v["compared"]
        assert v["baseline"]["commit"] == "abc1234"

    def test_flags_20pct_throughput_drop(self, guard, store):
        v = guard.evaluate(_fresh(value=32000.0, mfu=0.48),
                           guard.last_good(store, _METRIC), hardware=True)
        assert not v["ok"]
        failing = {c["name"] for c in v["checks"] if not c["ok"]}
        assert "throughput" in failing and "mfu" in failing

    def test_small_drop_within_threshold_passes(self, guard, store):
        v = guard.evaluate(_fresh(value=38000.0, mfu=0.57),
                           guard.last_good(store, _METRIC), hardware=True)
        assert v["ok"]

    def test_flags_post_warmup_retrace(self, guard, store):
        v = guard.evaluate(_fresh(post_warmup_retraces=1, retraces=2),
                           guard.last_good(store, _METRIC), hardware=True)
        assert not v["ok"]
        assert any(c["name"] == "retraces" and not c["ok"]
                   for c in v["checks"])

    def test_flags_starvation_rate(self, guard, store):
        v = guard.evaluate(_fresh(prefetch_starvations=5, steps=10),
                           guard.last_good(store, _METRIC), hardware=True)
        assert not v["ok"]
        assert any(c["name"] == "starvation" and not c["ok"]
                   for c in v["checks"])

    def test_flags_cold_compile_regression(self, guard, store):
        # baseline recorded a warm exec-cache start (~1s of compiles);
        # the fresh run paid a full cold compile — the cache regressed
        base = dict(guard.last_good(store, _METRIC))
        base["extra"] = {**base["extra"], "compile_ms_total": 1000.0}
        v = guard.evaluate(_fresh(compile_ms_total=90000.0), base,
                           hardware=True)
        assert not v["ok"]
        fail = next(c for c in v["checks"]
                    if c["name"] == "compile_ms" and not c["ok"])
        assert "exec cache" in fail["detail"]

    def test_compile_growth_within_slack_passes(self, guard, store):
        base = dict(guard.last_good(store, _METRIC))
        base["extra"] = {**base["extra"], "compile_ms_total": 100.0}
        # 10x growth but only +900 ms absolute: inside the slack — small
        # compile times are too noisy to gate fractionally
        v = guard.evaluate(_fresh(compile_ms_total=1000.0), base,
                           hardware=True)
        assert v["ok"]
        assert any(c["name"] == "compile_ms" and c["ok"]
                   for c in v["checks"])
        # modest fractional growth over a big baseline also passes
        base["extra"]["compile_ms_total"] = 80000.0
        assert guard.evaluate(_fresh(compile_ms_total=90000.0), base,
                              hardware=True)["ok"]

    def test_zero_warm_baseline_still_gates(self, guard, store):
        # a warm exec-cache run persists compile_ms_total = 0.0; a later
        # cold start past the slack must still fail (0.0 is presence,
        # not absence — the gate's whole point)
        base = dict(guard.last_good(store, _METRIC))
        base["extra"] = {**base["extra"], "compile_ms_total": 0.0}
        v = guard.evaluate(_fresh(compile_ms_total=90000.0), base,
                           hardware=True)
        assert not v["ok"]
        assert any(c["name"] == "compile_ms" and not c["ok"]
                   for c in v["checks"])
        # a warm fresh run vs the warm baseline passes
        assert guard.evaluate(_fresh(compile_ms_total=0.0), base,
                              hardware=True)["ok"]

    def test_compile_gate_skips_on_cache_state_mismatch(self, guard, store):
        # cache-on vs cache-off is an A/B dimension: a cache-off run
        # (no telemetry.exec_cache) judged against a warm-cache 0 ms
        # baseline is not a regression — the knob was just unset
        base = dict(guard.last_good(store, _METRIC))
        base["extra"] = {**base["extra"], "compile_ms_total": 0.0,
                         "exec_cache_enabled": True}
        v = guard.evaluate(_fresh(compile_ms_total=5000.0), base,
                           hardware=True)
        assert v["ok"]
        assert not any(c["name"] == "compile_ms" for c in v["checks"])
        # matching states still gate
        fresh = _fresh(compile_ms_total=5000.0,
                       exec_cache={"disk_hits": 0, "misses": 1})
        v = guard.evaluate(fresh, base, hardware=True)
        assert not v["ok"]
        assert any(c["name"] == "compile_ms" and not c["ok"]
                   for c in v["checks"])

    def test_no_compile_baseline_skips_gate(self, guard, store):
        v = guard.evaluate(_fresh(compile_ms_total=90000.0),
                           guard.last_good(store, _METRIC), hardware=True)
        assert v["ok"]
        assert not any(c["name"] == "compile_ms" for c in v["checks"])

    def test_flags_ttft_p99_growth(self, guard):
        # serving gate: p99 TTFT 40% over last-good fails past the 25%
        # default; throughput rides the generic value check
        base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                "backend": "tpu", "extra": {"ttft_ms_p99": 100.0}}
        fresh = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                 "unit": "tokens/s", "ttft_ms_p99": 140.0}
        v = guard.evaluate(fresh, base, hardware=True)
        assert not v["ok"]
        assert any(c["name"] == "ttft_p99" and not c["ok"]
                   for c in v["checks"])

    def test_ttft_growth_within_threshold_passes(self, guard):
        base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                "backend": "tpu", "extra": {"ttft_ms_p99": 100.0}}
        fresh = {"metric": "serving_tokens_per_sec", "value": 980.0,
                 "unit": "tokens/s", "ttft_ms_p99": 118.0}
        v = guard.evaluate(fresh, base, hardware=True)
        assert v["ok"]
        assert any(c["name"] == "ttft_p99" and c["ok"]
                   for c in v["checks"])

    def test_shared_prefix_absence_means_default_not_wildcard(
            self, guard, tmp_path):
        # a pre-prefix-cache serving record (no shared_prefix_tokens in
        # extra) was a shared=0 trace: it must stay the baseline for a
        # fresh PLAIN line but never for a shared-prefix line — the
        # 64-token-longer-prompt workload would cross-judge TTFT
        path = str(tmp_path / "store.json")
        with open(path, "w") as f:
            json.dump({"records": [
                {"metric": "serving_tokens_per_sec", "value": 900.0,
                 "unit": "tokens/s", "backend": "tpu",
                 "extra": {"requests": 32}}]}, f)
        plain = {"metric": "serving_tokens_per_sec", "value": 880.0,
                 "requests": 32, "shared_prefix_tokens": 0,
                 "prefix_cache": True}
        shared = dict(plain, shared_prefix_tokens=64)
        assert guard.last_good(
            path, "serving_tokens_per_sec",
            match=guard.config_match(plain)) is not None
        assert guard.last_good(
            path, "serving_tokens_per_sec",
            match=guard.config_match(shared)) is None

    def test_flags_prefix_hit_rate_collapse(self, guard):
        # prefix-cache gate (ISSUE 13): the shared-prompt trace's hit
        # rate dropped 50% vs last-good — sharing silently stopped
        base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                "backend": "tpu", "extra": {"prefix_hit_rate": 0.8}}
        fresh = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                 "unit": "tokens/s", "prefix_hit_rate": 0.4}
        v = guard.evaluate(fresh, base, hardware=True)
        assert not v["ok"]
        assert any(c["name"] == "prefix_hit" and not c["ok"]
                   for c in v["checks"])
        # a drop within the 25% default passes
        ok = dict(fresh, prefix_hit_rate=0.7)
        v2 = guard.evaluate(ok, base, hardware=True)
        assert v2["ok"]
        assert any(c["name"] == "prefix_hit" and c["ok"]
                   for c in v2["checks"])

    def test_prefix_hit_gate_skips_smoke_zero_and_missing(self, guard):
        base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                "backend": "tpu", "extra": {"prefix_hit_rate": 0.8}}
        # cpu smoke: skipped with the other hardware comparisons
        smoke = {"metric": "serving_tokens_per_sec", "value": 50.0,
                 "unit": "tokens/s", "prefix_hit_rate": 0.0,
                 "note": "cpu smoke mode; not a TPU number"}
        v = guard.evaluate(smoke, base)
        assert v["ok"]
        assert not any(c["name"] == "prefix_hit" for c in v["checks"])
        # a 0-rate baseline (plain trace, no shared prefix) pins nothing
        zero_base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                     "backend": "tpu", "extra": {"prefix_hit_rate": 0.0}}
        hw = {"metric": "serving_tokens_per_sec", "value": 1000.0,
              "unit": "tokens/s", "prefix_hit_rate": 0.0}
        v2 = guard.evaluate(hw, zero_base, hardware=True)
        assert v2["ok"]
        assert not any(c["name"] == "prefix_hit" for c in v2["checks"])
        # baseline predating the field: gate silently absent
        v3 = guard.evaluate(
            hw, {"metric": "serving_tokens_per_sec", "value": 1000.0,
                 "backend": "tpu", "extra": {}}, hardware=True)
        assert v3["ok"]
        assert not any(c["name"] == "prefix_hit" for c in v3["checks"])

    def test_flags_accept_rate_collapse(self, guard):
        # speculative gate (ISSUE 14): the repetitive trace's accept
        # rate dropped 50% vs last-good — the drafter stopped matching
        base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                "backend": "tpu", "extra": {"accept_rate": 0.6}}
        fresh = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                 "unit": "tokens/s", "accept_rate": 0.3}
        v = guard.evaluate(fresh, base, hardware=True)
        assert not v["ok"]
        assert any(c["name"] == "accept_rate" and not c["ok"]
                   for c in v["checks"])
        # a drop within the 25% default passes
        ok = dict(fresh, accept_rate=0.5)
        v2 = guard.evaluate(ok, base, hardware=True)
        assert v2["ok"]
        assert any(c["name"] == "accept_rate" and c["ok"]
                   for c in v2["checks"])

    def test_accept_gate_skips_smoke_zero_and_missing(self, guard):
        base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                "backend": "tpu", "extra": {"accept_rate": 0.6}}
        # cpu smoke: skipped with the other hardware comparisons
        smoke = {"metric": "serving_tokens_per_sec", "value": 50.0,
                 "unit": "tokens/s", "accept_rate": 0.0,
                 "note": "cpu smoke mode; not a TPU number"}
        v = guard.evaluate(smoke, base)
        assert v["ok"]
        assert not any(c["name"] == "accept_rate" for c in v["checks"])
        # a 0-rate baseline pins nothing
        zero_base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                     "backend": "tpu", "extra": {"accept_rate": 0.0}}
        hw = {"metric": "serving_tokens_per_sec", "value": 1000.0,
              "unit": "tokens/s", "accept_rate": 0.0}
        v2 = guard.evaluate(hw, zero_base, hardware=True)
        assert v2["ok"]
        assert not any(c["name"] == "accept_rate" for c in v2["checks"])
        # spec-off fresh lines never carry the field: gate absent
        off = {"metric": "serving_tokens_per_sec", "value": 1000.0,
               "unit": "tokens/s"}
        v3 = guard.evaluate(off, base, hardware=True)
        assert v3["ok"]
        assert not any(c["name"] == "accept_rate" for c in v3["checks"])

    def test_spec_config_keys_absence_means_plain_decode(
            self, guard, tmp_path):
        # a pre-speculation serving record (no spec/spec_k in extra) WAS
        # a plain-decode run: it must stay the baseline for a fresh
        # spec-off line but never for a spec-on line (a different
        # execution schedule must not cross-judge tokens/s or TTFT)
        path = str(tmp_path / "store.json")
        with open(path, "w") as f:
            json.dump({"records": [
                {"metric": "serving_tokens_per_sec", "value": 900.0,
                 "unit": "tokens/s", "backend": "tpu",
                 "extra": {"requests": 32}}]}, f)
        off = {"metric": "serving_tokens_per_sec", "value": 880.0,
               "requests": 32, "spec": False, "spec_k": 0}
        on = dict(off, spec=True, spec_k=4)
        assert guard.last_good(
            path, "serving_tokens_per_sec",
            match=guard.config_match(off)) is not None
        assert guard.last_good(
            path, "serving_tokens_per_sec",
            match=guard.config_match(on)) is None

    def test_ttft_gate_skips_cpu_smoke_and_no_baseline(self, guard):
        fresh = {"metric": "serving_tokens_per_sec", "value": 50.0,
                 "unit": "tokens/s", "ttft_ms_p99": 9000.0,
                 "note": "cpu smoke mode; not a TPU number"}
        base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                "backend": "tpu", "extra": {"ttft_ms_p99": 100.0}}
        v = guard.evaluate(fresh, base)  # smoke inferred from the note
        assert v["ok"]
        assert not any(c["name"] == "ttft_p99" for c in v["checks"])
        # hardware line judged against a baseline without the field:
        # gate silently absent, everything else still applies
        hw = {"metric": "serving_tokens_per_sec", "value": 1000.0,
              "unit": "tokens/s", "ttft_ms_p99": 9000.0}
        v2 = guard.evaluate(
            hw, {"metric": "serving_tokens_per_sec", "value": 1000.0,
                 "backend": "tpu", "extra": {}}, hardware=True)
        assert v2["ok"]
        assert not any(c["name"] == "ttft_p99" for c in v2["checks"])

    def test_flags_lost_kernel_engagement(self, guard):
        # engaged in the last-good record, composite now -> regression
        # (the tune-table row stopped matching)
        base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                "backend": "tpu",
                "extra": {"kernels": {"paged_attention": True,
                                      "flash": True}}}
        fresh = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                 "unit": "tokens/s",
                 "kernels": {"paged_attention": False, "flash": True}}
        v = guard.evaluate(fresh, base, hardware=True)
        assert not v["ok"]
        bad = [c for c in v["checks"] if c["name"] == "kernel_engagement"]
        assert bad and not bad[0]["ok"]
        assert "paged_attention" in bad[0]["detail"]

    def test_kernel_engagement_gate_covers_paged_attention_int8(
            self, guard):
        # the quantized-gather family (ISSUE 18) rides the same
        # name-agnostic kernels map: engaged-then-composite fails
        base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                "backend": "tpu",
                "extra": {"kernels": {"paged_attention_int8": True}}}
        fresh = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                 "unit": "tokens/s",
                 "kernels": {"paged_attention_int8": False}}
        v = guard.evaluate(fresh, base, hardware=True)
        assert not v["ok"]
        bad = [c for c in v["checks"] if c["name"] == "kernel_engagement"]
        assert bad and not bad[0]["ok"]
        assert "paged_attention_int8" in bad[0]["detail"]

    def test_kernel_engagement_absent_family_is_wildcard(self, guard):
        # a family the fresh line doesn't report wasn't exercised this
        # run — not a regression; newly-engaged families never fail
        base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                "backend": "tpu",
                "extra": {"kernels": {"flash": True,
                                      "flash_headbatch": False}}}
        fresh = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                 "unit": "tokens/s",
                 "kernels": {"flash_headbatch": True}}
        v = guard.evaluate(fresh, base, hardware=True)
        assert v["ok"]
        ok = [c for c in v["checks"] if c["name"] == "kernel_engagement"]
        assert ok and ok[0]["ok"]

    def test_kernel_engagement_skips_cpu_smoke_and_no_baseline(
            self, guard):
        fresh = {"metric": "serving_tokens_per_sec", "value": 50.0,
                 "unit": "tokens/s",
                 "kernels": {"paged_attention": False},
                 "note": "cpu smoke mode; not a TPU number"}
        base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                "backend": "tpu",
                "extra": {"kernels": {"paged_attention": True}}}
        v = guard.evaluate(fresh, base)  # smoke inferred from the note
        assert v["ok"]
        assert not any(c["name"] == "kernel_engagement"
                       for c in v["checks"])
        # baseline without the kernels field: gate silently absent
        hw = {"metric": "serving_tokens_per_sec", "value": 1000.0,
              "unit": "tokens/s", "kernels": {"paged_attention": False}}
        v2 = guard.evaluate(
            hw, {"metric": "serving_tokens_per_sec", "value": 1000.0,
                 "backend": "tpu", "extra": {}}, hardware=True)
        assert v2["ok"]
        assert not any(c["name"] == "kernel_engagement"
                       for c in v2["checks"])

    def test_flags_plan_drift_same_topology(self, guard):
        # the cost model flipped the planned sharding for the SAME
        # device count — a silent production-sharding change
        base = {"metric": "shard_plan_planned_vs_measured", "value": 900.0,
                "backend": "tpu",
                "extra": {"shard_plan": {"dp": 4, "mp": 2, "batch": 8,
                                         "devices": 8}}}
        fresh = {"metric": "shard_plan_planned_vs_measured", "value": 910.0,
                 "unit": "tokens/s",
                 "shard_plan": {"dp": 8, "mp": 1, "batch": 8,
                                "devices": 8}}
        v = guard.evaluate(fresh, base, hardware=True)
        assert not v["ok"]
        bad = [c for c in v["checks"] if c["name"] == "plan_drift"]
        assert bad and not bad[0]["ok"]
        assert "dp 4→8" in bad[0]["detail"]

    def test_flags_pp_drift_with_pre_pp_baseline(self, guard):
        # a baseline persisted before the planner's pp axis existed
        # reads as pp=1 (not a wildcard): a fresh pp2 plan for the same
        # topology is drift, not a pass
        base = {"metric": "shard_plan_planned_vs_measured", "value": 900.0,
                "backend": "tpu",
                "extra": {"shard_plan": {"dp": 8, "mp": 1, "batch": 8,
                                         "devices": 8}}}
        fresh = {"metric": "shard_plan_planned_vs_measured", "value": 910.0,
                 "unit": "tokens/s",
                 "shard_plan": {"dp": 4, "mp": 1, "pp": 2, "batch": 8,
                                "devices": 8}}
        v = guard.evaluate(fresh, base, hardware=True)
        bad = [c for c in v["checks"] if c["name"] == "plan_drift"]
        assert bad and not bad[0]["ok"]
        assert "pp 1→2" in bad[0]["detail"]

    def test_pp_joins_config_keys_with_default_one(self, guard):
        assert "pp" in guard.CONFIG_KEYS
        assert guard.CONFIG_KEY_DEFAULTS["pp"] == 1

    def test_kv_int8_joins_config_keys_with_default_false(
            self, guard, tmp_path):
        # bf16 and int8 serving rows must never cross-judge: kv_int8 is
        # a config key, and a record persisted before the int8 pool
        # existed reads as a bf16 run (default False, not a wildcard)
        assert "kv_int8" in guard.CONFIG_KEYS
        assert guard.CONFIG_KEY_DEFAULTS["kv_int8"] is False
        path = str(tmp_path / "store.json")
        with open(path, "w") as f:
            json.dump({"records": [
                {"metric": "serving_tokens_per_sec", "value": 900.0,
                 "unit": "tokens/s", "backend": "tpu",
                 "extra": {"requests": 32}}]}, f)
        bf16 = {"metric": "serving_tokens_per_sec", "value": 880.0,
                "requests": 32, "kv_int8": False}
        int8 = dict(bf16, kv_int8=True)
        assert guard.last_good(
            path, "serving_tokens_per_sec",
            match=guard.config_match(bf16)) is not None
        assert guard.last_good(
            path, "serving_tokens_per_sec",
            match=guard.config_match(int8)) is None

    def test_plan_drift_same_plan_passes(self, guard):
        plan = {"dp": 4, "mp": 2, "batch": 8, "devices": 8}
        base = {"metric": "shard_plan_planned_vs_measured", "value": 900.0,
                "backend": "tpu", "extra": {"shard_plan": dict(plan)}}
        fresh = {"metric": "shard_plan_planned_vs_measured", "value": 905.0,
                 "unit": "tokens/s", "shard_plan": dict(plan)}
        v = guard.evaluate(fresh, base, hardware=True)
        assert v["ok"]
        ok = [c for c in v["checks"] if c["name"] == "plan_drift"]
        assert ok and ok[0]["ok"]

    def test_plan_drift_skips_other_topology_smoke_and_missing(
            self, guard):
        base = {"metric": "shard_plan_planned_vs_measured", "value": 900.0,
                "backend": "tpu",
                "extra": {"shard_plan": {"dp": 4, "mp": 2, "batch": 8,
                                         "devices": 8}}}
        # different device count: not comparable, gate absent
        fresh16 = {"metric": "shard_plan_planned_vs_measured",
                   "value": 900.0, "unit": "tokens/s",
                   "shard_plan": {"dp": 16, "mp": 1, "batch": 8,
                                  "devices": 16}}
        v = guard.evaluate(fresh16, base, hardware=True)
        assert not any(c["name"] == "plan_drift" for c in v["checks"])
        # cpu smoke: hardware comparisons skipped entirely
        smoke = {"metric": "shard_plan_planned_vs_measured", "value": 10.0,
                 "unit": "tokens/s",
                 "shard_plan": {"dp": 8, "mp": 1, "batch": 8,
                                "devices": 8},
                 "note": "cpu smoke mode; not a TPU number"}
        v2 = guard.evaluate(smoke, base)
        assert v2["ok"]
        assert not any(c["name"] == "plan_drift" for c in v2["checks"])
        # baseline without the field: gate silently absent
        hw = {"metric": "shard_plan_planned_vs_measured", "value": 900.0,
              "unit": "tokens/s",
              "shard_plan": {"dp": 8, "mp": 1, "batch": 8, "devices": 8}}
        v3 = guard.evaluate(
            hw, {"metric": "shard_plan_planned_vs_measured",
                 "value": 900.0, "backend": "tpu", "extra": {}},
            hardware=True)
        assert not any(c["name"] == "plan_drift" for c in v3["checks"])
        # the gate can be disabled explicitly (--no-plan-drift)
        fresh = {"metric": "shard_plan_planned_vs_measured",
                 "value": 910.0, "unit": "tokens/s",
                 "shard_plan": {"dp": 8, "mp": 1, "batch": 8,
                                "devices": 8}}
        v4 = guard.evaluate(fresh, base, hardware=True,
                            thresholds={"plan_drift": False})
        assert not any(c["name"] == "plan_drift" for c in v4["checks"])

    def test_flags_fresh_slo_breach(self, guard):
        # SLO-breach gate (ISSUE 19): the burn-rate watchdog fired on a
        # trace that breached zero times in the last-good record
        base = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                "backend": "tpu",
                "extra": {"slo": {"breaches": 0, "worst_burn": 2.0}}}
        fresh = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                 "unit": "tokens/s",
                 "slo": {"breaches": 2, "worst_burn": 40.0}}
        v = guard.evaluate(fresh, base, hardware=True)
        assert not v["ok"]
        assert any(c["name"] == "slo_breach" and not c["ok"]
                   for c in v["checks"])
        # the gate can be disabled explicitly (--no-slo-breach)
        v2 = guard.evaluate(fresh, base, hardware=True,
                            thresholds={"slo_breach": False})
        assert not any(c["name"] == "slo_breach" for c in v2["checks"])

    def test_slo_breach_gate_skips_and_rides_baseline(self, guard):
        # zero fresh breaches pass; a baseline that already breached
        # rides forward; either side missing the sub-object skips
        base_b = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                  "backend": "tpu", "extra": {"slo": {"breaches": 3}}}
        fresh_b = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                   "unit": "tokens/s", "slo": {"breaches": 5}}
        v = guard.evaluate(fresh_b, base_b, hardware=True)
        assert any(c["name"] == "slo_breach" and c["ok"]
                   for c in v["checks"])
        base_0 = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                  "backend": "tpu", "extra": {"slo": {"breaches": 0}}}
        fresh_0 = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                   "unit": "tokens/s", "slo": {"breaches": 0}}
        v = guard.evaluate(fresh_0, base_0, hardware=True)
        assert any(c["name"] == "slo_breach" and c["ok"]
                   for c in v["checks"])
        no_sub = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                  "unit": "tokens/s"}
        v = guard.evaluate(no_sub, base_0, hardware=True)
        assert not any(c["name"] == "slo_breach" for c in v["checks"])
        base_no = {"metric": "serving_tokens_per_sec", "value": 1000.0,
                   "backend": "tpu", "extra": {}}
        v = guard.evaluate(fresh_b, base_no, hardware=True)
        assert not any(c["name"] == "slo_breach" for c in v["checks"])

    def test_slo_targets_join_config_keys(self, guard, tmp_path):
        # a record judged at PT_SLO_TTFT_MS_P99=200 never baselines a
        # fresh line judged at 100 (tighter target, different line in
        # the sand); pre-SLO records (no key) read as target-off
        path = str(tmp_path / "store.json")
        with open(path, "w") as f:
            json.dump({"records": [
                {"metric": "serving_tokens_per_sec", "value": 900.0,
                 "unit": "tokens/s", "backend": "tpu",
                 "extra": {"slo_ttft_ms_p99": 200.0}}]}, f)
        same = {"metric": "serving_tokens_per_sec", "value": 880.0,
                "slo_ttft_ms_p99": 200.0}
        tighter = {"metric": "serving_tokens_per_sec", "value": 880.0,
                   "slo_ttft_ms_p99": 100.0}
        off = {"metric": "serving_tokens_per_sec", "value": 880.0,
               "slo_ttft_ms_p99": None}
        assert guard.last_good(
            path, "serving_tokens_per_sec",
            match=guard.config_match(same)) is not None
        assert guard.last_good(
            path, "serving_tokens_per_sec",
            match=guard.config_match(tighter)) is None
        assert guard.last_good(
            path, "serving_tokens_per_sec",
            match=guard.config_match(off)) is None
        assert "slo_ttft_ms_p99" in guard.CONFIG_KEYS
        assert guard.CONFIG_KEY_DEFAULTS["slo_ttft_ms_p99"] is None

    def test_flags_save_cost_growth(self, guard):
        base = {"metric": "soak", "value": 900.0, "backend": "tpu",
                "extra": {"ckpt_save_ms_p50": 300.0}}
        fresh = {"metric": "soak", "value": 910.0, "unit": "samples/s",
                 "ckpt_save_ms_p50": 700.0}
        v = guard.evaluate(fresh, base, hardware=True)
        assert not v["ok"]
        assert any(c["name"] == "ckpt_save_ms" and not c["ok"]
                   for c in v["checks"])

    def test_save_cost_within_slack_passes(self, guard):
        # +100% but under the 250 ms absolute slack: small-save noise
        base = {"metric": "soak", "value": 900.0, "backend": "tpu",
                "extra": {"ckpt_save_ms_p50": 40.0}}
        fresh = {"metric": "soak", "value": 905.0, "unit": "samples/s",
                 "ckpt_save_ms_p50": 80.0}
        v = guard.evaluate(fresh, base, hardware=True)
        assert v["ok"]
        assert any(c["name"] == "ckpt_save_ms" and c["ok"]
                   for c in v["checks"])

    def test_save_cost_gate_absent_without_field(self, guard):
        base = {"metric": "soak", "value": 900.0, "backend": "tpu",
                "extra": {}}
        fresh = {"metric": "soak", "value": 905.0, "unit": "samples/s",
                 "ckpt_save_ms_p50": 9000.0}
        v = guard.evaluate(fresh, base, hardware=True)
        assert v["ok"]
        assert not any(c["name"] == "ckpt_save_ms" for c in v["checks"])

    def test_flags_error_line(self, guard, store):
        fresh = {"metric": _METRIC, "value": 0.0, "unit": "tokens/s",
                 "error": "bench watchdog fired"}
        v = guard.evaluate(fresh, guard.last_good(store, _METRIC))
        assert not v["ok"]
        assert any(c["name"] == "emitted" and not c["ok"]
                   for c in v["checks"])

    def test_cpu_smoke_skips_hardware_comparison(self, guard, store):
        fresh = _fresh(value=500.0, mfu=0.001)
        fresh["note"] = "cpu smoke mode; not a TPU number"
        v = guard.evaluate(fresh, guard.last_good(store, _METRIC))
        # 80x below the TPU record, but a laptop number is not a
        # regression — only the runtime-health checks gate
        assert v["ok"] and not v["compared"]
        # still fails on a retrace storm even on CPU
        fresh2 = _fresh(value=500.0, post_warmup_retraces=3)
        fresh2["note"] = "cpu smoke mode; not a TPU number"
        assert not guard.evaluate(fresh2, None)["ok"]

    def test_no_baseline_hw_line_passes_health_checks(self, guard):
        v = guard.evaluate(_fresh(), None, hardware=True)
        assert v["ok"] and not v["compared"] and "baseline" not in v


class TestLoadHelpers:
    def test_load_fresh_picks_last_metric_line(self, guard, tmp_path):
        p = str(tmp_path / "log.txt")
        with open(p, "w") as f:
            f.write("bench: backend=tpu\n")
            f.write('{"not_a_bench": 1}\n')
            f.write(json.dumps({"metric": "m", "value": 1.0}) + "\n")
            f.write("junk {\n")
            f.write(json.dumps({"metric": "m", "value": 2.0}) + "\n")
        assert guard.load_fresh(p)["value"] == 2.0

    def test_load_fresh_raises_on_no_line(self, guard, tmp_path):
        p = str(tmp_path / "empty.txt")
        open(p, "w").write("nothing here\n")
        with pytest.raises(ValueError, match="no bench JSON line"):
            guard.load_fresh(p)

    def test_last_good_missing_or_corrupt_store(self, guard, tmp_path):
        assert guard.last_good(str(tmp_path / "missing.json"), "m") is None
        p = str(tmp_path / "bad.json")
        open(p, "w").write("{corrupt")
        assert guard.last_good(p, "m") is None

    def test_last_good_skips_freshly_recorded_self(self, guard, tmp_path):
        """Benches persist BEFORE the guard judges: the newest record can
        be the run under judgment, and comparing it to itself would make
        the throughput gate always-pass."""
        p = str(tmp_path / "s.json")
        with open(p, "w") as f:
            json.dump({"records": [
                {"metric": _METRIC, "value": 40000.0, "unit": "tokens/s",
                 "backend": "tpu", "device": "d", "commit": "old"},
                {"metric": _METRIC, "value": 32000.0, "unit": "tokens/s",
                 "backend": "tpu", "device": "d", "commit": "new"},
            ]}, f)
        fresh = _fresh(value=32000.0, mfu=0.48)
        base = guard.last_good(p, _METRIC, fresh=fresh)
        assert base["value"] == 40000.0  # not the just-written 32000
        v = guard.evaluate(fresh, base, hardware=True)
        assert not v["ok"]  # the 20% drop IS flagged
        # without `fresh`, the newest record wins (the CPU-fallback
        # inline-surfacing use case keeps its semantics)
        assert guard.last_good(p, _METRIC)["value"] == 32000.0

    def test_find_bench_line_shared_scanner(self, guard):
        text = 'noise\n{"metric": "m", "value": 3.0}\n'
        assert guard.find_bench_line(text)["value"] == 3.0
        assert guard.find_bench_line("no json") is None

    def test_last_good_matches_sweep_config(self, guard, tmp_path):
        """A PT_BENCH_BATCH=16 sweep record must not become the baseline
        that judges a default b8 run (same metric name, different
        measurement)."""
        p = str(tmp_path / "s.json")
        with open(p, "w") as f:
            json.dump({"records": [
                {"metric": _METRIC, "value": 40000.0, "unit": "tokens/s",
                 "backend": "tpu", "device": "d",
                 "extra": {"batch": 8, "seq": 1024, "ce_chunk": 0}},
                {"metric": _METRIC, "value": 48000.0, "unit": "tokens/s",
                 "backend": "tpu", "device": "d",
                 "extra": {"batch": 16, "seq": 1024, "ce_chunk": 0}},
            ]}, f)
        fresh = _fresh(value=39000.0)
        fresh.update({"batch": 8, "seq": 1024, "ce_chunk": 0})
        base = guard.last_good(p, _METRIC, fresh=fresh,
                               match=guard.config_match(fresh))
        assert base["value"] == 40000.0  # the b8 record, not the b16 one
        assert guard.evaluate(fresh, base, hardware=True)["ok"]
        # without config keys in the line, no filter applies (legacy logs)
        assert guard.config_match({"metric": _METRIC}) == {}
        assert guard.last_good(p, _METRIC)["value"] == 48000.0

    def test_last_good_treats_absent_config_key_as_wildcard(
            self, guard, tmp_path):
        """A record persisted BEFORE a config knob existed (its extra
        lacks the key) must stay an eligible baseline — otherwise adding
        a CONFIG_KEYS entry orphans every prior hardware record and
        silently disables the gates it anchored (e.g. the pre-serving
        decode records vs the new int8_weights key)."""
        p = str(tmp_path / "s.json")
        with open(p, "w") as f:
            json.dump({"records": [
                {"metric": "llama_decode_tokens_per_sec_per_chip",
                 "value": 500.0, "unit": "tokens/s", "backend": "tpu",
                 "device": "d",
                 "extra": {"batch": 128}},  # predates int8_weights
            ]}, f)
        fresh = {"metric": "llama_decode_tokens_per_sec_per_chip",
                 "value": 480.0, "unit": "tokens/s", "batch": 128,
                 "int8_weights": False}
        base = guard.last_good(p, fresh["metric"], fresh=fresh,
                               match=guard.config_match(fresh))
        assert base is not None and base["value"] == 500.0
        # a PRESENT-but-different key still filters
        fresh_b64 = dict(fresh, batch=64)
        assert guard.last_good(p, fresh["metric"], fresh=fresh_b64,
                               match=guard.config_match(fresh_b64)) is None


class TestCLI:
    def _write(self, tmp_path, obj, name="fresh.json"):
        p = str(tmp_path / name)
        with open(p, "w") as f:
            f.write(json.dumps(obj) + "\n")
        return p

    def test_cli_pass_and_fail_exit_codes(self, guard, store, tmp_path,
                                          capsys):
        # value differs from the stored record: a REAL comparison happens
        # (an identical value would be skipped as the run's own record)
        ok = self._write(tmp_path, _fresh(value=39500.0, mfu=0.59))
        assert guard.main([ok, "--store", store, "--hardware", "yes"]) == 0
        out = capsys.readouterr().out
        assert "verdict: PASS" in out and "throughput" in out

        bad = self._write(tmp_path, _fresh(value=30000.0, mfu=0.45),
                          "bad.json")
        assert guard.main([bad, "--store", store, "--hardware", "yes"]) == 1
        out = capsys.readouterr().out
        assert "REGRESSION" in out and "throughput" in out

    def test_cli_thresholds_override(self, guard, store, tmp_path):
        bad = self._write(tmp_path, _fresh(value=30000.0, mfu=0.45))
        assert guard.main([bad, "--store", store, "--hardware", "yes",
                           "--throughput-drop", "0.5",
                           "--mfu-drop", "0.5"]) == 0

    def test_cli_require_baseline(self, guard, tmp_path):
        fresh = self._write(tmp_path, _fresh())
        empty = str(tmp_path / "empty_store.json")
        with open(empty, "w") as f:
            json.dump({"records": []}, f)
        assert guard.main([fresh, "--store", empty,
                           "--require-baseline"]) == 1
        assert guard.main([fresh, "--store", empty]) == 0

    def test_cli_unreadable_fresh(self, guard, tmp_path):
        assert guard.main([str(tmp_path / "nope.json")]) == 2

    def test_cli_skips_own_persisted_record(self, guard, tmp_path,
                                            capsys):
        """The documented flow `bench.py > log; perf_guard.py log` runs
        AFTER the bench persisted its record: the CLI must judge against
        the previous record, not the run's own."""
        p = str(tmp_path / "s.json")
        with open(p, "w") as f:
            json.dump({"records": [
                {"metric": _METRIC, "value": 40000.0, "unit": "tokens/s",
                 "backend": "tpu", "device": "d"},
                {"metric": _METRIC, "value": 30000.0, "unit": "tokens/s",
                 "backend": "tpu", "device": "d"},  # this run, persisted
            ]}, f)
        log = self._write(tmp_path, _fresh(value=30000.0, mfu=0.45))
        assert guard.main([log, "--store", p, "--hardware", "yes"]) == 1
        assert "REGRESSION" in capsys.readouterr().out


class TestBenchIntegration:
    """The CPU-smoke bench.py JSON line still parses with the new
    ``guard`` sub-object — exercised through bench.py's own embedding
    helper (the full CPU-smoke subprocess run is PERF territory; the
    contract under test is the line shape)."""

    @pytest.fixture()
    def bench(self, monkeypatch, store):
        monkeypatch.setenv("PT_MEASUREMENTS_PATH", store)
        monkeypatch.delenv("PT_BENCH_ASYNC", raising=False)
        return _load("bench_mod", "bench.py")

    def test_guard_verdict_embeds_and_line_parses(self, bench, capsys):
        line = {"metric": _METRIC, "value": 517.85, "unit": "tokens/s",
                "platform": "cpu",
                "note": "cpu smoke mode; not a TPU number",
                "telemetry": {"retraces": 1, "compiles": 1, "steps": 3,
                              "post_warmup_retraces": 0}}
        verdict = bench._guard_verdict(dict(line), on_cpu=True,
                                       baseline=None)
        line["guard"] = verdict
        # the one JSON line the driver parses must survive the addition
        rt = json.loads(json.dumps(line))
        assert rt["guard"]["ok"] is True
        assert rt["guard"]["compared"] is False
        names = {c["name"] for c in rt["guard"]["checks"]}
        assert "emitted" in names and "retraces" in names

    def test_guard_verdict_uses_pre_record_baseline(self, bench, capsys):
        """main() captures the baseline BEFORE persisting this run's
        record; _guard_verdict judges against exactly that (no store
        re-read — the store already holds the run itself by then)."""
        pre = {"metric": _METRIC, "value": 40000.0, "unit": "tokens/s",
               "backend": "tpu", "device": "d", "commit": "old",
               "extra": {"mfu": 0.6}}
        line = {"metric": _METRIC, "value": 30000.0, "unit": "tokens/s",
                "mfu": 0.45, "telemetry": {"retraces": 1, "compiles": 1,
                                           "steps": 10,
                                           "post_warmup_retraces": 0}}
        verdict = bench._guard_verdict(dict(line), on_cpu=False,
                                       baseline=pre)
        assert verdict["ok"] is False
        assert verdict["baseline"]["commit"] == "old"
        assert json.loads(json.dumps(verdict))  # still serializable
        # the failing verdict is announced on stderr mid-bench
        assert "REGRESSION" in capsys.readouterr().err
        # no baseline captured (first-ever hardware run): health checks
        # only, never a self-comparison against the fresh store record
        v2 = bench._guard_verdict(dict(line), on_cpu=False, baseline=None)
        assert v2["ok"] is True and v2["compared"] is False
