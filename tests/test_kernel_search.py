"""Kernel search harness (`ops/pallas/search.py` + `tools/kernel_search.py`).

Four layers:

- **Tune table** — fcntl-locked atomic read-modify-write (the
  durability fix for the old bare-write `flash_tune.json` tear),
  one-shot legacy migration, device filtering.
- **Engagement rules** — measured-faster-than-composite only; CPU /
  interpret rows never engage; verdicts never transfer across keys.
- **The search pipeline** — candidate enumeration + pruning, the
  mandatory interpret-parity pre-filter (a wrong-but-fast candidate is
  rejected before timing), persisted provenance, monitor counters.
- **Tier-1 CLI smoke** — `python tools/kernel_search.py --smoke` runs
  enumerate -> parity-filter -> timing for every registered family on
  CPU and exits 0 (the acceptance criterion).
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.ops.pallas import autotune, head_flash, search

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def table(tmp_path, monkeypatch):
    """Isolated unified table + isolated legacy flash cache (the
    loader-fallback migration reads it)."""
    path = str(tmp_path / "kernel_tune.json")
    monkeypatch.setenv("PT_KERNEL_TUNE_PATH", path)
    monkeypatch.setattr(search, "_table_cache", None)
    monkeypatch.setattr(autotune, "_CACHE_PATH",
                        str(tmp_path / "flash_tune.json"))
    monkeypatch.setattr(autotune, "_cache", None)
    return path


def _hw_row(key, ratio, family="famx", **extra):
    row = {"family": family, "key": key, "config": {"block_q": 128},
           "ratio": ratio, "t_kernel_ms": 1.0,
           "t_composite_ms": ratio, "backend": "tpu",
           "device": search._device_kind(), "interpret": False}
    row.update(extra)
    return row


def _put(family, key, row):
    search.update_table(
        lambda d: d.setdefault("families", {}).setdefault(
            family, {"entries": {}})["entries"].update({key: row}))


# -- tune table ---------------------------------------------------------------

class TestTable:
    def test_update_table_merges_concurrent_writers(self, table):
        # two read-modify-writes that never see each other's in-memory
        # state: the locked reload keeps both rows (the old
        # save_cache-style full overwrite dropped one)
        _put("a", "k1", _hw_row("k1", 1.5, family="a"))
        search._table_cache = None  # forget — like a second process
        _put("b", "k2", _hw_row("k2", 0.5, family="b"))
        data = search.load_table(refresh=True)
        assert "k1" in data["families"]["a"]["entries"]
        assert "k2" in data["families"]["b"]["entries"]

    def test_write_is_atomic_no_partial_file(self, table):
        _put("a", "k1", _hw_row("k1", 1.5, family="a"))
        # the table on disk is always complete valid JSON
        with open(table) as f:
            data = json.load(f)
        assert data["families"]["a"]["entries"]["k1"]["ratio"] == 1.5
        # no stray tmp files left behind
        stray = [f for f in os.listdir(os.path.dirname(table))
                 if f.startswith(".kernel_tune_")]
        assert stray == []

    def test_legacy_flash_migration_loader_fallback(self, table):
        # rows in the OLD flash_tune.json appear under the flash
        # namespace with ratio/config aliases — without touching disk
        autotune.save_cache({"entries": {
            autotune._key(1024, 1024, 128, True): {
                "sq": 1024, "sk": 1024, "d": 128, "causal": True,
                "block_q": 256, "block_k": 512, "ratio_fwd_bwd": 3.4,
                "backend": "tpu", "device": search._device_kind()}}})
        data = search.load_table(refresh=True)
        row = data["families"]["flash"]["entries"][
            autotune._key(1024, 1024, 128, True)]
        assert row["migrated_from"] == "flash_tune.json"
        assert row["ratio"] == 3.4
        assert row["config"] == {"block_q": 256, "block_k": 512}
        # and the unified row feeds engagement
        assert search.engaged(
            "flash", autotune._key(1024, 1024, 128, True)) is True

    def test_unified_row_wins_over_migrated(self, table):
        key = autotune._key(512, 512, 64, True)
        autotune.save_cache({"entries": {key: {
            "sq": 512, "sk": 512, "d": 64, "causal": True,
            "block_q": 128, "block_k": 128, "ratio_fwd_bwd": 0.7,
            "backend": "tpu", "device": search._device_kind()}}})
        _put("flash", key, _hw_row(key, 1.2, family="flash"))
        assert search.engaged("flash", key) is True  # unified row wins

    def test_other_device_rows_ignored(self, table):
        _put("famx", "k", _hw_row("k", 2.0, device="TPU v99"))
        assert search.lookup("famx", "k") is None
        assert search.engaged("famx", "k") is None

    def test_autotune_save_cache_locked_atomic(self, table):
        # the legacy writer now uses the same discipline: lock sidecar
        # + no partial file
        autotune.save_cache({"entries": {"x": {"sq": 1}}})
        assert os.path.exists(autotune._CACHE_PATH + ".lock")
        with open(autotune._CACHE_PATH) as f:
            assert json.load(f)["entries"]["x"]["sq"] == 1

    def test_autotune_update_cache_merges(self, table):
        autotune.update_cache(
            lambda c: c.setdefault("entries", {}).update({"a": {"v": 1}}))
        autotune._cache = None  # second-process view
        autotune.update_cache(
            lambda c: c.setdefault("entries", {}).update({"b": {"v": 2}}))
        cache = autotune.load_cache()
        assert set(cache["entries"]) >= {"a", "b"}


# -- engagement rules ---------------------------------------------------------

class TestEngagement:
    def test_no_row_returns_none(self, table):
        assert search.engaged("famx", "nope") is None
        assert search.best_config("famx", "nope") is None

    def test_measured_faster_engages(self, table):
        _put("famx", "k", _hw_row("k", 1.3))
        assert search.engaged("famx", "k") is True
        assert search.best_config("famx", "k") == {"block_q": 128}

    def test_measured_slower_disengages(self, table):
        _put("famx", "k", _hw_row("k", 0.8))
        assert search.engaged("famx", "k") is False

    def test_cpu_and_interpret_rows_never_engage(self, table):
        # the smoke CLI persists backend=cpu / interpret=true rows;
        # their wall-clock is meaningless and must not flip anything
        _put("famx", "kc", _hw_row("kc", 5.0, backend="cpu"))
        _put("famx", "ki", _hw_row("ki", 5.0, interpret=True))
        assert search.engaged("famx", "kc") is None
        assert search.engaged("famx", "ki") is None

    def test_verdict_is_exact_key_only(self, table):
        _put("famx", "k1", _hw_row("k1", 2.0))
        assert search.engaged("famx", "k2") is None

    def test_decide_counts_engagement(self, table):
        was = monitor.enabled()
        monitor.enable()
        try:
            base = monitor.snapshot()["counters"]
            _put("famx", "k", _hw_row("k", 1.3))
            assert search.decide("famx", "k") is True
            assert search.decide("famx", "missing") is False
            got = monitor.snapshot()["counters"]
            assert got.get("pallas/engaged", 0) - base.get(
                "pallas/engaged", 0) == 1
            assert got.get("pallas/fallback_composite", 0) - base.get(
                "pallas/fallback_composite", 0) == 1
            assert got.get("pallas/engaged/famx", 0) >= 1
        finally:
            if not was:
                monitor.disable()

    def test_engagement_report_shapes(self, table):
        _put("fam_a", "k", _hw_row("k", 1.5, family="fam_a"))
        _put("fam_b", "k", _hw_row("k", 0.5, family="fam_b"))
        _put("fam_c", "k", _hw_row("k", 9.9, family="fam_c",
                                   backend="cpu"))
        search.register_family(type("FamA", (search.KernelFamily,),
                                    {"name": "fam_a"})())
        search.register_family(type("FamB", (search.KernelFamily,),
                                    {"name": "fam_b"})())
        search.register_family(type("FamC", (search.KernelFamily,),
                                    {"name": "fam_c"})())
        try:
            rep = search.engagement_report()
            assert rep["fam_a"] is True
            assert rep["fam_b"] is False
            # cpu rows carry no verdict — and a family with NO hardware
            # verdict must still report False (not absent), so a
            # deleted row reads as a lost engagement, not a wildcard
            assert rep["fam_c"] is False
        finally:
            for n in ("fam_a", "fam_b", "fam_c"):
                search.FAMILIES.pop(n, None)


# -- candidate spaces ---------------------------------------------------------

class TestCandidates:
    def test_headbatch_blocks_tile_and_fit_vmem(self):
        fam = search.FAMILIES["flash_headbatch"]
        shape = (8, 1024, 1024, 12, 12, 128, True)
        cands = fam.candidates(shape)
        assert cands, "empty candidate space"
        for c in cands:
            assert 1024 % c["block_q"] == 0
            assert 1024 % c["block_k"] == 0
            assert head_flash.vmem_bytes(shape, c) <= fam.vmem_budget

    def test_headbatch_vmem_prune_shrinks_with_heads(self):
        fam = search.FAMILIES["flash_headbatch"]
        few = fam.candidates((8, 1024, 1024, 4, 4, 128, True))
        many = fam.candidates((8, 1024, 1024, 32, 32, 128, True))
        # with every head's state resident, more heads must prune the
        # big-block corner of the space
        assert max(c["block_q"] for c in many) \
            <= max(c["block_q"] for c in few)
        assert len(many) < len(few)

    def test_headbatch_space_never_guesses(self):
        """What the VMEM prune (or the d % 128 contract) rules out
        leaves an EMPTY space — the search then refuses the shape; it is
        never handed a config nobody checked (the old behaviour appended
        a minimal one, which the chip's compiler refused at 32 heads)."""
        fam = search.FAMILIES["flash_headbatch"]
        assert fam.candidates((1, 64, 64, 64, 64, 128, True)) == []
        assert fam.candidates((64, 512, 512, 12, 12, 64, False)) == []
        assert fam.candidates((8, 1024, 1024, 12, 12, 128, True))

    def test_registered_families(self):
        assert set(search.FAMILIES) == {"flash", "flash_headbatch"}

    def test_family_keys_encode_variants(self):
        base = head_flash.shape_key(8, 1024, 1024, 12, 12, 128, True)
        assert head_flash.shape_key(
            8, 1024, 1024, 12, 12, 128, True, dropout=True) != base
        assert head_flash.shape_key(
            8, 1024, 1024, 12, 12, 128, True, kmask=True) != base
        assert "kv4" in head_flash.shape_key(8, 1024, 1024, 12, 4, 128,
                                             True)


# -- the search pipeline ------------------------------------------------------

class _StubFamily(search.KernelFamily):
    """Tiny synthetic family: two candidates, one mathematically WRONG
    — the parity pre-filter must reject it before timing ever sees it,
    and the persisted row must carry the good one."""

    name = "stub"
    grad = False
    parity_atol = 1e-6

    def shapes(self):
        return [(8,)]

    def key(self, shape):
        return f"n{shape[0]}"

    def candidates(self, shape):
        return [{"variant": "good"}, {"variant": "broken"}]

    def make_inputs(self, shape):
        return (jnp.arange(float(shape[0])).reshape(1, shape[0]),)

    def build(self, shape, config, interpret):
        if config["variant"] == "broken":
            return lambda x: x * 2.0 + 1.0  # fast but wrong
        return lambda x: x * 2.0

    def build_composite(self, shape):
        return lambda x: x + x


class TestSearchPipeline:
    def test_parity_filter_rejects_wrong_candidate(self, table):
        was = monitor.enabled()
        monitor.enable()
        try:
            base = monitor.snapshot()["counters"]
            entry = search.search_shape(_StubFamily(), (8,), iters=2,
                                        verbose=False)
            got = monitor.snapshot()["counters"]
        finally:
            if not was:
                monitor.disable()
        assert entry["config"] == {"variant": "good"}
        assert entry["rejects"] == 1
        assert entry["candidates"] == 2
        assert entry["candidates_timed"] == 1
        assert "ratio" in entry and "timestamp" in entry
        assert entry["backend"] == "cpu" and entry["interpret"]
        # counters account the run
        assert got.get("search/candidates_timed", 0) - base.get(
            "search/candidates_timed", 0) == 1
        assert got.get("search/rejects", 0) - base.get(
            "search/rejects", 0) == 1
        # persisted under the family namespace, loadable fresh
        search._table_cache = None
        row = search.lookup("stub", "n8")
        assert row is not None and row["config"]["variant"] == "good"
        # ...but a cpu/interpret row never engages
        assert search.engaged("stub", "n8") is None

    def test_all_candidates_wrong_raises(self, table):
        class AllBroken(_StubFamily):
            def candidates(self, shape):
                return [{"variant": "broken"}]

        with pytest.raises(RuntimeError, match="parity"):
            search.search_shape(AllBroken(), (8,), iters=2,
                                verbose=False)

    def test_flash_family_on_persist_mirrors_legacy(self, table):
        fam = search.FAMILIES["flash"]
        entry = {"config": {"block_q": 128, "block_k": 128},
                 "t_kernel_ms": 1.0, "t_composite_ms": 2.0,
                 "ratio": 2.0, "backend": "tpu",
                 "device": search._device_kind(),
                 "timestamp": "2026-08-03T00:00:00Z"}
        fam.on_persist((2, 128, 128, 8, True), entry)
        legacy = autotune.load_cache()["entries"][
            autotune._key(128, 128, 8, True)]
        assert legacy["block_q"] == 128
        assert legacy["ratio_fwd_bwd"] == 2.0
        assert legacy["via"] == "kernel_search"

    def test_flash_family_never_mirrors_cpu_rows(self, table):
        fam = search.FAMILIES["flash"]
        fam.on_persist((2, 128, 128, 8, True),
                       {"config": {"block_q": 128, "block_k": 128},
                        "t_kernel_ms": 1.0, "backend": "cpu",
                        "interpret": True})
        assert autotune.load_cache().get("entries", {}) == {}

    def test_headbatch_search_end_to_end_interpret(self, table):
        fam = search.FAMILIES["flash_headbatch"]
        entry = search.search_shape(fam, fam.smoke_shapes()[0], iters=2,
                                    verbose=False)
        assert entry["candidates_timed"] >= 1
        assert entry["parity_max_err"] <= fam.parity_atol
        assert search.lookup("flash_headbatch", entry["key"]) is not None


# -- tier-1 CLI smoke ---------------------------------------------------------

def test_kernel_search_cli_smoke_runs_full_pipeline(tmp_path):
    """Acceptance criterion: `python tools/kernel_search.py --smoke`
    runs enumerate -> parity filter -> timing on CPU and exits 0, with
    the one-JSON-line contract; its rows land in the given table marked
    cpu/interpret (engagement-inert)."""
    table = str(tmp_path / "t.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "tools/kernel_search.py", "--smoke",
         "--table", table],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("{"))
    rec = json.loads(line)
    assert rec["metric"] == "kernel_search_shapes"
    assert rec["value"] >= 2  # flash + headbatch at least
    assert rec["failures"] == {}
    assert rec["note"] == "cpu smoke mode; not a TPU number"
    with open(table) as f:
        data = json.load(f)
    fams = data["families"]
    assert {"flash", "flash_headbatch"} <= set(fams)
    for row in fams["flash_headbatch"]["entries"].values():
        assert row["backend"] == "cpu" and row["interpret"]


def test_monitor_audit_membership():
    # the None-slot zero-overhead-off audit in test_memory_numerics
    # parametrizes over this list — membership is the contract
    assert "paddle_tpu.ops.pallas.search" in monitor.INSTRUMENTED_MODULES


def test_monitor_report_renders_kernel_section(tmp_path):
    """`monitor_report` renders the pallas/search counters and a bench
    line's `kernels` engagement map."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "monitor_report_t", os.path.join(ROOT, "tools",
                                         "monitor_report.py"))
    mr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mr)
    jsonl = tmp_path / "run.jsonl"
    jsonl.write_text(json.dumps({"event": "run_begin", "meta": {}}) + "\n"
                     + json.dumps({
                         "event": "run_end", "wall_s": 1.0,
                         "totals": {"counters": {
                             "pallas/engaged": 3,
                             "pallas/fallback_composite": 1,
                             "pallas/engaged/flash": 3,
                             "search/candidates_timed": 7,
                             "search/rejects": 2},
                             "gauges": {
                                 "search/best_ratio/flash": 3.4},
                             "histograms": {}}}) + "\n")
    bench = tmp_path / "bench.log"
    bench.write_text(json.dumps({
        "metric": "serving_tokens_per_sec", "value": 10.0,
        "unit": "tokens/s",
        "kernels": {"flash_headbatch": True, "flash": False}}) + "\n")
    text = mr.render(str(jsonl), bench_path=str(bench))
    assert "pallas kernels (engagement + search)" in text
    assert "engaged 3   composite fallbacks 1" in text
    assert "candidates timed 7" in text
    assert "best ratio flash: 3.4" in text
    assert "flash_headbatch=engaged" in text
