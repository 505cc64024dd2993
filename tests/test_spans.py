"""Flight-recorder span tracing (`paddle_tpu/monitor/spans.py`) tests.

Covers the SpanRecorder primitives (ring bound, lane ordering, chrome
export well-formedness), the zero-overhead-off contract for the new
`_spans` slots, the instrumented CPU `fit()` run (≥3 thread lanes, spans
well-formed, attribution buckets sum ≤ wall and cover ≥90% of it), the
profiler-merged export, the StepLogger run_end-on-error line, and monitor
watchpoints (the live retrace-storm warning bench.py arms post-warmup).
"""
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.monitor.spans import ATTRIBUTION_CATEGORIES, SpanRecorder

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_report_tool():
    spec = importlib.util.spec_from_file_location(
        "monitor_report", os.path.join(_ROOT, "tools", "monitor_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def mon(tmp_path, monkeypatch):
    """Enabled monitor with clean metrics/spans; restores disabled-off."""
    monkeypatch.setenv("PT_MONITOR_SINK", str(tmp_path / "steps.jsonl"))
    monitor.reset()
    monitor.enable()
    yield monitor
    monitor.disable()
    monitor.reset()


class TestSpanRecorder:
    def test_record_and_snapshot_order(self):
        r = SpanRecorder(capacity=16)
        t = time.perf_counter()
        r.record("a", "dispatch", t, t + 0.001)
        r.record("b", "sync", t + 0.002, t + 0.003, lane="sync_fences")
        spans = r.snapshot()
        assert [s[0] for s in spans] == ["a", "b"]
        assert spans[0][2] == "main"  # default lane on the main thread
        assert spans[1][2] == "sync_fences"
        assert r.count == 2 and r.dropped == 0

    def test_ring_bound_and_dropped(self):
        r = SpanRecorder(capacity=4)
        t = time.perf_counter()
        for i in range(10):
            r.record(f"s{i}", "dispatch", t + i, t + i + 0.5)
        spans = r.snapshot()
        assert len(spans) == 4
        # the ring keeps the most recent, in order
        assert [s[0] for s in spans] == ["s6", "s7", "s8", "s9"]
        assert r.count == 10 and r.dropped == 6

    def test_span_context_manager(self):
        r = SpanRecorder(capacity=8)
        with r.span("region", "compile", args={"k": 1}):
            pass
        (name, cat, lane, t0, t1, args) = r.snapshot()[0]
        assert name == "region" and cat == "compile"
        assert t1 >= t0 and args == {"k": 1}

    def test_thread_lane_defaults_to_thread_name(self):
        r = SpanRecorder(capacity=8)

        def work():
            t = time.perf_counter()
            r.record("w", "dispatch", t, t)

        th = threading.Thread(target=work, name="worker-lane")
        th.start()
        th.join()
        assert r.snapshot()[0][2] == "worker-lane"

    def test_chrome_events_well_formed_lanes_main_first(self):
        r = SpanRecorder(capacity=8)
        t = time.perf_counter()
        r.record("p", "prefetch_stage", t, t + 0.001,
                 lane="prefetch_producer")
        r.record("m", "dispatch", t, t + 0.002)  # main
        assert r.lanes()[0] == "main"
        events = r.chrome_events(pid=7)
        meta = [e for e in events if e["ph"] == "M"
                and e["name"] == "thread_name"]
        lanes = {e["args"]["name"]: e["tid"] for e in meta}
        assert lanes["main"] == 1
        xs = [e for e in events if e["ph"] == "X"]
        assert len(xs) == 2
        for e in xs:
            assert e["dur"] >= 0 and e["pid"] == 7
            assert e["tid"] in lanes.values()
            assert isinstance(e["ts"], float)

    def test_clear(self):
        r = SpanRecorder(capacity=8)
        r.record("a", "sync", 0.0, 1.0)
        r.clear()
        assert r.snapshot() == [] and r.count == 0


class TestZeroOverheadOff:
    def test_span_slots_none_when_disabled(self):
        assert not monitor.enabled()
        from paddle_tpu.distributed import collective
        from paddle_tpu.hapi import model as hapi_model
        from paddle_tpu.io import prefetch
        from paddle_tpu.jit import train_step
        from paddle_tpu.utils import timing

        for mod in (prefetch, train_step, timing, hapi_model, collective):
            assert mod._spans is None, mod.__name__

    def test_record_span_noop_when_disabled(self):
        assert not monitor.enabled()
        before = monitor.spans().count
        monitor.record_span("x", "sync", 0.0, 1.0)
        assert monitor.spans().count == before

    def test_enable_wires_disable_clears(self, mon):
        from paddle_tpu.io import prefetch
        from paddle_tpu.jit import train_step
        from paddle_tpu.utils import timing

        rec = monitor.spans()
        for mod in (prefetch, train_step, timing):
            assert mod._spans is rec, mod.__name__
        monitor.disable()
        for mod in (prefetch, train_step, timing):
            assert mod._spans is None, mod.__name__


class TestInstrumentationSpans:
    def test_device_sync_records_sync_span(self, mon):
        import jax.numpy as jnp

        from paddle_tpu.utils.timing import device_sync

        device_sync(jnp.ones((4,)))
        spans = monitor.spans().snapshot()
        syncs = [s for s in spans if s[0] == "sync/device_sync"]
        assert len(syncs) == 1
        assert syncs[0][1] == "sync" and syncs[0][2] == "sync_fences"

    def test_trainstep_compile_vs_dispatch_spans(self, mon):
        from paddle_tpu.jit.train_step import TrainStep

        net = pt.nn.Linear(4, 4)
        opt = pt.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
        step = TrainStep(net, opt, lambda m, x, y: ((m(x) - y) ** 2).mean())
        x = pt.to_tensor(np.ones((2, 4), np.float32))
        y = pt.to_tensor(np.zeros((2, 4), np.float32))
        step(x, y)
        step(x, y)
        names = [s[0] for s in monitor.spans().snapshot()]
        # first call is the fresh signature -> one compile span, second
        # call is a cache hit -> one dispatch span
        assert names.count("jit/trace_compile") == 1
        assert names.count("jit/step_dispatch") == 1

    def test_collective_span(self, mon):
        import paddle_tpu.distributed as dist

        try:
            x = pt.to_tensor(np.ones((4, 2), np.float32))
            try:
                dist.all_reduce(x)
            except AttributeError:
                pass  # pre-existing jax alias gap; span already recorded
            names = [s[0] for s in monitor.spans().snapshot()]
            assert "collective/all_reduce" in names
        finally:
            from paddle_tpu.distributed import env as env_mod

            if env_mod.get_env() is not None:
                env_mod.reset_env()


def _run_fit(tmp_path, steps=32, batch_size=4, log_freq=3):
    net = pt.nn.Linear(8, 4)
    model = pt.Model(net)
    model.prepare(
        pt.optimizer.SGD(learning_rate=0.1, parameters=net.parameters()),
        pt.nn.MSELoss())
    xs = np.ones((steps * batch_size, 8), np.float32)
    ys = np.zeros((steps * batch_size, 4), np.float32)
    ds = [(xs[i], ys[i]) for i in range(steps * batch_size)]
    model.fit(ds, batch_size=batch_size, epochs=1, verbose=0,
              log_freq=log_freq, device_prefetch=1)


class TestFitTraceExport:
    """The issue's acceptance run: a CPU fit with the monitor on yields a
    chrome trace with ≥3 distinct thread lanes whose spans are well-formed
    and whose attribution buckets sum to ≤ the measured wall time."""

    # fraction of the step-window wall the named buckets must explain.
    # "other" is legitimate python bookkeeping PLUS whatever the OS
    # scheduler steals on the shared 2-core box, so (like the
    # host-overhead smoke) the bound gets one noisy-neighbor retry with
    # a fresh run before it may fail the tier.
    WALL_COVERAGE_MIN = 0.75
    # two clean-slate retries: one was not enough on the shared box —
    # the bound still tripped ~1-in-4 full-suite runs when this module
    # follows compile-heavy ones (base tree and PRs alike; see the
    # flaky-test note), and each retry is an independent ~2 s fit
    _RETRIES = 2

    def test_fit_trace_lanes_wellformed_and_attribution(self, mon,
                                                        tmp_path):
        for attempt in range(self._RETRIES + 1):
            _run_fit(tmp_path)
            trace_path = str(tmp_path / "fit_trace.json")
            monitor.export_spans(trace_path)
            with open(trace_path) as f:
                trace = json.load(f)
            events = trace["traceEvents"]
            lanes = {e["args"]["name"]: e["tid"] for e in events
                     if e.get("ph") == "M" and e["name"] == "thread_name"}
            # producer thread, main/stepper, sync fences (+ steps lane)
            assert len(lanes) >= 3
            assert {"main", "prefetch_producer", "sync_fences"} \
                <= set(lanes)
            tids = set(lanes.values())
            xs = [e for e in events if e.get("ph") == "X"]
            assert xs
            for e in xs:
                assert e["name"] and "ts" in e and "dur" in e
                assert e["dur"] >= 0
                assert e["tid"] in tids

            # attribution: buckets never exceed the step wall they
            # decompose
            tool = _load_report_tool()
            steps, by_cat = tool.load_spans(trace_path)
            att = tool.attribute_spans(steps, by_cat)
            assert att["wall_ms"] > 0
            bucket_sum = sum(att["totals"][c]
                             for c in ATTRIBUTION_CATEGORIES)
            assert bucket_sum <= att["wall_ms"] + 1e-6
            for row in att["per_step"]:
                assert row["other"] >= 0
                assert sum(row[c] for c in ATTRIBUTION_CATEGORIES) \
                    <= row["dur_ms"] + 1e-6
            # the named categories must explain ≥90% of the MEASURED
            # host-blocked time (the same regions the counter histograms
            # time: transfer fences, bound waits, starved waits,
            # compiles) — per-step python bookkeeping is legitimately
            # "other"
            hists = monitor.snapshot().get("histograms", {})
            blocked_ms = sum(
                hists.get(h, {"sum": 0.0})["sum"]
                for h in ("sync/fence_ms", "async/bound_wait_ms",
                          "io/prefetch_wait_ms")
            ) + hists.get("jit/compile_ms", {"sum": 0.0})["sum"]
            assert blocked_ms > 0
            assert bucket_sum >= 0.9 * min(blocked_ms, att["wall_ms"]), (
                att["totals"], blocked_ms)
            # the instrumented regions still cover the bulk of the wall
            # — the one load-sensitive bound, retried on a clean slate
            if bucket_sum >= self.WALL_COVERAGE_MIN * att["wall_ms"]:
                return
            if attempt < self._RETRIES:
                monitor.reset()
        assert bucket_sum >= self.WALL_COVERAGE_MIN * att["wall_ms"], (
            att["totals"])

    def test_report_cli_spans_section(self, mon, tmp_path, capsys):
        _run_fit(tmp_path, steps=8)
        trace_path = str(tmp_path / "t.json")
        monitor.export_spans(trace_path)
        jsonl = str(tmp_path / "steps.jsonl")  # MonitorCallback sink
        report = _load_report_tool().main(
            [jsonl, "--trace", trace_path, "--spans"])
        assert "span attribution" in report
        assert "attributed:" in report
        assert "span lanes:" in report
        # satellite: the PR 2 counters render instead of being dropped
        assert "async pipeline" in report
        assert "prefetch: staged" in report
        assert "hapi host syncs" in report

    def test_report_cli_selftest(self):
        """`monitor_report.py --selftest` synthesizes its own fixtures
        (JSONL + spans trace + bench line) and asserts every section —
        including the ISSUE 16 requests/attribution sections — renders.
        Run as a subprocess: the tier-1 proof is the CLI contract."""
        proc = subprocess.run(
            [sys.executable,
             os.path.join(_ROOT, "tools", "monitor_report.py"),
             "--selftest"],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "selftest ok" in proc.stdout


class TestAttributionPass:
    def test_nested_spans_count_once_priority_order(self, tmp_path):
        # fence_wait [0,10]ms wrapping sync [2,8]; dispatch [12,15];
        # one step window [0,20]
        def ev(name, cat, t0_ms, t1_ms):
            return {"name": name, "cat": cat, "ph": "X", "ts": t0_ms * 1e3,
                    "dur": (t1_ms - t0_ms) * 1e3, "pid": 1, "tid": 1}

        trace = {"traceEvents": [
            ev("step/1", "step", 0, 20),
            ev("async/bound_wait", "fence_wait", 0, 10),
            ev("sync/device_sync", "sync", 2, 8),
            ev("jit/step_dispatch", "dispatch", 12, 15),
        ]}
        path = str(tmp_path / "synt.json")
        with open(path, "w") as f:
            json.dump(trace, f)
        tool = _load_report_tool()
        steps, by_cat = tool.load_spans(path)
        att = tool.attribute_spans(steps, by_cat)
        row = att["per_step"][0]
        assert row["fence_wait"] == pytest.approx(10.0)
        assert row["sync"] == pytest.approx(0.0)  # nested: counted once
        assert row["dispatch"] == pytest.approx(3.0)
        assert row["other"] == pytest.approx(7.0)
        assert att["wall_ms"] == pytest.approx(20.0)

    def test_no_step_markers_falls_back_to_extent(self, tmp_path):
        trace = {"traceEvents": [
            {"name": "s", "cat": "sync", "ph": "X", "ts": 1000.0,
             "dur": 2000.0, "pid": 1, "tid": 1}]}
        path = str(tmp_path / "nostep.json")
        with open(path, "w") as f:
            json.dump(trace, f)
        tool = _load_report_tool()
        att = tool.attribute_spans(*tool.load_spans(path))
        assert att["totals"]["sync"] == pytest.approx(2.0)
        assert att["per_step"][0]["step"] == "run"


class TestProfilerMerge:
    def test_export_merges_span_events(self, mon, tmp_path):
        import paddle_tpu.profiler as profiler

        p = profiler.Profiler()
        p.start()
        x = pt.ones([4, 4])
        (x @ x).sum()
        t = time.perf_counter()
        monitor.record_span("custom/region", "dispatch", t, t + 0.001)
        p.step()
        p.stop()
        path = str(tmp_path / "merged.json")
        p.export(path)
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        assert any(e.get("name") == "custom/region" for e in events)
        assert any(e.get("ph") == "M" and e.get("name") == "thread_name"
                   for e in events)
        # the existing counter tracks still export alongside
        assert any(e.get("ph") == "C" for e in events)
        # spans recorded during the run survive a disable() before export
        # (the ring outlives enablement; a teardown toggle must not erase
        # what the run recorded)
        monitor.disable()
        try:
            path2 = str(tmp_path / "after_disable.json")
            p.export(path2)
            with open(path2) as f:
                ev2 = json.load(f)["traceEvents"]
            assert any(e.get("name") == "custom/region" for e in ev2)
        finally:
            monitor.enable()  # the mon fixture's teardown expects enabled


class TestStepLoggerErrorPath:
    def test_context_manager_writes_error_run_end(self, mon, tmp_path):
        path = str(tmp_path / "err.jsonl")
        with pytest.raises(RuntimeError, match="boom"):
            with monitor.StepLogger(path) as log:
                log.log_step(loss=1.0)
                raise RuntimeError("boom")
        lines = [json.loads(ln) for ln in open(path)]
        assert lines[-1]["event"] == "run_end"
        assert "RuntimeError: boom" in lines[-1]["error"]
        assert lines[-1]["steps"] == 1

    def test_fit_crash_flushes_run_end(self, mon, tmp_path):
        from paddle_tpu.hapi.callbacks import Callback, MonitorCallback

        class Bomb(Callback):
            def on_train_batch_end(self, step, logs=None):
                if step >= 1:
                    raise RuntimeError("mid-epoch crash")

        path = str(tmp_path / "crash.jsonl")
        net = pt.nn.Linear(4, 2)
        model = pt.Model(net)
        model.prepare(
            pt.optimizer.SGD(learning_rate=0.1,
                             parameters=net.parameters()),
            pt.nn.MSELoss())
        xs = np.ones((8, 4), np.float32)
        ys = np.zeros((8, 2), np.float32)
        ds = [(xs[i], ys[i]) for i in range(8)]
        with pytest.raises(RuntimeError, match="mid-epoch crash"):
            model.fit(ds, batch_size=2, epochs=1, verbose=0,
                      callbacks=[MonitorCallback(path), Bomb()])
        lines = [json.loads(ln) for ln in open(path)]
        assert lines[-1]["event"] == "run_end"
        assert "mid-epoch crash" in lines[-1]["error"]
        # the crashed run is distinguishable from a truncated file: steps
        # logged before the crash are present AND terminated
        assert any("step" in ln for ln in lines)

    def test_clean_close_has_no_error_field(self, mon, tmp_path):
        path = str(tmp_path / "ok.jsonl")
        with monitor.StepLogger(path) as log:
            log.log_step(loss=1.0)
        end = [json.loads(ln) for ln in open(path)][-1]
        assert end["event"] == "run_end" and "error" not in end


class TestWatchpoints:
    def test_retrace_watchpoint_fires_once(self, mon, capsys):
        from paddle_tpu.jit.train_step import TrainStep

        fired = []
        net = pt.nn.Linear(4, 4)
        opt = pt.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
        step = TrainStep(net, opt, lambda m, x, y: ((m(x) - y) ** 2).mean())
        x = pt.to_tensor(np.ones((2, 4), np.float32))
        y = pt.to_tensor(np.zeros((2, 4), np.float32))
        step(x, y)  # warmup compile
        base = monitor.snapshot()["counters"]["jit/retraces"]
        monitor.watchpoint("jit/retraces", base,
                           message="post-warmup retrace storm",
                           callback=lambda n, v: fired.append((n, v)))
        step(x, y)  # cache hit: below ceiling, must not fire
        assert fired == []
        x2 = pt.to_tensor(np.ones((3, 4), np.float32))
        y2 = pt.to_tensor(np.zeros((3, 4), np.float32))
        step(x2, y2)  # shape change -> retrace -> fires
        step(pt.to_tensor(np.ones((5, 4), np.float32)),
             pt.to_tensor(np.zeros((5, 4), np.float32)))  # one-shot
        assert fired == [("jit/retraces", base + 1)]
        assert "post-warmup retrace storm" in capsys.readouterr().err

    def test_reset_clears_watchpoints(self, mon):
        monitor.watchpoint("jit/retraces", 0)
        monitor.reset()
        from paddle_tpu.monitor import _watchpoints

        assert _watchpoints == {}

    def test_unwatchable_counter_raises(self, mon):
        # an armed alarm that no site ever checks would silently never
        # fire — refuse it loudly instead
        with pytest.raises(ValueError, match="not checked live"):
            monitor.watchpoint("dispatch/op_apply", 10)

    def test_sync_storm_watchpoint_fires(self, mon, capsys):
        import jax.numpy as jnp

        from paddle_tpu.utils.timing import device_sync

        fired = []
        monitor.watchpoint("sync/fences", 1, message="sync storm",
                           callback=lambda n, v: fired.append(v))
        device_sync(jnp.ones((2,)))  # 1: at ceiling, no fire
        assert fired == []
        device_sync(jnp.ones((2,)))  # 2: past ceiling
        assert fired == [2]
        assert "sync storm" in capsys.readouterr().err
