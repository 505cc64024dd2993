"""Runtime telemetry (`paddle_tpu.monitor`) tests.

Covers the zero-overhead-when-off contract (no monitor callables on the
dispatch hot path unless enabled), counter thread-safety under concurrent
emit, instrumentation of jit retraces / sync fences / collectives / RNG /
AMP, the StepLogger JSONL sink (monotonic step ids, counter diffs), the
hapi MonitorCallback, and the tools/monitor_report.py renderer — including
the tier-1 smoke: PT_MONITOR-style 3-step training on the virtual 8-device
mesh yields exactly 1 retrace for fixed shapes, 2 after a shape change, and
zero sync fences on CPU.
"""
import importlib.util
import json
import os
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.jit.train_step import TrainStep
from paddle_tpu.ops import dispatch

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_report_tool():
    spec = importlib.util.spec_from_file_location(
        "monitor_report", os.path.join(_ROOT, "tools", "monitor_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def mon():
    """Enabled monitor with clean metrics; restores disabled-off state."""
    monitor.reset()
    monitor.enable()
    yield monitor
    monitor.disable()
    monitor.reset()


class TestMetricsPrimitives:
    def test_counter(self):
        c = monitor.counter("test/c1")
        c.reset()
        c.inc()
        c.inc(4)
        assert c.value == 5

    def test_gauge_and_histogram(self):
        g = monitor.gauge("test/g1")
        g.set(3)
        assert g.value == 3.0
        h = monitor.histogram("test/h1")
        h.reset()
        for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 5
        assert snap["min"] == 1.0 and snap["max"] == 100.0
        assert snap["p50"] == 3.0
        assert h.percentile(100) == 100.0

    def test_type_mismatch_raises(self):
        monitor.counter("test/typed")
        with pytest.raises(TypeError):
            monitor.histogram("test/typed")

    def test_snapshot_diff(self):
        c = monitor.counter("test/diffc")
        c.reset()
        prev = monitor.snapshot()
        c.inc(7)
        d = monitor.diff(prev)
        assert d["counters"]["test/diffc"] == 7
        # no-change diff is empty
        assert monitor.diff(monitor.snapshot()) == {}

    def test_counter_thread_safety_under_concurrent_emit(self):
        c = monitor.counter("test/threads")
        c.reset()
        h = monitor.histogram("test/threads_h")
        h.reset()
        n_threads, n_iters = 8, 2000

        def work():
            for i in range(n_iters):
                c.inc()
                h.observe(float(i))

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n_threads * n_iters
        assert h.count == n_threads * n_iters

    def test_registry_reset_keeps_objects_live(self):
        c = monitor.counter("test/reset")
        c.inc(3)
        monitor.reset()
        assert c.value == 0
        c.inc()  # the same object the instrumentation holds still counts
        assert monitor.counter("test/reset") is c
        assert c.value == 1


class TestZeroOverheadWhenOff:
    def test_hooks_none_when_disabled(self):
        """PT_MONITOR=0 contract: the dispatch hot path holds no monitor
        callable — the slot is None, guarded at registration. Covers
        every instrumentation site, including the PR 2 async-pipeline
        modules (io/prefetch, AsyncStepper's module, hapi) and the new
        `_spans` flight-recorder slots (monitor/spans.py)."""
        assert not monitor.enabled()
        assert dispatch._monitor is None
        import importlib

        ac_mod = importlib.import_module("paddle_tpu.amp.auto_cast")
        rng_mod = importlib.import_module("paddle_tpu.framework.random")
        from paddle_tpu.distributed import collective
        from paddle_tpu.hapi import model as hapi_model
        from paddle_tpu.io import prefetch
        from paddle_tpu.jit import train_step as ts_mod
        from paddle_tpu.utils import timing

        for mod in (timing, ts_mod, prefetch, hapi_model, collective,
                    rng_mod, ac_mod):
            assert mod._monitor is None, mod.__name__
        # every module that records spans: the span slot is None too
        for mod in (timing, ts_mod, prefetch, hapi_model, collective):
            assert mod._spans is None, mod.__name__

    def test_enable_wires_all_sites_disable_clears(self):
        from paddle_tpu.distributed import collective
        from paddle_tpu.hapi import model as hapi_model
        from paddle_tpu.io import prefetch
        from paddle_tpu.jit import train_step as ts_mod
        from paddle_tpu.utils import timing

        sites = (timing, ts_mod, prefetch, hapi_model, collective)
        monitor.enable()
        try:
            for mod in sites:
                assert mod._monitor is monitor, mod.__name__
                assert mod._spans is monitor.spans(), mod.__name__
        finally:
            monitor.disable()
        for mod in sites:
            assert mod._monitor is None and mod._spans is None, mod.__name__

    def test_counter_code_not_invoked_when_off(self):
        monitor.reset()
        before = monitor.snapshot()
        x = pt.ones([2, 2])
        _ = (x + 1) * 2
        assert monitor.snapshot() == before

    def test_enable_installs_disable_removes(self, mon):
        assert dispatch._monitor is monitor
        x = pt.ones([2, 2])
        _ = x + 1
        assert monitor.snapshot()["counters"]["dispatch/op_apply"] >= 1
        monitor.disable()
        assert dispatch._monitor is None

    def test_prim_cache_hit_miss_counted(self, mon):
        from paddle_tpu.tensor.math import add  # any cacheable op path

        x = pt.ones([3])
        add(x, x)
        add(x, x)
        c = monitor.snapshot()["counters"]
        assert c.get("dispatch/prim_cache_hit", 0) >= 1


class TestInstrumentationSites:
    def test_device_sync_histogram(self, mon):
        import jax.numpy as jnp

        from paddle_tpu.utils.timing import device_sync

        device_sync(jnp.ones((4,)))
        snap = monitor.snapshot()
        assert snap["counters"]["sync/fences"] == 1
        assert snap["histograms"]["sync/fence_ms"]["count"] == 1

    def test_rng_key_splits(self, mon):
        from paddle_tpu.framework import random as rng

        rng.next_key()
        rng.next_key()
        assert monitor.snapshot()["counters"]["rng/key_splits"] == 2

    def test_autocast_entries(self, mon):
        with pt.amp.auto_cast():
            pass
        with pt.amp.auto_cast(enable=False):
            pass  # disabled region does not count
        assert monitor.snapshot()["counters"]["amp/autocast_enters"] == 1

    def test_collective_counts_and_bytes(self, mon):
        import paddle_tpu.distributed as dist

        try:
            x = pt.to_tensor(np.ones((4, 2), np.float32))
            try:
                dist.all_reduce(x)  # world group, auto 8-device mesh
            except AttributeError:
                # pre-existing on this jax: no jax.shard_map alias — the
                # eager program build fails AFTER the telemetry fired,
                # which is all this test asserts
                pass
            snap = monitor.snapshot()
            assert snap["counters"]["collective/all_reduce"] == 1
            assert snap["counters"]["collective/bytes"] >= 4 * 2 * 4
        finally:
            # don't leak the auto mesh into the rest of this module
            from paddle_tpu.distributed import env as env_mod

            if env_mod.get_env() is not None:
                env_mod.reset_env()


class TestTrainStepTelemetry:
    def _build(self):
        net = pt.nn.Linear(4, 4)
        opt = pt.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
        return TrainStep(net, opt,
                         lambda m, x, y: ((m(x) - y) ** 2).mean())

    def test_retrace_and_compile_counts(self, mon):
        step = self._build()
        x = pt.to_tensor(np.ones((2, 4), np.float32))
        y = pt.to_tensor(np.zeros((2, 4), np.float32))
        for _ in range(3):
            step(x, y)
        c = monitor.snapshot()["counters"]
        assert c["jit/retraces"] == 1
        assert c["jit/compiles"] == 1
        assert monitor.snapshot()["histograms"]["jit/compile_ms"]["count"] == 1
        assert monitor.snapshot()["gauges"]["jit/signature_cache_size"] == 1
        # shape change -> one more retrace
        x2 = pt.to_tensor(np.ones((3, 4), np.float32))
        y2 = pt.to_tensor(np.zeros((3, 4), np.float32))
        step(x2, y2)
        c = monitor.snapshot()["counters"]
        assert c["jit/retraces"] == 2
        assert monitor.snapshot()["gauges"]["jit/signature_cache_size"] == 2

    def test_cache_size_gauge_sums_across_instances(self, mon):
        # two TrainStep instances must not clobber each other's size
        s1, s2 = self._build(), self._build()
        x = pt.to_tensor(np.ones((2, 4), np.float32))
        y = pt.to_tensor(np.zeros((2, 4), np.float32))
        s1(x, y)
        x2 = pt.to_tensor(np.ones((5, 4), np.float32))
        y2 = pt.to_tensor(np.zeros((5, 4), np.float32))
        s1(x2, y2)
        s2(x, y)
        assert monitor.snapshot()["gauges"]["jit/signature_cache_size"] == 3

    def test_donation_rebinds_counted(self, mon):
        net = pt.nn.Linear(4, 4)
        opt = pt.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
        step = TrainStep(net, opt,
                         lambda m, x, y: ((m(x) - y) ** 2).mean(),
                         donate=True)
        x = pt.to_tensor(np.ones((2, 4), np.float32))
        y = pt.to_tensor(np.zeros((2, 4), np.float32))
        step(x, y)
        step(x, y)
        n_params = len([p for p in net.parameters() if not p.stop_gradient])
        c = monitor.snapshot()["counters"]
        assert c["jit/donation_rebinds"] == 2 * n_params


class TestStepLogger:
    def test_jsonl_lines_and_counter_diff(self, mon, tmp_path):
        path = str(tmp_path / "run.jsonl")
        step = TestTrainStepTelemetry()._build()
        x = pt.to_tensor(np.ones((2, 4), np.float32))
        y = pt.to_tensor(np.zeros((2, 4), np.float32))
        with monitor.StepLogger(path, meta={"source": "test"}) as log:
            for _ in range(3):
                loss = step(x, y)
                log.log_step(loss=float(loss.numpy()), num_samples=2)
        lines = [json.loads(ln) for ln in open(path)]
        assert lines[0]["event"] == "run_begin"
        assert lines[0]["monitor_enabled"] is True
        steps = [ln for ln in lines if "step" in ln]
        assert [s["step"] for s in steps] == [1, 2, 3]  # monotonic
        assert all("loss" in s and "ips" in s and "dur_ms" in s
                   for s in steps)
        # exactly ONE retrace across the fixed-shape run, on step 1
        retraces = [s.get("counters", {}).get("jit/retraces", 0)
                    for s in steps]
        assert retraces == [1, 0, 0]
        end = lines[-1]
        assert end["event"] == "run_end" and end["steps"] == 3
        assert end["totals"]["counters"]["jit/retraces"] == 1
        # CPU-only guard: no sync fences during training
        assert end["totals"]["counters"].get("sync/fences", 0) == 0

    def test_works_with_monitor_disabled(self, tmp_path):
        assert not monitor.enabled()
        path = str(tmp_path / "off.jsonl")
        with monitor.StepLogger(path) as log:
            log.log_step(loss=1.0)
        lines = [json.loads(ln) for ln in open(path)]
        assert lines[0]["monitor_enabled"] is False
        assert lines[1]["step"] == 1

    def test_close_idempotent(self, mon, tmp_path):
        log = monitor.StepLogger(str(tmp_path / "x.jsonl"))
        log.close()
        log.close()


class TestMeshSmoke:
    """Tier-1 smoke from the issue: PT_MONITOR-enabled 3-step training on
    the virtual 8-device mesh -> parseable JSONL, monotonic ids, 1 retrace
    for fixed shapes (2 after a shape change), zero sync fences; then the
    report CLI renders a summary from it."""

    @pytest.fixture
    def mesh(self):
        from paddle_tpu.distributed import fleet

        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 2, "mp_degree": 4, "pp_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        yield
        from paddle_tpu.distributed import env as env_mod

        env_mod.reset_env()

    def test_three_step_mesh_run_and_report(self, mon, mesh, tmp_path,
                                            capsys):
        path = str(tmp_path / "mesh_run.jsonl")
        net = pt.nn.Linear(8, 8)
        opt = pt.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
        step = TrainStep(net, opt,
                         lambda m, x, y: ((m(x) - y) ** 2).mean())
        x = pt.to_tensor(np.ones((4, 8), np.float32))
        y = pt.to_tensor(np.zeros((4, 8), np.float32))
        with monitor.StepLogger(path, meta={"mesh": "dp2xmp4"}) as log:
            for _ in range(3):
                loss = step(x, y)
                log.log_step(loss=float(loss.numpy()), num_samples=4)
            # shape change -> second retrace, visible in the step diff
            x2 = pt.to_tensor(np.ones((2, 8), np.float32))
            y2 = pt.to_tensor(np.zeros((2, 8), np.float32))
            loss = step(x2, y2)
            log.log_step(loss=float(loss.numpy()), num_samples=2)
        lines = [json.loads(ln) for ln in open(path)]
        steps = [ln for ln in lines if "step" in ln]
        assert [s["step"] for s in steps] == [1, 2, 3, 4]
        retrace_total = sum(s.get("counters", {}).get("jit/retraces", 0)
                            for s in steps[:3])
        assert retrace_total == 1
        assert sum(s.get("counters", {}).get("jit/retraces", 0)
                   for s in steps) == 2
        end = lines[-1]
        assert end["totals"]["counters"].get("sync/fences", 0) == 0

        report = _load_report_tool().main([path])
        assert "steps: 4" in report
        assert "jit/retraces" in report
        assert "retrace timeline" in report


class TestMonitorCallback:
    def test_fit_emits_jsonl(self, mon, tmp_path):
        from paddle_tpu.hapi.callbacks import MonitorCallback

        path = str(tmp_path / "fit.jsonl")
        net = pt.nn.Linear(4, 2)
        model = pt.Model(net)
        model.prepare(
            pt.optimizer.SGD(learning_rate=0.1,
                             parameters=net.parameters()),
            pt.nn.MSELoss())
        xs = np.ones((8, 4), np.float32)
        ys = np.zeros((8, 2), np.float32)
        ds = [(xs[i], ys[i]) for i in range(8)]
        model.fit(ds, batch_size=4, epochs=1, verbose=0,
                  callbacks=[MonitorCallback(path)])
        lines = [json.loads(ln) for ln in open(path)]
        assert lines[0]["event"] == "run_begin"
        assert lines[0]["meta"]["source"] == "hapi.fit"
        steps = [ln for ln in lines if "step" in ln]
        assert len(steps) == 2 and steps[-1]["step"] == 2
        assert lines[-1]["event"] == "run_end"

    def test_auto_added_when_enabled(self, mon):
        from paddle_tpu.hapi.callbacks import (MonitorCallback,
                                               config_callbacks)

        cbks = config_callbacks(verbose=0)
        assert any(isinstance(c, MonitorCallback) for c in cbks.callbacks)
        monitor.disable()
        cbks = config_callbacks(verbose=0)
        assert not any(isinstance(c, MonitorCallback)
                       for c in cbks.callbacks)


class TestReportTool:
    def test_render_with_trace_join(self, mon, tmp_path):
        import paddle_tpu.profiler as profiler

        # build a trace with op events + monitor counter tracks
        p = profiler.Profiler()
        p.start()
        x = pt.ones([4, 4])
        (x @ x).sum()
        p.step()
        p.stop()
        trace_path = str(tmp_path / "trace.json")
        p.export(trace_path)

        path = str(tmp_path / "run.jsonl")
        with monitor.StepLogger(path) as log:
            log.log_step(loss=1.0, num_samples=4)
        tool = _load_report_tool()
        report = tool.render(path, trace_path=trace_path)
        assert "chrome trace" in report
        assert "matmul" in report
        assert "monitor/dispatch/op_apply" in report

    def test_render_tolerates_junk_lines(self, tmp_path):
        path = str(tmp_path / "junk.jsonl")
        with open(path, "w") as f:
            f.write('{"step": 1, "dur_ms": 5.0}\n')
            f.write("not json at all\n")
            f.write('{"step": 2, "dur_ms": 6.0}\n')
        report = _load_report_tool().render(path)
        assert "steps: 2" in report
