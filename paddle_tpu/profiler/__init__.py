"""Profiler: host event tracing + op stats + chrome-trace export.

Reference parity: `paddle.profiler.Profiler`
(`python/paddle/profiler/profiler.py:349`), scheduler states (`:79`),
`RecordEvent` instrumentation (C++ `host_event_recorder.h`), chrome trace
export (`chrometracing_logger.cc`), summary tables
(`profiler_statistic.py`), and the throughput `Benchmark` ips meter
(`profiler/timer.py:349`).

TPU-first design: host events come from a Python-side recorder hooked into
the op dispatcher (every `apply` is an event, like the reference's
RecordEvent inside each ad_func); device timing comes from XLA — per-op
device profiling is `jax.profiler` (xplane) territory, exposed via
`start_server`/`trace_export` passthroughs. The Chrome-trace file contract
is kept so existing tooling opens our traces.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time

from ..ops import dispatch as _dispatch
from ..ops import registry as _registry

__all__ = [
    "Profiler", "RecordEvent", "ProfilerTarget", "ProfilerState",
    "make_scheduler", "export_chrome_tracing", "load_profiler_result",
    "Benchmark", "benchmark",
]


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"
    CUSTOM_DEVICE = "custom_device"
    TPU = "tpu"


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Parity: `paddle.profiler.make_scheduler` — maps step number to state."""
    period = closed + ready + record
    if period < 1:
        raise ValueError(
            f"make_scheduler needs closed+ready+record >= 1, got "
            f"closed={closed} ready={ready} record={record}")

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


class _HostEventRecorder:
    """Thread-safe append-only event buffer (the Python analogue of
    `host_event_recorder.h`'s per-thread chunked buffers)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.events = []

    def emit(self, name, t0, t1, cat="op", args=None):
        with self._lock:
            self.events.append({
                "name": name, "ts": t0 * 1e6, "dur": (t1 - t0) * 1e6,
                "cat": cat, "pid": os.getpid(),
                "tid": threading.get_ident() % 100000,
                "ph": "X", "args": args or {},
            })

    def clear(self):
        with self._lock:
            self.events = []


_recorder = _HostEventRecorder()
_active_profiler = None


class RecordEvent:
    """Parity: `paddle.profiler.RecordEvent` — user-scoped host event."""

    def __init__(self, name, event_type="UserDefined"):
        self.name = name
        self.event_type = event_type
        self._t0 = None

    def begin(self):
        self._t0 = time.perf_counter()

    def end(self):
        if self._t0 is not None and _active_profiler is not None:
            _recorder.emit(self.name, self._t0, time.perf_counter(),
                           cat=self.event_type)
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def export_chrome_tracing(dir_name, worker_name=None):
    """Parity: on_trace_ready=export_chrome_tracing(dir)."""

    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{name}.json")
        prof.export(path)
        return path

    return handler


def load_profiler_result(path):
    with open(path) as f:
        return json.load(f)


class Profiler:
    """Parity: `paddle.profiler.Profiler(targets, scheduler, on_trace_ready)`.

    Records one host event per dispatched op via the dispatcher's check-hook
    slot plus explicit RecordEvent scopes; exports chrome trace and a
    summary table.
    """

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None):
        self._scheduler = scheduler if callable(scheduler) else None
        if isinstance(scheduler, (tuple, list)):
            start, stop = scheduler
            self._scheduler = make_scheduler(
                closed=start, ready=0, record=stop - start, repeat=1)
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self.step_num = 0
        self._state = ProfilerState.RECORD
        self._op_t0 = {}
        self._installed = False
        self._orig_count_call = None

    # -- dispatcher instrumentation --
    def _install(self):
        if self._installed or self._timer_only:
            return
        self._orig_count_call = _registry.count_call
        prof = self

        def counting_hook(op_name):
            prof._orig_count_call(op_name)
            if prof._state in (ProfilerState.RECORD,
                               ProfilerState.RECORD_AND_RETURN):
                now = time.perf_counter()
                # zero-duration instant op mark; op host cost on TPU is
                # dispatch-only (execution is async on device)
                _recorder.emit(op_name, now, now, cat="op_dispatch")

        _registry.count_call = counting_hook
        _dispatch.registry.count_call = counting_hook
        self._installed = True

    def _uninstall(self):
        if self._installed:
            _registry.count_call = self._orig_count_call
            _dispatch.registry.count_call = self._orig_count_call
            self._installed = False

    # -- lifecycle --
    def start(self):
        global _active_profiler
        _active_profiler = self
        _recorder.clear()
        self._baseline_counts = dict(_registry.op_stats())
        self._t_start = time.perf_counter()
        self._install()
        if self._scheduler:
            self._state = self._scheduler(self.step_num)

    def stop(self):
        global _active_profiler
        self._uninstall()
        self._emit_monitor_counters()
        self._t_stop = time.perf_counter()
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)
        _active_profiler = None

    def step(self, num_samples=None):
        self.step_num += 1
        if not self._scheduler or self._state in (
                ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._emit_memory_counter()
            self._emit_monitor_counters()
        if self._scheduler:
            prev = self._state
            self._state = self._scheduler(self.step_num)
            if (prev == ProfilerState.RECORD_AND_RETURN
                    and self._on_trace_ready is not None):
                self._on_trace_ready(self)

    def _emit_memory_counter(self):
        """Chrome-trace counter event with the device allocator stats
        (parity: `mem_tracing.h` memory events merged into the trace)."""
        from ..framework import device as dev

        stats = dev.memory_stats()
        if not stats:
            return
        now = time.perf_counter()
        with _recorder._lock:
            _recorder.events.append({
                "name": "device memory", "ph": "C", "ts": now * 1e6,
                "pid": os.getpid(), "cat": "memory",
                "args": {
                    "bytes_in_use": stats.get("bytes_in_use", 0),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
                },
            })

    def _emit_monitor_counters(self):
        """Runtime-telemetry counters (`paddle_tpu.monitor`) as chrome-trace
        ``ph:"C"`` counter events, so retraces / sync fences / collective
        bytes render as counter tracks on the same Perfetto timeline as the
        host events. No-op when the monitor is disabled."""
        from ..monitor import enabled as _mon_enabled, snapshot as _mon_snap

        if not _mon_enabled():
            return
        snap = _mon_snap()
        ts = time.perf_counter() * 1e6
        pid = os.getpid()
        events = []
        for section in ("counters", "gauges"):
            for name, v in snap.get(section, {}).items():
                events.append({"name": f"monitor/{name}", "ph": "C",
                               "ts": ts, "pid": pid, "cat": "monitor",
                               "args": {"value": v}})
        for name, h in snap.get("histograms", {}).items():
            events.append({"name": f"monitor/{name}", "ph": "C", "ts": ts,
                           "pid": pid, "cat": "monitor",
                           "args": {"count": h["count"], "p50": h["p50"],
                                    "p95": h["p95"]}})
        if events:
            with _recorder._lock:
                _recorder.events.extend(events)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- results --
    def export(self, path, format="json"):  # noqa: A002
        """Chrome-trace JSON: host events + monitor ``ph:"C"`` counter
        tracks, merged with the monitor's flight-recorder spans
        (``monitor/spans.py`` — same ``perf_counter`` clock epoch, so the
        span lanes line up with the op timeline)."""
        with _recorder._lock:
            events = list(_recorder.events)
        from ..monitor import span_events

        # unconditional: the ring retains spans across disable() (a
        # teardown that toggled the monitor off must not erase what the
        # run recorded), and an empty ring contributes nothing
        events.extend(span_events())
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return path

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        counts = _registry.op_stats()
        base = getattr(self, "_baseline_counts", {})
        delta = {k: v - base.get(k, 0) for k, v in counts.items()
                 if v - base.get(k, 0) > 0}
        wall = getattr(self, "_t_stop", time.perf_counter()) - \
            getattr(self, "_t_start", 0)
        lines = ["-" * 60,
                 f"{'Op':<40}{'Calls':>10}",
                 "=" * 60]
        for name, n in sorted(delta.items(), key=lambda kv: -kv[1]):
            lines.append(f"{name:<40}{n:>10}")
        lines.append("=" * 60)
        lines.append(f"Total ops: {sum(delta.values())}   "
                     f"wall: {wall * 1000:.1f} ms")
        table = "\n".join(lines)
        print(table)
        return table


class Benchmark:
    """Parity: the ips meter (`profiler/timer.py:349` `benchmark()`),
    reporting reader_cost / batch_cost / ips."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._batch_times = []
        self._reader_times = []
        self._t = None
        self._reader_t = None

    def begin(self):
        self.reset()
        self._t = time.perf_counter()

    def before_reader(self):
        self._reader_t = time.perf_counter()

    def after_reader(self):
        if self._reader_t is not None:
            self._reader_times.append(time.perf_counter() - self._reader_t)

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t is not None:
            self._batch_times.append((now - self._t, num_samples or 1))
        self._t = now

    def end(self):
        pass

    def step_info(self, unit="samples"):
        if not self._batch_times:
            return "no steps recorded"
        bt = sum(t for t, _ in self._batch_times) / len(self._batch_times)
        n = sum(s for _, s in self._batch_times)
        total = sum(t for t, _ in self._batch_times)
        ips = n / total if total else 0.0
        rc = (sum(self._reader_times) / len(self._reader_times)
              if self._reader_times else 0.0)
        return (f"reader_cost: {rc:.5f} s, batch_cost: {bt:.5f} s, "
                f"ips: {ips:.2f} {unit}/s")

    @property
    def ips(self):
        total = sum(t for t, _ in self._batch_times)
        n = sum(s for _, s in self._batch_times)
        return n / total if total else 0.0


_benchmark = Benchmark()


def benchmark():
    """Parity: `paddle.profiler.benchmark()` singleton."""
    return _benchmark


class SortedKeys:
    """Parity: paddle.profiler.SortedKeys — summary sort orders."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView:
    """Parity: paddle.profiler.SummaryView — which summary tables to
    print."""

    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(profiler_result=None, file_name="profiler.pb"):
    """Parity shim: the reference serializes its C++ profiler records to
    a paddle-specific protobuf. This build's record stream is the chrome
    trace (`Profiler.export`) and the xplane protobuf XLA's own profiler
    writes (`jax.profiler`); this writes the chrome-trace JSON to
    ``file_name`` so the call site still produces an artifact, and says
    so rather than emitting a paddle-proto nobody here can read."""
    if profiler_result is None or not hasattr(profiler_result, "export"):
        raise ValueError(
            "export_protobuf needs the Profiler object (this build "
            "serializes the chrome trace; pass profiler, or use "
            "profiler.export(path) directly)")
    profiler_result.export(file_name)
    return file_name


__all__ += ["SortedKeys", "SummaryView", "export_protobuf"]
