"""Pipeline flight recorder: cross-thread span tracing for the runtime.

Counters (`monitor/metrics.py`) say *how many* retraces, syncs, and
starvations a run paid; spans say *where the wall time went*. Each span is
one timed host-side region — a prefetch `device_put` staging, a compiled
step dispatch, a trace+compile, an AsyncStepper fence wait, a
`device_sync` transfer fence, a hapi materialization — recorded into a
bounded ring buffer with a *lane* (logical thread track) so the producer
thread, the main stepping thread, and the sync fences render as separate
rows on one timeline.

Zero-overhead-when-off: instrumented modules carry a module-global
``_spans`` slot (sibling of the ``_monitor`` counter slot) that is ``None``
unless :func:`paddle_tpu.monitor.enable` installed the recorder — off, the
hot path pays one ``is None`` check and no recorder code runs.

Clock contract: ring timestamps are ``time.perf_counter()`` seconds — the
same epoch the profiler's host events and ``ph:"C"`` counter tracks use
(`profiler/__init__.py:_HostEventRecorder.emit`), so a merged chrome trace
(`Profiler.export` or :func:`paddle_tpu.monitor.export_spans`) lines spans
up with the op timeline. That is NOT the clock of an xplane device trace
(``jax.profiler`` numbers its events from the start of its session):
device alignment comes from :class:`Phase`, whose
``jax.profiler.TraceAnnotation`` lands in the device trace's own file.

Categories double as host-blocked-time attribution buckets
(`tools/monitor_report.py --spans`): ``sync`` (transfer fences),
``fence_wait`` (AsyncStepper bound/drain), ``prefetch_starvation``
(consumer blocked on an empty buffer), ``compile`` (trace + XLA compile),
``dispatch`` (step/collective enqueue). Non-bucket categories (``step``
markers, producer-side ``prefetch_stage``, hapi ``phase`` brackets) carry
timeline context without entering the attribution sum.
"""
from __future__ import annotations

import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["SpanRecorder", "Phase", "ATTRIBUTION_CATEGORIES"]

# the buckets tools/monitor_report.py --spans decomposes host time into;
# order is the nesting priority (earlier wins an overlapping slice: a
# device_sync inside an AsyncStepper fence counts once, as fence_wait)
ATTRIBUTION_CATEGORIES = (
    "fence_wait", "prefetch_starvation", "compile", "dispatch", "sync",
)

_MAIN_THREAD_ID = threading.main_thread().ident


def _default_capacity() -> int:
    try:
        return max(1024, int(os.environ.get("PT_MONITOR_SPANS_CAP", "65536")))
    except ValueError:
        return 65536


class _Span:
    """Context-manager handle from :meth:`SpanRecorder.span`."""

    __slots__ = ("_rec", "_name", "_cat", "_lane", "_args", "_t0")

    def __init__(self, rec, name, cat, lane, args):
        self._rec = rec
        self._name = name
        self._cat = cat
        self._lane = lane
        self._args = args
        self._t0 = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._rec.record(self._name, self._cat, self._t0,
                         time.perf_counter(), lane=self._lane,
                         args=self._args)
        return False


class Phase:
    """One phase boundary of an always-on host loop (the serving engine's
    step), as ONE ``with`` per site that feeds three sinks:

    - a ``jax.profiler.TraceAnnotation(name, **args)``, ALWAYS: it records
      only while a profiler session is on, into the device trace's own
      file and on its clock — that is what "tracing on" means for these
      spans (``args`` have to be known when the phase opens);
    - ``acc[key] += wall seconds``, always: plain floats a caller reads as
      deltas with no profiler at all (``ServingEngine.counters``);
    - ``ring.record(...)`` of the same interval when the module's
      ``_spans`` slot is filled (``PT_MONITOR``), so the flight recorder
      and the blackbox dump hold the phases too. The body may add what
      it only knows at the end to ``.args`` for the ring record.

    ``t0``/``t1`` are the ``perf_counter`` stamps, for a caller that needs
    the boundary itself (the round's token-fetch end is every lane's
    attribution mark)."""

    __slots__ = ("_ann", "_acc", "_key", "_ring", "_name", "_cat",
                 "_lane", "args", "t0", "t1")

    def __init__(self, name, acc, key, ring=None, cat="phase", lane=None,
                 **args):
        self._ann = TraceAnnotation(name, **args)
        self._acc = acc
        self._key = key
        self._ring = ring
        self._name = name
        self._cat = cat
        self._lane = lane
        self.args = args
        self.t0 = self.t1 = None

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._acc[self._key] += self.t1 - self.t0
        if self._ring is not None:
            self._ring.record(self._name, self._cat, self.t0, self.t1,
                              lane=self._lane, args=self.args or None)
        return False


class SpanRecorder:
    """Bounded ring of completed spans, thread-safe, allocation-light.

    A span is ``(name, cat, lane, t0, t1, args)`` with ``t0``/``t1`` in
    ``time.perf_counter()`` seconds. The ring holds the most recent
    ``capacity`` spans (always-on recording must stay bounded on long
    runs; the tail is what a regression post-mortem reads); overwritten
    spans are counted in :attr:`dropped`.
    """

    def __init__(self, capacity: int | None = None):
        self._cap = capacity or _default_capacity()
        self._lock = threading.Lock()
        self._ring: list = [None] * self._cap
        self._pos = 0  # total spans ever recorded

    # -- recording -----------------------------------------------------------

    def record(self, name, cat, t0, t1=None, lane=None, args=None) -> None:
        """Append one completed span. ``lane`` defaults to "main" on the
        main thread, the thread's name elsewhere."""
        if t1 is None:
            t1 = time.perf_counter()
        if lane is None:
            t = threading.current_thread()
            lane = "main" if t.ident == _MAIN_THREAD_ID else t.name
        entry = (name, cat, lane, t0, t1, args)
        with self._lock:
            self._ring[self._pos % self._cap] = entry
            self._pos += 1

    def span(self, name, cat, lane=None, args=None) -> _Span:
        """``with recorder.span("hapi/fit_epoch", "phase"): ...``"""
        return _Span(self, name, cat, lane, args)

    # -- introspection -------------------------------------------------------

    @property
    def count(self) -> int:
        """Total spans recorded (including ones the ring overwrote)."""
        return self._pos

    @property
    def dropped(self) -> int:
        return max(0, self._pos - self._cap)

    def snapshot(self) -> list:
        """Retained spans in recording order (oldest first)."""
        with self._lock:
            n = min(self._pos, self._cap)
            if self._pos <= self._cap:
                return [s for s in self._ring[:n]]
            head = self._pos % self._cap
            return self._ring[head:] + self._ring[:head]

    @staticmethod
    def _lanes_of(spans: list) -> list:
        """Distinct lanes in ``spans``, "main" first, then by first
        appearance — the stable tid assignment chrome export uses."""
        seen: list = []
        for s in spans:
            if s[2] not in seen:
                seen.append(s[2])
        if "main" in seen:
            seen.remove("main")
            seen.insert(0, "main")
        return seen

    def lanes(self) -> list:
        return self._lanes_of(self.snapshot())

    def clear(self) -> None:
        with self._lock:
            self._ring = [None] * self._cap
            self._pos = 0

    # -- chrome-trace export -------------------------------------------------

    def chrome_events(self, pid: int | None = None) -> list:
        """Retained spans as chrome-trace ``ph:"X"`` complete events plus
        ``ph:"M"`` thread_name metadata per lane. Timestamps are
        ``perf_counter`` microseconds — the same epoch as the profiler's
        host events, so the two merge onto one timeline."""
        pid = pid if pid is not None else os.getpid()
        # lanes derive from this ONE snapshot: a concurrent writer that
        # wraps the ring between two snapshots could otherwise surface a
        # span whose lane has no tid
        spans = self.snapshot()
        tids = {lane: i + 1 for i, lane in enumerate(self._lanes_of(spans))}
        events = []
        for lane, tid in tids.items():
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": lane}})
            events.append({"name": "thread_sort_index", "ph": "M",
                           "pid": pid, "tid": tid,
                           "args": {"sort_index": tid}})
        for name, cat, lane, t0, t1, args in spans:
            ev = {"name": name, "cat": cat, "ph": "X",
                  "ts": t0 * 1e6, "dur": max(0.0, (t1 - t0) * 1e6),
                  "pid": pid, "tid": tids[lane]}
            if args:
                ev["args"] = dict(args)
            events.append(ev)
        return events

    def export_chrome(self, path: str) -> str:
        """Standalone trace file (merged export lives on
        ``Profiler.export`` / ``monitor.export_spans``)."""
        with open(path, "w") as f:
            json.dump({"traceEvents": self.chrome_events(),
                       "displayTimeUnit": "ms"}, f)
        return path
