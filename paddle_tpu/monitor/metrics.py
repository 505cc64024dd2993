"""Typed runtime metrics: Counter / Gauge / Histogram + a thread-safe registry.

Reference parity: the role of Paddle's profiler statistic collectors
(`python/paddle/profiler/profiler_statistic.py`) and the C++ host event
counters, rebuilt as process-wide typed metrics so the *runtime* itself
(dispatch, retraces, sync fences, collectives) is observable — not just
user-scoped host events.

Design: metrics are cheap enough to sit on hot paths when monitoring is ON
(one lock + int add), and are never consulted at all when OFF — the
instrumented modules guard on a module-global hook slot that is ``None``
unless :func:`paddle_tpu.monitor.enable` installed it (zero-overhead-off is
a registration property, not a per-call branch into monitor code).
"""
from __future__ import annotations

import math
import threading


class Counter:
    """Monotonic event counter."""

    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value

    def reset(self) -> None:
        with self._lock:
            self._value = 0


class Gauge:
    """Last-value-wins instantaneous metric (cache sizes, queue depths)."""

    __slots__ = ("name", "_lock", "_value", "_set")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0.0
        self._set = False

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)
            self._set = True

    @property
    def value(self) -> float:
        return self._value

    @property
    def is_set(self) -> bool:
        return self._set

    def reset(self) -> None:
        with self._lock:
            self._value = 0.0
            self._set = False


class Histogram:
    """Streaming distribution: exact count/sum/min/max plus percentile
    estimates over a bounded ring of the most recent observations (the
    tail matters for latency; a full sample log would be unbounded)."""

    __slots__ = ("name", "_lock", "_count", "_sum", "_min", "_max",
                 "_ring", "_pos")

    RING = 1024

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf
        self._ring = [0.0] * self.RING
        self._pos = 0

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            self._count += 1
            self._sum += v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v
            self._ring[self._pos % self.RING] = v
            self._pos += 1

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def percentile(self, p: float) -> float:
        """p in [0, 100], nearest-rank over the retained ring."""
        with self._lock:
            n = min(self._pos, self.RING)
            if n == 0:
                return 0.0
            data = sorted(self._ring[:n])
        idx = min(n - 1, max(0, int(math.ceil(p / 100.0 * n)) - 1))
        return data[idx]

    def snapshot(self) -> dict:
        with self._lock:
            n = min(self._pos, self.RING)
            data = sorted(self._ring[:n])
            count, total = self._count, self._sum
            lo = self._min if count else 0.0
            hi = self._max if count else 0.0

        def pct(p):
            if n == 0:
                return 0.0
            return data[min(n - 1, max(0, int(math.ceil(p / 100.0 * n)) - 1))]

        return {
            "count": count,
            "sum": round(total, 6),
            "mean": round(total / count, 6) if count else 0.0,
            "min": round(lo, 6),
            "max": round(hi, 6),
            "p50": round(pct(50), 6),
            "p95": round(pct(95), 6),
            "p99": round(pct(99), 6),
        }

    def reset(self) -> None:
        with self._lock:
            self._count = 0
            self._sum = 0.0
            self._min = math.inf
            self._max = -math.inf
            self._pos = 0


class Registry:
    """Thread-safe name -> metric store with typed get-or-create."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: dict = {}

    def _get(self, name: str, cls):
        m = self._metrics.get(name)
        if m is None:
            with self._lock:
                m = self._metrics.get(name)
                if m is None:
                    m = self._metrics[name] = cls(name)
        if not isinstance(m, cls):
            raise TypeError(
                f"metric {name!r} already registered as "
                f"{type(m).__name__}, requested {cls.__name__}")
        return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def snapshot(self) -> dict:
        """Typed snapshot: ``{"counters": {...}, "gauges": {...},
        "histograms": {...}}``. Zero counters, never-set gauges and empty
        histograms are omitted so sinks stay lean."""
        with self._lock:
            items = list(self._metrics.items())
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        for name, m in items:
            if isinstance(m, Counter):
                if m.value:
                    out["counters"][name] = m.value
            elif isinstance(m, Gauge):
                if m.is_set:
                    out["gauges"][name] = m.value
            elif isinstance(m, Histogram):
                if m.count:
                    out["histograms"][name] = m.snapshot()
        return out

    def reset(self) -> None:
        """Zero every metric (objects stay registered: instrumented code
        holds direct references to them)."""
        with self._lock:
            items = list(self._metrics.values())
        for m in items:
            m.reset()


def diff_snapshots(prev: dict, cur: dict) -> dict:
    """Delta between two :meth:`Registry.snapshot` results.

    Counters diff numerically; gauges report their current value when it
    changed; histograms diff count/sum and carry the current quantiles
    (quantiles are over the recent ring, not the interval — good enough
    for a per-step line). Unchanged/zero entries are dropped.
    """
    out: dict = {"counters": {}, "gauges": {}, "histograms": {}}
    pc = prev.get("counters", {})
    for name, v in cur.get("counters", {}).items():
        d = v - pc.get(name, 0)
        if d:
            out["counters"][name] = d
    pg = prev.get("gauges", {})
    for name, v in cur.get("gauges", {}).items():
        if pg.get(name) != v:
            out["gauges"][name] = v
    ph = prev.get("histograms", {})
    for name, h in cur.get("histograms", {}).items():
        p = ph.get(name, {})
        dcount = h["count"] - p.get("count", 0)
        if dcount:
            out["histograms"][name] = {
                "count": dcount,
                "sum": round(h["sum"] - p.get("sum", 0.0), 6),
                "p50": h["p50"], "p95": h["p95"], "max": h["max"],
            }
    return {k: v for k, v in out.items() if v}
