"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy seconds, the operations that took most
time, idle gaps attributed to the benchmark's own host spans, and the
time of named kernels. Reads the file with ``jax.profiler.ProfileData``
and nothing else. Checked on a small recorded trace in
``tests/test_trace.py``.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# operations that only contain others: their span covers their body's
# operations (which are on the same line) and the gaps between them
CONTAINERS = {"while", "conditional", "call"}
_SUFFIX = re.compile(r"[.\-_]?\d+$")
_OUT = re.compile(r"^\(?([a-z]+\d*)\[([\d,]*)\]")
_OPCODE = re.compile(r"[\s)]([a-z][a-z\-]*)\(")


def find_xplane(logdir):
    files = sorted(glob.glob(os.path.join(logdir, "plugins", "profile",
                                          "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def short_name(text: str) -> str:
    """The TPU's op events are named by their whole HLO instruction:
    ``%fusion.197 = (bf16[4096,32768]{...}, ...) fusion(...operands)``.
    Keep what identifies the operation and nothing of its operands (an
    operand may be called ``custom-call.3``): ``fusion.197 fusion
    bf16_4096_32768``. A name without `` = `` is kept as it is."""
    lhs, sep, rhs = text.partition(" = ")
    lhs = lhs.strip().lstrip("%")
    if not sep:
        return lhs
    out = _OUT.match(rhs.strip())
    sig = (out.group(1) + "_" + out.group(2).replace(",", "_")) if out \
        else ""
    op = _OPCODE.search(" " + rhs)
    return " ".join(x for x in (lhs, op.group(1) if op else "", sig) if x)


def op_bucket(name: str) -> str:
    """``fusion.123 fusion f32_2_4096`` and ``fusion.7 fusion f32_2_4096``
    are one bucket, ``fusion_f32_2_4096``."""
    parts = name.split(" ")
    base = _SUFFIX.sub("", parts[0]) or parts[0]
    return base + ("_" + parts[2] if len(parts) > 2 else "")


def _union(intervals):
    """Merge [start, end) intervals; returns the merged list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def load(path):
    """{'devices': {id: [(name, start_ns, dur_ns)]}, 'host': [(name,
    start_ns, dur_ns)]} — device events are the XLA Ops line of each TPU
    plane, host events everything on the host planes whose name starts
    with ``bench/`` (the benchmark's TraceAnnotations)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name = short_name(ev.name)
                    if name.split(" ")[1:2] and \
                            name.split(" ")[1] in CONTAINERS:
                        continue
                    evs.append((name, float(ev.start_ns),
                                float(ev.duration_ns)))
            devices[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench/"):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    return {"devices": devices, "host": host}


def reduce(trace, window=None, top=10):
    """Busy seconds (union of op intervals, averaged over devices), top
    operations by summed time (averaged over devices), and idle gaps by
    the host span that covers the gap's start. ``window`` = (start_ns,
    end_ns) clips everything; default: the first host span's start to the
    last one's end, else the device events' extent."""
    devs = {d: e for d, e in trace["devices"].items() if e}
    if not devs:
        return None
    host = sorted(trace["host"], key=lambda e: e[1])
    if window is None:
        if host:
            window = (host[0][1], max(s + d for _, s, d in host))
        else:
            window = (min(s for e in devs.values() for _, s, _ in e),
                      max(s + d for e in devs.values() for _, s, d in e))
    w0, w1 = window
    n = len(devs)
    busy = 0.0
    ops, gaps = {}, {}
    for evs in devs.values():
        clipped = [(nm, max(s, w0), min(s + d, w1)) for nm, s, d in evs
                   if s + d > w0 and s < w1]
        merged = _union([(s, e) for _, s, e in clipped])
        busy += sum(e - s for s, e in merged)
        for nm, s, e in clipped:
            b = op_bucket(nm)
            ops[b] = ops.get(b, 0.0) + (e - s)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 - g0 <= 0:
                continue
            label = "between_ops"
            for nm, s, d in host:
                if s <= g0 < s + d:
                    label = nm
                    break
            gaps[label] = gaps.get(label, 0.0) + (g1 - g0)

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy / n / 1e9, "window_s": (w1 - w0) / 1e9,
            "device_ops": ranked(ops), "idle_gaps": ranked(gaps),
            "devices": n}


def kernel_seconds(trace, pattern, window=None):
    """Summed device time of the events whose name matches ``pattern``,
    averaged over devices, and how many there were (per device)."""
    rx = re.compile(pattern)
    devs = {d: e for d, e in trace["devices"].items() if e}
    if not devs:
        return None
    total, count = 0.0, 0
    for evs in devs.values():
        for nm, s, d in evs:
            if window and not (s + d > window[0] and s < window[1]):
                continue
            if rx.search(nm):
                total += d
                count += 1
    return {"seconds": total / len(devs) / 1e9,
            "calls": count / len(devs)}


def busy_in_spans(trace, name):
    """For each host span called ``name``, in order of start: the seconds
    in which an operation ran on the device between the span's start and
    its end (union of the op intervals clipped to the span, averaged over
    devices). Host spans and device events share the trace's clock to
    about 0.1 ms (tests/test_trace.py), so this is for spans of many ms."""
    devs = [e for e in trace["devices"].values() if e]
    spans = sorted((s, s + d) for nm, s, d in trace["host"] if nm == name)
    if not devs or not spans:
        return []
    out = [0.0] * len(spans)
    for evs in devs:
        merged = _union([(s, s + d) for _, s, d in evs])
        j = 0
        for i, (s0, s1) in enumerate(spans):
            while j < len(merged) and merged[j][1] <= s0:
                j += 1
            k = j
            while k < len(merged) and merged[k][0] < s1:
                out[i] += min(s1, merged[k][1]) - max(s0, merged[k][0])
                k += 1
    return [v / len(devs) / 1e9 for v in out]
