"""The program's own spans in a traced serving run: the ``serving/*``
``jax.profiler.TraceAnnotation`` events that ``ServingEngine.step`` opens
at every phase boundary (``paddle_tpu/monitor/spans.Phase``). They are
in the same ``.xplane.pb`` as the device's op events and on its clock,
so the device's idle time can be split by what the host was doing.

Three reductions, checked on hand-made events and on a small trace
recorded on a TPU v5e (``tests/test_progspans.py``):

- each span's self time: its duration minus what its children cover;
- the idle partition: the device's idle intervals inside the traced
  window (the window ``trace.reduce`` used, the same device events)
  intersected with the innermost span at every instant, so every idle
  nanosecond falls to exactly one of a named phase, ``serving/step``'s
  own time, or ``OUTSIDE`` any step (the benchmark's loop);
- the steps: which ``serving/step`` holds which phases, and how long a
  step that decodes held its lanes before the round's dispatch.

A program without these spans (the parent of the PR that added them)
reduces to None, and every reader over it returns None.
"""
from __future__ import annotations

import bisect
import glob
import os

from . import common, manifest
from . import trace as trace_mod

PREFIX = "serving/"
STEP = "serving/step"
ROUND = "serving/dispatch"  # one per decode / verify round
OUTSIDE = "outside"


def newest_xplane():
    """The run's trace file. ``obs`` carries no path, and a run writes
    one trace under ``chiprun_out/traces/<workload>-<seed>/``
    (``common.trace_dir``): the newest there is this run's."""
    files = glob.glob(os.path.join(
        manifest.ROOT, "chiprun_out", "traces", "*", "plugins", "profile",
        "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def load_spans(path):
    """The host planes' events named ``serving/...`` as dicts ``name,
    start, end`` (ns on the trace's clock), ``args`` (what the
    annotation was opened with) and ``thread``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    start = float(ev.start_ns)
                    out.append({"name": ev.name, "start": start,
                                "end": start + float(ev.duration_ns),
                                "args": {k: v for k, v in ev.stats},
                                "thread": line.name})
    return out


def nest(spans):
    """The spans of the engine's thread (the one with most steps), in
    start order and outermost first, each with ``parent`` (an index into
    the returned list, or None) and ``self_ns``."""
    steps = {}
    for s in spans:
        if s["name"] == STEP:
            steps[s["thread"]] = steps.get(s["thread"], 0) + 1
    if not steps:
        return []
    thread = max(sorted(steps), key=steps.get)
    out = sorted((dict(s) for s in spans if s["thread"] == thread),
                 key=lambda s: (s["start"], -s["end"]))
    stack = []
    for i, s in enumerate(out):
        while stack and out[stack[-1]]["end"] <= s["start"]:
            stack.pop()
        s["parent"] = stack[-1] if stack else None
        s["self_ns"] = s["end"] - s["start"]
        if stack:
            p = out[stack[-1]]
            p["self_ns"] -= min(s["end"], p["end"]) - s["start"]
        stack.append(i)
    return out


def segments(nested, w0, w1):
    """[w0, w1) cut into pieces ``(start, end, name)``, in order and
    without overlap: the name of the innermost span at that time, or
    ``OUTSIDE``."""
    out, stack, t = [], [], w0

    def close(upto):
        nonlocal t
        upto = min(max(upto, w0), w1)
        if upto > t:
            out.append((t, upto, stack[-1]["name"] if stack else OUTSIDE))
            t = upto

    for s in nested:
        while stack and stack[-1]["end"] <= s["start"]:
            close(stack[-1]["end"])
            stack.pop()
        close(s["start"])
        stack.append(s)
    while stack:
        close(stack[-1]["end"])
        stack.pop()
    close(w1)
    return out


def idle_by_span(devices, segs, w0, w1):
    """Seconds of device idle time in [w0, w1) by the segment's name,
    averaged over devices. Busy time is the union of the op intervals
    clipped to the window, as in ``trace.reduce``: the values sum to its
    ``window_s - busy_s``."""
    devs = [e for e in devices.values() if e]
    idle = {}
    for evs in devs:
        merged = trace_mod._union(
            [(max(s, w0), min(s + d, w1)) for _, s, d in evs
             if s + d > w0 and s < w1])
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        j = 0
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            while j < len(segs) and segs[j][1] <= g0:
                j += 1
            k = j
            while k < len(segs) and segs[k][0] < g1:
                a, b, name = segs[k]
                if min(g1, b) > max(g0, a):
                    idle[name] = idle.get(name, 0.0) \
                        + min(g1, b) - max(g0, a)
                k += 1
    return {k: v / len(devs) / 1e9 for k, v in idle.items()}


def head_tail_idle(devices, nested, name, w0, w1):
    """Of the device's idle time inside the spans called ``name`` that
    start in the window: the seconds before the first op that runs in
    the span and after the last one (a span in which no op runs counts
    as head). For ``serving/token_fetch``: launch latency against the
    tail between the last op and the host holding the tokens."""
    spans = [s for s in nested if s["name"] == name
             and w0 <= s["start"] < w1]
    devs = [e for e in devices.values() if e]
    head = tail = 0.0
    for evs in devs:
        merged = trace_mod._union([(s, s + d) for _, s, d in evs])
        starts = [iv[0] for iv in merged]
        for sp in spans:
            a, b = sp["start"], sp["end"]
            i = max(0, bisect.bisect_right(starts, a) - 1)
            inside = [iv for iv in merged[i:bisect.bisect_left(starts, b)]
                      if iv[1] > a]
            if not inside:
                head += b - a
                continue
            head += max(0.0, inside[0][0] - a)
            tail += max(0.0, b - inside[-1][1])
    return head / len(devs) / 1e9, tail / len(devs) / 1e9


def steps_of(nested, w0, w1):
    """The steps that start in the window: ``start``, ``end``, ``phases``
    (name -> the spans anywhere below the step) and, for a step that
    decodes, ``hold_ms``: from its start to its round's dispatch — what
    admissions and prefills put before every decoding lane's token."""
    top = {}
    steps = []
    for i, s in enumerate(nested):
        if s["parent"] is None:
            if s["name"] == STEP and w0 <= s["start"] < w1:
                top[i] = {"start": s["start"], "end": s["end"],
                          "phases": {}}
                steps.append(top[i])
            continue
        root = top.get(s["parent"])
        top[i] = root
        if root is not None:
            root["phases"].setdefault(s["name"], []).append(s)
    for st in steps:
        if ROUND in st["phases"]:
            st["hold_ms"] = (st["phases"][ROUND][0]["start"]
                             - st["start"]) / 1e6
    return steps


def reduce(devices, spans, window):
    """Everything the readers use, or None where the window holds no
    ``serving/step``."""
    w0, w1 = window
    nested = nest(spans)
    steps = steps_of(nested, w0, w1)
    if not steps or not any(devices.values()):
        return None
    idle = idle_by_span(devices, segments(nested, w0, w1), w0, w1)
    self_s = {}
    for s in nested:
        if w0 <= s["start"] < w1:
            self_s[s["name"]] = self_s.get(s["name"], 0.0) \
                + s["self_ns"] / 1e9
    return {"window_s": (w1 - w0) / 1e9, "idle_s": sum(idle.values()),
            "idle_by_span": idle, "self_s": self_s, "steps": steps,
            "rounds": sum(len(st["phases"].get(ROUND, ()))
                          for st in steps),
            "fetch_head_tail_s": head_tail_idle(
                devices, nested, "serving/token_fetch", w0, w1)}


def of(obs):
    """The traced run's reduction, made once and kept in ``obs``; None
    for a run without a trace or a program without the spans. Prints the
    whole table as an earlier line (``program_idle``)."""
    if "progspans" not in obs:
        obs["progspans"] = _of_run(obs)
    return obs["progspans"]


def _of_run(obs):
    if obs.get("job") != "serve" or not obs.get("trace"):
        return None
    path = newest_xplane()
    host = obs["trace"]["host"]
    window = obs.get("trace_window")
    if window is None and host:
        window = (min(s for _, s, _ in host),
                  max(s + d for _, s, d in host))
    if path is None or window is None:
        return None
    red = reduce(obs["trace"]["devices"], load_spans(path), window)
    if red is not None:
        hold = [st["hold_ms"] for st in red["steps"] if "hold_ms" in st]
        common.note("program_idle", window_s=red["window_s"],
                    idle_s=red["idle_s"], rounds=red["rounds"],
                    steps=len(red["steps"]),
                    steps_with_prefill=sum(
                        "serving/prefill" in st["phases"]
                        for st in red["steps"]),
                    idle_s_by_span=red["idle_by_span"],
                    self_s_by_span=red["self_s"],
                    fetch_idle_head_tail_s=red["fetch_head_tail_s"],
                    hold_ms_mean=sum(hold) / len(hold) if hold else None,
                    hold_ms_max=max(hold, default=None))
    return red


def _for_loop(obs, loop):
    return of(obs) if obs.get("loop") == loop else None


def idle_ms_per_round(obs, loop, names=None):
    """Device-idle ms a traced round inside the spans called ``names``;
    None for ``names``: inside any ``serving/step``, all phases."""
    red = _for_loop(obs, loop)
    if red is None or not red["rounds"]:
        return None
    idle = red["idle_by_span"]
    if names is None:
        names = [k for k in idle if k != OUTSIDE]
    return 1e3 * sum(idle.get(n, 0.0) for n in names) / red["rounds"]


def unattributed_pct(obs, loop):
    """Share of the window's idle time in no phase: ``serving/step``'s
    own statements and the time outside any step."""
    red = _for_loop(obs, loop)
    if red is None or red["idle_s"] <= 0:
        return None
    idle = red["idle_by_span"]
    return 100.0 * (idle.get(STEP, 0.0) + idle.get(OUTSIDE, 0.0)) \
        / red["idle_s"]


def hold_ms(obs, loop, q):
    """Quantile ``q`` of ``hold_ms`` over the traced steps that decode."""
    red = _for_loop(obs, loop)
    if red is None:
        return None
    v = [st["hold_ms"] for st in red["steps"] if "hold_ms" in st]
    return common.quantile(v, q) if v else None
