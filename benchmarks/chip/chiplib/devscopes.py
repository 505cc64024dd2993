"""Device time by program scope in a traced serving run: which LAYER of
the step programs the busy half of a round went to, where
``trace.reduce``'s ``device_ops`` has compiler names (``fusion_f32_64_5``).

The join is instruction name within module, and the program owns both
halves of it:

- the trace (the run's ``.xplane.pb``, read once more as
  ``optext.device_events`` does): the first TPU plane's ``XLA Modules``
  line gives [start, end) and program of every execution
  (``jit__verify_step(693...)``); its ``XLA Ops`` line names every op
  event by its whole HLO instruction (``%fusion.12 = ...``). An op falls
  to the execution that holds its start. Containers (``while`` /
  ``conditional`` / ``call``) are left out as in ``trace.load``: their
  bodies' ops are events of their own;
- the program: every executable that goes through
  ``paddle_tpu/jit/exec_cache.get_or_compile`` leaves its text with
  ``paddle_tpu/monitor/scopes.py``, which reads it into ``{module:
  {instruction: [scope path, group, mixed, opcode, how]}}`` — the
  ``jax.named_scope`` names the step programs wear (``attn/rows``,
  ``moe/experts``, ``ssm/state_update``, ``norm``, ``head`` ...), the
  group each falls to (``attn`` / ``ffn`` / ``state`` / ``norm`` /
  ``head``), whether a fusion holds more than one group. The registry
  outlives ``common.drop_program_state()``.

``reduce`` is the pure reduction (checked on hand-made events in the
repository's ``tests/test_program_scopes.py`` and, in
``tests/test_devscopes.py`` here, on a trace recorded on a TPU v5e with
its dumped map beside it); ``table(obs)`` runs it on the run's own trace
once and prints the result as the ``device_by_scope`` note: device
seconds and calls by (program kind, scope path) — the 20 largest rows —
and by (program kind, group), ``rounds`` (executions of the decode and
verify programs that start in the traced window), ``prefill_calls``,
``mixed_pct`` (share of op time in fusions that hold more than one
group), ``unscoped_pct`` (under no declared scope, or an instruction the
map does not know: ``unknown_pct`` of it), ``stale_programs`` (recorded
serving programs whose text carries no scope at all: a compile-cache hit
on an executable built before the scopes — clear the cache directory) and
``host_device_skew_ms``: the largest (``serving/dispatch`` span start -
start of the execution it launched) over the traced rounds, clamped at 0.
An execution cannot start before its dispatch opens, so what reads above
0 is the host spans' clock running ahead of the device's in that session
— the skew that moves idle time between ``idle_launch_ms_per_round`` and
``idle_fetch_ms_per_round`` (their sum is sound; no metric is corrected
by this number).

A program without the registry (the parent of the PR that added it), a
run without a trace, a trace without module events: everything here
returns None and the metrics built on it are left out of the line.
"""
from __future__ import annotations

import bisect

from . import common, progspans
from . import trace as trace_mod

GROUPS = ("attn", "ffn", "state", "norm", "head")
ROUND_KINDS = ("decode", "verify")
UNSCOPED = "unscoped"
LABEL = "serving/"  # the compile sites' labels: serving/<kind>


def read_events(path):
    """(modules [(start_ns, end_ns, module name)], ops [(instruction
    name, start_ns, dur_ns)]) of the first TPU plane of a trace file."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if not trace_mod.DEVICE_PLANE.match(plane.name):
            continue
        modules, ops = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    start = float(ev.start_ns)
                    modules.append((start, start + float(ev.duration_ns),
                                    ev.name.partition("(")[0]))
            elif line.name == trace_mod.OPS_LINE:
                for ev in line.events:
                    name = ev.name.partition(" = ")[0].strip().lstrip("%")
                    ops.append((name, float(ev.start_ns),
                                float(ev.duration_ns)))
        return modules, ops
    return [], []


def kind_of(module, registry):
    """``decode`` / ``verify`` / ``prefill`` for a serving program (its
    compile site's label), else the label, else the module's name."""
    label = (registry.get(module) or {}).get("label") or module
    return label[len(LABEL):] if label.startswith(LABEL) else label


def reduce(modules, ops, registry, window=None, dispatches=()):
    """Device seconds by scope of the ops that start in ``window``
    ([start_ns, end_ns); the whole trace where None). ``registry`` is
    ``scopes.compiled()``; ``dispatches`` the start times (ns) of the
    ``serving/dispatch`` spans. Returns None where no execution of a
    known program starts in the window."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    w0, w1 = window or (float("-inf"), float("inf"))
    by_path, by_group, calls = {}, {}, {}
    total, mixed, unknown = {}, {}, {}
    for name, start, dur in ops:
        if not w0 <= start < w1:
            continue
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= modules[i][1]:
            continue  # in no execution: another client's, a transfer's
        module = modules[i][2]
        kind = kind_of(module, registry)
        row = (registry.get(module) or {}).get("instructions", {}).get(name)
        if row is not None and row[3] in trace_mod.CONTAINERS:
            continue
        path, group = (row[0], row[1]) if row else (UNSCOPED, "")
        sec = dur / 1e9
        total[kind] = total.get(kind, 0.0) + sec
        if row is None:  # an instruction the map does not know
            unknown[kind] = unknown.get(kind, 0.0) + sec
        g = by_group.setdefault(kind, {})
        g[group or UNSCOPED] = g.get(group or UNSCOPED, 0.0) + sec
        key = (kind, path if group else UNSCOPED)
        by_path[key] = by_path.get(key, 0.0) + sec
        calls[key] = calls.get(key, 0) + 1
        if row and row[2]:
            mixed[kind] = mixed.get(kind, 0.0) + sec
    executions = {}
    for start, _, module in modules:
        if w0 <= start < w1:
            k = kind_of(module, registry)
            executions[k] = executions.get(k, 0) + 1
    if not total:
        return None
    rounds = [m for m in modules
              if kind_of(m[2], registry) in ROUND_KINDS]
    return {"seconds": total, "by_group": by_group, "by_path": by_path,
            "calls": calls, "mixed_s": mixed, "unknown_s": unknown,
            "executions": executions,
            "rounds": sum(executions.get(k, 0) for k in ROUND_KINDS),
            "prefill_calls": executions.get("prefill", 0),
            "host_device_skew_ms": skew_ms(rounds, dispatches, w0, w1)}


def skew_ms(rounds, dispatches, w0, w1):
    """The largest (dispatch span start - start of the execution it
    launched), ms, clamped at 0; None without spans. One dispatch a
    round: paired in order from the end (the profiler may lose a
    session's first events), the pairs whose execution starts in the
    window read."""
    dispatches = sorted(dispatches)
    n = min(len(rounds), len(dispatches))
    ahead = [d - r[0] for d, r in zip(dispatches[len(dispatches) - n:],
                                      rounds[len(rounds) - n:])
             if w0 <= r[0] < w1]
    return max(0.0, max(ahead)) / 1e6 if ahead else None


def round_seconds(red, group=None):
    """Op seconds of the round programs (decode and verify), all or one
    group's."""
    if group is None:
        return sum(red["seconds"].get(k, 0.0) for k in ROUND_KINDS)
    return sum(red["by_group"].get(k, {}).get(group, 0.0)
               for k in ROUND_KINDS)


def table(obs):
    """The traced run's reduction, made once and kept in ``obs``; None
    for a run without a trace or a program without the registry. Prints
    the ``device_by_scope`` note."""
    if "devscopes" not in obs:
        obs["devscopes"] = _of_run(obs)
    return obs["devscopes"]


def _of_run(obs):
    if obs.get("job") != "serve" or not obs.get("trace"):
        return None
    try:
        from paddle_tpu.monitor import scopes
    except ImportError:  # the program has no scope registry
        return None
    registry = scopes.compiled()
    path = progspans.newest_xplane()
    if not registry or path is None:
        return None
    modules, ops = read_events(path)
    window = obs.get("trace_window")
    host = obs["trace"]["host"]
    if window is None and host:
        window = (min(s for _, s, _ in host),
                  max(s + d for _, s, d in host))
    red = reduce(modules, ops, registry, window,
                 [s["start"] for s in progspans.load_spans(path)
                  if s["name"] == progspans.ROUND])
    if red is None:
        return None
    busy = sum(red["seconds"].values())
    unscoped = sum(g.get(UNSCOPED, 0.0) for g in red["by_group"].values())
    top = sorted(red["by_path"].items(), key=lambda kv: -kv[1])[:20]
    common.note(
        "device_by_scope", rounds=red["rounds"],
        prefill_calls=red["prefill_calls"], executions=red["executions"],
        op_seconds=red["seconds"], seconds_by_group=red["by_group"],
        rows=[[k[0], k[1], v, red["calls"][k]] for k, v in top],
        mixed_pct=100.0 * sum(red["mixed_s"].values()) / busy,
        unscoped_pct=100.0 * unscoped / busy,
        unknown_pct=100.0 * sum(red["unknown_s"].values()) / busy,
        stale_programs=scopes.stale_programs(),
        host_device_skew_ms=red["host_device_skew_ms"])
    return red


def _for_loop(obs, loop):
    return table(obs) if obs.get("loop") == loop else None


def group_ms_per_round(obs, loop, group):
    """The round programs' device ms in ``group`` a traced round."""
    red = _for_loop(obs, loop)
    if red is None or not red["rounds"]:
        return None
    return 1e3 * round_seconds(red, group) / red["rounds"]


def unscoped_pct(obs, loop):
    """Share of the round programs' op time under no declared scope."""
    red = _for_loop(obs, loop)
    if red is None or round_seconds(red) <= 0:
        return None
    return 100.0 * round_seconds(red, UNSCOPED) / round_seconds(red)


def prefill_ms_per_round(obs, loop):
    """Device ms of the prefill program's executions a traced round."""
    red = _for_loop(obs, loop)
    if red is None or not red["rounds"]:
        return None
    return 1e3 * red["seconds"].get("prefill", 0.0) / red["rounds"]
