"""Device time of the operations that touch a given operand, in a traced
serving run's pure decode / verify rounds.

The TPU's op events are named by their whole HLO instruction — output
shape, opcode and operand shapes — and carry no ``jax.named_scope``
(the program's ``moe/...`` / ``mla/...`` scopes live in the compiled
module's metadata, which the trace does not hold). ``trace.load`` keeps
only a short name, so the readers that need to tell one layer's
operations apart read the run's ``.xplane.pb`` again, whole names this
time, and pick operations by the SHAPES in their text: an operation that
reads ``bf16[16,7680,4096]`` is an expert product whatever the compiler
calls it. Only rounds that prefilled nothing are used, so a round is one
program call. What a round REQUIRED follows from the tokens it fed: a
plain ``[lanes, 1]`` decode round its lanes; a verify round its lanes
plus its drafted tokens, which the benchmark's round records do not
hold — the window's mean (``spec_proposed_tokens / verify_steps`` of the
engine's counters) stands for each.
"""
from __future__ import annotations

import re

from . import progspans
from . import trace as trace_mod

STEP = "bench/engine_step"


def tokens_fed(obs, r):
    """Real (non-pad) tokens the round's one program call fed."""
    c = obs["counters"]
    drafted = c["spec_proposed_tokens"] / c["verify_steps"] \
        if r["verify_steps"] and c.get("verify_steps") else 0.0
    return r["lanes"] + drafted


def pure_round_spans(obs):
    """[(start_ns, end_ns, round)] of the traced rounds that ran one
    decode or verify step and no prefill chunk, paired with their
    ``bench/engine_step`` spans from the end (the profiler may lose the
    first)."""
    rounds = [r for r in obs["rounds"] if r["traced"]]
    spans = sorted((s, s + d) for nm, s, d in obs["trace"]["host"]
                   if nm == STEP)
    n = min(len(rounds), len(spans))
    return [(s0, s1, r) for (s0, s1), r in
            zip(spans[len(spans) - n:], rounds[len(rounds) - n:])
            if r["decode_steps"] + r["verify_steps"] == 1
            and not r["prefill_chunks"]]


def device_events(obs):
    """[(whole name, start_ns, dur_ns)] of the first TPU's op line in the
    run's trace file, containers left out; None without a file. Read
    once a run and kept in ``obs``."""
    if "optext_events" not in obs:
        obs["optext_events"] = _read_events()
    return obs["optext_events"]


def _read_events():
    from jax.profiler import ProfileData

    path = progspans.newest_xplane()
    if path is None:
        return None
    for plane in ProfileData.from_file(path).planes:
        if not trace_mod.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != trace_mod.OPS_LINE:
                continue
            out = []
            for ev in line.events:
                short = trace_mod.short_name(ev.name).split(" ")
                if short[1:2] and short[1] in trace_mod.CONTAINERS:
                    continue
                out.append((ev.name, float(ev.start_ns),
                            float(ev.duration_ns)))
            return out
    return None


def seconds_in_pure_rounds(obs, pattern):
    """(device seconds of the operations whose whole name matches
    ``pattern``, the pure rounds they ran in). None where there is no
    trace, no pure round, or nothing matched."""
    if obs.get("job") != "serve" or not obs.get("trace"):
        return None
    spans = pure_round_spans(obs)
    events = device_events(obs)
    if not spans or not events:
        return None
    rx = re.compile(pattern)
    hits = sorted((s, d) for nm, s, d in events if rx.search(nm))
    total, used, j = 0.0, [], 0
    for s0, s1, r in spans:
        while j < len(hits) and hits[j][0] < s0:
            j += 1
        got = 0.0
        while j < len(hits) and hits[j][0] < s1:
            got += hits[j][1]
            j += 1
        if got > 0:
            total += got
            used.append(r)
    return (total / 1e9, used) if used else None
