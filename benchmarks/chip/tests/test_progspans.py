"""The program's spans against the device's idle time: self time, the
idle partition and its identity, per-step grouping, and every reader over
them — on hand-made events with known answers, and on a small trace of
the program's serving engine recorded on a TPU v5e."""
import json
import os

import pytest

from chiplib import manifest, progspans, trace

MS = 1e6  # ns

READERS = ("idle_draft_ms_per_round", "idle_launch_ms_per_round",
           "idle_fetch_ms_per_round", "idle_sched_ms_per_round",
           "idle_prefill_ms_per_round", "idle_unattributed_pct",
           "steady_idle_in_step_ms_per_round", "prefill_hold_ms_p90")
PER_ROUND = READERS[:5]


def span(name, a, b, thread="engine", **args):
    return {"name": "serving/" + name, "start": a * MS, "end": b * MS,
            "args": args, "thread": thread}


def synthetic():
    """Two steps, the first with a prefill; 10 ms of the benchmark's own
    loop between them and after the second."""
    spans = [
        span("step", 0, 100), span("admit", 1, 3),
        span("prefill", 3, 40, request="r1", hit_tokens=0, miss_tokens=9),
        span("first_token_fetch", 30, 40), span("admit", 40, 41),
        span("grow", 41, 42), span("draft", 42, 50, lanes=2),
        span("pack", 50, 52),
        span("dispatch", 52, 55, kind="decode", lanes=2),
        span("token_fetch", 55, 90), span("emit", 90, 99),
        span("step", 110, 160), span("admit", 110, 111),
        span("grow", 111, 112), span("draft", 112, 120, lanes=2),
        span("pack", 120, 122),
        span("dispatch", 122, 125, kind="verify", lanes=2),
        span("token_fetch", 125, 150), span("emit", 150, 158),
        # not the engine's thread, and a step before the window
        span("step", 20, 30, thread="other"), span("step", -50, -10),
    ]
    dev = [("fusion.1 fusion f32_8", 5 * MS, 23 * MS),
           ("fusion.2 fusion f32_8", 29 * MS, 7 * MS),
           ("fusion.3 fusion f32_8", 53 * MS, 35 * MS),
           ("fusion.3 fusion f32_8", 60 * MS, 5 * MS),  # inside the last
           ("fusion.4 fusion f32_8", 123 * MS, 25 * MS)]
    host = [("bench/engine_step", 0.0, 100 * MS),
            ("bench/engine_step", 105 * MS, 65 * MS)]
    return spans, {"devices": {0: dev}, "host": host}


def observed(loop="backlog"):
    spans, tr = synthetic()
    red = progspans.reduce(tr["devices"], spans, (0.0, 170 * MS))
    return {"job": "serve", "loop": loop, "trace": tr, "progspans": red}


def read(name, obs):
    return manifest.metric_reader(name)(obs)


def test_self_time_is_duration_minus_children():
    nested = progspans.nest(synthetic()[0])
    assert {s["thread"] for s in nested} == {"engine"}
    first = [s for s in nested if s["start"] == 0][0]
    assert first["name"] == "serving/step" and first["parent"] is None
    assert first["self_ns"] == pytest.approx(2 * MS)
    pre = [s for s in nested if s["name"] == "serving/prefill"][0]
    assert nested[pre["parent"]] is first
    assert pre["self_ns"] == pytest.approx(27 * MS)
    fetch = [s for s in nested
             if s["name"] == "serving/first_token_fetch"][0]
    assert nested[fetch["parent"]] is pre
    red = observed()["progspans"]
    assert red["self_s"]["serving/step"] == pytest.approx(0.004)
    assert red["self_s"]["serving/token_fetch"] == pytest.approx(0.060)


def test_every_idle_nanosecond_falls_to_exactly_one_span():
    spans, tr = synthetic()
    red = progspans.reduce(tr["devices"], spans, (0.0, 170 * MS))
    ms = {k: v * 1e3 for k, v in red["idle_by_span"].items()}
    assert ms == pytest.approx({
        "serving/step": 4, "serving/admit": 4, "serving/prefill": 3,
        "serving/first_token_fetch": 4, "serving/grow": 2,
        "serving/draft": 16, "serving/pack": 4, "serving/dispatch": 2,
        "serving/token_fetch": 4, "serving/emit": 17,
        progspans.OUTSIDE: 20})
    # the same window and device events as the result line's busy_s
    r = trace.reduce(tr)
    assert r["window_s"] == pytest.approx(red["window_s"])
    assert red["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"])
    pieces = progspans.segments(progspans.nest(spans), 0.0, 170 * MS)
    assert pieces[0][0] == 0.0 and pieces[-1][1] == 170 * MS
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))


def test_fetch_idle_splits_into_launch_latency_and_tail():
    # fetch [55,90]: the op [53,88] runs from its start: head 0, tail 2;
    # fetch [125,150]: op [123,148]: head 0, tail 2
    red = observed()["progspans"]
    assert red["fetch_head_tail_s"] == pytest.approx((0.0, 0.004))
    spans, tr = synthetic()
    tr["devices"][0][2:4] = [("fusion.3 fusion f32_8", 58 * MS, 30 * MS)]
    nested = progspans.nest(spans)
    got = progspans.head_tail_idle(tr["devices"], nested,
                                   "serving/token_fetch", 0.0, 170 * MS)
    assert got == pytest.approx((0.003, 0.004))
    # a span in which nothing runs is all head
    tr["devices"][0] = tr["devices"][0][:1]
    got = progspans.head_tail_idle(tr["devices"], nested,
                                   "serving/token_fetch", 0.0, 170 * MS)
    assert got == pytest.approx((0.060, 0.0))


def test_steps_hold_their_phases_and_the_time_before_the_round():
    red = observed()["progspans"]
    assert red["rounds"] == 2 and len(red["steps"]) == 2
    a, b = red["steps"]
    assert sorted(a["phases"]) == sorted(
        "serving/" + n for n in (
            "admit", "prefill", "first_token_fetch", "grow", "draft",
            "pack", "dispatch", "token_fetch", "emit"))
    assert len(a["phases"]["serving/admit"]) == 2
    assert "serving/prefill" not in b["phases"]
    assert a["phases"]["serving/prefill"][0]["args"]["request"] == "r1"
    assert [s["args"]["kind"] for st in (a, b)
            for s in st["phases"]["serving/dispatch"]] \
        == ["decode", "verify"]
    assert (a["hold_ms"], b["hold_ms"]) == (52, 12)


def test_readers_and_their_identity():
    obs = observed("backlog")
    got = {n: read(n, obs) for n in READERS}
    assert got == pytest.approx({
        "idle_draft_ms_per_round": 8.0, "idle_launch_ms_per_round": 3.0,
        "idle_fetch_ms_per_round": 2.0, "idle_sched_ms_per_round": 11.5,
        "idle_prefill_ms_per_round": 3.5, "idle_unattributed_pct": 30.0,
        "steady_idle_in_step_ms_per_round": None,
        "prefill_hold_ms_p90": None})
    red = obs["progspans"]
    named = sum(got[n] for n in PER_ROUND) * red["rounds"] / 1e3
    assert named + got["idle_unattributed_pct"] / 100 * red["idle_s"] \
        == pytest.approx(red["idle_s"])
    obs = observed("open")
    got = {n: read(n, obs) for n in READERS}
    assert got["steady_idle_in_step_ms_per_round"] == pytest.approx(30.0)
    assert got["prefill_hold_ms_p90"] == 52
    assert all(got[n] is None for n in READERS[:6])


def test_a_program_without_the_spans_reads_nothing(monkeypatch, tmp_path):
    """The parent of the PR that added the spans, an untraced run, a
    training run, a checkout with no trace file: every reader returns
    None and none raises."""
    _, tr = synthetic()
    assert progspans.reduce(tr["devices"], [], (0.0, 170 * MS)) is None
    bare = [span("step", -50, -10)]  # no step inside the window
    assert progspans.reduce(tr["devices"], bare, (0.0, 170 * MS)) is None
    monkeypatch.setattr(progspans, "newest_xplane", lambda: RECORDED_BENCH)
    for obs in ({"job": "serve", "loop": "backlog", "trace": None},
                {"job": "train", "trace": tr},
                # PR 23's recording: bench/ spans, none of the program's
                {"job": "serve", "loop": "backlog",
                 "trace": trace.load(RECORDED_BENCH)}):
        assert [read(n, obs) for n in READERS] == [None] * len(READERS)
    monkeypatch.undo()
    monkeypatch.setattr(manifest, "ROOT", str(tmp_path))
    assert progspans.newest_xplane() is None
    obs = {"job": "serve", "loop": "open", "trace": tr}
    assert [read(n, obs) for n in READERS] == [None] * len(READERS)


DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED_BENCH = os.path.join(DATA, "small.xplane.pb")
RECORDED = os.path.join(DATA, "serving_rounds.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """A few steps of the program's serving engine at a tiny size on a
    TPU v5e, each inside ``bench/engine_step`` (record_serving_trace.py),
    and what the engine's counters said those steps did."""
    with open(os.path.join(DATA, "serving_rounds.json")) as f:
        did = json.load(f)
    tr = trace.load(RECORDED)
    assert list(tr["devices"]) == [0] and tr["devices"][0]
    return did, tr, progspans.load_spans(RECORDED)


def test_recorded_spans_nest_as_the_engine_opens_them(recorded):
    did, tr, spans = recorded
    assert did["device"] == "TPU v5 lite"
    assert {s["name"] for s in spans} == {"serving/" + n for n in (
        "step", "admit", "prefill", "first_token_fetch", "grow", "draft",
        "pack", "dispatch", "token_fetch", "emit")}
    nested = progspans.nest(spans)
    assert len(nested) == len(spans)  # one thread
    name = {i: s["name"] for i, s in enumerate(nested)}
    for s in nested:
        assert s["self_ns"] >= 0
        if s["name"] == "serving/step":
            assert s["parent"] is None
        elif s["name"] == "serving/first_token_fetch":
            assert name[s["parent"]] == "serving/prefill"
        else:
            assert name[s["parent"]] == "serving/step"
    # self times add up to the steps' own durations
    assert sum(s["self_ns"] for s in nested) == pytest.approx(
        sum(s["end"] - s["start"] for s in nested
            if s["parent"] is None))
    # each step lies inside the benchmark's annotation around it
    bench = sorted((s, s + d) for _, s, d in tr["host"])
    steps = [s for s in nested if s["name"] == "serving/step"]
    assert len(steps) == len(bench)
    assert all(b[0] <= s["start"] and s["end"] <= b[1]
               for s, b in zip(steps, bench))


def test_recorded_steps_match_the_engines_counters(recorded):
    did, tr, spans = recorded
    host = tr["host"]
    window = (min(s for _, s, _ in host), max(s + d for _, s, d in host))
    red = progspans.reduce(tr["devices"], spans, window)
    assert red["rounds"] == did["decode_steps"] + did["verify_steps"] > 0
    kinds = [s["args"]["kind"] for st in red["steps"]
             for s in st["phases"].get("serving/dispatch", [])]
    assert kinds.count("decode") == did["decode_steps"] > 0
    assert kinds.count("verify") == did["verify_steps"] > 0
    pre = [s for st in red["steps"]
           for s in st["phases"].get("serving/prefill", [])]
    assert len(pre) == did["admits"]
    assert sum(s["args"]["miss_tokens"] for s in pre) \
        == did["prefix_miss_tokens"]
    assert sorted(s["args"]["request"] for s in pre) \
        == ["rt0", "rt1", "rt2"]
    for st in red["steps"]:
        n = len(st["phases"].get("serving/dispatch", []))
        assert n <= 1
        for k in ("grow", "draft", "pack", "token_fetch", "emit"):
            assert len(st["phases"].get("serving/" + k, [])) == n
        if n:  # every phase of the round follows the step's start
            assert st["hold_ms"] > 0
    # the identity: what the partition holds is the window's idle time
    r = trace.reduce(tr)
    assert red["window_s"] == pytest.approx(r["window_s"])
    assert red["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"],
                                          rel=1e-9)
    # the chip idles while the host drafts and packs, and it works
    # through most of the fetch
    assert red["idle_by_span"]["serving/draft"] \
        == pytest.approx(red["self_s"]["serving/draft"], rel=0.05)
    assert red["idle_by_span"]["serving/token_fetch"] \
        < red["self_s"]["serving/token_fetch"]
    head, tail = red["fetch_head_tail_s"]
    assert 0 <= head + tail \
        <= red["idle_by_span"]["serving/token_fetch"] * (1 + 1e-9)


@pytest.mark.parametrize("loop", ["backlog", "open"])
def test_readers_on_the_recorded_trace(recorded, loop, monkeypatch,
                                       capsys):
    """Through ``of(obs)`` as a run reaches it: the newest trace file is
    found, reduced once for all readers, and the whole table printed as
    one earlier line."""
    did, tr, _ = recorded
    monkeypatch.setattr(progspans, "newest_xplane", lambda: RECORDED)
    obs = {"job": "serve", "loop": loop, "trace": tr}
    got = {n: read(n, obs) for n in READERS}
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["line"] for ln in lines] == ["program_idle"]
    assert lines[0]["rounds"] == did["decode_steps"] + did["verify_steps"]
    assert lines[0]["steps_with_prefill"] == 1  # one step admits all 3
    mine = READERS[:6] if loop == "backlog" else READERS[6:]
    assert all((got[n] is not None) == (n in mine) for n in READERS)
    assert all(got[n] >= 0 for n in mine)
    red = obs["progspans"]
    r = trace.reduce(tr)
    if loop == "backlog":
        named = sum(got[n] for n in PER_ROUND) * red["rounds"] / 1e3
        rest = got["idle_unattributed_pct"] / 100 * red["idle_s"]
        assert abs(named + rest - (r["window_s"] - r["busy_s"])) \
            <= 0.02 * r["window_s"]
        assert 0 <= got["idle_unattributed_pct"] <= 100
    else:
        assert got["steady_idle_in_step_ms_per_round"] * red["rounds"] \
            <= (r["window_s"] - r["busy_s"]) * 1e3
        # of five steps the 90th percentile is the longest hold: the
        # step that prefilled all three requests before its round
        assert got["prefill_hold_ms_p90"] == red["steps"][0]["hold_ms"] \
            == max(st["hold_ms"] for st in red["steps"])
