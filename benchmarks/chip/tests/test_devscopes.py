"""Device time by program scope on a trace recorded on a TPU v5e with the
program's scope map dumped beside it (``record_scoped_trace.py``): the
reduction reads the figures the recording session printed, every op of
the three step programs falls to a declared group, and the groups sum to
the programs' op time. (The reduction on hand-made events:
``tests/test_program_scopes.py`` at the repository's root.)"""
import json
import os

import pytest

from chiplib import devscopes, progspans

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
TRACE = os.path.join(DATA, "serving_scoped.xplane.pb")


def _json(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    registry = _json("serving_scoped_map.json")
    modules, ops = devscopes.read_events(TRACE)
    dispatches = [s["start"] for s in progspans.load_spans(TRACE)
                  if s["name"] == progspans.ROUND]
    return registry, modules, ops, dispatches


def test_the_recorded_session_reads_as_it_printed(recorded):
    registry, modules, ops, dispatches = recorded
    red = devscopes.reduce(modules, ops, registry, None, dispatches)
    want = _json("serving_scoped.json")
    assert red["rounds"] == want["rounds"]
    assert red["prefill_calls"] == want["prefill_calls"]
    assert red["executions"] == want["executions"]
    assert red["seconds"] == pytest.approx(want["seconds"])
    for kind, groups in want["by_group"].items():
        assert red["by_group"][kind] == pytest.approx(groups)
    assert red["mixed_s"] == pytest.approx(want["mixed_s"])
    assert red["host_device_skew_ms"] == pytest.approx(
        want["host_device_skew_ms"])
    assert sorted([k[0], k[1], v, red["calls"][k]]
                  for k, v in red["by_path"].items()) \
        == [[a, b, pytest.approx(c), d] for a, b, c, d in want["by_path"]]


def test_what_the_engines_counters_say_the_trace_holds(recorded):
    """``record_serving_trace.py``'s session: 3 decode and 2 verify
    rounds, 5 prefill chunks — one execution each."""
    registry, modules, ops, dispatches = recorded
    red = devscopes.reduce(modules, ops, registry, None, dispatches)
    assert red["executions"] == {"decode": 3, "verify": 2, "prefill": 5}
    assert red["rounds"] == len(dispatches) == 5
    assert {p["label"] for p in registry.values()} == {
        "serving/decode", "serving/verify", "serving/prefill"}
    assert all(p["scoped"] for p in registry.values())


def test_every_op_of_the_step_programs_falls_to_a_group(recorded):
    registry, modules, ops, dispatches = recorded
    red = devscopes.reduce(modules, ops, registry, None, dispatches)
    for kind in ("decode", "verify", "prefill"):
        groups = red["by_group"][kind]
        assert sum(groups.values()) == pytest.approx(red["seconds"][kind])
        assert groups.get(devscopes.UNSCOPED, 0.0) \
            <= 0.05 * red["seconds"][kind], groups
        assert set(groups) - {devscopes.UNSCOPED} <= set(devscopes.GROUPS)
        assert {"attn", "ffn", "head"} <= set(groups)
    # no event names an instruction the map does not know
    assert red["unknown_s"] == {}
    total = devscopes.round_seconds(red)
    assert sum(devscopes.round_seconds(red, g) for g in
               (*devscopes.GROUPS, devscopes.UNSCOPED)) \
        == pytest.approx(total)


def test_a_window_cuts_the_executions_it_holds(recorded):
    registry, modules, ops, dispatches = recorded
    rounds = sorted(m for m in modules
                    if devscopes.kind_of(m[2], registry)
                    in devscopes.ROUND_KINDS)
    # from the third round's start on
    red = devscopes.reduce(modules, ops, registry,
                           (rounds[2][0], float("inf")), dispatches)
    assert red["rounds"] == 3
    whole = devscopes.reduce(modules, ops, registry, None, dispatches)
    assert devscopes.round_seconds(red) < devscopes.round_seconds(whole)
