"""Per-request serving traces (ISSUE 16): lifecycle spans, TTFT/TPOT
attribution, and the black-box postmortem dump.

Four proof layers:

- **Token identity** — the traced engine (PT_MONITOR on) emits byte-
  identical tokens AND a byte-identical scheduler event ring vs the
  untraced engine: tracing is observation, never behavior.
- **Attribution telescoping** — every finished request's
  {queue, prefill, decode, preempted} buckets sum to its measured
  end-to-end latency (the engine advances ONE clock mark per phase
  boundary, so the identity is exact, not approximate), preempted
  requests bill their off-lane time to ``preempted_ms``, and the
  attribution stays on with the monitor off.
- **Span classes** — queue-wait/prefill/finish spans land on the
  ``req/<trace_id>`` lanes and the step's phases on ``serve/rounds``
  with the documented cats; spec rollback rounds record exactly one
  COMPLETE verify dispatch each (a rewound ``pool_len`` cannot leave
  an open span).
- **Phases** (ISSUE 24) — every phase of ``step()`` is one
  ``monitor/spans.Phase``: a ``jax.profiler.TraceAnnotation`` (in the
  device trace's own file whenever a profiler session is on), an
  always-on float in ``ServingEngine.counters``, and the ring under
  ``PT_MONITOR``. A constant number a round and a prefill, nested,
  telescoping to ``step_s``, and never feeding back into behavior.
- **Blackbox** — an engine raise writes ``serving_blackbox.json``
  (spans tail + scheduler state + finished journeys) without masking
  the error; a tiny ring cap still yields a well-formed artifact with
  ``spans_dropped`` accounting; ungated crash sites stay artifact-free.
"""
import glob
import json
import os

import jax
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.serving.engine as engine_mod
from paddle_tpu import monitor
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, generate
from paddle_tpu.monitor import blackbox
from paddle_tpu.monitor.spans import Phase, SpanRecorder
from paddle_tpu.serving import (RouterConfig, RouterEngine, ServingConfig,
                                ServingEngine)

# span -> the always-on counter it feeds (docs/OBSERVABILITY.md)
PHASES = {
    "serving/step": "step_s", "serving/admit": "admit_s",
    "serving/prefill": "prefill_s",
    "serving/first_token_fetch": "first_fetch_s",
    "serving/grow": "grow_s", "serving/draft": "draft_s",
    "serving/pack": "pack_s", "serving/dispatch": "dispatch_s",
    "serving/token_fetch": "fetch_s", "serving/emit": "emit_s",
}


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
    m.eval()
    return m


@pytest.fixture
def mon(tmp_path, monkeypatch):
    """Enabled monitor with clean metrics/spans; restores disabled-off."""
    monkeypatch.setenv("PT_MONITOR_SINK", str(tmp_path / "steps.jsonl"))
    monitor.reset()
    monitor.enable()
    yield monitor
    monitor.disable()
    monitor.reset()


def _workload(model, seed=0, n=6, plen=(3, 11), new=(4, 11)):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, model.config.vocab_size,
                         (int(rng.randint(*plen)),)).astype(np.int32),
             int(rng.randint(*new))) for _ in range(n)]


def _run(model, work, drafter=None, **cfg_kw):
    cfg = ServingConfig(**{**dict(max_lanes=3, block_size=4,
                                  prefill_chunk=8, max_seq_len=32),
                           **cfg_kw})
    eng = ServingEngine(model, cfg, drafter=drafter)
    handles = [eng.submit(p, max_new_tokens=n) for p, n in work]
    outs = eng.run()
    return eng, [outs[h.request_id] for h in handles], handles


def _spans_by_name(name):
    return [s for s in monitor.spans().snapshot() if s[0] == name]


def _norm(events):
    # request ids are a process-global counter: compare two rings with
    # ids rebased to each run's first submit
    base = min(e[1] for e in events if e[0] == "submit")
    return [(e[0], e[1] - base, *e[2:]) for e in events]


class _WrongDrafter:
    """Proposes, at every position, a continuation the model will NOT
    emit (its own greedy token + 1): every draft is rejected at its
    first token, so verify rounds and rollbacks are certain whatever a
    random-weight model emits."""

    def __init__(self, model, work):
        self.vocab = model.config.vocab_size
        self.refs = {tuple(p): generate(
            model, pt.to_tensor(np.asarray(p)[None, :]),
            max_new_tokens=n).numpy()[0] for p, n in work}

    def propose(self, ctx, k):
        for p, out in self.refs.items():
            if tuple(ctx[:len(p)]) == p:
                i = len(ctx) - len(p)  # out[i] is what comes next
                return (out[i:i + k] + 1) % self.vocab
        raise AssertionError("a context no request of the workload has")


def _profiled(tmp_path, fn):
    """``fn()`` under a jax.profiler session as the chip benchmark sets
    it (host tracer 1, no python tracer); returns fn's result and the
    session's ``serving/*`` host events [(name, start_ns, end_ns, args,
    thread)] in start order, outermost first."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serving/"):
                    events.append((ev.name, ev.start_ns,
                                   ev.start_ns + ev.duration_ns,
                                   {k: v for k, v in ev.stats},
                                   line.name))
    return out, sorted(events, key=lambda e: (e[1], -e[2]))


# -- token identity: tracing is observation -----------------------------------

class TestTracedIdentity:
    def test_traced_engine_tokens_and_events_identical(self, model, mon):
        work = _workload(model)
        eng_on, traced, _ = _run(model, work)
        traced_events = list(eng_on.scheduler.events)
        monitor.disable()
        try:
            eng_off, plain, _ = _run(model, work)
        finally:
            monitor.enable()
        # same tokens, same scheduler decisions, byte for byte: the
        # span/attribution layer never feeds back into behavior
        for a, b in zip(traced, plain):
            np.testing.assert_array_equal(a, b)

        assert _norm(traced_events) == _norm(list(eng_off.scheduler.events))
        for (p, n), out in zip(work, plain):
            np.testing.assert_array_equal(
                out, generate(model, pt.to_tensor(np.asarray(p)[None, :]),
                              max_new_tokens=n).numpy()[0])


    def test_profiler_session_changes_no_token_and_no_event(
            self, model, tmp_path):
        """A live jax.profiler session is what turns the phase
        annotations into recorded spans: same tokens, same scheduler
        ring as the run with no session."""
        work = _workload(model)
        (eng_on, traced, _), events = _profiled(
            tmp_path, lambda: _run(model, work))
        assert events, "the session recorded no serving/ span"
        eng_off, plain, _ = _run(model, work)
        for a, b in zip(traced, plain):
            np.testing.assert_array_equal(a, b)
        assert _norm(list(eng_on.scheduler.events)) \
            == _norm(list(eng_off.scheduler.events))


# -- attribution: telescoping latency buckets ---------------------------------

class TestAttribution:
    def test_buckets_sum_to_request_latency(self, model):
        # monitor OFF on purpose: attribution is always-on plain floats
        assert not monitor.enabled()
        eng, _, handles = _run(model, _workload(model))
        assert engine_mod._spans is None  # and yet:
        for h in handles:
            assert h.t_done is not None
            total = (h.t_done - h.t_submit) * 1e3
            parts = (h.queue_ms + h.prefill_ms + h.decode_ms
                     + h.preempted_ms)
            # exact telescoping (one clock mark per phase boundary) —
            # only float rounding separates the sum from the total
            assert parts == pytest.approx(total, rel=1e-6, abs=1e-3)
            assert h.prefill_ms > 0 and h.decode_ms > 0
            att = h.attribution()
            assert set(att) == {
                "queue_ms", "prefill_ms", "decode_ms", "preempted_ms",
                "prefill_refunded_tokens", "spec_rounds",
                "accepted_tokens"}

    def test_preempted_requests_bill_preempted_ms(self, model):
        # pressure geometry from test_serving's preemption proof
        eng, outs, handles = _run(
            model, _workload(model, seed=1, plen=(2, 9), new=(6, 12)),
            max_lanes=3, block_size=2, num_blocks=12, prefill_chunk=4,
            max_seq_len=20)
        assert eng.counters["preemptions"] > 0, "never preempted — vacuous"
        victims = [h for h in handles if h.preemptions]
        assert victims
        for h in victims:
            # off-lane wait after eviction is preempted time, not queue
            assert h.preempted_ms > 0
            total = (h.t_done - h.t_submit) * 1e3
            parts = (h.queue_ms + h.prefill_ms + h.decode_ms
                     + h.preempted_ms)
            assert parts == pytest.approx(total, rel=1e-6, abs=1e-3)


# -- span classes -------------------------------------------------------------

class TestServingSpans:
    def test_request_lifecycle_spans(self, model, mon):
        eng, _, handles = _run(model, _workload(model))
        spans = monitor.spans().snapshot()
        lanes = {s[2] for s in spans}
        assert "serve/rounds" in lanes
        for h in handles:
            assert h.trace_id == f"r{h.request_id}"
            assert f"req/{h.trace_id}" in lanes
        by_name = {}
        for s in spans:
            by_name.setdefault(s[0], []).append(s)
        assert len(by_name["serving/queue_wait"]) == len(handles)
        assert len(by_name["serving/prefill"]) \
            >= len(handles)  # >= : recompute prefills add more
        assert sum(s[5]["chunks"] for s in by_name["serving/prefill"]) \
            == eng.counters["prefill_chunks"]
        kinds = [s[5]["kind"] for s in by_name["serving/dispatch"]]
        assert kinds.count("decode") == eng.counters["decode_steps"]
        assert kinds.count("verify") == eng.counters["verify_steps"]
        assert len(by_name["serving/emit"]) == len(kinds)
        finishes = by_name["serving/request"]
        assert len(finishes) == len(handles)
        for s in finishes:
            args = s[5]
            assert s[1] == "serving_finish"
            parts = (args["queue_ms"] + args["prefill_ms"]
                     + args["decode_ms"] + args["preempted_ms"])
            assert parts == pytest.approx(args["total_ms"], abs=0.01)
            assert s[4] >= s[3]  # completed span, t1 >= t0

    def test_spec_rollback_closes_round_spans(self, model, mon):
        """Satellite 6: a verify round that REJECTS drafts (rolling
        pool_len back) must still record exactly one complete verify
        dispatch and one emit span with its counts — never an open/torn
        one — and token output must stay byte-identical to generate().
        The injected drafter is always wrong, so rejection is certain."""
        work = _workload(model, n=3)
        drafter = _WrongDrafter(model, work)
        eng, outs, _ = _run(model, work, drafter=drafter, spec=True,
                            spec_k=4)
        c = eng.counters
        assert c["verify_steps"] > 0, "spec never engaged"
        assert c["spec_proposed_tokens"] > 0
        assert c["spec_accepted_tokens"] == 0  # every draft rolled back
        vspans = [s for s in _spans_by_name("serving/dispatch")
                  if s[5]["kind"] == "verify"]
        assert len(vspans) == c["verify_steps"]
        espans = [s for s in _spans_by_name("serving/emit")
                  if "proposed" in s[5]]
        assert len(espans) == c["verify_steps"]
        assert sum(s[5]["proposed"] for s in espans) \
            == c["spec_proposed_tokens"]
        for s in vspans + espans:
            assert s[4] >= s[3], "open/torn round span"
        for s in espans:
            assert s[5]["accepted"] <= s[5]["proposed"]
            # the rewound lanes kept decoding: one token a lane a round
            assert s[5]["emitted"] == s[5]["lanes"]
        # token identity survives rollback
        for (p, n), out in zip(work, outs):
            np.testing.assert_array_equal(out, drafter.refs[tuple(p)])

    def test_preempt_marker_and_requeue_span(self, model, mon):
        eng, _, handles = _run(
            model, _workload(model, seed=1, plen=(2, 9), new=(6, 12)),
            max_lanes=3, block_size=2, num_blocks=12, prefill_chunk=4,
            max_seq_len=20)
        assert eng.counters["preemptions"] > 0
        marks = _spans_by_name("serving/preempt")
        assert len(marks) == eng.counters["preemptions"]
        for s in marks:
            assert s[3] == s[4]  # zero-length marker
        # every victim that got back on a lane recorded its off-lane
        # wait as a requeue_wait span on its own trace lane
        requeues = _spans_by_name("serving/requeue_wait")
        assert len(requeues) > 0
        assert all(s[5]["preemptions"] > 0 for s in requeues)


# -- phases of step(): annotation + counter + ring ----------------------------

class _CountingPhase(Phase):
    opened: list = []

    def __init__(self, name, *a, **kw):
        _CountingPhase.opened.append(name)
        super().__init__(name, *a, **kw)


class TestPhases:
    def test_profiler_session_holds_nested_phases(self, model, tmp_path):
        """(a) under a profiler session every ``serving/step`` event
        contains its phases, properly nested, on one thread, with the
        ``request`` / ``kind`` args."""
        (eng, _, handles), ev = _profiled(
            tmp_path, lambda: _run(model, _workload(model)))
        assert {e[0] for e in ev} == set(PHASES)
        assert len({e[4] for e in ev}) == 1, "more than one thread"
        inside = {"serving/first_token_fetch": "serving/prefill"}
        stack, steps = [], 0
        for name, t0, t1, args, _ in ev:
            while stack and stack[-1][2] <= t0:
                stack.pop()
            if name == "serving/step":
                assert not stack, "a step inside another span"
                steps += 1
            else:
                parent = stack[-1]
                assert parent[0] == inside.get(name, "serving/step")
                assert parent[1] <= t0 and t1 <= parent[2], \
                    f"{name} sticks out of its {parent[0]}"
            stack.append((name, t0, t1))
        c = eng.counters
        by = {}
        for e in ev:
            by.setdefault(e[0], []).append(e)
        assert steps == len(by["serving/step"]) > 0
        kinds = [e[3]["kind"] for e in by["serving/dispatch"]]
        assert kinds.count("decode") == c["decode_steps"] > 0
        assert kinds.count("verify") == c["verify_steps"]
        assert all(1 <= e[3]["lanes"] <= 3 for e in by["serving/dispatch"])
        assert all(e[3]["lanes"] >= 1 for e in by["serving/draft"])
        pre = by["serving/prefill"]
        assert sorted(e[3]["request"] for e in pre) \
            == sorted(h.trace_id for h in handles)
        assert sum(e[3]["miss_tokens"] for e in pre) \
            == c["prefix_miss_tokens"]
        assert sum(e[3]["hit_tokens"] for e in pre) == c["prefix_hit_tokens"]

    @pytest.mark.parametrize("lanes", [1, 3])
    def test_spans_a_round_do_not_grow_with_lanes(self, model, lanes,
                                                  monkeypatch):
        """(b) a constant number of always-on spans a step, a round and
        a prefill: none per lane, per token or per chunk."""
        monkeypatch.setattr(engine_mod, "Phase", _CountingPhase)
        _CountingPhase.opened = []
        assert engine_mod._spans is None  # the always-on path
        eng, _, handles = _run(model, _workload(model), max_lanes=lanes,
                               prefill_chunk=4)  # several chunks a prompt
        n = {k: _CountingPhase.opened.count(k) for k in PHASES}
        c = eng.counters
        rounds = c["decode_steps"] + c["verify_steps"]
        assert c["admits"] == len(handles) and c["preemptions"] == 0
        assert c["prefill_chunks"] > c["admits"]
        assert c["decoded_tokens"] > rounds or lanes == 1
        assert n["serving/step"] > 0
        # every step ends its admission loop on one empty admit
        assert n["serving/admit"] == n["serving/step"] + c["admits"]
        assert n["serving/prefill"] == n["serving/first_token_fetch"] \
            == c["admits"]
        for k in ("grow", "draft", "pack", "dispatch", "token_fetch",
                  "emit"):
            assert n["serving/" + k] == rounds, k
        assert len(_CountingPhase.opened) == sum(n.values())

    def test_counters_telescope_and_sum_across_replicas(self, model):
        """(c) the phase counters add up to ``step_s`` but for the few
        statements between them, appear in ``stats()``, and a
        ``RouterEngine`` sums them over its replicas."""
        eng, _, _ = _run(model, _workload(model, n=2))  # compiles
        before = dict(eng.counters)
        for p, n in _workload(model, seed=5):
            eng.submit(p, max_new_tokens=n)
        eng.run()
        d = {k: eng.counters[k] - before[k] for k in PHASES.values()}
        assert all(v > 0 for v in d.values()), d
        assert d["first_fetch_s"] <= d["prefill_s"]
        parts = sum(v for k, v in d.items()
                    if k not in ("step_s", "first_fetch_s"))
        assert 0.75 * d["step_s"] <= parts <= d["step_s"]
        st = eng.stats()
        assert "decode_wall_s" not in st
        for k in PHASES.values():
            assert st[k] == eng.counters[k]
        router = RouterEngine(
            model, ServingConfig(max_lanes=3, block_size=4,
                                 prefill_chunk=8, max_seq_len=32),
            RouterConfig(replicas=2, mode="inproc"))
        for i, (p, n) in enumerate(_workload(model, seed=6)):
            router.submit(p, max_new_tokens=n, request_id=f"x{i}")
        router.run()
        rst = router.stats()
        reps = [r._engine.counters for r in router._replicas]
        assert all(c["step_s"] > 0 for c in reps), "a replica sat idle"
        for k in PHASES.values():
            assert rst[k] == pytest.approx(sum(c[k] for c in reps))

    def test_ring_and_blackbox_hold_the_phases(self, model, mon, tmp_path):
        """(e) under PT_MONITOR the ring receives every phase, on the
        perf_counter clock of its other spans, and the blackbox dump
        still holds them."""
        eng, _, handles = _run(model, _workload(model))
        spans = monitor.spans().snapshot()
        by = {}
        for s in spans:
            by.setdefault(s[0], []).append(s)
        assert set(PHASES) <= set(by)
        assert {s[2] for s in by["serving/prefill"]} \
            == {f"req/{h.trace_id}" for h in handles}
        for name in set(PHASES) - {"serving/prefill"}:
            assert {s[2] for s in by[name]} == {"serve/rounds"}, name
        # what the ring holds is what the counters summed
        for name, key in PHASES.items():
            assert sum(s[4] - s[3] for s in by[name]) \
                == pytest.approx(eng.counters[key]), name
        # a request's finish span (stamped by the engine's own clock)
        # contains its prefill phase: one clock for the whole ring
        for h in handles:
            lane = [s for s in spans if s[2] == f"req/{h.trace_id}"]
            fin, = [s for s in lane if s[0] == "serving/request"]
            pre = [s for s in lane if s[0] == "serving/prefill"]
            assert pre and all(fin[3] <= s[3] and s[4] <= fin[4]
                               for s in pre)
        out = blackbox.dump(path=str(tmp_path / "bb.json"),
                            reason="phase_test")
        names = {sp["name"] for sp in json.loads(open(out).read())["spans"]}
        assert {"serving/step", "serving/dispatch", "serving/token_fetch",
                "serving/emit"} <= names


# -- ring cap + blackbox ------------------------------------------------------

class TestBlackbox:
    def test_ring_cap_evicts_cleanly_and_dump_stays_wellformed(
            self, model, tmp_path, monkeypatch):
        """Satellite 3: under a tiny span ring the oldest spans evict,
        the engine keeps running, and the blackbox artifact still emits
        well-formed (partial) journeys with honest drop accounting."""
        monkeypatch.setattr(monitor, "_span_recorder",
                            SpanRecorder(capacity=8))
        monkeypatch.setenv("PT_MONITOR_SINK",
                           str(tmp_path / "steps.jsonl"))
        monitor.reset()
        monitor.enable()
        try:
            eng, _, handles = _run(model, _workload(model))
            rec = monitor.spans()
            assert rec is engine_mod._spans  # the small ring got wired
            assert rec.count > 8 and rec.dropped > 0
            assert len(rec.snapshot()) <= 8
            out = blackbox.dump(path=str(tmp_path / "bb.json"),
                                reason="ring_cap_test")
            assert out is not None
            art = json.loads(open(out).read())
            assert art["version"] == 1
            assert art["spans_recorded"] == rec.count
            assert art["spans_dropped"] >= rec.dropped
            assert 0 < len(art["spans"]) <= 8
            for sp in art["spans"]:
                assert {"name", "cat", "lane", "t0", "t1",
                        "args"} <= set(sp)
            # every live engine registers a provider — find THIS one by
            # its finished journeys (earlier tests' engines may linger)
            eng_state = next(
                v for k, v in art["state"].items()
                if k.startswith("serving_engine")
                and len(v.get("finished_tail", [])) == len(handles))
            assert eng_state["scheduler"]["pool"]["free"] \
                + eng_state["scheduler"]["pool"]["used"] \
                + eng_state["scheduler"]["pool"]["cold"] \
                == eng_state["scheduler"]["pool"]["capacity"]
            # finished journeys survive even when their spans evicted
            for j in eng_state["finished_tail"]:
                assert j["total_ms"] is not None
        finally:
            monitor.disable()
            monitor.reset()

    def test_engine_raise_writes_blackbox(self, model, tmp_path,
                                          monkeypatch):
        bb = tmp_path / "serving_blackbox.json"
        monkeypatch.setenv("PT_SERVE_BLACKBOX", str(bb))
        eng, _, _ = _run(model, _workload(model, n=2))

        def boom(*a, **kw):
            raise ValueError("injected prefill failure")

        monkeypatch.setattr(eng, "_prefill", boom)
        eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
        with pytest.raises(ValueError, match="injected prefill"):
            eng.run()
        assert bb.exists()
        art = json.loads(bb.read_text())
        assert art["reason"] == "serving_engine_raise"
        assert "injected prefill" in art["error"]
        assert isinstance(art["spans"], list)
        # the mid-flight request is captured with its partial journey
        # (scan: every live engine registers a provider)
        live = [v["scheduler"] for k, v in art["state"].items()
                if k.startswith("serving_engine")
                and v.get("scheduler", {}).get("requests")]
        assert live, "no live requests in the postmortem"
        assert {"trace_id", "state", "queue_ms",
                "decode_ms"} <= set(live[-1]["requests"][0])

    def test_raise_without_audience_stays_artifact_free(
            self, model, tmp_path, monkeypatch):
        monkeypatch.delenv("PT_SERVE_BLACKBOX", raising=False)
        monkeypatch.chdir(tmp_path)
        assert not monitor.enabled()
        eng, _, _ = _run(model, _workload(model, n=2))
        monkeypatch.setattr(
            eng, "_prefill",
            lambda *a, **kw: (_ for _ in ()).throw(ValueError("x")))
        eng.submit(np.arange(4, dtype=np.int32), max_new_tokens=3)
        with pytest.raises(ValueError):
            eng.run()
        assert not os.path.exists(blackbox.DEFAULT_PATH)

    def test_env_zero_disables_even_with_monitor(self, mon, tmp_path,
                                                 monkeypatch):
        monkeypatch.setenv("PT_SERVE_BLACKBOX", "0")
        monkeypatch.chdir(tmp_path)
        assert blackbox.maybe_dump(reason="gated") is None
        assert not os.path.exists(blackbox.DEFAULT_PATH)
