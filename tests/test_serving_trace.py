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
- **Span classes** — queue-wait/prefill/round/finish spans land on the
  ``req/<trace_id>`` and ``serve/rounds`` lanes with the documented
  cats; spec rollback rounds record exactly one COMPLETE verify span
  each (a rewound ``pool_len`` cannot leave an open span).
- **Blackbox** — an engine raise writes ``serving_blackbox.json``
  (spans tail + scheduler state + finished journeys) without masking
  the error; a tiny ring cap still yields a well-formed artifact with
  ``spans_dropped`` accounting; ungated crash sites stay artifact-free.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.serving.engine as engine_mod
from paddle_tpu import monitor
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, generate
from paddle_tpu.monitor import blackbox
from paddle_tpu.monitor.spans import SpanRecorder
from paddle_tpu.serving import ServingConfig, ServingEngine


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


def _run(model, work, **cfg_kw):
    cfg = ServingConfig(**{**dict(max_lanes=3, block_size=4,
                                  prefill_chunk=8, max_seq_len=32),
                           **cfg_kw})
    eng = ServingEngine(model, cfg)
    handles = [eng.submit(p, max_new_tokens=n) for p, n in work]
    outs = eng.run()
    return eng, [outs[h.request_id] for h in handles], handles


def _spans_by_name(name):
    return [s for s in monitor.spans().snapshot() if s[0] == name]


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

        def _norm(events):
            # request ids are a process-global counter: compare the two
            # rings with ids rebased to each run's first submit
            base = min(e[1] for e in events if e[0] == "submit")
            return [(e[0], e[1] - base, *e[2:]) for e in events]

        assert _norm(traced_events) == _norm(list(eng_off.scheduler.events))
        for (p, n), out in zip(work, plain):
            np.testing.assert_array_equal(
                out, generate(model, pt.to_tensor(np.asarray(p)[None, :]),
                              max_new_tokens=n).numpy()[0])


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
        rounds = by_name.get("serving/decode_round", []) \
            + by_name.get("serving/verify_round", [])
        assert len(rounds) == eng.counters["decode_steps"] \
            + eng.counters["verify_steps"]
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
        pool_len back) must still record exactly one complete
        verify_round span — never an open/torn one — and token output
        must stay byte-identical to generate()."""
        rng = np.random.RandomState(3)
        motif = rng.randint(0, model.config.vocab_size, (4,))
        work = [(np.tile(motif, 4).astype(np.int32), 8) for _ in range(3)]
        eng, outs, _ = _run(model, work, spec=True, spec_k=4)
        assert eng.counters["verify_steps"] > 0, "spec never engaged"
        rejected = (eng.counters["spec_proposed_tokens"]
                    - eng.counters["spec_accepted_tokens"])
        vspans = _spans_by_name("serving/verify_round")
        assert len(vspans) == eng.counters["verify_steps"]
        for s in vspans:
            assert s[4] >= s[3], "open/torn round span"
            assert s[5]["accepted"] <= s[5]["proposed"]
        # token identity survives rollback (tolerate all-accepted runs,
        # but the motif workload normally rejects at least once)
        for (p, n), out in zip(work, outs):
            np.testing.assert_array_equal(
                out, generate(model, pt.to_tensor(np.asarray(p)[None, :]),
                              max_new_tokens=n).numpy()[0])
        if rejected:
            # the rewound lanes kept decoding: rounds after a rollback
            # still recorded (count above already pins one span/round)
            assert eng.counters["decoded_tokens"] > 0

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
