"""The reduction from a profiler trace to busy time, top operations,
idle gaps, kernel time and busy time inside host spans: on hand-made events with
known answers, and on a small trace recorded on a TPU v5e."""
import os

import pytest

from chiplib import trace

MS = 1e6  # ns


def synthetic():
    dev = [  # name, start, duration (ns)
        ("fusion.1 fusion f32_8", 10 * MS, 10 * MS),
        ("fusion.2 fusion f32_8", 15 * MS, 10 * MS),   # overlaps fusion.1
        ("custom-call.3 custom-call bf16_4", 30 * MS, 5 * MS),
        ("all-reduce.4 all-reduce f32_8", 40 * MS, 10 * MS),
        ("fusion.5 fusion f32_16", 45 * MS, 2 * MS),   # hides 2 ms of it
        ("fusion.6 fusion f32_16", 80 * MS, 10 * MS),
    ]
    host = [("bench/train_step", 0.0, 60 * MS),
            ("bench/batch_prep", 60 * MS, 15 * MS),
            ("bench/train_step", 75 * MS, 25 * MS)]
    return {"devices": {0: dev}, "host": host}


def test_busy_is_the_union_and_the_window_the_host_spans():
    r = trace.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.100)
    # [10,25] + [30,35] + [40,50] + [80,90] = 40 ms
    assert r["busy_s"] == pytest.approx(0.040)
    ops = dict(r["device_ops"])
    assert ops["fusion_f32_8"] == pytest.approx(0.020)
    assert ops["fusion_f32_16"] == pytest.approx(0.012)
    assert ops["all-reduce_f32_8"] == pytest.approx(0.010)


def test_idle_gaps_go_to_the_host_span_that_covers_their_start():
    gaps = dict(trace.reduce(synthetic())["idle_gaps"])
    # gaps: [0,10] [25,30] [35,40] [50,80] [90,100]
    assert gaps["bench/train_step"] == pytest.approx(0.060)
    assert sum(gaps.values()) == pytest.approx(0.060)
    t = synthetic()
    t["host"] = [("bench/train_step", 0.0, 55 * MS),
                 ("bench/batch_prep", 55 * MS, 45 * MS)]
    t["devices"][0].append(("fusion.9 fusion f32_8", 60 * MS, 5 * MS))
    gaps = dict(trace.reduce(t)["idle_gaps"])
    assert gaps["bench/batch_prep"] == pytest.approx(0.015 + 0.010)


def test_kernel_time_matches_the_op_itself_never_an_operand():
    k = trace.kernel_seconds(synthetic(), r"^\S+ custom-call( |$)")
    assert k == {"seconds": pytest.approx(0.005), "calls": 1}
    text = ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %custom-call.3), "
            "kind=kLoop")
    assert trace.short_name(text) == "fusion.7 fusion f32_8"
    text = ('%custom-call.12 = (bf16[2,32,4096,128]{3,2,1,0:T(8,128)(2,1)},'
            ' f32[2]{0}) custom-call(bf16[2,4096]{1,0} %x), '
            'custom_call_target="tpu_custom_call"')
    assert trace.short_name(text) == \
        "custom-call.12 custom-call bf16_2_32_4096_128"
    assert trace.op_bucket(trace.short_name(text)) == \
        "custom-call_bf16_2_32_4096_128"


def test_device_busy_time_inside_each_host_span():
    # spans [0,60] [75,100]: ops [10,25] [30,35] [40,50] | [80,90]
    busy = trace.busy_in_spans(synthetic(), "bench/train_step")
    assert busy == [pytest.approx(0.030), pytest.approx(0.010)]
    # an op that straddles a span's end is clipped to the span
    assert trace.busy_in_spans(synthetic(), "bench/batch_prep") == [0.0]
    t = synthetic()
    t["devices"][0].append(("fusion.9 fusion f32_8", 55 * MS, 10 * MS))
    assert trace.busy_in_spans(t, "bench/train_step")[0] == \
        pytest.approx(0.035)
    assert trace.busy_in_spans(t, "bench/batch_prep") == \
        [pytest.approx(0.005)]
    assert trace.busy_in_spans(t, "bench/none") == []


def test_decode_roofline_is_over_device_time_inside_the_round():
    """Rounds pair with their ``bench/engine_step`` spans from the end;
    prefill rounds and untraced rounds are left out; the share is the
    least time for weights + live K/V over the DEVICE's busy time in the
    round, whatever the host span lasted."""
    from chiplib import costs, manifest

    m = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 2,
         "num_key_value_heads": 1, "intermediate_size": 16,
         "vocab_size": 32}
    rnd = {"ms": 50.0, "prefill_chunks": 0, "decode_steps": 1,
           "verify_steps": 0, "live_kv_tokens": 100, "traced": True}
    rounds = [dict(rnd, traced=False),             # before the profiler
              dict(rnd, prefill_chunks=3),         # not a pure round
              dict(rnd, live_kv_tokens=100),       # 10 ms busy
              dict(rnd, decode_steps=0, verify_steps=1,
                   live_kv_tokens=300, ms=500.0)]  # 20 ms busy, slow host
    host = [("bench/engine_step", 0.0, 50 * MS),
            ("bench/engine_step", 50 * MS, 50 * MS),
            ("bench/engine_step", 100 * MS, 500 * MS)]
    dev = [("fusion.1 fusion f32_8", 5 * MS, 40 * MS),
           ("fusion.2 fusion f32_8", 60 * MS, 10 * MS),
           ("fusion.3 fusion f32_8", 110 * MS, 20 * MS)]
    hbm = 1e6
    obs = {"job": "serve", "loop": "backlog", "rounds": rounds,
           "trace": {"devices": {0: dev}, "host": host}, "model": m,
           "layers": 1, "peaks": {"hbm_bytes_per_s": hbm},
           "arch": manifest.Files().arch("llama_dense")}
    read = manifest.metric_reader("decode_step_roofline")
    a = 100.0 * costs.decode_round_bytes(m, 1, 100) / hbm / 0.010
    b = 100.0 * costs.decode_round_bytes(m, 1, 300) / hbm / 0.020
    assert read(obs) == pytest.approx((a + b) / 2)
    assert read(dict(obs, trace=None)) is None
    assert read(dict(obs, loop="open")) is None


def test_a_trace_without_device_events_reduces_to_nothing():
    assert trace.reduce({"devices": {}, "host": []}) is None
    assert trace.reduce({"devices": {0: []}, "host": []}) is None


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small.xplane.pb")


def test_recorded_tpu_trace():
    """Three calls of one small jitted matmul on a TPU v5e, each inside a
    ``bench/train_step`` annotation, with a 2 ms ``bench/batch_prep``
    sleep after each (recorded by PR 23's chip run)."""
    t = trace.load(RECORDED)
    assert list(t["devices"]) == [0]
    names = [n for n, _, _ in t["host"]]
    assert names.count("bench/train_step") == 3
    assert names.count("bench/batch_prep") == 3
    r = trace.reduce(t)
    assert 0 < r["busy_s"] < r["window_s"] < 1.0
    assert r["device_ops"] and r["device_ops"][0][1] > 0
    gaps = dict(r["idle_gaps"])
    # the device idles through the sleeps: at least 3 x 2 ms
    assert gaps["bench/batch_prep"] >= 0.006
    assert all(" " not in k and "%" not in k for k, _ in r["device_ops"])
    # one reading a span, never more than the device was busy in all.
    # In this recording each 2.4 us matmul starts 40-100 us BEFORE the
    # host span that launched it: host and device clocks agree to about
    # 0.1 ms, so a span has to last many ms for its reading to mean much
    busy = trace.busy_in_spans(t, "bench/train_step")
    assert len(busy) == 3
    assert 0 <= sum(busy) <= r["busy_s"] * (1 + 1e-9)
    lead = [h[1] - min(s for _, s, _ in t["devices"][0] if s > h[1] - 2e5)
            for h in t["host"] if h[0] == "bench/train_step"]
    assert all(0 < x < 2e5 for x in lead)  # under 0.2 ms
