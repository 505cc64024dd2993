"""int8 KV-cache quantization (ISSUE 18 — the dtype-polymorphic block
pool, `ServingConfig(kv_int8=)` / `PT_SERVE_KV_INT8`).

Five layers:

- **Quant helpers** — `quantize_kv`/`dequantize_kv` round-trip within
  the per-(position, kv_head) amax step, scales are content-derived
  (same tokens → bit-equal scales, the prefix-sharing precondition).
- **Pool invariants in int8 mode** — the engine's pools store int8 K/V
  plus paired fp32 scale tensors indexed by the SAME block ids; the
  host ledger's accounting, double-free / cross-owner raises, and
  `free + used + cold == capacity` carry over untouched.
- **Tier-1 CPU end-to-end** — THE acceptance proofs: the int8 engine is
  token-identical to the quantize-aware `generate(kv_int8=True)`
  reference AND to the share-nothing int8 engine — under prefix
  sharing, speculative rollback, preemption-recompute churn, and a
  3-replica router — with exec-cache misses == 3, zero second-wave
  compiles, and `kv_int8=False` restoring today's engine exactly
  (scales are None, so the bf16 programs carry no dead buffers).
- **Capacity** — at equal `PT_SERVE_BLOCKS` byte budget the int8 pool
  reports >= 1.9x `allocatable_tokens` at head_dim=128 (2d/(d+4), the
  bench line's arithmetic) and the engine's resident pool bytes drop
  accordingly.
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, generate
from paddle_tpu.serving import (
    RouterConfig, RouterEngine, ServingConfig, ServingEngine,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_by_path(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- quant helpers ------------------------------------------------------------

class TestQuantizeKv:
    def test_round_trip_within_one_step(self):
        import jax.numpy as jnp

        from paddle_tpu.quantization import dequantize_kv, quantize_kv

        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(5, 7, 2, 16).astype(np.float32))
        q, s = quantize_kv(x)
        assert q.dtype == jnp.int8
        assert s.shape == x.shape[:-1]  # one scale per (pos, kv_head)
        err = np.abs(np.asarray(dequantize_kv(q, s, x.dtype)) - x)
        # symmetric round-to-nearest: error <= half the amax/127 step
        step = np.asarray(s)[..., None]
        assert (err <= 0.5 * step + 1e-7).all()

    def test_scales_are_content_derived(self):
        # identical content quantizes to bit-equal (q, s) — the
        # precondition for prefix sharing to share scale slots
        import jax.numpy as jnp

        from paddle_tpu.quantization import quantize_kv

        x = jnp.asarray(np.random.RandomState(1)
                        .randn(3, 4, 2, 8).astype(np.float32))
        q1, s1 = quantize_kv(x)
        q2, s2 = quantize_kv(jnp.array(x))
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))

    def test_zero_rows_survive(self):
        import jax.numpy as jnp

        from paddle_tpu.quantization import dequantize_kv, quantize_kv

        q, s = quantize_kv(jnp.zeros((2, 3, 1, 4)))
        out = np.asarray(dequantize_kv(q, s, jnp.float32))
        assert np.isfinite(out).all() and (out == 0).all()


# -- end-to-end (compiled; tier-1 CPU) ----------------------------------------

@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
    m.eval()
    return m


def _reference_q(model, prompt, new):
    """The quantize-aware reference: generate() round-tripping K/V
    through the SAME quantize_kv/dequantize_kv the engine fuses into
    its compiled programs."""
    return generate(model, pt.to_tensor(np.asarray(prompt)[None, :]),
                    max_new_tokens=new, kv_int8=True).numpy()[0]


def _workload(model, seed, n=8, plen=(3, 13), new=(8, 25)):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        p = rng.randint(0, model.config.vocab_size,
                        (int(rng.randint(*plen)),)).astype(np.int32)
        out.append((p, int(rng.randint(*new))))
    return out


GEOM = dict(max_lanes=3, block_size=4, prefill_chunk=8, max_seq_len=48)


class TestConfigKnob:
    def test_env_default_and_explicit(self, monkeypatch):
        monkeypatch.delenv("PT_SERVE_KV_INT8", raising=False)
        assert ServingConfig().kv_int8 is False
        monkeypatch.setenv("PT_SERVE_KV_INT8", "1")
        assert ServingConfig().kv_int8 is True
        assert ServingConfig(kv_int8=False).kv_int8 is False
        monkeypatch.setenv("PT_SERVE_KV_INT8", "0")
        assert ServingConfig().kv_int8 is False
        assert ServingConfig(kv_int8=True).kv_int8 is True


class TestInt8PoolInvariants:
    def test_pools_and_scales_paired(self, model):
        eng = ServingEngine(model, ServingConfig(kv_int8=True, **GEOM))
        import jax.numpy as jnp

        kpool, vpool, kscale, vscale = eng._pools
        assert kpool.dtype == jnp.int8
        assert vpool.dtype == jnp.int8
        # paired fp32 amax scales, one per (position, kv_head), the
        # null block included (its zero scale dequantizes to zero)
        assert kscale.dtype == jnp.float32
        assert kscale.shape == kpool.shape[:-1]
        assert vscale.shape == vpool.shape[:-1]
        assert eng.kv_pool_bytes == (kpool.nbytes + vpool.nbytes
                                     + kscale.nbytes + vscale.nbytes)
        assert eng.stats()["kv_int8"] is True
        assert eng.stats()["kv_pool_bytes"] == eng.kv_pool_bytes

    def test_ledger_raises_unchanged_in_int8_mode(self, model):
        # the host ledger is the same object either way: accounting,
        # double-free and cross-owner raises hold on an engine that has
        # actually served int8 traffic
        eng = ServingEngine(model, ServingConfig(kv_int8=True, **GEOM))
        for p, n in _workload(model, seed=3, n=4):
            eng.submit(p, max_new_tokens=n)
        eng.run()
        pool = eng.scheduler.pool
        pool.check_invariant()
        assert pool.free_count + pool.used_count + pool.cold_count \
            == pool.capacity
        blocks = pool.alloc(2, "probe")
        pool.free(blocks, "probe")
        with pytest.raises(ValueError, match="double-free|not allocated"):
            pool.free(blocks, "probe")
        a = pool.alloc(1, "a")
        with pytest.raises(ValueError, match="owned by"):
            pool.free(a, "b")
        pool.free(a, "a")
        pool.check_invariant()


def test_int8_token_identity_three_compiles_no_retrace(model, tmp_path):
    """THE acceptance proof: the int8 engine's outputs are
    byte-identical to the quantize-aware generate(kv_int8=True)
    reference AND to the share-nothing int8 engine; exactly 3
    exec-cache misses (dtype is a static key — one prefill, one decode,
    one verify); a second wave and the share-nothing engine add ZERO
    fresh compiles."""
    from paddle_tpu.jit import exec_cache as ec

    work = _workload(model, seed=0)
    ec.enable(str(tmp_path))
    ec.clear()
    try:
        eng = ServingEngine(model, ServingConfig(kv_int8=True, **GEOM))
        assert eng.spec_active
        handles = [eng.submit(p, max_new_tokens=n) for p, n in work]
        outs = eng.run()
        assert ec.stats()["misses"] == 3, ec.stats()
        assert eng.counters["verify_steps"] > 0
        assert eng.counters["kv_quant_writes"] > 0
        assert eng.counters["kv_quant_tokens"] > 0
        for h, (p, n) in zip(handles, work):
            np.testing.assert_array_equal(
                outs[h.request_id], _reference_q(model, p, n),
                err_msg=f"request {h.request_id} diverged from the "
                        f"quantize-aware generate(kv_int8=True)")
        # second wave through the SAME engine: zero fresh compiles —
        # admission/eviction/draft churn never retraces in int8 mode
        h2 = [eng.submit(p, max_new_tokens=n) for p, n in work[:3]]
        outs2 = eng.run()
        assert ec.stats()["misses"] == 3, "int8 retrace!"
        for h, (p, n) in zip(h2, work[:3]):
            np.testing.assert_array_equal(
                outs2[h.request_id], _reference_q(model, p, n))
        # share-nothing int8 engine: same three programs (prefix cache
        # is host-side bookkeeping), identical tokens
        eng_sn = ServingEngine(model, ServingConfig(
            kv_int8=True, prefix_cache=False, **GEOM))
        h3 = [eng_sn.submit(p, max_new_tokens=n) for p, n in work]
        outs3 = eng_sn.run()
        assert ec.stats()["misses"] == 3, ec.stats()
        for h, hsn in zip(handles, h3):
            np.testing.assert_array_equal(
                outs3[hsn.request_id], outs[h.request_id])
    finally:
        ec.disable()
        ec.clear()


def test_int8_off_restores_baseline_engine(model):
    """kv_int8=False must be today's engine exactly: no scale tensors
    (None contributes nothing to the compiled programs), pool at the
    model dtype, quant counters parked at zero, tokens identical to
    plain generate()."""
    eng = ServingEngine(model, ServingConfig(**GEOM))
    assert eng._pools[2] is None and eng._pools[3] is None
    assert eng._pools[0].dtype == np.dtype(model.config.dtype)
    assert eng.stats()["kv_int8"] is False
    work = _workload(model, seed=2, n=4)
    handles = [eng.submit(p, max_new_tokens=n) for p, n in work]
    outs = eng.run()
    assert eng.counters["kv_quant_writes"] == 0
    assert eng.counters["kv_quant_tokens"] == 0
    for h, (p, n) in zip(handles, work):
        np.testing.assert_array_equal(
            outs[h.request_id],
            generate(model, pt.to_tensor(np.asarray(p)[None, :]),
                     max_new_tokens=n).numpy()[0])


def test_int8_prefix_spec_preemption_churn_identity(model):
    """int8 × prefix-cache sharing × speculative rollback × a pool too
    small for the load (preemption-recompute): shared blocks share
    their content-derived scales, rejected drafts rewind pool_len past
    quantized tail slots, re-admissions re-quantize — and every output
    still matches the quantize-aware reference."""
    rng = np.random.RandomState(9)
    prefix = rng.randint(0, model.config.vocab_size,
                         (4,)).astype(np.int32)
    work = []
    for _ in range(8):
        motif = rng.randint(0, model.config.vocab_size, (3,))
        sfx = np.tile(motif, 3)[:int(rng.randint(2, 8))]
        work.append((np.concatenate([prefix, sfx]).astype(np.int32),
                     int(rng.randint(8, 17))))
    eng = ServingEngine(model, ServingConfig(
        kv_int8=True, max_lanes=3, block_size=2, num_blocks=14,
        prefill_chunk=4, max_seq_len=32))
    handles = [eng.submit(p, max_new_tokens=n) for p, n in work]
    outs = eng.run()
    st = eng.stats()
    assert st["preemptions"] > 0, "pressure config never preempted"
    assert st["prefix_hit_tokens"] > 0, "sharing never engaged"
    assert st["spec_proposed_tokens"] > 0, "speculation never proposed"
    # rollback exercised: not every proposed draft token was accepted
    assert st["spec_accepted_tokens"] < st["spec_proposed_tokens"]
    for h, (p, n) in zip(handles, work):
        np.testing.assert_array_equal(
            outs[h.request_id], _reference_q(model, p, n),
            err_msg=f"request {h.request_id} diverged under churn")
    eng.scheduler.pool.check_invariant()


def test_int8_router_token_identity(model):
    """A 3-replica router over int8 engines: same submit/step surface,
    outputs identical to the quantize-aware reference."""
    router = RouterEngine(
        model, ServingConfig(kv_int8=True, **GEOM),
        RouterConfig(replicas=3, mode="inproc"))
    work = _workload(model, seed=4, n=9)
    handles = [router.submit(p, max_new_tokens=n) for p, n in work]
    outs = router.run()
    assert router.stats()["kv_int8"] is True
    for h, (p, n) in zip(handles, work):
        np.testing.assert_array_equal(
            outs[h.request_id], _reference_q(model, p, n),
            err_msg=f"request {h.request_id} diverged through the router")


# -- capacity -----------------------------------------------------------------

class TestCapacity:
    def test_allocatable_tokens_ratio_at_d128(self):
        """ISSUE 18 acceptance: at equal PT_SERVE_BLOCKS byte budget,
        int8 reports >= 1.9x allocatable_tokens (2d/(d+4) = 1.939 at
        head_dim=128) — straight from the bench line's arithmetic."""
        import types

        sb = _load_by_path("serving_bench_cap_t",
                           "benchmarks/serving_bench.py")
        cfg = types.SimpleNamespace(
            num_hidden_layers=12, num_attention_heads=4,
            num_key_value_heads=4, hidden_size=512, dtype="bfloat16")
        per_bf16, alloc_bf16 = sb.kv_byte_model(cfg, 64, 16, 2, 0)
        per_int8, alloc_int8 = sb.kv_byte_model(cfg, 64, 16, 1, 4)
        assert alloc_bf16 == 64 * 16  # bf16 lands exactly on the pool
        assert alloc_int8 / alloc_bf16 >= 1.9
        assert per_int8 / per_bf16 == pytest.approx(
            (128 + 4) / (2 * 128))

    def test_engine_pool_bytes_shrink(self, model):
        # the resident pools themselves: int8 + scales is strictly
        # smaller than the unquantized pool at the same num_blocks
        bf = ServingEngine(model, ServingConfig(**GEOM))
        q = ServingEngine(model, ServingConfig(kv_int8=True, **GEOM))
        assert q.stats()["num_blocks"] == bf.stats()["num_blocks"]
        d = model.config.hidden_size // model.config.num_attention_heads
        el = np.dtype(model.config.dtype).itemsize
        expect = (d + 4) / (d * el)  # int8 + fp32 scale vs base dtype
        assert q.kv_pool_bytes / bf.kv_pool_bytes \
            == pytest.approx(expect)


# -- monitor ------------------------------------------------------------------

def test_kv_quant_monitor_counters(model):
    """serving/kv_quant_* counters mirror the engine's always-on ints
    and the pool-bytes gauge lands — all under the None-slot contract
    (a bf16 engine moves none of them)."""
    was = monitor.enabled()
    monitor.enable()
    try:
        base = monitor.snapshot()["counters"]
        eng = ServingEngine(model, ServingConfig(kv_int8=True, **GEOM))
        for p, n in _workload(model, seed=6, n=4):
            eng.submit(p, max_new_tokens=n)
        eng.run()
        got = monitor.snapshot()

        def delta(k):
            return got["counters"].get(k, 0) - base.get(k, 0)

        c = eng.counters
        assert delta("serving/kv_quant_writes") == c["kv_quant_writes"] > 0
        assert delta("serving/kv_quant_tokens") == c["kv_quant_tokens"] > 0
        assert got["gauges"]["serving/kv_pool_bytes"] == eng.kv_pool_bytes
        # bf16 engine: counters parked
        before = monitor.snapshot()["counters"]
        eng2 = ServingEngine(model, ServingConfig(**GEOM))
        eng2.submit(np.arange(5, dtype=np.int32), max_new_tokens=4)
        eng2.run()
        after = monitor.snapshot()["counters"]
        assert after.get("serving/kv_quant_writes", 0) \
            == before.get("serving/kv_quant_writes", 0)
    finally:
        if not was:
            monitor.disable()


def test_monitor_report_renders_kv_pool_line(tmp_path):
    """monitor_report's serving section renders the int8 pool: dtype,
    resident bytes, quantize-on-write totals."""
    mr = _load_by_path("monitor_report_kv_t", "tools/monitor_report.py")
    bench = tmp_path / "serving.log"
    bench.write_text(json.dumps({
        "metric": "serving_tokens_per_sec", "value": 100.0,
        "unit": "tokens/s", "telemetry": {"serving": {
            "admits": 4, "prefill_steps": 6, "decode_steps": 10,
            "kv_quant_writes": 24, "kv_quant_tokens": 87}}}) + "\n")
    jsonl = tmp_path / "run.jsonl"
    jsonl.write_text(json.dumps({"event": "run_begin", "meta": {}}) + "\n")
    text = mr.render(str(jsonl), bench_path=str(bench))
    assert "kv pool: int8" in text
    assert "24 quantizing write(s)" in text
    assert "87 token(s) quantized" in text


# -- bench contract -----------------------------------------------------------

def test_serving_bench_int8_contract_line():
    """ISSUE 18 acceptance via the bench: the int8 smoke line reports
    kv_int8, the pool-derived kv_bytes_per_token, an allocatable_tokens
    capacity >= 1.9x the embedded bf16 replay's, and the kv_bf16 A/B
    sub-object."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PT_SERVE_BENCH_REQUESTS"] = "6"
    env["PT_SERVE_BENCH_RATE"] = "200"
    env["PT_SERVE_KV_INT8"] = "1"
    env["PT_SERVE_BENCH_KV_AB"] = "1"
    proc = subprocess.run(
        [sys.executable, "benchmarks/serving_bench.py", "--smoke"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("{"))
    rec = json.loads(line)
    assert rec["metric"] == "serving_tokens_per_sec"
    assert rec["kv_int8"] is True
    ab = rec["kv_bf16"]
    assert rec["kv_bytes_per_token"] < ab["kv_bytes_per_token"]
    assert rec["allocatable_tokens"] >= 1.9 * ab["allocatable_tokens"]
    assert rec["kv_pool_bytes"] < ab["kv_pool_bytes"]
    assert ab["tokens_per_sec"] > 0 and ab["ttft_ms_p50"] is not None
    tel = rec["telemetry"]["serving"]
    assert tel["kv_quant_writes"] > 0 and tel["kv_quant_tokens"] > 0
    assert rec["kv_pool_bytes"] > 0
    assert set(rec["kernels"]) == {"flash", "flash_headbatch"}
