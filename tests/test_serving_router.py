"""Multi-replica serving router (`paddle_tpu/serving/router`).

The ISSUE 17 acceptance spine, mirrored on test_serving.py's identity
discipline:

- **Token identity + compile-free scale-out** — a 3-replica router's
  greedy output is byte-identical to the single-engine
  :class:`ServingEngine` and to per-request ``generate()``, with
  process-wide exec-cache fresh compiles == 3 (replicas 2..N ride the
  warm cache) and zero retraces across a second wave.
- **Affinity wins** — on a shared-prefix trace, prefix-affinity
  dispatch pays strictly fewer total prefill chunks than affinity-off
  (least-loaded) routing, without touching a single emitted token.
- **Failure drain** — a replica whose ``step()`` raises mid-trace is
  marked dead; every request finishes on survivors with tokens
  identical to the no-failure run, and the blackbox artifact names the
  dead replica.
- **Determinism** — dispatch is in PTL005's scope: the same trace
  replays to byte-identical routing decisions.
- **Worker mode** — the process-per-replica deployment shape behind
  the same class produces the same tokens through the JSON-line pipe
  protocol.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM, generate
from paddle_tpu.serving import (
    RouterConfig, RouterEngine, ServingConfig, ServingEngine,
)

GEOM = dict(max_lanes=3, block_size=4, prefill_chunk=8, max_seq_len=32)


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
    m.eval()
    return m


def _reference(model, prompt, new):
    return generate(model, pt.to_tensor(np.asarray(prompt)[None, :]),
                    max_new_tokens=new).numpy()[0]


def _mixed_workload(model, rng, n):
    out = []
    for _ in range(n):
        plen, new = int(rng.randint(3, 13)), int(rng.randint(4, 10))
        prompt = rng.randint(0, model.config.vocab_size,
                             (plen,)).astype(np.int32)
        out.append((prompt, new))
    return out


def _shared_prefix_workload(model, rng, n, prefix_len=8):
    prefix = rng.randint(0, model.config.vocab_size,
                         (prefix_len,)).astype(np.int32)
    out = []
    for _ in range(n):
        suffix = rng.randint(
            0, model.config.vocab_size,
            (int(rng.randint(1, 6)),)).astype(np.int32)
        out.append((np.concatenate([prefix, suffix]),
                    int(rng.randint(4, 10))))
    return out


def _run(engine, work):
    for i, (p, n) in enumerate(work):
        engine.submit(p, max_new_tokens=n, request_id=f"r{i}")
    return engine.run()


# -- config -------------------------------------------------------------------

class TestRouterConfig:
    def test_env_defaults(self, monkeypatch):
        monkeypatch.setenv("PT_SERVE_REPLICAS", "5")
        monkeypatch.setenv("PT_SERVE_AFFINITY", "0")
        rc = RouterConfig()
        assert rc.replicas == 5 and rc.affinity is False
        assert rc.mode == "inproc"

    def test_explicit_beats_env(self, monkeypatch):
        monkeypatch.setenv("PT_SERVE_REPLICAS", "5")
        assert RouterConfig(replicas=2).replicas == 2
        with pytest.raises(ValueError):
            RouterConfig(replicas=0)
        with pytest.raises(ValueError):
            RouterConfig(mode="bogus")

    def test_worker_mode_needs_factory(self):
        with pytest.raises(ValueError, match="factory"):
            RouterConfig(mode="worker")

    def test_inproc_needs_model(self):
        with pytest.raises(ValueError, match="model"):
            RouterEngine(config=GEOM,
                         router_config=RouterConfig(replicas=2))


# -- the acceptance spine -----------------------------------------------------

def test_router_token_identical_and_three_compiles(model, tmp_path):
    """THE tentpole proof: a 3-replica router is byte-identical to the
    single engine and to generate(), and the whole fleet compiles 3
    programs TOTAL — replica 1 pays prefill+decode+verify, replicas
    2..3 ride the warm exec cache. A second wave adds zero compiles
    (no retraces)."""
    from paddle_tpu.jit import exec_cache as ec

    rng = np.random.RandomState(0)
    work = _mixed_workload(model, rng, 9)
    assert len({p.size for p, _ in work}) > 1, "prompts all equal"
    ec.enable(str(tmp_path))
    ec.clear()
    try:
        router = RouterEngine(
            model, ServingConfig(**GEOM),
            RouterConfig(replicas=3, mode="inproc"))
        router.warmup()
        misses = ec.stats()["misses"]
        assert misses == 3, \
            f"3 replicas must share 3 compiled programs: {ec.stats()}"
        routed = _run(router, work)

        single = ServingEngine(model, ServingConfig(**GEOM))
        base = _run(single, work)
        assert set(routed) == set(base)
        for i, (p, n) in enumerate(work):
            ref = _reference(model, p, n)
            np.testing.assert_array_equal(
                routed[f"r{i}"], ref,
                err_msg=f"routed r{i} diverged from generate()")
            np.testing.assert_array_equal(routed[f"r{i}"], base[f"r{i}"])
        # second wave through the same router: still zero fresh compiles
        r2 = router.submit(work[0][0], max_new_tokens=5, request_id="w2")
        outs2 = router.run()
        np.testing.assert_array_equal(
            outs2["w2"], _reference(model, work[0][0], 5))
        assert ec.stats()["misses"] == 3, "router retraced!"
        assert r2.request_id == "w2"
        assert router.counters["dispatches"] == 10
        assert router.counters["finished"] == 10
    finally:
        ec.disable()
        ec.clear()


def test_router_affinity_beats_affinity_off(model):
    """On a shared-prefix trace, affinity-on funnels same-opening
    requests to the replica that already published their blocks —
    strictly fewer total prefill chunks than least-loaded spreading,
    same tokens byte for byte."""
    work = _shared_prefix_workload(model, np.random.RandomState(7), 9)
    results, chunks, stats = {}, {}, {}
    for label, aff in (("on", True), ("off", False)):
        router = RouterEngine(
            model, ServingConfig(**GEOM),
            RouterConfig(replicas=3, affinity=aff, mode="inproc"))
        results[label] = _run(router, work)
        s = router.stats()
        chunks[label] = s["prefill_chunks"]
        stats[label] = s
    assert chunks["on"] < chunks["off"], chunks
    assert stats["on"]["affinity_hit_rate"] > 0
    assert stats["off"]["affinity_hit_rate"] == 0
    # least-loaded actually spread the load (the A/B is not vacuous)
    spread_off = [c for c in stats["off"]["dispatches_per_replica"] if c]
    assert len(spread_off) == 3, stats["off"]
    for i in range(len(work)):
        ref = _reference(model, *work[i])
        np.testing.assert_array_equal(results["on"][f"r{i}"], ref)
        np.testing.assert_array_equal(results["off"][f"r{i}"], ref)


def test_router_replica_death_drains_to_survivors(model, tmp_path,
                                                  monkeypatch):
    """Kill a replica mid-trace (injected step() raise): every request
    — queued and in-flight on the dead replica — finishes on survivors
    with tokens identical to the no-failure run, and the blackbox
    artifact names the dead replica."""
    bb = tmp_path / "router_blackbox.json"
    monkeypatch.setenv("PT_SERVE_BLACKBOX", str(bb))
    work = _shared_prefix_workload(model, np.random.RandomState(3), 9)

    single = ServingEngine(model, ServingConfig(**GEOM))
    base = _run(single, work)

    router = RouterEngine(
        model, ServingConfig(**GEOM),
        RouterConfig(replicas=3, mode="inproc"))
    for i, (p, n) in enumerate(work):
        router.submit(p, max_new_tokens=n, request_id=f"r{i}")
    # a couple of healthy rounds so the affinity target is mid-flight
    router.step()
    router.step()

    def boom():
        raise RuntimeError("injected replica failure")

    monkeypatch.setattr(router._replicas[0]._engine, "step", boom)
    outs = router.run()
    assert set(outs) == set(base)
    for i in range(len(work)):
        np.testing.assert_array_equal(
            outs[f"r{i}"], base[f"r{i}"],
            err_msg=f"r{i} diverged after the drain")
    assert router.counters["dead_replicas"] == 1
    assert router.counters["redispatches"] > 0
    assert 0 in router._dead
    # survivors only from here on: replica 0 never dispatched again
    n_before = router.dispatch_counts[0]
    router.submit(work[0][0], max_new_tokens=4, request_id="after")
    router.run()
    assert router.dispatch_counts[0] == n_before
    # the postmortem artifact names the dead replica
    art = json.loads(bb.read_text())
    state = art["state"]["serving_router"]
    assert state["dead"] == {"0": "RuntimeError: injected replica "
                                  "failure"}
    assert state["replicas"][0]["dead"] is True
    assert state["replicas"][1]["dead"] is False
    assert art["reason"] == "router_replica_dead"


def test_router_all_dead_raises(model, monkeypatch):
    router = RouterEngine(
        model, ServingConfig(**GEOM),
        RouterConfig(replicas=2, mode="inproc"))
    router.submit([1, 2, 3], max_new_tokens=4, request_id="a")

    def boom():
        raise RuntimeError("down")

    monkeypatch.setattr(router._replicas[0]._engine, "step", boom)
    monkeypatch.setattr(router._replicas[1]._engine, "step", boom)
    with pytest.raises(RuntimeError, match="all 2 router replicas"):
        router.run()


def test_router_duplicate_request_id(model):
    router = RouterEngine(
        model, ServingConfig(**GEOM),
        RouterConfig(replicas=2, mode="inproc"))
    router.submit([1, 2, 3], max_new_tokens=4, request_id="dup")
    with pytest.raises(ValueError, match="duplicate"):
        router.submit([4, 5], max_new_tokens=4, request_id="dup")


def test_router_deterministic_dispatch(model):
    """PTL005's scope in action: the same submission trace routes
    byte-identically on a fresh router — per-replica dispatch counts
    and the full counter dict replay exactly."""
    work = _shared_prefix_workload(model, np.random.RandomState(11), 8)
    seen = []
    for _ in range(2):
        router = RouterEngine(
            model, ServingConfig(**GEOM),
            RouterConfig(replicas=3, mode="inproc"))
        _run(router, work)
        seen.append((list(router.dispatch_counts),
                     dict(router.counters)))
    assert seen[0] == seen[1]


# -- monitor contract ---------------------------------------------------------

def test_router_monitor_counters(model):
    assert "paddle_tpu.serving.router" in monitor.INSTRUMENTED_MODULES
    work = _shared_prefix_workload(model, np.random.RandomState(5), 6)
    was = monitor.enabled()
    monitor.enable()
    try:
        monitor.reset()
        router = RouterEngine(
            model, ServingConfig(**GEOM),
            RouterConfig(replicas=3, mode="inproc"))
        _run(router, work)
        snap = monitor.snapshot()["counters"]
        assert snap["router/dispatches"] == 6
        assert snap["router/affinity_hits"] \
            + snap["router/affinity_misses"] == 6
        assert snap["router/affinity_hits"] > 0
        assert snap.get("router/dispatches/0", 0) > 0
        assert snap.get("router/dead_replicas", 0) == 0
    finally:
        monitor.reset()
        if not was:
            monitor.disable()


def test_router_monitor_dead_counter(model, monkeypatch):
    was = monitor.enabled()
    monitor.enable()
    try:
        monitor.reset()
        router = RouterEngine(
            model, ServingConfig(**GEOM),
            RouterConfig(replicas=2, mode="inproc"))
        router.submit([1, 2, 3, 4, 5], max_new_tokens=4,
                      request_id="x")

        def boom():
            raise RuntimeError("down")

        monkeypatch.setattr(router._replicas[0]._engine, "step", boom)
        monkeypatch.setattr(router._replicas[1]._engine, "step", boom)
        with pytest.raises(RuntimeError):
            router.run()
        snap = monitor.snapshot()["counters"]
        assert snap["router/dead_replicas"] >= 1
        assert snap["router/redispatches"] >= 1
    finally:
        monitor.reset()
        if not was:
            monitor.disable()


# -- worker mode --------------------------------------------------------------

def test_router_worker_mode_token_identity(model, tmp_path):
    """The process-per-replica deployment shape: two subprocess workers
    behind the same RouterEngine class produce the same tokens as the
    in-process single engine, over the JSON-line pipe protocol."""
    factory = tmp_path / "rw_factory.py"
    factory.write_text(
        "import jax\n"
        # tests force CPU: the worker pins it in-process, whatever
        # environment the router hands it
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import paddle_tpu as pt\n"
        "from paddle_tpu.models.llama import LlamaConfig, "
        "LlamaForCausalLM\n"
        "def build():\n"
        "    pt.seed(0)\n"
        "    m = LlamaForCausalLM(LlamaConfig.tiny("
        "num_hidden_layers=2))\n"
        "    m.eval()\n"
        "    return m\n")
    old_pp = os.environ.get("PYTHONPATH")
    os.environ["PYTHONPATH"] = str(tmp_path) + os.pathsep \
        + (old_pp or "")
    work = _mixed_workload(model, np.random.RandomState(2), 4)
    single = ServingEngine(model, ServingConfig(**GEOM))
    base = _run(single, work)
    router = RouterEngine(
        config=GEOM,
        router_config=RouterConfig(replicas=2, mode="worker",
                                   worker_factory="rw_factory:build"))
    try:
        outs = _run(router, work)
        assert set(outs) == set(base)
        for i in range(len(work)):
            np.testing.assert_array_equal(outs[f"r{i}"], base[f"r{i}"])
        assert sum(router.dispatch_counts) == len(work)
        assert router.stats()["decoded_tokens"] > 0
    finally:
        router.close()
        if old_pp is None:
            os.environ.pop("PYTHONPATH", None)
        else:
            os.environ["PYTHONPATH"] = old_pp
