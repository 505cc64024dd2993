"""Continuous-batching serving runtime (`paddle_tpu/serving`).

Three layers, mirroring the subsystem's own split:

- **BlockPool safety** — the double-free/alias bug class a paged KV
  cache dies of is unrepresentable: every misuse raises, and the
  free+used==capacity identity holds through churn.
- **Scheduler policy properties** — pure-host simulation of the
  engine's scheduling round over seeded traces: byte-identical replay,
  termination (no starvation: preemption victims are always the NEWEST
  runner, so the oldest request always progresses), preempted requests
  keep their tokens and their blocks return to the pool.
- **Tier-1 CPU end-to-end** — the acceptance proof: ≥8 requests with
  unequal prompt/output lengths through :class:`ServingEngine` are
  token-identical to per-request ``generate()`` calls, with the decode
  step compiled exactly ONCE (exec-cache counters show no per-request
  retraces), plus the serving bench's one-JSON-line contract.
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
    FINISHED, RUNNING, WAITING, BlockPool, FCFSScheduler, Request,
    ServingConfig, ServingEngine, blocks_needed, prefix_keys,
)
from paddle_tpu.serving.engine import PREFILL_CHUNK, default_prefill_chunk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_by_path(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- block pool ---------------------------------------------------------------

class TestBlockPool:
    def test_blocks_needed(self):
        assert blocks_needed(0, 4) == 0
        assert blocks_needed(1, 4) == 1
        assert blocks_needed(4, 4) == 1
        assert blocks_needed(5, 4) == 2

    def test_null_block_reserved(self):
        pool = BlockPool(4, 2)
        got = pool.alloc(3, "a")
        assert got is not None and 0 not in got
        assert pool.alloc(1, "b") is None  # capacity is num_blocks - 1
        with pytest.raises(ValueError):
            BlockPool(1, 2)  # no room for the null block
        with pytest.raises(ValueError):
            BlockPool(4, 0)

    def test_double_free_raises(self):
        pool = BlockPool(8, 2)
        blocks = pool.alloc(2, "req")
        pool.free(blocks, "req")
        with pytest.raises(ValueError, match="double-free|not allocated"):
            pool.free(blocks, "req")
        pool.check_invariant()

    def test_cross_owner_free_raises(self):
        pool = BlockPool(8, 2)
        a = pool.alloc(2, "a")
        pool.alloc(2, "b")
        with pytest.raises(ValueError, match="owned by"):
            pool.free(a, "b")
        # the failed free must not have leaked anything
        pool.check_invariant()
        assert pool.used_count == 4

    def test_never_allocated_free_raises(self):
        pool = BlockPool(8, 2)
        with pytest.raises(ValueError):
            pool.free([3], "ghost")

    def test_lifo_reuse_and_accounting(self):
        pool = BlockPool(8, 2)
        a = pool.alloc(3, "a")
        pool.free(a, "a")
        b = pool.alloc(3, "b")
        assert b == a[::-1]  # LIFO: just-freed blocks hand out first
        assert pool.free_count + pool.used_count == pool.capacity
        pool.check_invariant()


# -- block pool: ref-counted prefix sharing -----------------------------------

def _publish_ctx(pool, tokens, blocks, owner):
    """Index ``owner``'s full context blocks under their chain keys —
    the scheduler's publish_prefix in miniature."""
    for i, key in enumerate(prefix_keys(tokens, pool.block_size)):
        pool.publish(key, blocks[i], owner)


class TestBlockPoolSharing:
    def test_prefix_keys_chain(self):
        # keys name the WHOLE context through their block: equal heads
        # share, a changed early token changes every later key too
        k1 = prefix_keys([1, 2, 3, 4, 5, 6], 2)
        k2 = prefix_keys([1, 2, 3, 4, 9, 9], 2)
        k3 = prefix_keys([9, 2, 3, 4, 5, 6], 2)
        assert len(k1) == 3
        assert k1[:2] == k2[:2] and k1[2] != k2[2]
        assert all(a != b for a, b in zip(k1, k3))
        # limit_tokens caps the keyed span to full blocks below it
        assert prefix_keys([1, 2, 3, 4], 2, limit_tokens=3) == k1[:1]
        assert prefix_keys([1], 2) == []

    def test_publish_lookup_acquire_roundtrip(self):
        pool = BlockPool(8, 2)
        toks = [1, 2, 3, 4, 5]  # 2 full blocks + 1 partial
        a_blocks = pool.alloc(3, "a")
        _publish_ctx(pool, toks, a_blocks, "a")
        keys = prefix_keys(toks, 2)
        assert pool.lookup(keys) == a_blocks[:2]
        # a different continuation matches only the shared head
        assert pool.lookup(prefix_keys([1, 2, 9, 9], 2)) == a_blocks[:1]
        pool.acquire(a_blocks[:2], "b")
        assert pool.refcount(a_blocks[0]) == 2
        assert pool.shared_count == 2
        pool.check_invariant()
        # both holders release; indexed blocks park cold, partial frees
        pool.free(a_blocks, "a")
        pool.free(a_blocks[:2], "b")
        assert pool.used_count == 0
        assert pool.cold_count == 2
        assert pool.free_count + pool.used_count + pool.cold_count \
            == pool.capacity
        pool.check_invariant()

    def test_shared_double_free_and_no_reference_raise(self):
        pool = BlockPool(8, 2)
        blocks = pool.alloc(2, "a")
        _publish_ctx(pool, [1, 2, 3, 4], blocks, "a")
        pool.acquire(blocks, "b")
        # "c" holds no reference: the cross-owner raise survives sharing
        with pytest.raises(ValueError, match="owned by"):
            pool.free(blocks, "c")
        pool.free(blocks, "a")
        # a's reference is spent — freeing again is a double-free even
        # though b still holds the (live, shared) blocks
        with pytest.raises(ValueError, match="owned by"):
            pool.free(blocks, "a")
        pool.free(blocks, "b")
        with pytest.raises(ValueError, match="not allocated|owned by"):
            pool.free(blocks, "b")
        pool.check_invariant()

    def test_accounting_with_live_shared_blocks(self):
        pool = BlockPool(10, 2)
        shared = pool.alloc(3, "a")
        _publish_ctx(pool, [1, 2, 3, 4, 5, 6], shared, "a")
        pool.acquire(shared, "b")
        pool.acquire(shared, "c")
        private = pool.alloc(2, "d")
        # a shared block counts ONCE however many holders it has
        assert pool.used_count == 5
        assert pool.free_count == pool.capacity - 5
        assert pool.refcount(shared[0]) == 3
        pool.check_invariant()
        pool.free(shared, "b")
        assert pool.used_count == 5  # still referenced by a and c
        pool.free(shared, "a")
        pool.free(shared, "c")
        assert pool.used_count == 2 and pool.cold_count == 3
        pool.free(private, "d")
        assert pool.used_count == 0
        pool.check_invariant()

    def test_cold_lru_reclaim_order_and_index_eviction(self):
        pool = BlockPool(6, 2)  # capacity 5
        a = pool.alloc(2, "a")
        b = pool.alloc(2, "b")
        _publish_ctx(pool, [1, 2, 3, 4], a, "a")
        _publish_ctx(pool, [7, 8, 9, 10], b, "b")
        pool.free(a, "a")   # cold, oldest
        pool.free(b, "b")   # cold, newest
        assert pool.cold_count == 4 and pool.free_count == 1
        # free list (1 block) serves first; then cold reclaims in
        # release order — a's blocks go before b's
        got = pool.alloc(3, "c")
        assert got[1:] == a
        assert pool.cold_count == 2
        # a's index entries are gone, b's survive
        assert pool.lookup(prefix_keys([1, 2, 3, 4], 2)) == []
        assert pool.lookup(prefix_keys([7, 8, 9, 10], 2)) == b
        pool.check_invariant()

    def test_pressure_never_reclaims_referenced_blocks(self):
        pool = BlockPool(6, 2)  # capacity 5
        shared = pool.alloc(2, "a")
        _publish_ctx(pool, [1, 2, 3, 4], shared, "a")
        pool.acquire(shared, "b")
        pool.free(shared, "a")  # b still holds both — NOT cold
        assert pool.cold_count == 0
        held = pool.alloc(3, "c")
        assert held is not None
        # pool is now fully referenced: alloc must refuse, not steal
        assert pool.alloc(1, "d") is None
        assert pool.lookup(prefix_keys([1, 2, 3, 4], 2)) == shared
        assert pool.refcount(shared[0]) == 1
        pool.check_invariant()
        pool.free(held, "c")
        pool.free(shared, "b")

    def test_acquire_revives_cold_and_rejects_stale(self):
        pool = BlockPool(6, 2)
        a = pool.alloc(2, "a")
        _publish_ctx(pool, [1, 2, 3, 4], a, "a")
        pool.free(a, "a")
        hits = pool.lookup(prefix_keys([1, 2, 3, 4], 2))
        pool.acquire(hits, "b")  # revive off the cold LRU
        assert pool.cold_count == 0 and pool.refcount(hits[0]) == 1
        # double-acquire by the same owner is a table bug upstream
        with pytest.raises(ValueError, match="already held"):
            pool.acquire(hits, "b")
        pool.free(hits, "b")
        # reclaim everything (the blocks are re-issued to "hog");
        # acquiring the stale lookup result must raise, not alias
        pool.alloc(pool.capacity, "hog")
        with pytest.raises(ValueError, match="acquire must follow"):
            pool.acquire(hits, "c")
        pool.check_invariant()

    def test_publish_validations(self):
        pool = BlockPool(8, 2)
        a = pool.alloc(2, "a")
        b = pool.alloc(2, "b")
        keys = prefix_keys([1, 2, 3, 4], 2)
        with pytest.raises(ValueError, match="not held"):
            pool.publish(keys[0], a[0], "b")
        assert pool.publish(keys[0], a[0], "a")
        # first publisher wins: b's same-content copy stays private
        assert not pool.publish(keys[0], b[0], "b")
        assert pool.lookup(keys[:1]) == [a[0]]
        # re-publishing the indexed block is a no-op
        assert pool.publish(keys[0], a[0], "a")
        # one block, two different content keys = immutability broken
        with pytest.raises(ValueError, match="different key"):
            pool.publish(keys[1], a[0], "a")
        pool.check_invariant()


# -- scheduler policy (pure host — no jax) ------------------------------------

def _sim_emit(sched, req, tok):
    """Engine's _emit without the device: append, finish when done."""
    req.output.append(tok)
    if len(req.output) >= req.max_new_tokens:
        sched.finish(req)


def _sim_round(sched, preempt_victims=None):
    """One ServingEngine.step in pure host logic: admit + fake-prefill
    (first token emitted unless the request is a recompute re-admission),
    growth walk in FCFS order with preemption, one decode emit per
    surviving lane. Token values are just output positions — the replay
    comparison rides on the scheduler's event log, not token content."""
    def on_preempt(victim):
        # no-starvation witness: at preemption time every still-running
        # request is OLDER (smaller admit seq) than the victim
        assert all(r._admit_seq <= victim._admit_seq
                   for r in sched.running())
        if preempt_victims is not None:
            preempt_victims.append(victim.request_id)

    for req in sched.admit():
        req.pool_len = len(req.prefill_tokens)
        if not req.output:
            _sim_emit(sched, req, 0)
    for req in sched.running():
        if req.state == RUNNING:
            sched.ensure_capacity(req, on_preempt=on_preempt)
    act = sched.running()
    for req in act:
        req.pool_len += 1
        _sim_emit(sched, req, len(req.output))
    sched.pool.check_invariant()
    return bool(act)


def _make_sched(num_blocks=9, block_size=2, max_lanes=3, max_seq_len=16):
    return FCFSScheduler(BlockPool(num_blocks, block_size), max_lanes,
                         blocks_needed(max_seq_len, block_size),
                         max_seq_len)


def _trace_requests(n, seed, max_seq_len=16):
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.randint(1, max_seq_len // 2))
        new = int(rng.randint(1, max_seq_len - plen + 1))
        reqs.append(Request(rng.randint(0, 100, (plen,)),
                            max_new_tokens=new, request_id=i))
    return reqs


def _replay(seed, n=12, **geom):
    sched = _make_sched(**geom)
    victims = []
    reqs = _trace_requests(n, seed,
                           max_seq_len=geom.get("max_seq_len", 16))
    for r in reqs:
        sched.submit(r)
    rounds = 0
    while sched.has_work():
        _sim_round(sched, victims)
        rounds += 1
        assert rounds < 10_000, "scheduler livelocked"
    return sched, reqs, victims


class TestScheduler:
    def test_deterministic_replay(self):
        # same seeded trace, two fresh schedulers: the event logs (every
        # admit/preempt/finish decision) must match byte for byte
        s1, _, v1 = _replay(seed=7)
        s2, _, v2 = _replay(seed=7)
        assert s1.events == s2.events
        assert v1 == v2

    def test_all_finish_under_pressure(self):
        # pool far too small for the offered load: preemption churn must
        # still drain every request (no starvation, no livelock)
        # capacity 8 = one max-size request; 3 lanes contend for it
        sched, reqs, victims = _replay(seed=3, n=16, num_blocks=9)
        assert victims, "pressure config never preempted — test is vacuous"
        assert all(r.state == FINISHED for r in reqs)
        assert all(len(r.output) == r.max_new_tokens for r in reqs)
        # everything returned: pool empty, lanes empty
        assert sched.pool.used_count == 0
        assert sched.lanes_occupied == 0

    def test_preempted_request_keeps_tokens(self):
        # each request needs 5 blocks total; capacity 5 forces the two
        # lanes to fight over growth
        sched = _make_sched(num_blocks=6, block_size=2, max_lanes=2,
                            max_seq_len=10)
        a = sched.submit(Request([1, 2], max_new_tokens=8, request_id="a"))
        b = sched.submit(Request([3, 4], max_new_tokens=8, request_id="b"))
        victims = []
        while sched.has_work():
            _sim_round(sched, victims)
        assert "b" in victims and "a" not in victims  # newest loses
        assert b.preemptions >= 1
        assert len(b.output) == 8
        # recompute contract: prefill_tokens replays prompt + kept output
        assert a.state == FINISHED and b.state == FINISHED

    def test_finished_lane_reclaimed_for_waiting(self):
        sched = _make_sched(max_lanes=1)
        a = sched.submit(Request([1], max_new_tokens=2, request_id="a"))
        b = sched.submit(Request([2], max_new_tokens=2, request_id="b"))
        _sim_round(sched)
        # the single lane serves a to completion before b ever runs
        assert a.state == FINISHED and b.state != RUNNING
        while sched.has_work():
            _sim_round(sched)
        order = [e for e in sched.events if e[0] in ("admit", "finish")]
        assert order == [("admit", "a", 0), ("finish", "a", None),
                         ("admit", "b", 0), ("finish", "b", None)]

    def test_submit_validates_at_the_door(self):
        sched = _make_sched(max_seq_len=16)
        with pytest.raises(ValueError, match="max_seq_len"):
            sched.submit(Request([0] * 10, max_new_tokens=10))
        small = FCFSScheduler(BlockPool(3, 2), 2, 2, 16)
        with pytest.raises(ValueError, match="KV blocks"):
            small.submit(Request([0] * 5, max_new_tokens=1))
        with pytest.raises(ValueError):
            Request([], max_new_tokens=1)
        with pytest.raises(ValueError):
            Request([1], max_new_tokens=0)

    def test_events_ring_is_bounded(self):
        # long-running servers must not grow with request history
        sched = FCFSScheduler(BlockPool(9, 2), 3, 8, 16, events_cap=8)
        for i in range(20):
            sched.submit(Request([1], max_new_tokens=1, request_id=i))
        while sched.has_work():
            _sim_round(sched)
        assert len(sched.events) == 8
        assert sched.events[-1][0] == "finish"

    def test_prefill_tokens_excludes_pending(self):
        r = Request([1, 2, 3], max_new_tokens=4)
        np.testing.assert_array_equal(r.prefill_tokens, [1, 2, 3])
        r.output = [10, 11]
        np.testing.assert_array_equal(r.prefill_tokens, [1, 2, 3, 10])


# -- scheduler + prefix cache (pure host) -------------------------------------

def _sim_round_sharing(sched, victims=None):
    """_sim_round with the engine's publish step AND its one-lane-at-a-
    time admission: each fake prefill publishes before the next
    admission's lookup, so same-round burst arrivals (and recompute
    re-admissions) share."""
    while True:
        batch = sched.admit(limit=1)
        if not batch:
            break
        req = batch[0]
        req.pool_len = len(req.prefill_tokens)
        sched.publish_prefix(req)
        if not req.output:
            _sim_emit(sched, req, 0)
    for req in sched.running():
        if req.state == RUNNING:
            sched.ensure_capacity(req, on_preempt=(
                victims.append if victims is not None else None))
    act = sched.running()
    for req in act:
        req.pool_len += 1
        _sim_emit(sched, req, len(req.output))
    sched.pool.check_invariant()
    return bool(act)


def _shared_prefix_requests(n, seed, prefix_len=4, max_seq_len=16):
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, 100, (prefix_len,))
    reqs = []
    for i in range(n):
        plen = int(rng.randint(1, max_seq_len // 2 - prefix_len))
        new = int(rng.randint(1, max_seq_len - prefix_len - plen + 1))
        prompt = np.concatenate([prefix, rng.randint(0, 100, (plen,))])
        reqs.append(Request(prompt, max_new_tokens=new, request_id=i))
    return reqs


def _replay_sharing(seed, n=12, prefix_len=4, **geom):
    sched = _make_sched(**geom)
    reqs = _shared_prefix_requests(
        n, seed, prefix_len=prefix_len,
        max_seq_len=geom.get("max_seq_len", 16))
    for r in reqs:
        sched.submit(r)
    rounds = 0
    while sched.has_work():
        _sim_round_sharing(sched)
        rounds += 1
        assert rounds < 10_000, "scheduler livelocked"
    return sched, reqs


class TestSchedulerPrefixCache:
    def test_sharing_engages_and_replays_deterministically(self):
        s1, r1 = _replay_sharing(seed=11)
        s2, _ = _replay_sharing(seed=11)
        hits = [e for e in s1.events if e[0] == "prefix_hit"]
        assert hits, "shared-prefix trace never hit the cache"
        # the full decision log — admits, prefix hits, preemptions,
        # finishes — replays byte-identically (blake2b keys, no hash())
        assert list(s1.events) == list(s2.events)
        assert all(r.state == FINISHED for r in r1)
        assert s1.pool.used_count == 0
        assert s1.pool.cold_count > 0  # released prefixes parked, not freed

    def test_sharing_under_pressure_drains_and_accounts(self):
        # pool far too small for the offered load: preemption + cold-LRU
        # reclaim churn must still drain every request with the
        # free+used+cold identity intact (checked every round)
        sched, reqs = _replay_sharing(seed=3, n=16, num_blocks=9)
        assert any(r.preemptions for r in reqs), \
            "pressure config never preempted — test is vacuous"
        assert all(r.state == FINISHED for r in reqs)
        assert all(len(r.output) == r.max_new_tokens for r in reqs)
        assert sched.pool.used_count == 0
        assert sched.lanes_occupied == 0

    def test_prefix_cache_off_restores_share_nothing_pool(self):
        sched = _make_sched()
        sched.prefix_cache = False
        reqs = _shared_prefix_requests(6, seed=5)
        for r in reqs:
            sched.submit(r)
        while sched.has_work():
            _sim_round_sharing(sched)
        assert not any(e[0] == "prefix_hit" for e in sched.events)
        assert sched.pool.cold_count == 0
        assert sched.pool.indexed_count == 0
        assert all(r.prefix_cached_tokens == 0 for r in reqs)

    def test_ttft_grouping_key_is_first_admission_only(self):
        # a cold-admitted request later re-admitted through the cache
        # keeps ttft_cached_tokens == 0: the bench's cached-vs-cold
        # TTFT A/B must group by the prefill that set t_first
        sched = _make_sched(num_blocks=9, block_size=2, max_lanes=2,
                            max_seq_len=12)
        a = sched.submit(Request([1, 2, 3, 4], max_new_tokens=6,
                                 request_id="a"))
        b = sched.submit(Request([1, 2, 3, 4], max_new_tokens=6,
                                 request_id="b"))
        _sim_round_sharing(sched)
        assert a.ttft_cached_tokens == 0  # first publisher: cold
        assert b.ttft_cached_tokens > 0   # same-trace follower: cached
        while sched.has_work():
            _sim_round_sharing(sched)
        if a.preemptions or b.preemptions:
            # recompute credit accrues to the lifetime counter only
            assert a.ttft_cached_tokens == 0
        assert b.prefix_cached_tokens >= b.ttft_cached_tokens

    def test_admit_failure_returns_hits_to_cold(self):
        # geometry: block 2, lane table 8 blocks, capacity 8
        sched = _make_sched(num_blocks=9, block_size=2, max_lanes=3)
        a = sched.submit(Request([1, 2, 3, 4, 5], max_new_tokens=3,
                                 request_id="a"))
        while not a.finished:  # a publishes [1,2] / [3,4], then frees
            _sim_round_sharing(sched)
        # hog the pool so the next admit's PRIVATE alloc fails after its
        # prefix hits were acquired
        hog = sched.submit(Request([9] * 9, max_new_tokens=4,
                                   request_id="hog"))
        sched.admit()
        assert hog.state == RUNNING
        b = sched.submit(Request([1, 2, 3, 4, 9, 9, 9, 9, 9, 9, 9],
                                 max_new_tokens=3, request_id="b"))
        sched.admit()
        sched.pool.check_invariant()
        assert b.state == WAITING  # 2 hits acquired, private alloc failed
        assert b.blocks == []  # ...and the hits were fully released
        # the matched prefix is back on the cold LRU, still indexed
        assert sched.pool.lookup(prefix_keys([1, 2, 3, 4], 2)) != []
        while sched.has_work():
            _sim_round_sharing(sched)
        assert b.state == FINISHED
        sched.pool.check_invariant()


# -- config / knobs -----------------------------------------------------------

class TestServingConfig:
    def test_env_knobs(self, monkeypatch):
        monkeypatch.setenv("PT_SERVE_LANES", "5")
        monkeypatch.setenv("PT_SERVE_BLOCK", "8")
        monkeypatch.setenv("PT_SERVE_BLOCKS", "33")
        monkeypatch.setenv("PT_SERVE_PREFILL_CHUNK", "16")
        monkeypatch.setenv("PT_SERVE_MAX_LEN", "64")
        monkeypatch.setenv("PT_DECODE_INT8", "1")
        cfg = ServingConfig()
        assert (cfg.max_lanes, cfg.block_size, cfg.num_blocks,
                cfg.prefill_chunk, cfg.max_seq_len,
                cfg.int8_weights) == (5, 8, 33, 16, 64, True)
        assert cfg.prefix_cache is True  # auto on
        monkeypatch.setenv("PT_SERVE_PREFIX_CACHE", "0")
        assert ServingConfig().prefix_cache is False
        assert ServingConfig(prefix_cache=True).prefix_cache is True

    @pytest.mark.parametrize("max_seq_len,block,want", [
        (4096, 16, PREFILL_CHUNK),     # the chip-chosen width as it is
        (160, 16, 128), (100, 16, 96),  # whole blocks under max_seq_len
        (48, 4, 48), (20, 2, 20), (33, 4, 32),
        (10, 16, 16),                  # at least one block
        (4096, 48, 96), (4096, 256, 256),
    ])
    def test_default_chunk_is_fitted_to_the_geometry(
            self, monkeypatch, max_seq_len, block, want):
        """Unset, the prefill width is the engine's: PREFILL_CHUNK in
        whole blocks, never past ``max_seq_len`` rounded down to whole
        blocks. ``PT_SERVE_PREFILL_CHUNK`` and the argument still
        override it, taken as given (wider than the table too)."""
        monkeypatch.delenv("PT_SERVE_PREFILL_CHUNK", raising=False)
        assert ServingConfig().prefill_chunk is None
        assert default_prefill_chunk(max_seq_len, block) == want
        assert want % block == 0
        assert want <= max(max_seq_len // block, 1) * block
        monkeypatch.setenv("PT_SERVE_PREFILL_CHUNK", "32")
        assert ServingConfig().prefill_chunk == 32
        assert ServingConfig(prefill_chunk=200).prefill_chunk == 200

    def test_engine_resolves_the_default_chunk(self, monkeypatch):
        monkeypatch.delenv("PT_SERVE_PREFILL_CHUNK", raising=False)
        pt.seed(0)
        m = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
        m.eval()
        geom = dict(max_lanes=2, block_size=4, max_seq_len=50)
        eng = ServingEngine(m, ServingConfig(**geom))
        assert eng.prefill_chunk == eng.stats()["prefill_chunk"] == 48
        monkeypatch.setenv("PT_SERVE_PREFILL_CHUNK", "6")
        assert ServingEngine(m, ServingConfig(**geom)).prefill_chunk == 6
        assert ServingEngine(m, ServingConfig(
            prefill_chunk=70, **geom)).prefill_chunk == 70
        with pytest.raises(ValueError):
            ServingConfig(prefill_chunk=0)

    def test_explicit_beats_env(self, monkeypatch):
        monkeypatch.setenv("PT_SERVE_LANES", "5")
        assert ServingConfig(max_lanes=2).max_lanes == 2
        with pytest.raises(ValueError):
            ServingConfig(max_lanes=0)

    def test_monitor_audit_membership(self):
        # the None-slot zero-overhead-off audit in test_memory_numerics
        # parametrizes over this list — membership is the contract
        assert "paddle_tpu.serving.engine" in monitor.INSTRUMENTED_MODULES
        # the scheduler's _spans slot (queue-wait/preempt trace spans)
        # joined the same contract in ISSUE 16
        assert "paddle_tpu.serving.scheduler" in monitor.INSTRUMENTED_MODULES


# -- bench trace / probe helpers (pure) ---------------------------------------

class TestBenchHelpers:
    def test_trace_is_seeded_and_sorted(self):
        sb = _load_by_path("serving_bench_t", "benchmarks/serving_bench.py")
        t1 = sb.build_trace(16, 4.0, 100, (3, 12), (4, 12), seed=5)
        t2 = sb.build_trace(16, 4.0, 100, (3, 12), (4, 12), seed=5)
        assert len(t1) == 16
        assert [a for a, _, _ in t1] == sorted(a for a, _, _ in t1)
        for (a1, p1, n1), (a2, p2, n2) in zip(t1, t2):
            assert a1 == a2 and n1 == n2
            np.testing.assert_array_equal(p1, p2)
        t3 = sb.build_trace(16, 4.0, 100, (3, 12), (4, 12), seed=6)
        assert any(not np.array_equal(p1, p3) for (_, p1, _), (_, p3, _)
                   in zip(t1, t3))

    def test_chip_probe_summarize(self):
        probe = _load_by_path("ec_probe_t", "tools/exec_cache_chip_probe.py")
        cold = {"metric": "m", "telemetry": {
            "compile_ms_total": 900.0, "exec_cache": {"serialized": 3}}}
        warm = {"metric": "m", "telemetry": {
            "compile_ms_total": 40.0,
            "exec_cache": {"disk_hits": 3, "errors": 0}}}
        rec = probe.summarize(cold, warm)
        assert rec["serialize_executable_ok"]
        assert rec["value"] == 860.0
        # a backend whose executables don't round-trip fails the verdict
        warm_bad = {"metric": "m", "telemetry": {
            "compile_ms_total": 900.0,
            "exec_cache": {"disk_hits": 0, "errors": 3}}}
        rec2 = probe.summarize(cold, warm_bad)
        assert not rec2["serialize_executable_ok"]
        assert rec2["deserialize_errors_warm"] == 3


# -- end-to-end (compiled; tier-1 CPU) ----------------------------------------

@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
    m.eval()
    return m


def _reference(model, prompt, new):
    return generate(model, pt.to_tensor(np.asarray(prompt)[None, :]),
                    max_new_tokens=new).numpy()[0]


def test_engine_token_identical_and_single_compile(model, tmp_path):
    """THE acceptance proof: 8 requests, unequal prompt/output lengths,
    outputs token-identical to sequential generate() calls, and the
    exec-cache counters show exactly one compile per phase program —
    admission/eviction/growth never retraces."""
    from paddle_tpu.jit import exec_cache as ec

    ec.enable(str(tmp_path))
    ec.clear()
    try:
        eng = ServingEngine(model, ServingConfig(
            max_lanes=3, block_size=4, prefill_chunk=8, max_seq_len=32))
        rng = np.random.RandomState(0)
        reqs = []
        for _ in range(8):
            plen, new = int(rng.randint(3, 13)), int(rng.randint(4, 13))
            prompt = rng.randint(0, model.config.vocab_size,
                                 (plen,)).astype(np.int32)
            reqs.append((eng.submit(prompt, max_new_tokens=new),
                         prompt, new))
        assert len({p.size for _, p, _ in reqs}) > 1, "prompts all equal"
        assert len({n for _, _, n in reqs}) > 1, "output lengths all equal"
        outs = eng.run()
        assert eng.counters["decode_steps"] \
            + eng.counters["verify_steps"] > 0
        misses = ec.stats()["misses"]
        # speculation is auto-on: prefill + decode + verify, once each
        assert misses == 3, f"prefill+decode+verify should compile " \
                            f"once each: {ec.stats()}"
        for r, prompt, new in reqs:
            np.testing.assert_array_equal(
                outs[r.request_id], _reference(model, prompt, new),
                err_msg=f"request {r.request_id} diverged from generate()")
        # a second wave through the SAME engine: zero fresh compiles
        r2 = eng.submit(rng.randint(0, model.config.vocab_size, (7,)),
                        max_new_tokens=6)
        outs2 = eng.run()
        assert ec.stats()["misses"] == misses, "per-request retrace!"
        np.testing.assert_array_equal(
            outs2[r2.request_id],
            _reference(model, r2.prompt, 6))
    finally:
        ec.disable()
        ec.clear()


def test_engine_preemption_recompute_token_identical(model):
    """A pool too small for the offered load forces preemption; the
    recompute path (re-prefill prompt+kept output on re-admission) must
    still reproduce generate() bit for bit."""
    eng = ServingEngine(model, ServingConfig(
        max_lanes=3, block_size=2, num_blocks=12, prefill_chunk=4,
        max_seq_len=20))
    rng = np.random.RandomState(1)
    reqs = []
    for _ in range(6):
        plen, new = int(rng.randint(2, 9)), int(rng.randint(6, 12))
        prompt = rng.randint(0, model.config.vocab_size,
                             (plen,)).astype(np.int32)
        reqs.append((eng.submit(prompt, max_new_tokens=new), prompt, new))
    outs = eng.run()
    assert eng.counters["preemptions"] > 0, \
        "pressure config never preempted — test is vacuous"
    for r, prompt, new in reqs:
        np.testing.assert_array_equal(
            outs[r.request_id], _reference(model, prompt, new),
            err_msg=f"request {r.request_id} (preemptions="
                    f"{r.preemptions}) diverged")
    assert eng.scheduler.pool.used_count == 0  # evicted KV reclaimed


def _shared_prefix_workload(model, rng, n, prefix_len=8, sfx=(1, 6),
                            new=(4, 10)):
    prefix = rng.randint(0, model.config.vocab_size,
                         (prefix_len,)).astype(np.int32)
    out = []
    for _ in range(n):
        suffix = rng.randint(0, model.config.vocab_size,
                             (int(rng.randint(*sfx)),)).astype(np.int32)
        out.append((np.concatenate([prefix, suffix]),
                    int(rng.randint(*new))))
    return out


def test_engine_prefix_cache_token_identity_and_fewer_prefills(
        model, tmp_path):
    """ISSUE 13 acceptance: ≥8 requests sharing a common prefix are
    token-identical to per-request generate() AND to the cache-off
    engine, with strictly fewer prefill chunks — and with ZERO new
    compiled programs (the same two exec-cached executables serve
    cache-on, cache-off, and a second wave; no retraces)."""
    from paddle_tpu.jit import exec_cache as ec

    geom = dict(max_lanes=3, block_size=4, prefill_chunk=8,
                max_seq_len=32)
    work = _shared_prefix_workload(model, np.random.RandomState(7), 8)
    ec.enable(str(tmp_path))
    ec.clear()
    try:
        results, chunks = {}, {}
        for label, pc in (("on", True), ("off", False)):
            eng = ServingEngine(model, ServingConfig(
                prefix_cache=pc, **geom))
            handles = [eng.submit(p, max_new_tokens=n) for p, n in work]
            outs = eng.run()
            results[label] = [outs[h.request_id] for h in handles]
            chunks[label] = eng.counters["prefill_chunks"]
            if pc:
                assert eng.counters["prefix_hit_tokens"] > 0
                assert eng.stats()["prefix_cache"] is True
                # released prefixes parked on the cold LRU, not freed
                assert eng.stats()["cold_blocks"] > 0
                # a second wave through the SAME engine hits the now-
                # warm index from the first token on
                hit0 = eng.counters["prefix_hit_tokens"]
                h2 = [eng.submit(p, max_new_tokens=n)
                      for p, n in work[:3]]
                outs2 = eng.run()
                assert eng.counters["prefix_hit_tokens"] > hit0
                for h, (p, n) in zip(h2, work[:3]):
                    np.testing.assert_array_equal(
                        outs2[h.request_id], _reference(model, p, n))
            eng.scheduler.pool.check_invariant()
        # the tentpole claim: sharing removed prefill compute...
        assert chunks["on"] < chunks["off"], chunks
        # ...without touching a single emitted token
        for i, (p, n) in enumerate(work):
            ref = _reference(model, p, n)
            np.testing.assert_array_equal(results["on"][i], ref)
            np.testing.assert_array_equal(results["off"][i], ref)
        # zero new compiled programs: one prefill + one decode + one
        # verify compile served every engine and wave above (cache
        # on/off share keys — sharing is host bookkeeping, invisible to
        # the programs)
        assert ec.stats()["misses"] == 3, ec.stats()
    finally:
        ec.disable()
        ec.clear()


def test_engine_same_round_burst_shares(model):
    """A burst that fills every lane in ONE scheduling round still
    shares: the engine admits one lane at a time with the prefill (and
    publish) in between, so lanes 2..L hit lane 1's blocks."""
    eng = ServingEngine(model, ServingConfig(
        max_lanes=3, block_size=4, prefill_chunk=8, max_seq_len=32))
    work = _shared_prefix_workload(model, np.random.RandomState(13), 3)
    handles = [eng.submit(p, max_new_tokens=n) for p, n in work]
    eng.step()  # one round admits (and prefills) all three lanes
    assert eng.scheduler.lanes_occupied == 3
    assert eng.counters["prefix_hit_tokens"] >= 2 * 8, eng.counters
    outs = eng.run()
    for h, (p, n) in zip(handles, work):
        np.testing.assert_array_equal(
            outs[h.request_id], _reference(model, p, n))


def test_engine_prefix_cache_preemption_churn_and_replay(model):
    """Token identity + determinism under the worst case: a pool too
    small for the shared-prefix load, so admission hits, cold-LRU
    reclaims, preemptions, and recompute re-admissions (which re-hit
    the victim's own published blocks) interleave. Two identical
    engines must also replay byte-identical event logs — blake2b chain
    keys keep sharing decisions deterministic."""
    work = _shared_prefix_workload(model, np.random.RandomState(9), 8,
                                   prefix_len=4, sfx=(1, 5), new=(6, 11))

    def run_once():
        eng = ServingEngine(model, ServingConfig(
            max_lanes=3, block_size=2, num_blocks=12, prefill_chunk=4,
            max_seq_len=20, prefix_cache=True))
        handles = [eng.submit(p, max_new_tokens=n, request_id=i)
                   for i, (p, n) in enumerate(work)]
        outs = eng.run()
        return eng, [outs[h.request_id] for h in handles]

    eng1, out1 = run_once()
    assert eng1.counters["preemptions"] > 0, \
        "pressure config never preempted — test is vacuous"
    assert eng1.counters["prefix_hit_tokens"] > 0, \
        "pressure config never shared — test is vacuous"
    for (p, n), got in zip(work, out1):
        np.testing.assert_array_equal(got, _reference(model, p, n))
    eng1.scheduler.pool.check_invariant()
    assert eng1.scheduler.pool.used_count == 0
    eng2, out2 = run_once()
    assert list(eng1.scheduler.events) == list(eng2.scheduler.events)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)


def test_engine_prefix_monitor_counters(model):
    """serving/prefix_* counters mirror the engine's always-on ints,
    and the shared/cold gauges land."""
    was = monitor.enabled()
    monitor.enable()
    try:
        base = monitor.snapshot()["counters"]
        eng = ServingEngine(model, ServingConfig(
            max_lanes=2, block_size=4, prefill_chunk=8, max_seq_len=32))
        for p, n in _shared_prefix_workload(
                model, np.random.RandomState(4), 5):
            eng.submit(p, max_new_tokens=n)
        eng.run()
        got = monitor.snapshot()["counters"]

        def delta(k):
            return got.get(k, 0) - base.get(k, 0)

        assert delta("serving/prefix_hit_tokens") == \
            eng.counters["prefix_hit_tokens"] > 0
        assert delta("serving/prefix_miss_tokens") == \
            eng.counters["prefix_miss_tokens"] > 0
        gauges = monitor.snapshot()["gauges"]
        assert "serving/shared_blocks" in gauges
        assert "serving/cold_blocks" in gauges
    finally:
        if not was:
            monitor.disable()


def test_engine_eos_early_stop(model):
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, model.config.vocab_size, (5,)).astype(np.int32)
    ref = _reference(model, prompt, 8)
    eos = int(ref[3])
    eng = ServingEngine(model, ServingConfig(
        max_lanes=2, block_size=4, prefill_chunk=8, max_seq_len=32))
    req = eng.submit(prompt, max_new_tokens=8, eos_token_id=eos)
    out = eng.run()[req.request_id]
    assert int(out[-1]) == eos
    np.testing.assert_array_equal(out, ref[:len(out)])
    assert len(out) <= 4  # stopped at the eos, not at max_new_tokens


def test_engine_monitor_counters(model):
    """PT_MONITOR wiring: serving/* counters account the run; the
    always-on plain-int ServingEngine.counters agree."""
    was = monitor.enabled()
    monitor.enable()
    try:
        base = monitor.snapshot()["counters"]
        eng = ServingEngine(model, ServingConfig(
            max_lanes=2, block_size=4, prefill_chunk=8, max_seq_len=32))
        rng = np.random.RandomState(3)
        for _ in range(3):
            eng.submit(rng.randint(0, model.config.vocab_size, (4,)),
                       max_new_tokens=4)
        eng.run()
        got = monitor.snapshot()["counters"]

        def delta(k):
            return got.get(k, 0) - base.get(k, 0)

        assert delta("serving/admits") == 3
        assert delta("serving/evictions") == 3  # all finished → reclaimed
        assert delta("serving/decode_steps") == eng.counters["decode_steps"]
        assert delta("serving/prefill_steps") == \
            eng.counters["prefill_chunks"]
        hist = monitor.snapshot()["histograms"].get("serving/queue_wait_ms")
        assert hist and hist["count"] >= 3
    finally:
        if not was:
            monitor.disable()


def test_engine_rejects_duplicates_and_oversize(model):
    eng = ServingEngine(model, ServingConfig(
        max_lanes=2, block_size=4, prefill_chunk=8, max_seq_len=16))
    eng.submit([1, 2, 3], max_new_tokens=2, request_id="dup")
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit([4, 5], max_new_tokens=2, request_id="dup")
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(list(range(15)), max_new_tokens=4)
    # finished-but-uncollected ids are still taken — a reuse would
    # silently overwrite the uncollected result
    while eng.has_work():
        eng.step()
    with pytest.raises(ValueError, match="uncollected"):
        eng.submit([6], max_new_tokens=2, request_id="dup")
    eng.pop_finished()
    eng.submit([6], max_new_tokens=2, request_id="dup")  # now reusable
    eng.run()


def test_engine_retires_collected_requests(model):
    """run()/pop_finished() collect-and-retire: the engine keeps no
    reference to a collected request (flat host memory under continuous
    feed) and its id becomes reusable."""
    eng = ServingEngine(model, ServingConfig(
        max_lanes=2, block_size=4, prefill_chunk=8, max_seq_len=32))
    eng.submit([1, 2, 3], max_new_tokens=3, request_id="r")
    out1 = eng.run()
    assert list(out1) == ["r"]
    assert eng.run() == {}  # already collected
    st = eng.stats()
    assert st["requests"] == 0 and st["uncollected"] == 0
    eng.submit([4, 5], max_new_tokens=2, request_id="r")  # id reusable
    out2 = eng.run()
    assert list(out2) == ["r"] and len(out2["r"]) == 2


def _parent_read_logits(params, kpool, vpool, tables, ids, pos, wlimit,
                        cfg):
    """Verify-style logits of `engine._pool_forward`'s layer math with
    the K/V read as it was before ISSUE 25: the layer's pool sliced out
    FIRST (``kp[li]``), then gathered by the block table."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving.families import dense_gqa as E

    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    d = cfg.hidden_size // nh
    b, s = ids.shape
    B, M = kpool.shape[2], tables.shape[1]
    x = params["embed"][ids].astype(jnp.dtype(cfg.dtype))
    blk = jnp.take_along_axis(tables, jnp.minimum(pos // B, M - 1), axis=1)
    ok = pos < wlimit[:, None]
    blk, off = jnp.where(ok, blk, 0), jnp.where(ok, pos % B, 0)
    for li in range(kpool.shape[0]):
        lp = {k: params[k][li] for k in
              ("ln1", "qkv", "o", "ln2", "gate_up", "down")}
        qkv = E._mm(E._rms(x, lp["ln1"], cfg.rms_norm_eps), lp["qkv"])
        q, k, v = jnp.split(qkv, [nh * d, nh * d + nkv * d], axis=-1)
        q, k = E._rope_at(q.reshape(b, s, nh, d), k.reshape(b, s, nkv, d),
                          pos, cfg.rope_theta)
        kpool = kpool.at[li, blk, off].set(k)
        vpool = vpool.at[li, blk, off].set(v.reshape(b, s, nkv, d))
        out = E._attend_lanes(
            q, kpool[li][tables].reshape(b, M * B, nkv, d),
            vpool[li][tables].reshape(b, M * B, nkv, d), pos, nh, nkv)
        x = x + E._mm(out.reshape(b, s, nh * d), lp["o"])
        gate, up = jnp.split(E._mm(E._rms(x, lp["ln2"], cfg.rms_norm_eps),
                                   lp["gate_up"]), 2, axis=-1)
        x = x + E._mm(jax.nn.silu(gate) * up, lp["down"])
    x = E._rms(x, params["norm"], cfg.rms_norm_eps)
    return E._mm(x, params["lm_head"]).astype(jnp.float32)


def test_engine_each_layer_reads_its_own_pool():
    """The K/V read is ONE gather on the stacked pool by (layer, block)
    (ISSUE 25). A 3-layer model whose layers' K/V differ by an order of
    magnitude, a pool of 23 blocks (a multiple of nothing in the
    [3, 7] table, the 4-token block or the depth): served tokens equal
    generate()'s, and on a pool filled with per-layer-distinct values
    the logits equal, to fp32 rounding (ISSUE 28: the read goes row by
    row), those of the layer-first ``kp[li][tables]`` read over the whole
    table — which a read from a neighbouring layer or block misses by
    far more than rounding."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving.engine import pack_rows
    from paddle_tpu.serving.families import dense_gqa as E

    pt.seed(3)
    m = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=3))
    m.eval()
    for blk, scale in zip(m.model.layers, (0.3, 1.0, 3.0)):
        w = blk.self_attn.qkv_proj.weight
        w._data = w._data * scale
    eng = ServingEngine(m, ServingConfig(
        max_lanes=3, block_size=4, num_blocks=23, prefill_chunk=8,
        max_seq_len=28))
    assert eng._pools[0].shape[:3] == (3, 23, 4) and eng.blocks_per_lane == 7
    rng = np.random.RandomState(25)
    reqs = []
    for _ in range(7):
        plen, new = int(rng.randint(3, 14)), int(rng.randint(4, 13))
        prompt = rng.randint(0, m.config.vocab_size,
                             (plen,)).astype(np.int32)
        reqs.append((eng.submit(prompt, max_new_tokens=new), prompt, new))
    outs = eng.run()
    for r, prompt, new in reqs:
        np.testing.assert_array_equal(
            outs[r.request_id], _reference(m, prompt, new),
            err_msg=f"request {r.request_id} diverged from generate()")

    # the same programs' forward on a pool no layer shares with another
    shape = eng._pools[0].shape  # the heads merged into the last axis
    layer_of = np.arange(3, dtype=np.float32).reshape(3, 1, 1, 1)
    kpool = jnp.asarray(rng.randn(*shape).astype(np.float32)
                        * (1 + 2 * layer_of) + layer_of)
    vpool = jnp.asarray(rng.randn(*shape).astype(np.float32)
                        * (1 + 2 * layer_of) - layer_of)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, 23))[:21].reshape(3, 7), jnp.int32)
    cur = jnp.asarray([0, 9, 22], jnp.int32)
    toks = jnp.asarray(rng.randint(0, m.config.vocab_size, (3, 5)),
                       jnp.int32)
    pos = cur[:, None] + jnp.arange(5, dtype=jnp.int32)[None, :]
    wlimit = cur + jnp.asarray([1, 5, 3], jnp.int32)
    cfg, params = eng._gcfg, eng._params

    def served(kp, vp, tb):
        # every lane's whole table as rows of 2 blocks, run 3 at a time:
        # a lane's softmax is put together from 4 rows over 3-4 tiles
        rows, wblk, _, _ = pack_rows(
            [(i, list(np.asarray(tb[i])), int(cur[i]), 28)
             for i in range(3)], 3, 5, 4, 2, 12)
        x, *_ = E._pool_forward(
            params, kp, vp, None, None,
            (jnp.asarray(rows), jnp.asarray(wblk)), toks, pos, wlimit, cfg,
            tile=3)
        x = E._rms(x, params["norm"], cfg.rms_norm_eps)
        return E._mm(x, params["lm_head"]).astype(jnp.float32)

    got = np.asarray(served(kpool, vpool, tables))
    nkv = cfg.num_key_value_heads or cfg.num_attention_heads
    want = np.asarray(jax.jit(_parent_read_logits, static_argnums=7)(
        params, kpool.reshape(*shape[:3], nkv, -1),
        vpool.reshape(*shape[:3], nkv, -1), tables, toks, pos, wlimit, cfg))
    # row by row and recombined: fp32 rounding apart, the same softmax
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # what the assertion above can see: the neighbouring layer's pool,
    # or the neighbouring block, is a different answer altogether
    for wrong in (served(jnp.roll(kpool, 1, axis=0),
                         jnp.roll(vpool, 1, axis=0), tables),
                  served(kpool, vpool, tables % 22 + 1)):
        assert np.abs(np.asarray(wrong) - want).max() > 0.1


def test_stats_keys_the_benchmark_reads(model):
    """What ``benchmarks/chip`` reads off an engine, by name: of
    ``stats()`` the read-path key (chiplib/serve.py:423; a constant now
    that each family has one read) and the pool's bytes (serve.py:473);
    of ``counters`` the per-round differences (serve.py:102), and what
    chiplib/optext.py and metrics/*.py divide."""
    eng = ServingEngine(model, ServingConfig(
        max_lanes=2, block_size=4, prefill_chunk=8, max_seq_len=32))
    stats = eng.stats()
    assert stats["paged_attention"] is False
    assert "paged_family" not in stats and "paged_dead" not in stats
    assert stats["kv_pool_bytes"] == eng.kv_pool_bytes > 0
    for key in ("prefill_chunks", "decode_steps", "verify_steps",
                "decoded_tokens", "spec_proposed_tokens",
                "spec_accepted_tokens", "prefix_hit_tokens",
                "prefix_miss_tokens", "preemptions"):
        assert eng.counters[key] == 0, key


def test_monitor_report_renders_bench_serving_section(tmp_path):
    """`monitor_report --bench serving.log` must render the serving
    counters serving_bench embeds in its telemetry."""
    mr = _load_by_path("monitor_report_t", "tools/monitor_report.py")
    bench = tmp_path / "serving.log"
    bench.write_text(json.dumps({
        "metric": "serving_tokens_per_sec", "value": 100.0,
        "unit": "tokens/s", "telemetry": {"serving": {
            "admits": 4, "evictions": 4, "prefill_steps": 6,
            "decode_steps": 11, "prefix_hit_tokens": 30,
            "prefix_miss_tokens": 10}}}) + "\n")
    jsonl = tmp_path / "run.jsonl"
    jsonl.write_text(json.dumps({"event": "run_begin", "meta": {}}) + "\n")
    text = mr.render(str(jsonl), bench_path=str(bench))
    assert "serving (continuous batching) (bench)" in text
    assert "decode steps 11" in text
    assert "prefix cache: 30 cached + 10 prefilled" in text
    assert "75% hit rate" in text


def test_serving_bench_smoke_emits_contract_line():
    """`python benchmarks/serving_bench.py --smoke` prints one parseable
    JSON line carrying the acceptance keys."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PT_SERVE_BENCH_REQUESTS"] = "8"
    env["PT_SERVE_BENCH_RATE"] = "200"
    env["PT_SERVE_BENCH_SHARED"] = "8"  # shared-system-prompt mode
    proc = subprocess.run(
        [sys.executable, "benchmarks/serving_bench.py", "--smoke"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("{"))
    rec = json.loads(line)
    assert rec["metric"] == "serving_tokens_per_sec"
    assert rec["tokens_per_sec"] > 0
    assert rec["ttft_ms_p50"] is not None
    assert rec["ttft_ms_p99"] is not None
    assert rec["ttft_ms_p99"] >= rec["ttft_ms_p50"]
    assert rec["completed"] == rec["requests"] == 8
    assert rec["note"] == "cpu smoke mode; not a TPU number"
    # prefix-cache contract fields (ISSUE 13): hit rate + the
    # cached-vs-cold TTFT A/B parse out of the line
    assert rec["prefix_cache"] is True
    assert rec["shared_prefix_tokens"] == 8
    assert 0 < rec["prefix_hit_rate"] <= 1
    assert rec["prefix_hit_tokens"] > 0
    assert rec["prefix_miss_tokens"] > 0
    assert rec["ttft_ms_p50_cached"] is not None
    assert rec["ttft_ms_p50_cold"] is not None
