"""Speculative decoding in the serving engine (ISSUE 14 —
`serving/speculative.py` + the `[lanes, k+1]` verify step).

Four layers:

- **Drafter (pure host, no jax)** — prompt-lookup n-gram proposal is
  deterministic, longest-ngram-first, most-recent-match, k-capped.
- **Scheduler draft growth** — `grow_for_draft` never preempts, trims
  to the pool/lane/max_seq_len ceiling, stays deterministic.
- **Tier-1 CPU end-to-end** — THE acceptance proofs: spec-on engine
  output is byte-identical to per-request `generate()` AND to the
  spec-off engine (through prefix-cache sharing and
  preemption-recompute churn, with byte-identical scheduler event
  replay), exec-cache misses == 3 (prefill, decode, verify) with zero
  retraces across a second wave, and on a repetitive trace spec-on
  finishes in strictly fewer decode rounds with accept_rate > 0.
- **Satellites** — monitor counters/histogram under the None-slot
  contract, monitor_report rendering, the serving_bench spec contract
  line (accept_rate > 0, tokens_per_decode_step > 1, spec-off A/B).
"""
import functools
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
    BlockPool, Drafter, FCFSScheduler, NgramDrafter, Request,
    ServingConfig, ServingEngine, blocks_needed, prefix_keys,
)
from paddle_tpu.serving.speculative import LaneContext, NgramIndex

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_by_path(name, relpath):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- drafter (pure host) ------------------------------------------------------

class TestNgramDrafter:
    def test_proposes_continuation_of_most_recent_match(self):
        d = NgramDrafter()
        # tail [7, 8] occurred twice; the MOST RECENT earlier occurrence
        # (index 4) wins, so the proposal is what followed it there
        toks = [7, 8, 1, 2, 7, 8, 3, 4, 7, 8]
        np.testing.assert_array_equal(d.propose(toks, 2), [3, 4])
        # k caps the proposal
        np.testing.assert_array_equal(d.propose(toks, 1), [3])
        # a proposal may run past the match into later context
        np.testing.assert_array_equal(d.propose(toks, 4), [3, 4, 7, 8])

    def test_longest_ngram_wins(self):
        d = NgramDrafter(max_ngram=3)
        # tail [1, 2, 3]: the trigram matches at 0 (→ 9), while the
        # bigram [2, 3] also matches at 1 — the trigram must win
        toks = [1, 2, 3, 9, 5, 1, 2, 3]
        np.testing.assert_array_equal(d.propose(toks, 1), [9])

    def test_no_match_and_tiny_context_are_empty(self):
        d = NgramDrafter()
        assert d.propose([1, 2, 3, 4], 4).size == 0  # no repeats
        assert d.propose([5], 4).size == 0
        assert d.propose([1, 1], 0).size == 0  # k=0

    def test_unigram_fallback_and_determinism(self):
        d = NgramDrafter()
        toks = [4, 9, 4]  # only the unigram [4] repeats
        np.testing.assert_array_equal(d.propose(toks, 2), [9, 4])
        rng = np.random.RandomState(3)
        for _ in range(20):
            t = rng.randint(0, 5, (int(rng.randint(2, 40)),))
            k = int(rng.randint(1, 6))
            a, b = d.propose(t, k), d.propose(t, k)
            np.testing.assert_array_equal(a, b)
            assert a.size <= k

    def test_validates_ngram_bounds(self):
        with pytest.raises(ValueError):
            NgramDrafter(max_ngram=0)
        with pytest.raises(ValueError):
            NgramDrafter(max_ngram=2, min_ngram=3)

    def test_monitor_audit_membership(self):
        # the None-slot zero-overhead-off audit in test_memory_numerics
        # parametrizes over this list — membership is the contract
        assert "paddle_tpu.serving.speculative" \
            in monitor.INSTRUMENTED_MODULES


# -- the per-request index against the stateless scan (pure host) -------------

_HITS = ("draft_hits_ngram3", "draft_hits_ngram2", "draft_hits_ngram1")


class TestNgramIndex:
    @pytest.mark.parametrize("ngrams", [(3, 1), (2, 2), (1, 1)],
                             ids=lambda ng: "ngram%d-%d" % ng)
    @pytest.mark.parametrize("alphabet", [2, 4, 50, "wide"])
    def test_proposes_what_the_scan_proposes_on_every_prefix(
            self, alphabet, ngrams):
        """The index is held to `NgramDrafter.propose`, the definition:
        same draft at every step of contexts that grow by 1-5 tokens a
        step (as accepted drafts make them), from prompts of 1-40
        tokens, for k 1-4. The alphabets make 3-, 2- and 1-gram hits
        and misses all occur (asserted through the tallies); "wide" has
        rare ids past the keys' 21-bit fields, in the prompt or after
        it, so the index is built wide or rebuilt so mid-way."""
        d = NgramDrafter(*ngrams)
        rng = np.random.RandomState(
            1000 * (7 if alphabet == "wide" else alphabet)
            + 10 * ngrams[0] + ngrams[1])
        tally, lookups, tokens, widths = {}, 0, 0, set()
        for _ in range(40):
            size = int(rng.randint(41, 140))
            if alphabet == "wide":
                seq = rng.choice([3, 7, 2 ** 21 + 5, 2 ** 31 - 1], size,
                                 p=[.48, .48, .02, .02])
            else:
                seq = rng.randint(0, alphabet, (size,))
            n = int(rng.randint(1, 41))
            ix = d.begin(seq[:n], seq.size - n, tally)
            assert isinstance(ix, NgramIndex)
            born = ix._shift
            while n < seq.size:
                step = int(rng.randint(1, 6))
                k = int(rng.randint(1, 5))
                new, n = seq[n:n + step].tolist(), min(n + step, seq.size)
                got = ix.propose(new, k)
                np.testing.assert_array_equal(got, d.propose(seq[:n], k))
                assert got.dtype == np.int32 and got.size <= k
                lookups += 1
            assert ix.n == seq.size
            np.testing.assert_array_equal(ix.buf[:ix.n], seq)
            widths.add((born, ix._shift))
            assert ix._shift == (32 if seq.max() >= 2 ** 21 else 21)
            # and the maps are what the docstring says, no entry more:
            # every n-gram that ended before the last token, with the
            # position after its most recent occurrence
            toks = seq.tolist()
            for ng, _, after in ix._levels:
                assert after == {
                    functools.reduce(
                        lambda key, t: (key << ix._shift) | t,
                        toks[s:s + ng]): s + ng
                    for s in range(seq.size - ng)}
            assert [ng for ng, _, _ in ix._levels] == \
                list(range(ngrams[0], ngrams[1] - 1, -1))
            tokens += seq.size
        assert widths == ({(21, 21), (21, 32), (32, 32)}
                          if alphabet == "wide" else {(21, 21)})
        assert tally["draft_indexed_tokens"] == tokens
        hits = {ng: tally.get(f"draft_hits_ngram{ng}", 0)
                for ng in (1, 2, 3)}
        assert sum(hits.values()) + tally.get("draft_misses", 0) == lookups
        lo, hi = ngrams[1], ngrams[0]
        assert not any(hits[ng] for ng in hits if not lo <= ng <= hi), hits
        if alphabet == 50:  # long matches are rare, misses common
            assert hits[lo] > 0 and tally["draft_misses"] > 0
        else:
            assert all(hits[ng] > 0 for ng in range(lo, hi + 1)), hits

    def test_tail_is_not_its_own_match_and_draft_stops_at_the_context(self):
        ix = NgramDrafter().begin([5, 6, 7], 8)
        assert ix.propose([], 4).size == 0  # nothing repeats yet
        # [.., 5, 6, 7] again: the trigram's earlier occurrence ends at 3
        np.testing.assert_array_equal(ix.propose([9, 5, 6, 7], 4),
                                      [9, 5, 6, 7])
        # the match sits one before the end: one token to propose, not
        # whatever the buffer holds past the context
        np.testing.assert_array_equal(ix.propose([7], 4), [7])
        assert ix.propose([], 0).size == 0  # k == 0

    def test_stateless_drafters_get_the_growing_context(self):
        """What the engine builds for a drafter with `propose` alone:
        it sees prompt + every token told so far, as one int32 view."""
        seen = []

        def echo(tokens, k):
            seen.append(np.array(tokens))
            return tokens[-k:]

        ctx = LaneContext(echo, np.array([3, 4], np.int64), 5, {})
        np.testing.assert_array_equal(ctx.propose([9], 2), [4, 9])
        np.testing.assert_array_equal(ctx.propose([8, 7], 1), [7])
        assert [a.tolist() for a in seen] == [[3, 4, 9], [3, 4, 9, 8, 7]]
        assert all(a.dtype == np.int32 for a in seen)
        assert not hasattr(Drafter, "begin")  # optional: the engine asks
        with pytest.raises(ValueError):
            ctx.propose([1, 1, 1], 1)  # more than `room` allowed


# -- scheduler draft growth (pure host) ---------------------------------------

class TestGrowForDraft:
    def _sched(self, num_blocks=9, block_size=2, max_seq_len=16):
        return FCFSScheduler(BlockPool(num_blocks, block_size), 2,
                             blocks_needed(max_seq_len, block_size),
                             max_seq_len)

    def _admit_one(self, sched, plen=3, new=8):
        req = sched.submit(Request([1] * plen, max_new_tokens=new,
                                   request_id="a"))
        sched.admit()
        req.pool_len = plen  # simulate the prefill
        return req

    def test_grows_blocks_and_reports_coverage(self):
        sched = self._sched()
        req = self._admit_one(sched)  # ctx 3 → 2 blocks cover pos 0..3
        have = len(req.blocks)
        got = sched.grow_for_draft(req, 4)  # positions 4..7 → 2 more
        assert got == 4
        assert len(req.blocks) == have + 2
        sched.pool.check_invariant()

    def test_dry_pool_trims_and_never_preempts(self):
        sched = self._sched(num_blocks=9)  # capacity 8
        req = self._admit_one(sched)
        hog = sched.submit(Request([1, 2], max_new_tokens=2,
                                   request_id="hog"))
        sched.admit()
        free = sched.pool.allocatable
        got = sched.grow_for_draft(req, 8)
        # everything free was granted, nothing evicted anyone
        assert got == len(req.blocks) * 2 - req.pool_len - 1
        assert sched.pool.allocatable == max(0, free - (got + 1) // 2)
        assert hog.state == "running"  # speculation never preempts
        assert not any(e[0] == "preempt" for e in sched.events)

    def test_release_returns_rejected_draft_blocks(self):
        # a failed speculation must leave NO allocation pressure behind
        # (the no-harm half of grow_for_draft's contract), and both
        # decisions land in the replayable event trail
        sched = self._sched()
        req = self._admit_one(sched)
        free0 = sched.pool.free_count
        assert sched.grow_for_draft(req, 4) == 4
        assert sched.pool.free_count < free0
        freed = sched.release_draft_blocks(req)
        assert freed == 2
        assert sched.pool.free_count == free0
        assert sched.release_draft_blocks(req) == 0  # idempotent
        assert ("draft_grow", "a", 2) in sched.events
        assert ("draft_release", "a", 2) in sched.events
        sched.pool.check_invariant()

    def test_draft_growth_never_reclaims_cold_cached_blocks(self):
        # evicting a cached prefix's index entry to back a GUESS would
        # trade real prefill savings for speculative ones: draft growth
        # draws from the free list only, cold blocks survive
        sched = self._sched(num_blocks=6, block_size=2)  # capacity 5
        pool = sched.pool
        cached = pool.alloc(2, "done")
        for i, key in enumerate(prefix_keys([1, 2, 3, 4], 2)):
            pool.publish(key, cached[i], "done")
        pool.free(cached, "done")  # parks cold, still indexed
        assert pool.cold_count == 2
        # 2 blocks at admission; ONE true-free block left
        req = self._admit_one(sched, plen=3, new=4)
        got = sched.grow_for_draft(req, 6)
        assert got == 2  # only the free block backed the draft
        assert pool.cold_count == 2  # cached prefix untouched...
        assert pool.lookup(prefix_keys([1, 2, 3, 4], 2)) == cached
        # ...while ensure_capacity (real growth) still may reclaim it
        pool.check_invariant()

    def test_clamps_to_lane_and_seq_ceiling(self):
        sched = self._sched(num_blocks=32, block_size=2, max_seq_len=10)
        req = self._admit_one(sched, plen=3, new=7)
        # ceiling 10 positions: pool_len 3 + 1 decode write → 6 left
        assert sched.grow_for_draft(req, 99) == 6
        assert sched.grow_for_draft(req, 0) == 0
        assert sched.grow_for_draft(req, -2) == 0


# -- config / knobs -----------------------------------------------------------

class TestSpecConfig:
    def test_env_knobs(self, monkeypatch):
        assert ServingConfig().spec is True  # auto on (greedy engine)
        assert ServingConfig().spec_k == 4
        monkeypatch.setenv("PT_SERVE_SPEC", "0")
        assert ServingConfig().spec is False
        monkeypatch.setenv("PT_SERVE_SPEC", "1")
        monkeypatch.setenv("PT_SERVE_SPEC_K", "7")
        cfg = ServingConfig()
        assert cfg.spec is True and cfg.spec_k == 7
        # explicit beats env
        assert ServingConfig(spec=False).spec is False
        assert ServingConfig(spec_k=2).spec_k == 2

    def test_k0_degenerates_to_plain_decode(self):
        cfg = ServingConfig(spec=True, spec_k=0)
        assert cfg.spec is False  # k=0 IS plain decode
        with pytest.raises(ValueError):
            ServingConfig(spec_k=-1)


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


def _workload(model, seed, n=8, plen=(3, 13), new=(8, 25)):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        p = rng.randint(0, model.config.vocab_size,
                        (int(rng.randint(*plen)),)).astype(np.int32)
        out.append((p, int(rng.randint(*new))))
    return out


def test_spec_token_identity_three_compiles_no_retrace(model, tmp_path):
    """THE acceptance proof: the spec-on engine's outputs are
    byte-identical to per-request generate() AND to the spec-off
    engine; exactly 3 exec-cache misses (prefill, decode, verify); a
    second wave and the spec-off engine add ZERO fresh compiles."""
    from paddle_tpu.jit import exec_cache as ec

    geom = dict(max_lanes=3, block_size=4, prefill_chunk=8,
                max_seq_len=48)
    work = _workload(model, seed=0)
    ec.enable(str(tmp_path))
    ec.clear()
    try:
        eng = ServingEngine(model, ServingConfig(**geom))
        assert eng.spec_active
        handles = [eng.submit(p, max_new_tokens=n) for p, n in work]
        outs = eng.run()
        assert ec.stats()["misses"] == 3, ec.stats()
        # the workload must actually exercise speculation or the proof
        # is vacuous
        assert eng.counters["verify_steps"] > 0
        assert eng.counters["spec_accepted_tokens"] > 0
        for h, (p, n) in zip(handles, work):
            np.testing.assert_array_equal(
                outs[h.request_id], _reference(model, p, n),
                err_msg=f"request {h.request_id} diverged from "
                        f"generate() on the speculative path")
        # second wave through the SAME engine: zero fresh compiles
        h2 = [eng.submit(p, max_new_tokens=n) for p, n in work[:3]]
        outs2 = eng.run()
        assert ec.stats()["misses"] == 3, "speculative retrace!"
        for h, (p, n) in zip(h2, work[:3]):
            np.testing.assert_array_equal(
                outs2[h.request_id], _reference(model, p, n))
        # spec-off engine: same two base programs (no new compiles),
        # identical tokens, and MORE decode rounds on this workload
        eng_off = ServingEngine(model, ServingConfig(spec=False, **geom))
        assert not eng_off.spec_active and eng_off._verify_exec is None
        h3 = [eng_off.submit(p, max_new_tokens=n) for p, n in work]
        outs3 = eng_off.run()
        assert ec.stats()["misses"] == 3, ec.stats()
        assert eng_off.counters["verify_steps"] == 0
        for h, hoff in zip(handles, h3):
            np.testing.assert_array_equal(
                outs3[hoff.request_id], outs[h.request_id])
    finally:
        ec.disable()
        ec.clear()


def test_spec_fewer_rounds_on_repetitive_trace(model):
    """On a repetition-friendly workload (tiled-motif prompts) spec-on
    must finish in STRICTLY fewer decode rounds than spec-off, with a
    positive accept rate and >1 tokens per round — the tentpole's
    throughput mechanism, minus the hardware."""
    rng = np.random.RandomState(5)
    work = []
    for _ in range(6):
        motif = rng.randint(0, model.config.vocab_size, (4,))
        plen = int(rng.randint(6, 13))
        work.append((np.tile(motif, -(-plen // 4))[:plen]
                     .astype(np.int32), int(rng.randint(16, 25))))
    geom = dict(max_lanes=3, block_size=4, prefill_chunk=8,
                max_seq_len=48)
    rounds, outs = {}, {}
    for label, spec in (("on", True), ("off", False)):
        eng = ServingEngine(model, ServingConfig(spec=spec, **geom))
        handles = [eng.submit(p, max_new_tokens=n) for p, n in work]
        res = eng.run()
        outs[label] = [res[h.request_id] for h in handles]
        rounds[label] = eng.stats()["decode_rounds"]
        if spec:
            st = eng.stats()
            assert st["spec_proposed_tokens"] > 0
            assert st["spec_accepted_tokens"] > 0
            accept = st["spec_accepted_tokens"] \
                / st["spec_proposed_tokens"]
            assert accept > 0
            assert st["decoded_tokens"] / st["decode_rounds"] > 1
    assert rounds["on"] < rounds["off"], rounds
    for a, b, (p, n) in zip(outs["on"], outs["off"], work):
        ref = _reference(model, p, n)
        np.testing.assert_array_equal(a, ref)
        np.testing.assert_array_equal(b, ref)


def test_spec_prefix_cache_preemption_churn_identity_and_replay(model):
    """Speculation × prefix-cache sharing × preemption-recompute, under
    a pool too small for the load: token identity to generate() holds,
    and two identical engines replay byte-identical scheduler event
    logs (the drafter is deterministic, so speculation adds no replay
    noise)."""
    rng = np.random.RandomState(9)
    prefix = rng.randint(0, model.config.vocab_size, (4,)).astype(np.int32)
    work = []
    for _ in range(8):
        sfx = rng.randint(0, model.config.vocab_size,
                          (int(rng.randint(1, 5)),)).astype(np.int32)
        work.append((np.concatenate([prefix, sfx]),
                     int(rng.randint(6, 11))))

    def run_once():
        eng = ServingEngine(model, ServingConfig(
            max_lanes=3, block_size=2, num_blocks=12, prefill_chunk=4,
            max_seq_len=20, prefix_cache=True))
        assert eng.spec_active
        handles = [eng.submit(p, max_new_tokens=n, request_id=i)
                   for i, (p, n) in enumerate(work)]
        res = eng.run()
        return eng, [res[h.request_id] for h in handles]

    eng1, out1 = run_once()
    assert eng1.counters["preemptions"] > 0, \
        "pressure config never preempted — test is vacuous"
    assert eng1.counters["prefix_hit_tokens"] > 0, \
        "pressure config never shared — test is vacuous"
    assert eng1.counters["verify_steps"] > 0, \
        "pressure config never speculated — test is vacuous"
    for (p, n), got in zip(work, out1):
        np.testing.assert_array_equal(got, _reference(model, p, n))
    eng1.scheduler.pool.check_invariant()
    assert eng1.scheduler.pool.used_count == 0
    eng2, out2 = run_once()
    assert list(eng1.scheduler.events) == list(eng2.scheduler.events)
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)


class _ScanDrafter:
    """The stateless scan called per lane per round — what the engine
    did before the index: `propose` alone, no `begin`."""

    def __init__(self):
        self._scan = NgramDrafter().propose
        self.calls = 0

    def propose(self, tokens, k):
        self.calls += 1
        return self._scan(tokens, k)


def _churn_engine(model, drafter=None):
    """Repeating prompts on a pool too small for the load: drafts hit,
    some of each are rejected, lanes are preempted and re-admitted."""
    rng = np.random.RandomState(21)
    eng = ServingEngine(model, ServingConfig(
        max_lanes=3, block_size=2, num_blocks=16, prefill_chunk=4,
        max_seq_len=32, prefix_cache=False), drafter=drafter)
    handles = []
    for i in range(8):
        motif = rng.randint(0, model.config.vocab_size, (3,))
        plen = int(rng.randint(5, 11))
        handles.append(eng.submit(
            np.tile(motif, 4)[:plen].astype(np.int32),
            max_new_tokens=int(rng.randint(10, 19)), request_id=i))
    res = eng.run()
    return eng, handles, [res[h.request_id] for h in handles]


def test_index_drafts_what_the_scan_drafts_through_preemption(model):
    """Token for token and draft for draft: the engine with the
    per-request index against the same engine handed the stateless scan
    every round, through partly rejected drafts and preempted,
    re-admitted lanes — and a re-admitted request's tokens are indexed
    once."""
    scan = _ScanDrafter()
    e_ix, handles, out_ix = _churn_engine(model)
    e_sc, _, out_sc = _churn_engine(model, drafter=scan)
    c = e_ix.counters
    assert c["preemptions"] > 0, "never preempted: vacuous"
    assert 0 < c["spec_accepted_tokens"] < c["spec_proposed_tokens"], \
        "drafts all accepted or all rejected: vacuous"
    for a, b in zip(out_ix, out_sc):
        np.testing.assert_array_equal(a, b)
    for key in ("spec_proposed_tokens", "spec_accepted_tokens",
                "spec_bonus_tokens", "verify_steps", "decode_steps",
                "decoded_tokens", "preemptions"):
        assert c[key] == e_sc.counters[key], key
    assert list(e_ix.scheduler.events) == list(e_sc.scheduler.events)
    # one lookup per call of the scan; the scan's engine indexes nothing
    assert sum(c[k] for k in _HITS) + c["draft_misses"] == scan.calls
    assert all(e_sc.counters[k] == 0 for k in
               _HITS + ("draft_misses", "draft_indexed_tokens"))
    # every request here drafts from its first round to its last, so all
    # of its context but the final round's tokens is indexed, once
    # (preempted or not): prompt + output, less what the last verify
    # round emitted (1 + accepted, never told to the index)
    total = sum(h.prompt.size + len(h.output) for h in handles)
    assert total - 5 * len(handles) <= c["draft_indexed_tokens"] < total
    assert all(h._draft is None for h in handles)  # dies with the request


@pytest.mark.parametrize("monitored", [False, True],
                         ids=["monitor-off", "monitor-on"])
def test_draft_lookup_counters(model, monkeypatch, monitored):
    """Hits by matched length + misses == the lane-rounds in which the
    lane had room to draft (every call of the index), with monitoring
    on or off; indexed tokens == the context the drafting requests
    showed it."""
    calls, told = [], []
    real_init, real = NgramIndex.__init__, NgramIndex.propose

    def init(self, max_ngram, min_ngram, prompt, room, tally=None):
        told.append(len(prompt))
        real_init(self, max_ngram, min_ngram, prompt, room, tally)

    def propose(self, new_tokens, k):
        calls.append(k)
        told.append(len(new_tokens))
        return real(self, new_tokens, k)

    monkeypatch.setattr(NgramIndex, "__init__", init)
    monkeypatch.setattr(NgramIndex, "propose", propose)
    was = monitor.enabled()
    (monitor.enable if monitored else monitor.disable)()
    try:
        base = monitor.snapshot()["counters"] \
            .get("serving/spec_draft_calls", 0)
        eng, handles, _ = _churn_engine(model)
        ticks = monitor.snapshot()["counters"] \
            .get("serving/spec_draft_calls", 0) - base
    finally:
        (monitor.enable if was else monitor.disable)()
    c = eng.stats()
    assert c["preemptions"] > 0
    assert len(calls) > 0 and min(calls) > 0  # cap > 0, or no lookup
    assert sum(c[k] for k in _HITS) + c["draft_misses"] == len(calls)
    assert ticks == (len(calls) if monitored else 0)
    assert c["draft_hits_ngram3"] > 0 and c["draft_misses"] > 0
    assert c["draft_indexed_tokens"] == sum(told)
    # a request that is never given room to draft opens no index
    eng2 = ServingEngine(model, ServingConfig(
        max_lanes=2, block_size=4, prefill_chunk=8, max_seq_len=32))
    eng2.submit([1, 2, 1, 2, 1], max_new_tokens=2)
    eng2.run()
    assert eng2.counters["draft_indexed_tokens"] == 0
    assert sum(eng2.counters[k] for k in _HITS) \
        + eng2.counters["draft_misses"] == 0


class _NullDrafter(Drafter):
    def __init__(self):
        self.calls = 0

    def propose(self, tokens, k):
        self.calls += 1
        return np.zeros((0,), np.int32)


def test_null_draft_lanes_degenerate_to_plain_decode(model):
    """A drafter that never proposes: every round runs the plain [L, 1]
    decode program (verify_steps == 0) and the output stream is plain
    decode's, byte for byte."""
    geom = dict(max_lanes=2, block_size=4, prefill_chunk=8,
                max_seq_len=32)
    work = _workload(model, seed=2, n=4, new=(4, 10))
    null = _NullDrafter()
    eng = ServingEngine(model, ServingConfig(**geom), drafter=null)
    assert eng.spec_active  # spec on, drafter just never fires
    handles = [eng.submit(p, max_new_tokens=n) for p, n in work]
    outs = eng.run()
    assert null.calls > 0
    assert eng.counters["verify_steps"] == 0
    assert eng.counters["decode_steps"] > 0
    assert eng.counters["spec_proposed_tokens"] == 0
    for h, (p, n) in zip(handles, work):
        np.testing.assert_array_equal(
            outs[h.request_id], _reference(model, p, n))


def test_spec_k0_never_compiles_verify(model, tmp_path):
    """spec_k=0 (or PT_SERVE_SPEC=0) is TODAY's engine: two compiled
    programs, no drafter, no verify path."""
    from paddle_tpu.jit import exec_cache as ec

    ec.enable(str(tmp_path))
    ec.clear()
    try:
        eng = ServingEngine(model, ServingConfig(
            max_lanes=2, block_size=4, prefill_chunk=8, max_seq_len=32,
            spec=True, spec_k=0))
        assert not eng.spec_active and eng.drafter is None
        r = eng.submit([1, 2, 3], max_new_tokens=4)
        outs = eng.run()
        assert ec.stats()["misses"] == 2, ec.stats()
        assert eng._verify_exec is None
        np.testing.assert_array_equal(
            outs[r.request_id], _reference(model, [1, 2, 3], 4))
    finally:
        ec.disable()
        ec.clear()


def test_spec_monitor_counters(model):
    """serving/spec_* counters mirror the engine's always-on ints, the
    per-round accept-rate histogram fills, and the drafter's call
    counter ticks — all under the None-slot contract."""
    was = monitor.enabled()
    monitor.enable()
    try:
        base = monitor.snapshot()["counters"]
        eng = ServingEngine(model, ServingConfig(
            max_lanes=2, block_size=4, prefill_chunk=8, max_seq_len=48))
        rng = np.random.RandomState(5)
        for _ in range(4):
            motif = rng.randint(0, model.config.vocab_size, (3,))
            eng.submit(np.tile(motif, 3).astype(np.int32),
                       max_new_tokens=16)
        eng.run()
        got = monitor.snapshot()["counters"]

        def delta(k):
            return got.get(k, 0) - base.get(k, 0)

        c = eng.counters
        assert delta("serving/verify_steps") == c["verify_steps"] > 0
        assert delta("serving/spec_proposed_tokens") == \
            c["spec_proposed_tokens"] > 0
        assert delta("serving/spec_accepted_tokens") == \
            c["spec_accepted_tokens"] > 0
        assert delta("serving/spec_bonus_tokens") == \
            c["spec_bonus_tokens"] > 0
        assert delta("serving/decoded_tokens") == c["decoded_tokens"]
        assert delta("serving/spec_draft_calls") > 0
        hist = monitor.snapshot()["histograms"] \
            .get("serving/spec_accept_rate")
        assert hist and hist["count"] >= 1
    finally:
        if not was:
            monitor.disable()


def test_monitor_report_renders_spec_section(tmp_path):
    """monitor_report's serving section renders accept rate and
    tokens-per-decode-step from a bench line's serving telemetry."""
    mr = _load_by_path("monitor_report_spec_t", "tools/monitor_report.py")
    bench = tmp_path / "serving.log"
    bench.write_text(json.dumps({
        "metric": "serving_tokens_per_sec", "value": 100.0,
        "unit": "tokens/s", "telemetry": {"serving": {
            "admits": 4, "prefill_steps": 6, "decode_steps": 10,
            "verify_steps": 10, "decoded_tokens": 60,
            "spec_proposed_tokens": 40, "spec_accepted_tokens": 30,
            "spec_bonus_tokens": 9}}}) + "\n")
    jsonl = tmp_path / "run.jsonl"
    jsonl.write_text(json.dumps({"event": "run_begin", "meta": {}}) + "\n")
    text = mr.render(str(jsonl), bench_path=str(bench))
    assert "verify steps 10" in text
    assert "40 proposed" in text
    assert "30 accepted (75% accept rate)" in text
    assert "9 bonus" in text
    assert "tokens per decode step: 3.00" in text


def test_serving_bench_spec_smoke_contract_line():
    """ISSUE 14 acceptance: on the seeded repetitive smoke trace the
    bench line reports accept_rate > 0, tokens_per_decode_step > 1, and
    a spec-off replay that needed STRICTLY more decode rounds."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PT_SERVE_BENCH_REQUESTS"] = "8"
    env["PT_SERVE_BENCH_RATE"] = "200"
    env["PT_SERVE_BENCH_SPEC_K"] = "4"
    env["PT_SERVE_BENCH_SPEC_AB"] = "1"
    proc = subprocess.run(
        [sys.executable, "benchmarks/serving_bench.py", "--smoke"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("{"))
    rec = json.loads(line)
    assert rec["metric"] == "serving_tokens_per_sec"
    assert rec["spec"] is True and rec["spec_k"] == 4
    assert rec["accept_rate"] > 0
    assert rec["tokens_per_decode_step"] > 1
    assert rec["verify_steps"] > 0
    assert rec["decode_rounds"] == rec["decode_steps"] \
        + rec["verify_steps"]
    assert rec["spec_off"]["decode_rounds"] > rec["decode_rounds"]
    assert rec["spec_off"]["tokens_per_sec"] > 0
    assert rec["completed"] == rec["requests"] == 8
    # spec fields ride next to the standard serving contract keys
    assert rec["tokens_per_sec"] > 0
    assert rec["ttft_ms_p99"] >= rec["ttft_ms_p50"]
