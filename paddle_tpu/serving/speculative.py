"""Speculative-decoding drafters for the serving engine (ROADMAP 3c).

Host-side, jax-free token proposal: a :class:`Drafter` looks at one
lane's known context (prompt + every generated token, the pending one
included) and proposes up to ``k`` continuation tokens. The engine then
scores all lanes' proposals in ONE compiled verify step
(``engine._verify_step``, shape ``[lanes, k+1]``) and accepts each
lane's longest prefix that matches the model's own greedy choices, plus
one bonus token — the greedy output stream is byte-identical to plain
decode (tests/test_serving_spec.py), only the number of decode rounds
changes.

The default drafter is **prompt-lookup n-gram matching** (the
draft-model-free scheme of arXiv:2304.04487 / vLLM's
``[ngram]`` speculator): the lane's most recent tokens are matched
against its own earlier context, and the tokens that followed the most
recent earlier occurrence become the draft. No extra weights, no device
work — repetition in the workload (code, quoted context, chatty list
output, a model settling into a loop) is the entire win condition.

What a round pays for it: the engine drafts for every running lane in
every round, between two launches, while the device waits — so the cost
of LOOKING for a draft is paid whether the lookup hits or not. A
request's context only ever grows (a rejected draft never enters it, a
preempted request comes back with the tokens it had), so the default
drafter keeps, per request, an :class:`NgramIndex`: a map from each
n-gram to the position after its most recent occurrence, extended by
the tokens the round before emitted and asked once per n. That is a
few microseconds a lane whatever the context's length (8-11 us on the
chip's host, 0.26 ms a round at 32 lanes: PERF.md section 6, PR 30),
where re-scanning prompt + output (:meth:`NgramDrafter.propose`, the
stateless definition the index is held to) is ~100-230 us a lane at
450-2800 tokens (5.3 ms a round there). The price is the first draft of a request, which
indexes its whole prompt (~0.4 ms per 1024 tokens, once), and ~0.3 KB
of host memory a token. The engine reaches the index through
``begin``, an optional hook (:class:`Drafter`); a drafter with
``propose`` alone is handed each lane's context every round as before.

Determinism contract: drafting feeds the scheduler's replayable event
stream, so a drafter must be a pure function of the tokens it is shown
— no RNG, no clocks, no hash()-ordered iteration (the index's
dictionaries are keyed by ints and only ever looked up, never
iterated). This module is in ``pt-lint``'s PTL005 byte-identity scope
(docs/STATIC_ANALYSIS.md) to keep it that way.

Monitor contract: carries a ``_monitor`` None-slot
(``monitor.INSTRUMENTED_MODULES``) — when monitoring is off no monitor
callable is ever invoked; ``serving/spec_draft_calls`` counts lookups
(one per lane per round, stateless or indexed; the engine itself
accounts proposed/accepted/bonus tokens, post-trim — see
``engine._verify_round`` — and hands its ``counters`` to ``begin`` for
the index's own tallies).
"""
from __future__ import annotations

import sys

import numpy as np

from ..monitor import _register as _monitor_register

__all__ = ["Drafter", "LaneContext", "NgramDrafter", "NgramIndex"]

# telemetry slot (paddle_tpu.monitor None-slot contract): None unless
# PT_MONITOR wired it
_monitor = None

_EMPTY = np.zeros((0,), np.int32)
# bits a token id takes in an n-gram's key (NgramIndex)
_NARROW, _WIDE = 21, 32


class Drafter:
    """Draft-proposal protocol: subclass (or duck-type) with
    :meth:`propose`. The slot a learned draft model would fill — the
    engine only ever calls a drafter host-side, between compiled steps,
    so a model-backed drafter just runs its own (cheap) forward here and
    returns tokens.

    Optional stateful hook, looked up once by the engine:
    ``begin(prompt, room, tally)``. The engine calls it when a request
    first drafts, keeps what it returns with the request (through
    preemption, until it finishes) and calls that object's
    ``propose(new_tokens, k)`` each round with the tokens emitted since
    its last call; the object counts the tokens it holds as ``n``.
    ``room`` is the most tokens the request may emit; ``tally`` is a
    mapping of ints the state may add its own counts to (the engine's
    ``counters``). A drafter without it gets a :class:`LaneContext`,
    which calls :meth:`propose` with the whole context every round."""

    def propose(self, tokens: np.ndarray, k: int) -> np.ndarray:
        """Up to ``k`` proposed continuation tokens for a lane whose
        known context is ``tokens`` (1-D int array: prompt + generated,
        pending token last; a read-only view of the lane's buffer).
        Return an empty array to skip speculation for this lane this
        round. MUST be deterministic in ``tokens`` (see module
        docstring)."""
        raise NotImplementedError

    def observe(self, tokens: np.ndarray, accepted: int) -> None:
        """Optional feedback hook: the engine reports how many of the
        last proposal's tokens were accepted. Default: ignore."""


class LaneContext:
    """One request's known context (prompt + emitted tokens) in a buffer
    allocated once, and the stateless way to draft from it: append the
    round's new tokens, hand a drafter's ``propose`` the whole view."""

    __slots__ = ("_propose", "buf", "n")

    def __init__(self, propose, prompt, room: int, tally=None):
        # (``tally``: the hook's signature; this context counts nothing)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        self._propose = propose
        self.buf = np.empty((prompt.size + int(room),), np.int32)
        self.buf[:prompt.size] = prompt
        self.n = int(prompt.size)

    def propose(self, new_tokens, k: int) -> np.ndarray:
        n = self.n + len(new_tokens)
        self.buf[self.n:n] = new_tokens
        self.n = n
        return self._propose(self.buf[:n], k)


class NgramIndex(LaneContext):
    """:meth:`NgramDrafter.propose` over a context that only grows,
    without the scan: for each n (``min_ngram <= n <= max_ngram``) a map
    from every n-gram that ENDED BEFORE the context's last token to the
    position just after its most recent occurrence. The tail n-gram is
    looked up before it is inserted — it enters its map only once a
    token follows it, so it is never its own match, exactly
    ``propose``'s windows ``[:n - ng]`` — and a later occurrence
    overwrites an earlier one, which IS "most recent".

    An n-gram's key is ONE int, its token ids side by side (newest
    lowest) in fields of 21 bits — three fit an int64, which numpy
    packs and a dict hashes fastest — or of 32 from the moment an id
    needs more (no vocabulary today does; the index is rebuilt once,
    and stays exact for any non-negative int32). Ints, so nothing the
    garbage collector tracks: 64 requests of 3584 tokens hold ~75 MB
    (~0.3 KB a token) it never visits. The prompt is indexed in one
    pass per n when the request first drafts (~0.4 ms per 1024
    tokens); a round's new tokens one by one off a rolling key (~1 us
    each).

    ``tally`` (a mapping of ints, the engine's ``counters``) gets
    ``draft_indexed_tokens`` (every token of the context once, however
    often the request is preempted), and per lookup one of
    ``draft_hits_ngram<n>`` (the matched length) or ``draft_misses``.
    """

    __slots__ = ("_levels", "_roll", "_shift", "_tally")

    def __init__(self, max_ngram: int, min_ngram: int, prompt, room: int,
                 tally=None):
        super().__init__(None, prompt, room)
        self._tally = tally if tally is not None else {}
        wide = self.n and int(self.buf[:self.n].max()) >> _NARROW
        self._index(max_ngram, min_ngram, _WIDE if wide else _NARROW)
        self._count("draft_indexed_tokens", self.n)

    def _index(self, max_ngram: int, min_ngram: int, shift: int) -> None:
        """Every level over the context as it stands, ``shift`` bits an
        id: one vectorised pass per n, each window zipped with the
        position after it (a later occurrence overwrites an earlier
        one); the positions stop at the last token, so the tail's own
        windows stay out."""
        n = self.n
        toks = self.buf[:n].astype(np.int64)
        levels = []
        keys = toks[:n - 1]
        for ng in range(1, max_ngram + 1):
            if ng > 1:  # each (ng - 1)-gram with the token after it
                if ng * shift > 63:
                    keys = keys.astype(object)  # past an int64
                keys = (keys[:-1] << shift) | toks[ng - 1:n - 1]
            if ng >= min_ngram:
                levels.append((ng, (1 << shift * ng) - 1,
                               dict(zip(keys.tolist(), range(ng, n)))))
        self._levels = tuple(reversed(levels))  # longest first
        self._shift = shift
        self._roll = 0
        for t in toks[-max_ngram:].tolist():
            self._roll = (self._roll << shift) | t

    def _count(self, key: str, by: int = 1) -> None:
        self._tally[key] = self._tally.get(key, 0) + by

    def propose(self, new_tokens, k: int) -> np.ndarray:
        m = _monitor
        if m is not None:
            m.on_spec_draft_call()
        if len(new_tokens) and max(new_tokens) >> self._shift:
            # an id wider than the keys' fields: index again, wide
            self._index(self._levels[0][0], self._levels[-1][0], _WIDE)
        levels, roll, shift = self._levels, self._roll, self._shift
        top, buf, n = levels[0][1], self.buf, self.n
        for t in new_tokens:
            # the old tail now has a token after it, at position n
            for ng, mask, after in levels:
                if ng <= n:
                    after[roll & mask] = n
            roll = ((roll << shift) | int(t)) & top
            buf[n] = t
            n += 1
        self.n, self._roll = n, roll
        self._count("draft_indexed_tokens", len(new_tokens))
        if k > 0:
            for ng, mask, after in levels:
                # (no n-gram is in its map before the context is longer
                # than it: a short context misses, as it must)
                start = after.get(roll & mask)
                if start is not None:
                    self._count(f"draft_hits_ngram{ng}")
                    return buf[start:min(start + int(k), n)].copy()
        self._count("draft_misses")
        return _EMPTY


class NgramDrafter(Drafter):
    """Prompt-lookup drafting: propose the continuation of the most
    recent earlier occurrence of the context's tail n-gram.

    Longest n-gram first (``max_ngram`` down to ``min_ngram``): a longer
    match is stronger evidence the context is repeating. Among equal
    n-grams the MOST RECENT earlier occurrence wins — locality beats
    antiquity, and "last match" is as deterministic as "first".

    :meth:`propose` is the stateless definition: three numpy passes over
    the whole context, ~100-230 us at 450-2800 tokens. The engine does
    not call it: through :meth:`begin` each request gets an
    :class:`NgramIndex`, which proposes the same drafts
    (tests/test_serving_spec.py holds the two equal on every prefix of
    seeded sequences) for what the round's new tokens cost.
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError(
                f"need 1 <= min_ngram <= max_ngram, got "
                f"({min_ngram}, {max_ngram})")
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)

    def begin(self, prompt, room: int, tally=None) -> NgramIndex:
        return NgramIndex(self.max_ngram, self.min_ngram, prompt, room,
                          tally)

    def propose(self, tokens, k: int) -> np.ndarray:
        m = _monitor
        if m is not None:
            m.on_spec_draft_call()
        toks = np.ascontiguousarray(np.asarray(tokens, np.int32)
                                    .reshape(-1))
        n = int(toks.size)
        if k <= 0 or n < 2:
            return _EMPTY
        for ng in range(min(self.max_ngram, n - 1),
                        self.min_ngram - 1, -1):
            pattern = toks[n - ng:]
            # candidate starts 0..n-ng-1: every window that ends before
            # the tail n-gram itself, so a match always has at least one
            # following token to propose
            windows = np.lib.stride_tricks.sliding_window_view(
                toks, ng)[:n - ng]
            hits = np.nonzero((windows == pattern).all(axis=1))[0]
            if hits.size:
                start = int(hits[-1]) + ng  # most recent occurrence
                return toks[start:start + int(k)].copy()
        return _EMPTY


_monitor_register(sys.modules[__name__])
