"""The one general traffic generator for serving cells. A traffic mix is
a data file of parameters; this reads it.

**The seed chooses content and order, never the shape of the traffic.**
A mix is a table of request classes. Each class has sessions of one or
more turns; lengths come from quantile tables (piecewise-linear inverse
CDFs) evaluated on a fixed grid, and are dealt to (session, turn) by a
permutation that depends on the class alone. So every run of a mix, on
any seed, serves the same multiset of (prompt length, cached prefix,
output length). ``--seed`` decides which session arrives when, how
sessions interleave, and every token id. Arrival gaps in an open loop are
the quantiles of the exponential at the mix's rate, in an order the seed
picks; both that order and the interleaving of sessions are stratified,
so that no seed gets a burst or a run of long requests that another does
not (PR 23's first sets: the same shapes in a free random order gave a
90th percentile of TTFT between 384 and 909 ms, each repeating within
7% on its own seed).

**A mix may fix the order too** (``order_seed``): which session arrives
when and the order of the gaps then come from that constant, and
``--seed`` makes the token ids (and the weights) alone. Under load the
order IS work: which decoding requests a prefill lands on. The driver's
check of PR 23 read the median TPOT of ``chat-doc-steady`` 4.9% and 7.5%
apart over six seeds where one seed repeats within 1%; the program's own
scheduler on a fixed clock gives 2.4% from the order alone (24 orders).

Class parameters (all lengths in tokens):
  share                  share of the mix's requests
  turns                  requests to a session (follow-ups reuse context)
  shared_prefix_tokens   one prefix common to ALL sessions of the class
  context_tokens         quantile table: a session's own context (a
                         document), part of every turn's prompt
  new_tokens             quantile table: new prompt tokens of a turn
  output_tokens          quantile table: tokens to generate in a turn
  history                true: a turn's prompt carries the earlier turns'
                         new tokens and stand-in answers of the lengths
                         generated (the real answers are not known before
                         the run, so earlier PROMPTS hit the prefix cache
                         and earlier answers do not)
  prefill_in_setup       true: prefix + context of each session are put
                         through the engine during set-up, so the window
                         holds only warm asks of them
"""
from __future__ import annotations

import math
import zlib

import numpy as np


def inv_cdf(table, q):
    """Piecewise-linear inverse CDF through [[q0, v0], [q1, v1], ...]."""
    for (qa, va), (qb, vb) in zip(table, table[1:]):
        if q <= qb:
            return va + (vb - va) * (q - qa) / (qb - qa)
    return table[-1][1]


def grid(table, n):
    """n lengths: the table at the mid-quantiles (i + 0.5) / n."""
    return [max(1, int(round(inv_cdf(table, (i + 0.5) / n))))
            for i in range(n)]


def _fixed_rng(*names):
    return np.random.default_rng(
        [zlib.crc32("/".join(map(str, names)).encode())])


def class_counts(classes, n):
    """Requests per class: shares of n by largest remainder, each a
    multiple of the class's turns."""
    sessions = []
    for c in classes:
        sessions.append(max(1, int(round(c["share"] * n / c["turns"]))))
    return sessions


def shapes(mix, n_requests):
    """The run's multiset of request shapes, independent of the seed:
    [{cls, session, turn, prompt_len, cached_len, new, out, context,
    prefix, parts}], in (class, session, turn) order."""
    out = []
    for ci, c in enumerate(mix["classes"]):
        n_sess = class_counts(mix["classes"], n_requests)[ci]
        turns = c["turns"]
        n = n_sess * turns
        fixed = _fixed_rng(mix.get("name", ""), c["name"], n)
        news = np.asarray(grid(c["new_tokens"], n))[fixed.permutation(n)]
        outs = np.asarray(grid(c["output_tokens"], n))[fixed.permutation(n)]
        ctxs = (np.asarray(grid(c["context_tokens"], n_sess))
                [fixed.permutation(n_sess)]
                if c.get("context_tokens") else np.zeros(n_sess, int))
        prefix = int(c.get("shared_prefix_tokens", 0))
        for s in range(n_sess):
            base = prefix + int(ctxs[s])
            hist = 0
            warm = base if c.get("prefill_in_setup") else 0
            for t in range(turns):
                new, o = int(news[s * turns + t]), int(outs[s * turns + t])
                plen = base + hist + new
                cached = min(warm, plen - 1)
                out.append({"cls": c["name"], "ci": ci, "session": s,
                            "turn": t, "prompt_len": plen, "new": new,
                            "out": o, "cached_len": cached,
                            "prefix": prefix, "context": int(ctxs[s]),
                            "hist": hist})
                # what later turns can find cached: this turn's prompt
                warm = plen
                if c.get("history"):
                    hist += new + o
                else:
                    warm = max(base, 0)
    return out


def _tokens(seed, vocab, n, *stream):
    rng = np.random.default_rng([int(seed), *[int(x) for x in stream]])
    return rng.integers(0, vocab, n, dtype=np.int32)


def materialise(mix, shp, seed, vocab):
    """Token ids for every shape: a class's prefix is one stream, a
    session's context another, each turn's new tokens and stand-in answer
    their own. Adds 'prompt' to each shape."""
    by_session = {}
    for r in shp:
        by_session.setdefault((r["ci"], r["session"]), []).append(r)
    for (ci, s), turns in by_session.items():
        prefix = _tokens(seed, vocab, turns[0]["prefix"], 1, ci)
        ctx = _tokens(seed, vocab, turns[0]["context"], 2, ci, s)
        hist = np.zeros(0, np.int32)
        c = mix["classes"][ci]
        for r in sorted(turns, key=lambda r: r["turn"]):
            new = _tokens(seed, vocab, r["new"], 3, ci, s, r["turn"])
            r["prompt"] = np.concatenate([prefix, ctx, hist, new])
            assert r["prompt"].size == r["prompt_len"]
            if c.get("history"):
                hist = np.concatenate(
                    [hist, new,
                     _tokens(seed, vocab, r["out"], 4, ci, s, r["turn"])])
    return shp


def setup_fills(mix, shp, seed, vocab):
    """Prompts to put through the engine during set-up: prefix + context
    of every session of a ``prefill_in_setup`` class (once each)."""
    fills, seen = [], set()
    for r in shp:
        c = mix["classes"][r["ci"]]
        if not c.get("prefill_in_setup"):
            continue
        key = (r["ci"], r["session"] if r["context"] else -1)
        if key in seen or r["prefix"] + r["context"] == 0:
            continue
        seen.add(key)
        fills.append(r["prompt"][: r["prefix"] + r["context"]])
    return fills


def order_sessions(shp, seed):
    """Sessions spread evenly over the stream: a session of T turns has
    turn t in the t-th T-th of the stream (so its turns keep their order
    and lie about a T-th of the window apart, as a user's think time
    does), and within each such slice the sessions of a class follow one
    another in an order the seed picks, evenly spaced with a jitter. Any
    stretch of the stream then carries the classes in their shares, on
    every seed."""
    rng = np.random.default_rng([int(seed), 0x0DE2])
    by_class = {}
    for r in shp:
        by_class.setdefault(r["ci"], {}).setdefault(r["session"],
                                                    []).append(r)
    keyed = []
    for ci, sessions in sorted(by_class.items()):
        ids = sorted(sessions)
        rank = dict(zip(ids, rng.permutation(len(ids))))
        for sid in ids:
            turns = sorted(sessions[sid], key=lambda r: r["turn"])
            where = (rank[sid] + rng.random()) / len(ids)
            for r in turns:
                keyed.append(((r["turn"] + where) / len(turns), ci, sid, r))
    return [r for *_, r in sorted(keyed, key=lambda x: x[:3])]


def order_balanced(shp, seed, candidates=8):
    """Independent requests: a seeded shuffle that keeps the running
    means of prompt and output length on the whole mix's means. At each
    place the seed draws ``candidates`` of the remaining shapes and the
    one that brings both running means closest is taken. Any stretch of
    the stream then costs the engine about the same on every seed, which
    a free shuffle of heavy-tailed lengths does not (PR 23: tokens/s of
    the backlog cell spread 5% over seeds under a shuffle stratified in
    groups of 16, each seed repeating within 0.3%)."""
    rng = np.random.default_rng([int(seed), 0x57A7])
    rest = list(shp)
    mean_p = float(np.mean([r["prompt_len"] for r in shp]))
    mean_o = float(np.mean([r["out"] for r in shp]))
    sum_p = sum_o = 0.0
    out = []
    while rest:
        k = len(out) + 1
        picks = rng.choice(len(rest), size=min(candidates, len(rest)),
                           replace=False)
        best = min(picks, key=lambda j: (
            abs((sum_p + rest[j]["prompt_len"]) / k - mean_p) / mean_p
            + abs((sum_o + rest[j]["out"]) / k - mean_o) / mean_o))
        r = rest.pop(int(best))
        out.append(r)
        sum_p += r["prompt_len"]
        sum_o += r["out"]
    return out


def arrival_times(n, rate, seed, group=8):
    """n due times: the exponential's mid-quantile gaps at ``rate``,
    accumulated in a seeded order. The order is stratified: the sorted
    gaps are dealt into groups that each span the whole distribution, and
    the seed shuffles inside each group and the groups' order. Every seed
    has the same gaps; any ``group`` consecutive arrivals take about
    ``group / rate`` seconds, so no seed gets a burst another does not."""
    rng = np.random.default_rng([int(seed), 0xA221])
    gaps = np.sort(np.asarray([-math.log(1.0 - (i + 0.5) / n) / rate
                               for i in range(n)]))
    n_groups = max(1, n // group)
    order = []
    for g in rng.permutation(n_groups):
        members = np.arange(g, n, n_groups)
        order += list(members[rng.permutation(len(members))])
    return np.cumsum(gaps[order]) - gaps[0]


def schedule(mix, seed, seconds, vocab):
    """(requests in arrival order, set-up fills). Open loop: 'due' is
    set; backlog: 'due' is None and the list is one cycle of the stream."""
    if mix["loop"] == "open":
        n = max(1, int(round(mix["rate_rps"] * seconds)))
    else:
        n = int(mix["cycle_requests"])
    shp = materialise(mix, shapes(mix, n), seed, vocab)
    fills = setup_fills(mix, shp, seed, vocab)
    independent = all(c["turns"] == 1 for c in mix["classes"])
    order = mix.get("order_seed", seed)
    reqs = (order_balanced(shp, order) if independent
            else order_sessions(shp, order))
    if mix["loop"] == "open":
        due = arrival_times(len(reqs), mix["rate_rps"], order)
        for r, t in zip(reqs, due):
            r["due"] = float(t)
    else:
        for r in reqs:
            r["due"] = None
    return reqs, fills
