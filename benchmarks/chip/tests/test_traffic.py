"""The seed chooses content and order, never the shape of the traffic."""

import numpy as np
import pytest

from chiplib import manifest, traffic

MIXES = ["chat-backlog", "chat-doc-steady"]


def shape_key(r):
    return (r["cls"], r["prompt_len"], r["cached_len"], r["out"])


@pytest.mark.parametrize("name", MIXES)
def test_same_multiset_of_shapes_for_every_seed_in_another_order(name):
    mix = manifest.Files().traffic(name)
    mix.pop("order_seed", None)  # the order from the seed; fixed: below
    runs = [traffic.schedule(mix, seed, 48, 32768)[0]
            for seed in (0, 7, 2 ** 31 + 12345)]
    keys = [[shape_key(r) for r in reqs] for reqs in runs]
    assert sorted(keys[0]) == sorted(keys[1]) == sorted(keys[2])
    assert keys[0] != keys[1] and keys[1] != keys[2]
    # content differs with the seed, and repeats with it
    again = traffic.schedule(mix, 7, 48, 32768)[0]
    assert all(np.array_equal(a["prompt"], b["prompt"])
               for a, b in zip(runs[1], again))
    assert not np.array_equal(runs[0][0]["prompt"][:8],
                              runs[1][0]["prompt"][:8]) \
        or keys[0][0] != keys[1][0]


def test_a_mix_may_fix_the_order_and_leave_the_seed_the_content():
    mix = manifest.Files().traffic("chat-doc-steady")
    a, b = (traffic.schedule(mix, seed, 48, 32768)[0]
            for seed in (7, 2 ** 31 + 12345))
    assert [shape_key(r) for r in a] == [shape_key(r) for r in b]
    assert [r["due"] for r in a] == [r["due"] for r in b]
    assert [(r["cls"], r["session"], r["turn"]) for r in a] \
        == [(r["cls"], r["session"], r["turn"]) for r in b]
    assert not any(np.array_equal(x["prompt"][-8:], y["prompt"][-8:])
                   for x, y in zip(a, b))
    other = traffic.schedule(dict(mix, order_seed=mix["order_seed"] + 1),
                             7, 48, 32768)[0]
    assert sorted(map(shape_key, other)) == sorted(map(shape_key, a))
    assert [shape_key(r) for r in other] != [shape_key(r) for r in a]


def test_open_loop_gaps_are_the_exponentials_quantiles():
    mix = manifest.Files().traffic("chat-doc-steady")
    mix.pop("order_seed", None)  # the order from the seed
    due = [np.asarray([r["due"] for r in traffic.schedule(
        mix, s, 48, 32768)[0]]) for s in (1, 2)]
    n = len(due[0])
    grid = np.asarray([-np.log(1.0 - (i + 0.5) / n) / mix["rate_rps"]
                       for i in range(n)])
    for d in due:  # every gap is one of the grid's, each used once
        gaps = np.sort(np.diff(d))
        rest = np.sort(grid)
        j = np.searchsorted(rest, gaps - 1e-9)
        assert np.allclose(rest[np.minimum(j, n - 1)], gaps)
        assert len(set(j.tolist())) == len(j)
    assert not np.allclose(due[0], due[1])
    assert abs(due[0][-1] - n / mix["rate_rps"]) < 0.1 * n / mix["rate_rps"]
    assert (np.diff(due[0]) >= 0).all()


def test_sessions_keep_their_order_and_share_their_prefix():
    mix = manifest.Files().traffic("chat-doc-steady")
    reqs, fills = traffic.schedule(mix, 3, 48, 32768)
    last = {}
    for r in reqs:
        key = (r["cls"], r["session"])
        assert last.get(key, -1) < r["turn"]
        last[key] = r["turn"]
    chats = [r for r in reqs if r["cls"] == "chat"]
    assert all(np.array_equal(r["prompt"][:512], chats[0]["prompt"][:512])
               for r in chats)
    docs = [r for r in reqs if r["cls"] == "doc-warm" and r["session"] == 0]
    n = docs[0]["context"]
    assert all(np.array_equal(r["prompt"][:n], docs[0]["prompt"][:n])
               for r in docs)
    # set-up fills cover every warm session's context once
    warm_sessions = {(r["ci"], r["session"]) for r in reqs
                     if r["cls"] == "doc-warm"}
    assert len(fills) == len(warm_sessions) + 1  # + the system prompt
    longest = max(r["prompt_len"] + r["out"] for r in reqs)
    assert longest <= 4096


def test_backlog_stream_keeps_its_running_means():
    mix = manifest.Files().traffic("chat-backlog")
    reqs, fills = traffic.schedule(mix, 11, 48, 32768)
    assert not fills and len(reqs) == mix["cycle_requests"]
    work = np.asarray([r["prompt_len"] + 4 * r["out"] for r in reqs])
    for k in (32, 64, 128, 192):  # any prefix costs what the mix costs
        assert abs(work[:k].mean() - work.mean()) < 0.05 * work.mean()
    prompts = sorted(r["prompt_len"] for r in reqs)
    assert 200 <= prompts[128] <= 320 and prompts[-1] <= 2048
    assert max(r["prompt_len"] + r["out"] for r in reqs) <= 4096


def test_inverse_cdf_and_grid():
    table = [[0.0, 10], [0.5, 20], [1.0, 100]]
    assert traffic.inv_cdf(table, 0.25) == 15
    assert traffic.inv_cdf(table, 0.75) == 60
    g = traffic.grid(table, 4)
    assert g == sorted(g) and g[0] >= 10 and g[-1] <= 100
