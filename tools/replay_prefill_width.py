"""Replays ONE request of a benchmark serve cell at several prefill widths
and says where the widths differ: in the tokens served, in the reference's
reading of them, in the program's logits and in the experts the router
chose.

    python3 tools/replay_prefill_width.py --workload serve-conv-moe-backlog \
        --seed 2147487486 --widths 128,512 [--serial K | --window S] \
        [--out FILE]

The cell's model, its seeded weights, the request's tokens and the
reference are the benchmark's own (``benchmarks/chip/chiplib``, read and
never edited); the request is the ``--serial``-th prompt the backlog loop
submits (its shape from the cycle's fixed order, its token ids from the
seed and the serial; default: the cycle's longest, prompt + answer). Nothing here is a device metric: the
tool runs wherever JAX does (on the chip through the chip tool — the
arithmetic in question is the chip's —, at a tiny size on the CPU in
``tests/test_prefill_width.py``).

With ``--window S`` the tool first makes ONE RUN of the cell as
``benchmarks/chip/run.py`` makes it (``S`` seconds, untraced; the run's own
lines and its ``result``), with the harness's ``reference_gaps`` wrapped so
that each sampled request says which it is and where its widest gap lies
(``sampled`` lines: a run prints only the widest of all), and replays the
request whose gap was the widest (unless that is ``--serial``'s: one
replayed before).

Per width ``W``, one JSON line each:

- ``served``: the request ALONE through a ``ServingEngine`` with
  ``prefill_chunk=W`` (the programs a cell runs); where its tokens first
  differ from the first width's;
- ``reference``: the harness's ``reference_gaps`` over what that engine
  served — per served position, how far the served token's logit lies
  under the float32 reference's best (the number ``correct`` bounds): the
  widest, WHERE it is (position in the sequence), the quantiles;
- ``forced``: the prompt and the FIRST width's served tokens fed through
  the family's own prefill program ``W`` positions a call (same weights,
  fresh pools), with the router's choice at every position of every expert
  layer and the logits at every served position taken out of the traced
  program (``route_top_k`` and the family's ``_stack`` are wrapped while it
  is traced; the program's arithmetic is what it is in the engine).

and one ``widths`` line a later width against the first: the (layer,
position) pairs whose chosen experts differ (a ROUTE FLIP), the first of
them each with the first width's margin there (a flip of rounding is one
between two scores next to each other), the widest difference of the two programs' logits over the served
positions and where, and how many positions' first choice differs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def _say(path, line, /, **facts):
    text = json.dumps({"line": line, **facts})
    print(text, flush=True)
    if path:
        with open(path, "a") as f:
            f.write(text + "\n")


def the_request(mix, seed, vocab, serial=None):
    """(prompt ids, answer length, serial) of the ``serial``-th prompt a
    backlog submits (default: the cycle's longest, prompt + answer): its
    shape from the traffic's fixed order, its ids as
    ``chiplib.serve.backlog`` makes that prompt's."""
    from chiplib import traffic

    reqs, _ = traffic.schedule(mix, seed, 1.0, vocab)
    if serial is None:
        serial = max(range(len(reqs)), key=lambda i: reqs[i]["prompt_len"]
                     + reqs[i]["out"])
    r = reqs[serial % len(reqs)]
    return (traffic._tokens(seed, vocab, r["prompt_len"], 9, serial),
            int(r["out"]), serial)


def serve_alone(model, cfg, width, prompt, n_out):
    """The tokens a ``width``-wide engine serves the request, alone."""
    from paddle_tpu.serving import ServingConfig, ServingEngine

    s = cfg["serve"]
    engine = ServingEngine(model, ServingConfig(
        max_lanes=s["max_lanes"], max_seq_len=s["max_seq_len"],
        num_blocks=s.get("num_blocks"), prefill_chunk=width))
    assert engine.prefill_chunk == width, (engine.prefill_chunk, width)
    h = engine.submit(prompt, max_new_tokens=n_out)
    while engine.has_work():
        engine.step()
    assert h.finished and len(h.output) == n_out, (h.state, len(h.output))
    return engine, np.asarray(h.output, np.int32)


# the family's head as its programs apply it, to the logits
_HEADS = {
    "conv_moe": lambda x, p, g, rms: rms(x, p["norm"], g.norm_eps)
    @ p["embed"].T,
    "window_moe": lambda x, p, g, rms: rms(x, p["norm"],
                                           g.layernorm_epsilon)
    @ p["lm_head"],
}


def forced(engine, width, ids, first):
    """``ids`` through the engine's family's prefill program ``width``
    positions a call, from fresh pools. Returns (the router's choice
    ``[expert layers, len(ids), k]`` sorted a position, its margin
    ``[expert layers, len(ids)]``, the float32 logits at positions
    ``first ..``)."""
    import importlib
    import inspect

    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate.distributed.models.moe import held_experts
    from paddle_tpu.models.generation import _rms
    from paddle_tpu.serving.engine import blocks_needed

    fam = engine._family
    if fam.name not in _HEADS:
        raise SystemExit(f"no head written down for family {fam.name!r}")
    module = importlib.import_module(type(fam).__module__)
    program, statics = fam.program("prefill")

    def tapped(params, *operands):
        routes, margins, hidden = [], [], []
        route, stack = held_experts.route_top_k, module._stack

        def tap_route(*a, **k):
            idx, g = route(*a, **k)
            routes.append(jnp.sort(idx, -1))
            # the scores the choice was made of, as ``route_top_k`` does
            given = inspect.signature(route).bind(*a, **k).arguments
            s = jax.nn.sigmoid(jnp.dot(
                given["u"].astype(jnp.float32),
                given["w_router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            if given.get("bias") is not None:
                s = s + given["bias"].astype(jnp.float32)
            top = jax.lax.top_k(s, given["top_k"] + 1)[0]
            margins.append(top[:, -2] - top[:, -1])
            return idx, g

        def tap_stack(*a, **k):
            out = stack(*a, **k)
            hidden.append(out[0])
            return out

        held_experts.route_top_k, module._stack = tap_route, tap_stack
        try:
            _, *pools = program(params, *operands, **statics)
        finally:
            held_experts.route_top_k, module._stack = route, stack
        return pools, jnp.stack(routes), jnp.stack(margins), hidden[0][0]

    run = jax.jit(tapped, donate_argnums=fam.donate_argnums)
    B = engine.config.block_size
    n = int(ids.size)
    blocks = list(range(1, blocks_needed(n, B) + 1))
    pools = tuple(fam.make_pools(len(blocks) + 1, B))
    routes, margins, rows = [], [], []
    for start in range(0, n, width):
        chunk = np.zeros((1, width), np.int32)
        piece = ids[start:start + width]
        chunk[0, :piece.size] = piece
        read = engine._pack_read(
            "prefill", 1, width, [(0, blocks, start, min(start + width, n))],
            slot=0)
        pools, r, m, x = run(engine._params, *pools, *jax.device_put(
            (read, chunk, np.int32(start), np.int32(n), np.int32(0))))
        routes.append(np.asarray(r)[:, :piece.size])
        margins.append(np.asarray(m)[:, :piece.size])
        lo = max(first - start, 0)
        if lo < piece.size:
            rows.append(x[lo:piece.size])
    del pools
    head = jax.jit(lambda x, p: _HEADS[fam.name](
        x, p, fam.gcfg, _rms).astype(jnp.float32))
    return (np.concatenate(routes, 1), np.concatenate(margins, 1),
            np.asarray(head(jnp.concatenate(rows), engine._params)))


def _gap_facts(gaps, first):
    at = int(np.argmax(gaps))
    order = np.argsort(gaps)[::-1][:5]
    return {"widest": float(gaps[at]), "widest_at_served": at,
            "widest_at_position": first + 1 + at,
            "five_widest": [[first + 1 + int(i), float(gaps[i])]
                            for i in order],
            "p50": float(np.quantile(gaps, 0.5)),
            "p90": float(np.quantile(gaps, 0.9)),
            "p99": float(np.quantile(gaps, 0.99)),
            "over_0.1": int((gaps > 0.1).sum()), "n": int(gaps.size)}


def replay(files, workload, seed, widths, serial=None, out=None):
    """Everything of the tool but the argument parsing (``files``: a
    ``chiplib.manifest.Files``; a test brings its own). Returns the lines
    it printed, by name."""
    import jax

    from chiplib import common, manifest, modelbuild
    from chiplib.serve import reference_gaps
    from paddle_tpu.distributed import env as env_mod

    man = files.load()
    cell = manifest.cell(man, workload)
    cfg = files.config(man, cell["config"])
    mix = files.traffic(cell["traffic"])
    arch = files.arch(cfg["arch"])
    vocab = cfg["model"]["vocab_size"]
    prompt, n_out, serial = the_request(mix, seed, vocab, serial)
    first = int(prompt.size) - 1  # the position whose logits pick served[0]
    said = {"served": {}, "forced": {}, "reference": {}, "widths": {}}

    def say(line, width, **facts):
        said[line][width] = facts
        _say(out, line, width=width, **facts)

    _say(out, "request", workload=workload, seed=int(seed), serial=serial,
         prompt_len=int(prompt.size), out=n_out,
         device=jax.devices()[0].device_kind)
    env_mod.init_mesh(dp=1, devices=list(jax.devices()[:1]))
    s = cfg["serve"]
    model, specs, keys, _ = modelbuild.build(
        arch, cfg, cfg["num_hidden_layers"]["serve"], s["max_seq_len"], seed,
        **cfg.get("model_flags", {}))
    model.eval()
    served, routes, margins, logits = {}, {}, {}, {}
    for w in widths:
        engine, served[w] = serve_alone(model, cfg, w, prompt, n_out)
        differ = np.flatnonzero(served[w] != served[widths[0]])
        say("served", w, prefill_chunks=engine.counters["prefill_chunks"],
            first_token_differs_at=int(differ[0]) if differ.size else None,
            tokens_that_differ=int(differ.size))
        ids = np.concatenate([prompt, served[widths[0]]])[:-1]
        routes[w], margins[w], logits[w] = forced(engine, w, ids, first)
        mine = logits[w].argmax(-1)
        say("forced", w, expert_layers=int(routes[w].shape[0]),
            positions=int(ids.size),
            first_choice_is_not_the_served_token=int(
                (mine != served[widths[0]]).sum()))
        del engine
        common.drop_program_state()
    a = widths[0]
    for w in widths[1:]:
        flips = np.argwhere((routes[w] != routes[a]).any(-1))  # [layer, pos]
        flips = flips[np.argsort(flips[:, 1], kind="stable")]
        at_flips = margins[a][flips[:, 0], flips[:, 1]]
        diff = np.abs(logits[w] - logits[a])
        at = np.unravel_index(int(diff.argmax()), diff.shape)
        say("widths", w, against=a, route_flips=int(len(flips)),
            of=int(routes[a].shape[0] * routes[a].shape[1]),
            positions_with_a_flip=int(np.unique(flips[:, 1]).size),
            first_flips=[[int(li), int(p), float(m)] for (li, p), m in
                         zip(flips[:12], at_flips)],
            margin_at_flips_max=float(at_flips.max()) if len(flips) else None,
            margin_p50_everywhere=float(np.quantile(margins[a], 0.5)),
            flips_in_the_served_span=int((flips[:, 1] >= first).sum()),
            widest_logit_difference=float(diff.max()),
            widest_at_position=first + int(at[0]), widest_at_token=int(at[1]),
            logit_difference_p50_of_row_max=float(
                np.quantile(diff.max(-1), 0.5)),
            logit_difference_p99_of_row_max=float(
                np.quantile(diff.max(-1), 0.99)),
            first_choices_that_differ=int(
                (logits[w].argmax(-1) != logits[a].argmax(-1)).sum()))
    # the reference last, with the device to itself (as a run has it)
    del model, routes, margins, logits
    env_mod.reset_env()
    common.drop_program_state()
    ref = files.reference(cfg["reference"])
    for w, g in zip(widths, reference_gaps(
            ref, cfg, specs, keys, [(prompt, served[w]) for w in widths])):
        say("reference", w, limit=files.limits(workload)["served_logit_gap"],
            **_gap_facts(g["gaps"], first))
    return said


def window(files, workload, seed, seconds, out=None, require_chip=True):
    """One run of the cell (``run.run_cell``, untraced) in which every
    sampled request says what the reference read of it (``require_chip``
    False: a test's, on the CPU). Returns (the run's result, the
    ``sampled`` facts, widest gap first)."""
    import time

    import run as runner
    from chiplib import manifest, serve, traffic

    man = files.load()
    cell = manifest.cell(man, workload)
    vocab = files.config(man, cell["config"])["model"]["vocab_size"]
    reqs, _ = traffic.schedule(files.traffic(cell["traffic"]), seed, 1.0,
                               vocab)
    said, inner = [], serve.reference_gaps

    def serial_of(prompt):
        """Which submitted prompt of the backlog this is, by its ids."""
        for k in range(64 * len(reqs)):
            if reqs[k % len(reqs)]["prompt_len"] == prompt.size \
                    and np.array_equal(prompt, traffic._tokens(
                        seed, vocab, prompt.size, 9, k)):
                return k
        return None

    def told(ref, cfg, specs, keys, samples, **kw):
        gaps = inner(ref, cfg, specs, keys, samples, **kw)
        for (prompt, served), g in zip(samples, gaps):
            said.append({"serial": serial_of(prompt),
                         "prompt_len": int(prompt.size),
                         "served": int(served.size),
                         **_gap_facts(g["gaps"], int(prompt.size) - 1)})
            _say(out, "sampled", **said[-1])
        return gaps

    serve.reference_gaps = told
    try:
        result = runner.run_cell(workload, seed, seconds, 0, files=files,
                                 t_start=time.perf_counter(),
                                 require_chip=require_chip)
    finally:
        serve.reference_gaps = inner
    _say(out, "result", **result)
    return result, sorted(said, key=lambda s: -s["widest"])


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-conv-moe-backlog")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--widths", default="128,512")
    ap.add_argument("--serial", type=int, default=None,
                    help="which submitted prompt of the backlog (0 is the "
                    "first); default: the cycle's longest")
    ap.add_argument("--window", type=float, default=0.0,
                    help="first a run of the cell of this many seconds; "
                    "the request replayed is its sample's widest gap")
    ap.add_argument("--out", default="", help="append the lines here too")
    args = ap.parse_args()
    from chiplib import common, manifest

    common.enable_compile_cache()
    files, serial = manifest.Files(), args.serial
    if args.window:
        _, sampled = window(files, args.workload, args.seed, args.window,
                            args.out)
        serial = sampled[0]["serial"]
        if serial == args.serial:  # given with --window: replayed before
            return
    replay(files, args.workload, args.seed,
           [int(w) for w in args.widths.split(",")], serial, args.out)


if __name__ == "__main__":
    main()
