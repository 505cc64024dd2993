"""Time ONE layer's live-rows read, call by call, at the served geometries
of the six serving families: the fused kernel
(``ops/pallas/row_attention.py``) and, where the tree has one, the XLA
read over the same rows (``dense_gqa._attend_rows``; before PR 47
``latent_moe._attend_rows``). A kernel PR starts from this table
(PERF.md section 6, PR 47), not from a rewrite:

    chiprun -- python tools/bench_row_read.py --out chiprun_out/reads.json
    ... --tree .bench_checkout/parent   # the same calls on another checkout
    ... --sweep                         # the latent form's constants too
    JAX_PLATFORMS=cpu python tools/bench_row_read.py --smoke   # tiny, CPU

A call is ``--layers`` layers' reads of one stacked pool in ONE jitted
program, as a step program makes them (a single read is under what the
host takes to dispatch a call, ~0.25 ms), timed as PR 35's and PR 39's
single-call scripts timed theirs: 2 warm calls, then ``--calls``
dispatched back to back over the host clock and one fence
(``utils/timing.device_sync``); ``ms`` is
device time a LAYER. Inputs come from fixed seeds (the same in every
tree: ``probe`` holds a few outputs to lay side by side).
``least_ms`` is the larger of the read's FLOP at the chip's bfloat16 peak
and its live cache bytes at the HBM rate. Without a TPU it raises, unless
``--smoke`` asks for tiny shapes on the CPU (interpret mode: no time
means anything there).
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

# TPU v5e's published peaks (benchmarks/chip/chiplib/peaks.py)
PEAK_FLOPS, HBM_RATE = 197e12, 819e9

# family (the configuration served): lanes, heads, KV heads (0: the latent
# pool's one shared head), key width, value width, prefill width, the
# tokens a lane holds in its cell's rounds
GEOMETRIES = {
    "latent_moe": ("openpangu-ultra-moe-718b", 64, 128, 0, 640, 512, 128, 720),
    "linear_latent_moe": ("kimi-linear-48b-a3b", 64, 32, 0, 640, 512, 128,
                          720),
    "dense_gqa": ("mistral-7b-v0.3", 32, 32, 8, 128, 128, 128, 600),
    "hybrid_ssm": ("granite-4.0-h-micro", 64, 32, 8, 64, 64, 128, 720),
    "window_moe": ("mimo-v2.5 (full layers)", 64, 64, 4, 192, 128, 512,
                   3100),
    "conv_moe": ("lfm2-24b-a2b", 64, 32, 8, 64, 64, 512, 3100),
}
CHUNK_CONTEXTS = (0, 896, 1920)  # PR 35's three
BLOCK = 16
# the latent form's constants (row_attention.py; the family's ROW_BLOCKS)
# that ``--sweep`` steers: (blocks a row, _Q_ROWS, _SIDE_ROWS)
SWEEP = [(16, 128, 128), (16, 128, 256), (16, 128, 640), (16, 64, 640),
         (16, 320, 320), (16, 320, 640), (16, 640, 640), (32, 128, 640),
         (32, 320, 640), (32, 640, 640), (8, 128, 640)]


def _calls(lanes, width, ctx, smoke):
    """(label, lanes of the call, positions, tokens each lane holds)."""
    import numpy as np

    rng = np.random.RandomState(7)
    held = rng.randint(ctx // 2, ctx * 3 // 2 + 1, size=lanes)
    out = [("round_1", lanes, 1, held), ("round_5", lanes, 5, held)]
    for c in CHUNK_CONTEXTS[:1 if smoke else None]:
        out.append((f"chunk_at_{c}", 1, width, np.asarray([c])))
    return out


def _tile(b):
    """The rows operand's ``tile`` (what the XLA read runs at a time): a
    prefill chunk's, a round's (``families/common.py``)."""
    return 4 if b == 1 else 16


def _operands(geom, b, s, held, w, layers, smoke):
    """One call's operands on the device: queries, positions, the packed
    rows, a stacked pool of ``layers`` layers a pool."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.serving.engine import fit_rows, pack_rows

    _, _, nh, nkv, d, dv, _, _ = geom
    dt = jnp.float32 if smoke else jnp.bfloat16
    table = -(-(int(held.max()) + s) // BLOCK)
    nb = 1 + b * table
    ids = np.random.RandomState(3).permutation(np.arange(1, nb)).reshape(
        b, table).astype(np.int32)
    w, _, cap = fit_rows((w, _tile(b)), b, table)
    rows, _, n, live = pack_rows(
        [(i, ids[i], int(held[i]), int(held[i]) + s) for i in range(b)],
        b, s, BLOCK, w, cap)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = (jax.random.normal(keys[0], (b, s, nh, d), jnp.float32) * 0.3
         ).astype(dt)
    pools = [jax.random.normal(k, (layers, nb, BLOCK, max(nkv, 1) * width),
                               dt) * 0.5
             for k, width in zip(keys[1:], (d,) if not nkv else (d, dv))]
    pos = jnp.asarray(held[:, None] + np.arange(s)[None, :], jnp.int32)
    return q, pos, jnp.asarray(rows), pools, n, live


def _reads(geom, b, s, layers, smoke):
    """{name: jitted program(q, pos, rows, *pools)} of what this tree
    has: every layer of the pools read once, the outputs added up."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.row_attention import row_attention
    from paddle_tpu.serving.families import dense_gqa

    _, _, nh, nkv, d, dv, _, _ = geom
    out = {}
    if nkv:
        scale = d ** -0.5
        out["kernel"] = lambda li, q, pos, rows, kp, vp: row_attention(
            q, pos, rows, kp, vp, li, nkv, scale)

        def xla(li, q, pos, rows, kp, vp):
            nb, B = kp.shape[1:3]

            def gather(blocks):
                at = blocks + li * nb
                return tuple(
                    p.reshape(-1, B, nkv, p.shape[3] // nkv)[at].reshape(
                        blocks.shape[0], -1, nkv, p.shape[3] // nkv)
                    for p in (kp, vp))

            return dense_gqa._attend_rows(q, pos, rows, gather, _tile(b),
                                          nkv)

        if d == dv:  # ``_attend_rows`` folds values as wide as the keys
            out["xla"] = xla
        return {k: _program(v, layers) for k, v in out.items()}

    from paddle_tpu.models import latent_moe as model
    from paddle_tpu.serving.families import latent_moe as latent

    class cfg:
        kv_lora_rank, qk_rope_head_dim, qk_nope_head_dim = dv, 64, 128
        v_head_dim, num_attention_heads = 128, nh

    w_v = (jax.random.normal(jax.random.PRNGKey(5), (dv, nh * 256),
                             jnp.float32) * 0.02).astype(
        jnp.float32 if smoke else jnp.bfloat16)
    lp = {"kv_b": w_v}
    if "dv" in inspect.signature(row_attention).parameters:
        def kernel(li, qq, pos, rows, pool):
            o_lat = row_attention(qq, pos, rows, pool, None, li, 1,
                                  192 ** -0.5, dv=dv)
            return model.unabsorb_output(o_lat, lp, cfg)

        out["kernel"] = kernel
    if hasattr(latent, "_attend_rows"):  # a tree before PR 47
        def xla(li, qq, pos, rows, pool):
            nb, B, W = pool.shape[1:]

            def gather(blocks):
                return pool.reshape(-1, B, W)[blocks + li * nb].reshape(
                    blocks.shape[0], -1, W)

            def attend(qq, pos):
                return latent._attend_rows(
                    qq, pos, rows, gather, latent.read_form(
                        "prefill" if b == 1 else "verify")[1], lp, cfg)

            if s > latent.QUERY_TILE and s % latent.QUERY_TILE == 0:
                return latent._attend_tiles(
                    qq, pos, s // latent.QUERY_TILE, attend, nh * 128)
            return attend(qq, pos)

        out["xla"] = xla
    return {k: _program(v, layers) for k, v in out.items()}


def _program(read, layers):
    import jax

    def program(*operands):
        outs = [read(li, *operands) for li in range(layers)]
        return sum(outs[1:], outs[0])

    return jax.jit(program)


def _time(fn, args, calls):
    from paddle_tpu.utils.timing import device_sync  # the repo's fence

    for _ in range(2):
        t = time.perf_counter()
        out = device_sync(fn(*args))
    if time.perf_counter() - t > 0.1:  # (the float32 XLA read of a K/V
        calls = 1                      # family's round: seconds a call)
    t = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    device_sync(out)
    return (time.perf_counter() - t) / calls * 1e3, out


def _least_ms(geom, b, s, held, live_blocks):
    """The read's FLOP at the peak, its live cache's bytes at the HBM
    rate: (the larger of the two in ms, which)."""
    _, _, nh, nkv, d, dv, _, _ = geom
    seen = float(sum(s * (int(h) + (s + 1) / 2) for h in held))
    flops = 2 * nh * (d + dv) * seen
    nbytes = live_blocks * BLOCK * 2 * max(nkv, 1) * (d + (dv if nkv else 0))
    by = {"flops": flops / PEAK_FLOPS * 1e3, "bytes": nbytes / HBM_RATE * 1e3}
    which = max(by, key=by.get)
    return by[which], which


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout whose reads run")
    ap.add_argument("--families", default=",".join(GEOMETRIES))
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--layers", type=int, default=5)
    ap.add_argument("--sweep", action="store_true",
                    help="the latent form's constants, verify round + chunk")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    import jax
    import numpy as np

    from paddle_tpu.framework.device import on_tpu

    if not on_tpu() and not args.smoke:
        raise SystemExit("bench_row_read: no TPU (use --smoke on the CPU)")
    lines = []

    def emit(**line):
        lines.append(line)
        print(json.dumps(line), flush=True)

    def run(fam, w, only=None, **tag):
        geom = GEOMETRIES[fam]
        if args.smoke:  # a few heads of the same widths, short contexts
            geom = (geom[0], 3, max(geom[3], 1) * 2, geom[3], *geom[4:6],
                    16, 40)
        for label, b, s, held in _calls(geom[1], geom[6], geom[7],
                                        args.smoke):
            if only and label not in only:
                continue
            q, pos, rows, pools, n, live = _operands(
                geom, b, s, held, w, args.layers, args.smoke)
            least, which = _least_ms(geom, b, s, held, live)
            for name, fn in _reads(geom, b, s, args.layers,
                                   args.smoke).items():
                ms, out = _time(fn, (q, pos, rows, *pools), args.calls)
                ms /= args.layers
                probe = np.asarray(out[0, -1].reshape(-1)[:6], np.float32)
                emit(family=fam, served=geom[0], call=label, read=name,
                     lanes=b, positions=s, live_rows=n, row_blocks=w,
                     ms=round(ms, 4), least_ms=round(least, 4),
                     least_by=which, pct=round(100 * least / ms, 1),
                     probe=[round(float(x), 4) for x in probe], **tag)

    fams = [f for f in args.families.split(",") if f]
    for fam in fams:
        run(fam, 16)
    if args.sweep:
        from paddle_tpu.ops.pallas import row_attention as RA

        kept = RA._Q_ROWS, RA._SIDE_ROWS
        for fam in (f for f in fams if not GEOMETRIES[f][3]):
            for w, q_rows, side in SWEEP:
                RA._Q_ROWS, RA._SIDE_ROWS = q_rows, side
                jax.clear_caches()
                run(fam, w, only=("round_5", "chunk_at_896"),
                    q_rows=q_rows, side_rows=side)
        RA._Q_ROWS, RA._SIDE_ROWS = kept
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
