"""Plain reference for a latent-attention (MLA), sparse-expert decoder
with sandwich norm (openPangu-Ultra-MoE-718B's ``config.json``; the layer
equations are ISSUE 27's, written out in ``paddle_tpu/models/latent_moe.py``'s
docstring too): forward pass, the next-next-token (MTP) module and a loss
in ``jax.numpy``, float32, matmul precision "highest". No kernels, no
cache, no sorting, no batching, nothing imported from the program.

Attention is the PUBLISHED, non-absorbed form: keys and values are
expanded from the latent for every position (``[k_nope | v] = c_kv
W_kvb``) and scores are ``(q_nope.k_nope + q_rope.k_rope) / sqrt(nope +
rope)``, so the program's absorbed form is checked against it. It runs in
blocks of query rows (scores ``[heads, rows, T]``: 128 x 512 x 3584 x 4 B
= 0.94 GB a block) so a 3584-token sequence fits beside a layer's
float32 weights. Routing is ``top_k`` on float32 sigmoid scores; each
held expert is run over every token and weighted by the gate the token
gave it (zero where it was not chosen).

**The chip's share.** ``m["n_routed_experts"]`` experts are held, from
``m["first_held_expert"]`` on, of ``m["router_experts"]`` that are routed
over; the gate is normalised over all ``num_experts_per_tok`` chosen
experts, the sum runs over the chosen experts that are held, the shared
expert is whole. What absent experts would add is left out, as in the
program, and that partial result goes on.

Departures from the source, all exact re-arrangements or stated
assumptions (the configuration file lists the latter under ``assumed``):
W is ``[in, out]``, applied as ``x @ W``; gate and up arrive fused as
``gate_up`` (gate first), per expert too (``[experts, in, 2 * width]``);
``q_b``'s and ``kv_b``'s output axes are ``[heads, nope | rope]`` and
``[heads, nope | v]``; ``kv_a``'s is ``[latent | rope]``; RoPE pairs
``(x_i, x_{i+d/2})`` (with seeded weights a permutation of the rotary
dims is a relabelling); sigmoid scoring with no group limit and no
correction bias; the sandwich norm's placement (a norm on each
sub-layer's input and on its output, before the residual add); the MTP
module is DeepSeek-V3's, sharing embedding, final norm and head.

**Near-ties at the router, and what the harness's walk gets.** The
router picks the ``k`` largest of ``router_experts`` scores, and the
program computes them from bfloat16 activations. At the published widths
the ``k``-th and ``(k+1)``-th of 256 logits lie 0.05 of their spread apart
on average and bfloat16 moves the difference of two logits by 0.01 of it:
the program chooses another set than float32 for about one token in ten,
a layer (a bfloat16-rounded copy of this file on the CPU: PERF.md section
6, PR 27, second session). Where that adds or removes a HELD expert the
layer's output moves by one gate's worth (~30% before its norm), and the
served token's logit gap at that position says who won a coin toss, not how
precisely the model was computed — it drowned the fp8 control's readings.
Which unheld experts are chosen changes nothing this chip computes (the
gates' sum moves by the difference of two near-equal scores).

So ``layer_forward`` with ``quant`` false — the float32 chain of the
harness's layer-by-layer walk — carries ALTERNATES beside the sequence:
``x`` is ``[T, STREAMS, hidden + 1]``; stream 0 is the reference's own
routing everywhere, streams 1.. the same position under another routing,
the last channel a mark (streams 1..: 1 where live; stream 0: 2 where the
position is left out, see below). In an expert layer a held expert is
UNDECIDED for a token where its logit lies within ``TIE_MARGIN`` x the
spread (standard deviation over the experts) of that token's logits from
the boundary, the midpoint of the ``k``-th and ``(k+1)``-th. With one
undecided held expert the position gets an alternate: that expert's
membership toggled (it changes places with the unheld expert just across
the boundary, gates normalised again). An alternate attends the sequence's
own (stream 0) entries before its position and its own entry at it, goes
through the following layers as a row of its own, and spawns further
alternates where it meets an undecided expert itself, so every
combination of toggles at one position is followed. A position is LEFT
OUT of the comparison (the mark; ``head_logits`` gives it a gap of 0)
where one of its rows meets two or more undecided held experts at once,
or needs more alternates than ``STREAMS - 1``. What a toggle at an
EARLIER position does to later ones through attention stays in the
reading. ``head_logits`` on such an ``x`` returns, per position, the
largest over the live streams of ``logits - max(logits)``: the harness's
``best - logits[token]`` then reads the token's gap under the routing that
suits it best, for the served token and for the control's first choice
alike. The control's own chain (``quant`` true) and the whole-model forms
carry no alternates. ``coverage`` counts what a sequence's marks say.

``quant`` is the CONTROL: every linear layer's two operands fake-quantised
to an 8-bit float (e4m3, per-tensor scale) — the nearest precision below
the configuration's bfloat16. The router's product is left in float32:
the published gate computes in float32 whatever the model's precision,
so a lower-precision deployment would too, and a control that merely
re-routed tokens would say nothing about the limit. It has to fail the
comparison that the program passes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROWS = 512  # query rows a block of attention
# the alternates of ``layer_forward`` (module docstring). TIE_MARGIN: a held
# expert is undecided within 0.03 of the token's logit spread from the
# boundary, so two logits up to 0.06 apart may change places: six times
# the 0.010 (rms) by which bfloat16 activations move the difference of two
# router logits at the published widths (a bfloat16-rounded copy of this
# file on the CPU, where every margin from 0.02 to 0.06 read the same:
# PERF.md section 6, PR 27, second session). A position carries its own
# routing and STREAMS - 1 others (more make no difference: what is left
# out is two undecided experts at once); a layer computes at most 1 /
# ALT_SHARE of the sequence's length of them.
TIE_MARGIN = 0.03
STREAMS = 4
ALT_SHARE = 2


def fp8(x):
    """Fake-quantise to an 8-bit float (4 exponent bits, 3 mantissa bits,
    largest finite value 240) with a per-tensor scale; the gradient
    passes straight through. ``reduce_precision`` and not a cast pair:
    the TPU compiler may elide float32 -> float8 -> float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    q = jax.lax.reduce_precision(x / s, exponent_bits=4,
                                 mantissa_bits=3) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, quant):
    if quant:
        x, w = fp8(x), fp8(w)
    return jnp.matmul(x, w, precision="highest")


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [T, H, d]; pairs (x_i, x_{i+d/2}) rotated by pos * theta^(-2i/d)."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, w_gate_up, w_down, quant):
    gate, up = jnp.split(_mm(x, w_gate_up, quant), 2, axis=-1)
    return _mm(jax.nn.silu(gate) * up, w_down, quant)


def attention(a, lw, m, quant, pos=None, n_seq=None):
    """Latent attention, ``a`` [R, hidden] already normed. The first
    ``n_seq`` rows are one sequence in order (all of them by default); any
    further rows are alternates of the position ``pos[r]`` (module
    docstring). Every row attends the sequence's keys BEFORE its position
    and its own entry at it — for a row of the sequence that is plain
    causal attention. Non-absorbed, blocked over query rows."""
    R = a.shape[0]
    T = R if n_seq is None else n_seq
    if pos is None:
        pos = jnp.arange(R)
    nh = m["num_attention_heads"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    dc, eps, theta = m["kv_lora_rank"], m["rms_norm_eps"], m["rope_theta"]
    c_q = rms_norm(_mm(a, lw["q_a"], quant), lw["q_norm"], eps)
    q = _mm(c_q, lw["q_b"], quant).reshape(R, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos, theta)
    kv = _mm(a, lw["kv_a"], quant)
    c_kv = rms_norm(kv[:, :dc], lw["kv_norm"], eps)
    k_rope = rope(kv[:, None, dc:], pos, theta)[:, 0]      # [R, dr]
    kv_up = _mm(c_kv, lw["kv_b"], quant).reshape(R, nh, dn + dv)
    k_nope, v = kv_up[..., :dn], kv_up[..., dn:]
    scale = 1.0 / jnp.sqrt(F32(dn + dr))

    def block(start):
        r = jnp.minimum(start + jnp.arange(ROWS), R - 1)
        qn, qr, at = q_nope[r], q_rope[r], pos[r]
        s = (jnp.einsum("rhd,thd->hrt", qn, k_nope[:T], precision="highest")
             + jnp.einsum("rhd,td->hrt", qr, k_rope[:T],
                          precision="highest")) * scale
        s = jnp.where(jnp.arange(T)[None, None, :] < at[None, :, None], s,
                      -jnp.inf)
        own = (jnp.sum(qn * k_nope[r], -1)
               + jnp.sum(qr * k_rope[r][:, None, :], -1)) * scale  # [r, h]
        p = jax.nn.softmax(jnp.concatenate([s, own.T[..., None]], -1), -1)
        return jnp.einsum("hrt,thd->rhd", p[..., :T], v[:T],
                          precision="highest") \
            + p[..., T].T[..., None] * v[r]

    n_blocks = -(-R // ROWS)
    out = jax.lax.map(block, jnp.arange(n_blocks) * ROWS)
    out = out.reshape(n_blocks * ROWS, nh * dv)[:R]
    return _mm(out, lw["o"], quant)


def _experts(u, lw, m, quant, alternate):
    """(the held experts' share of the routed sum plus the shared expert;
    the same with the undecided held expert's membership toggled; whether
    there is exactly one such expert; whether there are more: module
    docstring). ``u`` [T, hidden] already normed. Without ``alternate``,
    or where every expert is chosen, the last three are None."""
    k = m["num_experts_per_tok"]
    first, held = m.get("first_held_expert", 0), m["n_routed_experts"]
    z = jnp.matmul(u, lw["router"], precision="highest")
    s = jax.nn.sigmoid(z)
    alternate = alternate and k < z.shape[-1]  # else nothing to change with
    vals, idx = jax.lax.top_k(s, k + alternate)
    top_s, top_i = vals[:, :k], idx[:, :k]

    def gates(s_k):
        return s_k / (jnp.sum(s_k, -1, keepdims=True) + 1e-20) \
            * m["routed_scaling_factor"]

    chosen = [(top_i, gates(top_s))]
    one = crowd = None
    if alternate:
        edge = jnp.mean(jnp.take_along_axis(z, idx[:, k - 1:], -1), -1)
        far = jnp.abs(z[:, first:first + held] - edge[:, None])
        near = far < TIE_MARGIN * jnp.std(z, -1)[:, None]
        one, crowd = jnp.sum(near, -1) == 1, jnp.sum(near, -1) > 1
        e = first + jnp.argmin(jnp.where(near, far, jnp.inf), -1)
        inside = jnp.any(top_i == e[:, None], -1)[:, None]
        # leaving, its place takes the (k+1)-th; entering, it takes the k-th's
        place = jnp.where(inside, top_i == e[:, None],
                          jnp.arange(k)[None, :] == k - 1)
        chosen.append((
            jnp.where(place, jnp.where(inside, idx[:, k:], e[:, None]),
                      top_i),
            gates(jnp.where(place, jnp.where(
                inside, vals[:, k:], jnp.take_along_axis(s, e[:, None], -1)),
                top_s))))

    def run(e, ys):  # a loop, not 16 copies of the expert in the program
        out = swiglu(
            u, jax.lax.dynamic_index_in_dim(lw["experts_gate_up"], e, 0,
                                            False),
            jax.lax.dynamic_index_in_dim(lw["experts_down"], e, 0, False),
            quant)
        return tuple(
            y + jnp.sum(jnp.where(ids == first + e, g, 0.0), -1)[:, None]
            * out for y, (ids, g) in zip(ys, chosen))

    shared = swiglu(u, lw["shared_gate_up"], lw["shared_down"], quant)
    ys = jax.lax.fori_loop(0, held, run, (shared,) * len(chosen))
    return ys[0], ys[-1], one, crowd


def experts(u, lw, m, quant):
    """The held experts' share of the routed sum, plus the shared expert.
    ``u`` [T, hidden] already normed."""
    return _experts(u, lw, m, quant, False)[0]


def _layer(x, lw, m, quant, pos=None, n_seq=None, alternate=False):
    """One decoder layer on rows ``x`` [R, hidden] (``attention`` says
    what the rows are). Returns (the rows' output; their output with the
    undecided expert toggled, and ``_experts``' two flags, or None)."""
    eps = m["rms_norm_eps"]
    a = attention(rms_norm(x, lw["ln_in"], eps), lw, m, quant, pos, n_seq)
    x = x + rms_norm(a, lw["ln_attn_out"], eps)
    u = rms_norm(x, lw["ln_mlp_in"], eps)
    if "router" not in lw:
        y = swiglu(u, lw["gate_up"], lw["down"], quant)
        return x + rms_norm(y, lw["ln_mlp_out"], eps), None, None, None
    y, y_alt, one, crowd = _experts(u, lw, m, quant, alternate)
    out = x + rms_norm(y, lw["ln_mlp_out"], eps)
    if one is None:
        return out, None, None, None
    return out, x + rms_norm(y_alt, lw["ln_mlp_out"], eps), one, crowd


def layer_forward(x, lw, *, li, m, quant):
    """One decoder layer on one sequence; a dense layer has ``gate_up`` /
    ``down``, an expert layer ``router`` and the expert leaves. ``li``
    (the layer's index, traced) is not needed. The control's chain
    (``quant``) is ``x`` [T, hidden] in and out. The float32 chain takes
    that from the embedding and ``[T, STREAMS, hidden + 1]`` from itself,
    and returns the latter: the sequence, each position's alternates and
    the marks (module docstring)."""
    del li
    if quant:
        return _layer(x, lw, m, True)[0]
    if x.ndim == 2:
        x = jnp.concatenate(
            [x, jnp.ones((x.shape[0], 1), x.dtype)], -1)[:, None]
        x = jnp.pad(x, ((0, 0), (0, STREAMS - 1), (0, 0)))
    T, S, H = x.shape[0], x.shape[1], x.shape[2] - 1
    A, N = S - 1, max(T // ALT_SHARE, 8)
    # the live alternates, gathered into N rows behind the sequence's own
    live = x[:, 1:, H] > 0                                     # [T, A]
    at = jnp.nonzero(live.reshape(-1), size=N, fill_value=0)[0]
    ok = jnp.arange(N) < jnp.sum(live)
    a_pos, a_slot = jnp.where(ok, at // A, T), at % A          # T: nowhere
    rows = jnp.concatenate(
        [x[:, 0, :H],
         jnp.where(ok[:, None], x[:, 1:, :H].reshape(T * A, H)[at], 0)])
    pos = jnp.concatenate([jnp.arange(T), jnp.minimum(a_pos, T - 1)])
    out, out_alt, one, crowd = _layer(rows, lw, m, False, pos, T,
                                      alternate=True)
    marked = jnp.ones((T + N, 1), x.dtype)
    new = jnp.zeros_like(x).at[:, 0].set(
        jnp.concatenate([out[:T], x[:, 0, H:]], -1))
    new = new.at[a_pos, 1 + a_slot].set(
        jnp.concatenate([out[T:], marked[T:]], -1), mode="drop")
    if one is None:
        return new
    # each toggle goes into its position's next free slot: the sequence's
    # own first, then its alternates' in slot order
    real = jnp.concatenate([jnp.ones(T, bool), ok])
    one, crowd = one & real, crowd & real
    one_alt = jnp.zeros((T, A), bool).at[a_pos, a_slot].set(one[T:],
                                                            mode="drop")
    before = jnp.cumsum(one_alt, -1) - one_alt
    free = jnp.sum(live, -1)                                   # [T]
    p = jnp.minimum(a_pos, T - 1)
    slot = jnp.concatenate([free, free[p] + one[:T][p] + before[p, a_slot]])
    to = jnp.concatenate([jnp.arange(T), a_pos])
    new = new.at[to, 1 + jnp.where(one, slot, A)].set(
        jnp.concatenate([out_alt, marked], -1), mode="drop")
    # left out: a row met several undecided experts, no slot was free, or
    # an alternate found no room among the N rows and was lost
    lost = live & (jnp.cumsum(live.reshape(-1)).reshape(T, A) > N)
    left = jnp.zeros(T, jnp.int32).at[to].max(
        (crowd | (one & (slot >= A))).astype(jnp.int32), mode="drop") > 0
    left = left | jnp.any(lost, -1)
    return new.at[:, 0, H].max(jnp.where(left, 2.0, 0.0))


def head_logits(x, top, *, m, quant):
    """Logits of rows ``x`` [K, hidden] of the stack's output: the final
    norm, then the head. On rows with alternates, ``x`` [K, STREAMS,
    hidden + 1]: per row the largest, over its live streams, of ``logits -
    max(logits)`` — 0 at a stream's first choice, and at every other
    token minus the smallest gap any stream gives it; all 0 on a row that
    is left out (module docstring)."""
    def plain(h):
        return _mm(rms_norm(h, top["norm"], m["rms_norm_eps"]),
                   top["lm_head"], quant)

    if x.ndim == 2:
        return plain(x)
    H = x.shape[2] - 1
    best = None
    for s in range(x.shape[1]):
        lg = plain(x[:, s, :H])
        lg = lg - jnp.max(lg, -1, keepdims=True)
        if s:
            lg = jnp.where(x[:, s, H:] > 0, lg, -jnp.inf)
        best = lg if best is None else jnp.maximum(best, lg)
    return jnp.where(x[:, 0, H:] > 1, 0.0, best)


def coverage(x):
    """Of the float32 chain's ``x`` [K, STREAMS, hidden + 1]: (positions,
    positions with an alternate, positions left out)."""
    H = x.shape[2] - 1
    return (x.shape[0], int(jnp.sum(jnp.any(x[:, 1:, H] > 0, -1))),
            int(jnp.sum(x[:, 0, H] > 1)))


# -- whole-model forms, for the CPU tests --------------------------------------

def hidden_states(params, ids, m, quant=False):
    """The stack's output [T, hidden] of one sequence, BEFORE the final
    norm. ``params``: top-level leaves and ``layers`` (a list of leaf
    dicts), as ``chiplib.modelbuild.reference_params`` builds them."""
    x = params["embed"][ids]
    for lw in params["layers"]:
        x = _layer(x, lw, m, quant)[0]
    return x


def forward(params, ids, m, quant=False):
    """Logits [T, vocab] of one sequence."""
    return head_logits(hidden_states(params, ids, m, quant), params, m=m,
                       quant=quant)


def mtp_logits(params, ids, m, hidden=None, quant=False):
    """[T-1, vocab]: at position i the logits for token i+2, from the
    stack's output at i and the embedding of token i+1 (``params["mtp"]``:
    ``e_norm``, ``h_norm``, ``proj`` [2 hidden, hidden] and ``layer``, an
    expert layer's leaves)."""
    if hidden is None:
        hidden = hidden_states(params, ids, m, quant)
    p, eps = params["mtp"], m["rms_norm_eps"]
    mixed = _mm(jnp.concatenate(
        [rms_norm(params["embed"][ids[1:]], p["e_norm"], eps),
         rms_norm(hidden[:-1], p["h_norm"], eps)], -1), p["proj"], quant)
    return head_logits(_layer(mixed, p["layer"], m, quant)[0], params, m=m,
                       quant=quant)


def _xent(logits, labels):
    return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[:, None], -1)[:, 0]


def loss_fn(params, ids, labels, m, mtp_weight=0.0, quant=False):
    """Mean cross-entropy of ``ids`` / ``labels`` [B, T] (labels already
    shifted), plus ``mtp_weight`` x the MTP module's on token i+2."""
    main, extra = [], []
    for r in range(ids.shape[0]):
        h = hidden_states(params, ids[r], m, quant)
        main.append(_xent(head_logits(h, params, m=m, quant=quant),
                          labels[r]))
        if mtp_weight:
            extra.append(_xent(mtp_logits(params, ids[r], m, h, quant),
                               labels[r][1:]))
    loss = jnp.mean(jnp.stack(main))
    if mtp_weight:
        loss = loss + mtp_weight * jnp.mean(jnp.stack(extra))
    return loss
