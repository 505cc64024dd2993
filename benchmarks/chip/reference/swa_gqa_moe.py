"""Plain reference for a window-attention / full-attention sparse-expert
decoder (MiMo-V2.5's ``config.json``, ``model_type`` ``mimo_v2``; the layer
equations are ISSUE 38's, written out in ``paddle_tpu/models/window_moe.py``'s
docstring too): the forward pass in ``jax.numpy``, float32, matmul
precision "highest". No kernels, no cache, no ring, no rows, no sorting, no
batching, nothing imported from the program. The helpers every reference
shares (``fp8``, ``_mm``, ``rms_norm``, ``swiglu``, the head over
alternates, ``coverage``) are ``reference/mla_moe.py``'s, loaded from the
file beside this one.

Plain pre-norm: ``x <- x + Attn(N(x; ln_in))``, ``x <- x + FFN(N(x;
ln_post))``; the three kinds of layer are told apart by ``lw``'s keys.

- Attention, both kinds: ``[q | k | v] = a qkv`` with 64 query heads of
  ``head_dim`` and ``G`` key/value heads (keys ``head_dim`` wide, values
  ``v_head_dim``); the FIRST ``int(head_dim x partial_rotary_factor)``
  columns of every q and k head rotated (rotate-half), the rest as they
  are; ``v`` times ``attention_value_scale``; scores ``q.k /
  sqrt(head_dim)`` over WHOLE sequences in blocks of query rows (64 heads x
  9.7k x 9.7k float32 scores at once would be 24 GB).
- A layer with ``sink`` is a WINDOW layer (``swa_num_key_value_heads``,
  ``swa_rope_theta``): the band ``0 <= t - j < sliding_window`` is a mask
  of its own over the whole sequence's scores, and the sink is one more
  column of logits (one a head, the same for every row) that the softmax
  runs over and that is then dropped: it takes mass and gives no value.
- A layer without is a FULL layer (``num_key_value_heads``,
  ``rope_theta``): plain causal softmax.
- A layer with ``router`` has the expert layer: float32 sigmoid scores,
  the ``k`` largest of ``s + router_bias``, gates ``s / sum(s)`` over the
  chosen (times ``routed_scaling_factor``; null is 1); each held expert is
  run over every token and weighted by the gate the token gave it; what
  absent experts would add is left out (``reference/mla_moe.py``: the
  chip's share); NO shared expert. One without has a dense SwiGLU
  (``gate_up`` / ``down``).

Departures from the published code, all exact re-arrangements or stated
assumptions (the configuration file lists the latter under ``assumed``):
W is ``[in, out]``, applied as ``x @ W``; q, k, v arrive as one matrix
``qkv`` (q first: ``attention_projection_layout`` ``fused_qkv``); gate and
up arrive fused (gate first), per expert too; the sink is one logit a
head; ``attention_chunk_size`` is the same window under an older key.

**Near-ties at the router** are followed as ``reference/mla_moe.py``
follows them (its docstring says why): the float32 chain of the harness's
walk carries ALTERNATES, ``x`` ``[T, STREAMS, hidden + 1]``, with the
undecided expert found on the selection score as
``reference/kda_mla_moe.py`` finds it. An alternate row goes through the
following layers on its own: it attends the sequence's own keys before
its position (inside the band, in a window layer) and its own at it.

``quant`` is the CONTROL: every linear layer's two operands fake-quantised
to an 8-bit float (e4m3, per-tensor scale) — the nearest precision below
the configuration's bfloat16. The router's product, the rotary embedding
and the attention products stay float32, as in the other references. It
has to fail the comparison that the program passes.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp


def _beside(name):
    spec = importlib.util.spec_from_file_location(
        "chip_reference_" + name, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_R = _beside("mla_moe")
F32, ROWS = _R.F32, _R.ROWS
TIE_MARGIN, STREAMS, ALT_SHARE = _R.TIE_MARGIN, _R.STREAMS, _R.ALT_SHARE
fp8, _mm, rms_norm, swiglu = _R.fp8, _R._mm, _R.rms_norm, _R.swiglu
coverage = _R.coverage


def head_logits(x, top, *, m, quant):
    """``reference/mla_moe.py``'s head (the final norm, then the head;
    over alternates: per row the largest over its live streams of
    ``logits - max(logits)``), under this family's name for the norm's
    epsilon."""
    return _R.head_logits(x, top, m={"rms_norm_eps": m["layernorm_epsilon"]},
                          quant=quant)


def partial_rope(x, pos, theta, n_rot):
    """x [R, heads, d]: the first ``n_rot`` columns rotated by ``pos``
    (pairs ``(x_i, x_{i + n_rot/2})``, angle ``pos theta^(-2i / n_rot)``),
    the others as they are."""
    inv = 1.0 / (theta ** (jnp.arange(0, n_rot, 2, dtype=F32) / n_rot))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :n_rot // 2], x[..., n_rot // 2:n_rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., n_rot:]], -1)


def attention(a, lw, m, quant, pos=None, n_seq=None):
    """Either kind of attention, ``a`` [R, hidden] already normed. The
    first ``n_seq`` rows are one sequence in order (all of them by
    default); any further rows are alternates of the position ``pos[r]``.
    Every row attends the sequence's keys BEFORE its position (a window
    layer: those inside the band) and its own entry at it — for a row of
    the sequence that is plain causal attention. Blocked over query rows."""
    R = a.shape[0]
    T = R if n_seq is None else n_seq
    if pos is None:
        pos = jnp.arange(R)
    window = "sink" in lw
    nh, dk, dv = m["num_attention_heads"], m["head_dim"], m["v_head_dim"]
    G = m["swa_num_key_value_heads" if window else "num_key_value_heads"]
    theta = m["swa_rope_theta" if window else "rope_theta"]
    n_rot = int(dk * m["partial_rotary_factor"])
    g = nh // G
    qkv = _mm(a, lw["qkv"], quant)
    q = partial_rope(qkv[:, :nh * dk].reshape(R, nh, dk), pos, theta, n_rot)
    k = partial_rope(qkv[:, nh * dk:(nh + G) * dk].reshape(R, G, dk), pos,
                     theta, n_rot)
    v = qkv[:, (nh + G) * dk:].reshape(R, G, dv) * m["attention_value_scale"]
    q = q.reshape(R, G, g, dk)
    scale = 1.0 / jnp.sqrt(F32(dk))

    def block(start):
        r = jnp.minimum(start + jnp.arange(ROWS), R - 1)
        s = jnp.einsum("rkgd,tkd->kgrt", q[r], k[:T],
                       precision="highest") * scale
        back = pos[r][:, None] - jnp.arange(T)[None, :]        # [rows, T]
        seen = back > 0
        if window:  # the band, a mask of its own
            seen = seen & (back < m["sliding_window"])
        s = jnp.where(seen[None, None], s, -jnp.inf)
        own = jnp.einsum("rkgd,rkd->kgr", q[r], k[r],
                         precision="highest") * scale
        cols = [s, own[..., None]]
        if window:  # the sink, an appended column
            cols.append(jnp.broadcast_to(
                lw["sink"].reshape(G, g, 1, 1), (G, g, ROWS, 1)))
        p = jax.nn.softmax(jnp.concatenate(cols, -1), -1)
        return jnp.einsum("kgrt,tkd->rkgd", p[..., :T], v[:T],
                          precision="highest") \
            + jnp.moveaxis(p[..., T], -1, 0)[..., None] * v[r][:, :, None]

    n_blocks = -(-R // ROWS)
    out = jax.lax.map(block, jnp.arange(n_blocks) * ROWS)
    return _mm(out.reshape(n_blocks * ROWS, nh * dv)[:R], lw["o"], quant)


def _experts(u, lw, m, quant, alternate):
    """(the held experts' share of the routed sum; the same with the
    undecided held expert's membership toggled; whether there is exactly
    one such expert; whether there are more: ``reference/mla_moe.py``'s
    docstring). ``u`` [T, hidden] already normed. Without ``alternate``,
    or where every expert is chosen, the last three are None."""
    k = m["num_experts_per_tok"]
    first, held = m.get("first_held_expert", 0), m["n_routed_experts"]
    scaling = m.get("routed_scaling_factor") or 1.0  # null: no factor
    z = jnp.matmul(u, lw["router"], precision="highest")
    s = jax.nn.sigmoid(z)
    sel = s + lw["router_bias"]            # chooses; the gates are s alone
    alternate = alternate and k < z.shape[-1]  # else nothing to change with
    sel_top, idx = jax.lax.top_k(sel, k + alternate)
    vals = jnp.take_along_axis(s, idx, -1)
    top_s, top_i = vals[:, :k], idx[:, :k]

    def gates(s_k):
        return s_k / (jnp.sum(s_k, -1, keepdims=True) + 1e-20) * scaling

    chosen = [(top_i, gates(top_s))]
    one = crowd = None
    if alternate:
        edge = jnp.mean(sel_top[:, k - 1:], -1)
        mine, s_mine = sel[:, first:first + held], s[:, first:first + held]
        # a score's distance from the boundary, in logits
        far = jnp.abs(mine - edge[:, None]) / (s_mine * (1.0 - s_mine))
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

    ys = jax.lax.fori_loop(0, held, run,
                           (jnp.zeros_like(u),) * len(chosen))
    return ys[0], ys[-1], one, crowd


def experts(u, lw, m, quant):
    """The held experts' share of the routed sum. ``u`` [T, hidden]
    already normed."""
    return _experts(u, lw, m, quant, False)[0]


def _layer(x, lw, m, quant, pos=None, n_seq=None, alt=None,
           alternate=False):
    """One layer on rows ``x`` [R, hidden] (``attention`` says what the
    rows are; ``alt`` is not needed: no state). Returns (the rows' output;
    their output with the undecided expert toggled, and ``_experts``' two
    flags, or None)."""
    del alt
    eps = m["layernorm_epsilon"]
    x = x + attention(rms_norm(x, lw["ln_in"], eps), lw, m, quant, pos,
                      n_seq)
    u = rms_norm(x, lw["ln_post"], eps)
    if "router" not in lw:
        return x + swiglu(u, lw["gate_up"], lw["down"], quant), None, \
            None, None
    y, y_alt, one, crowd = _experts(u, lw, m, quant, alternate)
    if one is None:
        return x + y, None, None, None
    return x + y, x + y_alt, one, crowd


def layer_forward(x, lw, *, li, m, quant):
    """One layer on one sequence; ``lw``'s keys tell its kind. ``li`` (the
    layer's index, traced) is not needed. The control's chain (``quant``)
    is ``x`` [T, hidden] in and out. The float32 chain takes that from the
    embedding and ``[T, STREAMS, hidden + 1]`` from itself, and returns
    the latter: the sequence, each position's alternates and the marks
    (``reference/mla_moe.py``'s ``layer_forward``, whose bookkeeping this
    repeats around this file's ``_layer``, as ``reference/kda_mla_moe.py``
    does)."""
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
                                      (a_pos, a_slot, A), alternate=True)
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


# -- whole-model form, for the CPU tests ----------------------------------------

def forward(params, ids, m, quant=False):
    """Logits [T, vocab] of one sequence, no alternates. ``params``:
    top-level leaves and ``layers`` (a list of leaf dicts), as
    ``chiplib.modelbuild.reference_params`` builds them."""
    x = params["embed"][ids]
    for lw in params["layers"]:
        x = _layer(x, lw, m, quant)[0]
    return head_logits(x, params, m=m, quant=quant)
