"""Plain reference for a linear-attention / latent-attention sparse-expert
decoder (Kimi-Linear-48B-A3B's ``config.json``, ``model_type``
``kimi_linear``; the layer equations are ISSUE 34's, written out in
``paddle_tpu/models/linear_latent_moe.py``'s docstring too): the forward
pass in ``jax.numpy``, float32, matmul precision "highest". No kernels, no
cache, no chunking, no sorting, no batching, nothing imported from the
program. The helpers every reference shares (``fp8``, ``_mm``,
``rms_norm``, ``swiglu``, the head over alternates, ``coverage``) are
``reference/mla_moe.py``'s, loaded from the file beside this one.

Plain pre-norm: ``x <- x + Mix(N(x; ln_in))``, ``x <- x + FFN(N(x;
ln_post))``; the three kinds of layer are told apart by ``lw``'s keys.

- A layer with ``qkv`` is GATED DELTA-RULE linear attention, and it is the
  RECURRENCE itself, one position at a time under ``lax.scan``: ``S' =
  Diag(exp g_t) S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``,
  ``o_t = S_t^T q_t`` per head, so the program's chunked form (a
  triangular solve inside a chunk, a carried state between chunks, a state
  array indexed by lane across calls, a verify round that applies only
  what was accepted) is checked against what it has to equal.
- A layer with ``kv_a`` is latent attention in the PUBLISHED, up-projected
  form with a direct query projection and no position embedding (the
  ``qk_rope_head_dim`` columns are plain columns), in blocks of query
  rows, so the program's absorbed form is checked against it.
- A layer with ``router`` has the expert layer: float32 sigmoid scores,
  the ``k`` largest of ``s + router_bias``, gates ``s / sum(s) x
  routed_scaling_factor`` over the chosen; each held expert is run over
  every token and weighted by the gate the token gave it; what absent
  experts would add is left out (``reference/mla_moe.py``: the chip's
  share). One without has a dense SwiGLU (``gate_up`` / ``down``).

Departures from the published code, all exact re-arrangements or stated
assumptions (the configuration file lists the latter under ``assumed``):
W is ``[in, out]``, applied as ``x @ W``; q, k, v arrive as one matrix
``qkv`` (q first) and their three depthwise convolutions as one
``conv_w`` ``[3 x channels, 1, taps]`` (no bias, silu after); gate and up
arrive fused (gate first), per expert too; the two low-rank gates are
``f_a`` / ``f_b`` (decay, with ``dt_bias`` a channel and ``A_log`` a head)
and ``g_a`` / ``g_b`` (output); q and k are L2-normalised with 1e-6 under
the root and q scaled by ``d^-1/2``; the state is float32; ``kv_b``'s
output axis is ``[heads, nope | v]``, ``kv_a``'s ``[latent | pe]``.

**Near-ties at the router** are followed as ``reference/mla_moe.py``
follows them (its docstring says why): the float32 chain of the
harness's walk carries ALTERNATES, ``x`` ``[T, STREAMS, hidden + 1]``. A
held expert is undecided for a token where its selection score ``s + b``
lies within ``TIE_MARGIN`` x the spread of the token's logits from the
boundary (the midpoint of the ``k``-th and ``(k+1)``-th selection scores;
a difference of scores counts as a difference of logits through
``s(1-s)``). An alternate row goes through the following layers on its
own: through latent attention it attends the sequence's own entries
before its position and its own at it; through a linear-attention layer
it reads the sequence's own state before its position and the sequence's
own conv rows, and updates NOTHING — the state stays stream 0's. What a
toggle at an EARLIER position does to later ones stays in the reading.

``quant`` is the CONTROL: every linear layer's two operands fake-quantised
to an 8-bit float (e4m3, per-tensor scale) — the nearest precision below
the configuration's bfloat16. The router's product, the convolutions, the
recurrence and the attention products stay float32, as in the other
references. It has to fail the comparison that the program passes.
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
head_logits, coverage = _R.head_logits, _R.coverage


def _dense(vals, alt, T):
    """Alternate rows' values [N, ...] laid out by (position, slot): [T,
    A, ...]; rows that are nowhere (position T) are dropped."""
    a_pos, a_slot, A = alt
    return jnp.zeros((T, A) + vals.shape[1:], vals.dtype).at[
        a_pos, a_slot].set(vals, mode="drop")


def linear_attention(a, lw, m, quant, n_seq=None, alt=None):
    """The gated delta-rule mixer, ``a`` [R, hidden] already normed. The
    first ``n_seq`` rows are one sequence in order, from a zero state and
    zeros before the start; any further rows are alternates, ``alt`` =
    (their positions [N], with ``n_seq`` for a row that is nowhere; their
    slots [N]; slots a position). An alternate reads the conv rows and the
    state of the SEQUENCE before its position and updates neither."""
    R = a.shape[0]
    T = R if n_seq is None else n_seq
    la = m["linear_attn_config"]
    H, d, K = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    w = H * d
    raw = _mm(a, lw["qkv"], quant)                              # [R, 3w]
    f = _mm(_mm(a, lw["f_a"], quant), lw["f_b"], quant) + lw["dt_bias"]
    g = -jnp.exp(lw["A_log"])[:, None] * jax.nn.softplus(f).reshape(R, H, d)
    beta = jax.nn.sigmoid(_mm(a, lw["b"], quant))               # [R, H]
    gate = jax.nn.sigmoid(_mm(_mm(a, lw["g_a"], quant), lw["g_b"], quant))
    taps = lw["conv_w"][:, 0, :]                                # [3w, K]
    padded = jnp.concatenate([jnp.zeros((K - 1, 3 * w), F32), raw[:T]])
    c = sum(taps[:, j] * padded[j:j + T] for j in range(K))
    if alt is not None:
        at = jnp.minimum(alt[0], T - 1)
        c = jnp.concatenate([c, taps[:, K - 1] * raw[T:] + sum(
            taps[:, j] * padded[at + j] for j in range(K - 1))])
    c = jax.nn.silu(c).reshape(R, 3, H, d)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    q, k, v = unit(c[:, 0]) * d ** -0.5, unit(c[:, 1]), c[:, 2]

    def old(S, x):  # S^T x per head: [H, dk, dv], [.., H, dk] -> [.., H, dv]
        return jnp.einsum("hkv,...hk->...hv", S, x, precision="highest")

    def step(S, inp):
        (q_t, k_t, v_t, g_t, b_t), others = inp
        Sd = jnp.exp(g_t)[..., None] * S
        S_t = Sd + k_t[..., None] * (b_t[:, None] * (v_t - old(Sd, k_t))
                                     )[:, None, :]
        o_t = old(S_t, q_t)
        if others is None:
            return S_t, (o_t, None)
        # the same step from the same S, for rows that keep nothing
        aq, ak, av, ag, ab = others                              # [A, H, .]
        u = ab[..., None] * (av - old(S, jnp.exp(ag) * ak))
        ao = old(S, jnp.exp(ag) * aq) \
            + jnp.sum(ak * aq, -1, keepdims=True) * u
        return S_t, (o_t, ao)

    seq = tuple(x[:T] for x in (q, k, v, g, beta))
    others = None if alt is None else tuple(
        _dense(x[T:], alt, T) for x in (q, k, v, g, beta))
    _, (o, ao) = jax.lax.scan(step, jnp.zeros((H, d, d), F32),
                              (seq, others))
    if alt is not None:
        o = jnp.concatenate([o, ao[at, alt[1]]])
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + m["rms_norm_eps"]) * lw["o_norm"]
    return _mm(o.reshape(R, w) * gate, lw["o"], quant)


def latent_attention(a, lw, m, quant, pos=None, n_seq=None):
    """Latent attention without position embedding, ``a`` [R, hidden]
    already normed; rows as in ``reference/mla_moe.py``'s ``attention``:
    every row attends the sequence's keys BEFORE its position and its own
    entry at it. Up-projected form, blocked over query rows."""
    R = a.shape[0]
    T = R if n_seq is None else n_seq
    if pos is None:
        pos = jnp.arange(R)
    nh = m["num_attention_heads"]
    dn, dr, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                  m["v_head_dim"])
    dc = m["kv_lora_rank"]
    q = _mm(a, lw["q"], quant).reshape(R, nh, dn + dr)
    kv = _mm(a, lw["kv_a"], quant)
    c_kv = rms_norm(kv[:, :dc], lw["kv_norm"], m["rms_norm_eps"])
    kv_up = _mm(c_kv, lw["kv_b"], quant).reshape(R, nh, dn + dv)
    # the key of head h: [k_nope_h | k_pe], k_pe shared by all heads
    key = jnp.concatenate(
        [kv_up[..., :dn],
         jnp.broadcast_to(kv[:, None, dc:], (R, nh, dr))], -1)
    v = kv_up[..., dn:]
    scale = 1.0 / jnp.sqrt(F32(dn + dr))

    def block(start):
        r = jnp.minimum(start + jnp.arange(ROWS), R - 1)
        s = jnp.einsum("rhd,thd->hrt", q[r], key[:T],
                       precision="highest") * scale
        s = jnp.where(jnp.arange(T)[None, None, :] < pos[r][None, :, None],
                      s, -jnp.inf)
        own = jnp.sum(q[r] * key[r], -1) * scale                # [r, h]
        p = jax.nn.softmax(jnp.concatenate([s, own.T[..., None]], -1), -1)
        return jnp.einsum("hrt,thd->rhd", p[..., :T], v[:T],
                          precision="highest") \
            + p[..., T].T[..., None] * v[r]

    n_blocks = -(-R // ROWS)
    out = jax.lax.map(block, jnp.arange(n_blocks) * ROWS)
    return _mm(out.reshape(n_blocks * ROWS, nh * dv)[:R], lw["o"], quant)


def _experts(u, lw, m, quant, alternate):
    """(the held experts' share of the routed sum plus the shared expert;
    the same with the undecided held expert's membership toggled; whether
    there is exactly one such expert; whether there are more: module
    docstring). ``u`` [T, hidden] already normed. Without ``alternate``,
    or where every expert is chosen, the last three are None."""
    k = m["num_experts_per_token"]
    first, held = m.get("first_held_expert", 0), m["num_experts"]
    z = jnp.matmul(u, lw["router"], precision="highest")
    s = jax.nn.sigmoid(z)
    sel = s + lw["router_bias"]            # chooses; the gates are s alone
    alternate = alternate and k < z.shape[-1]  # else nothing to change with
    sel_top, idx = jax.lax.top_k(sel, k + alternate)
    vals = jnp.take_along_axis(s, idx, -1)
    top_s, top_i = vals[:, :k], idx[:, :k]

    def gates(s_k):
        return s_k / (jnp.sum(s_k, -1, keepdims=True) + 1e-20) \
            * m["routed_scaling_factor"]

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

    shared = swiglu(u, lw["shared_gate_up"], lw["shared_down"], quant)
    ys = jax.lax.fori_loop(0, held, run, (shared,) * len(chosen))
    return ys[0], ys[-1], one, crowd


def experts(u, lw, m, quant):
    """The held experts' share of the routed sum, plus the shared expert.
    ``u`` [T, hidden] already normed."""
    return _experts(u, lw, m, quant, False)[0]


def _layer(x, lw, m, quant, pos=None, n_seq=None, alt=None,
           alternate=False):
    """One layer on rows ``x`` [R, hidden] (the mixers say what the rows
    are). Returns (the rows' output; their output with the undecided
    expert toggled, and ``_experts``' two flags, or None)."""
    eps = m["rms_norm_eps"]
    a = rms_norm(x, lw["ln_in"], eps)
    x = x + (linear_attention(a, lw, m, quant, n_seq, alt) if "qkv" in lw
             else latent_attention(a, lw, m, quant, pos, n_seq))
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
    repeats around this file's ``_layer``)."""
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
