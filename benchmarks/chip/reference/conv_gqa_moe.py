"""Plain reference for a short-convolution / grouped-query sparse-expert
decoder (LFM2-24B-A2B's ``config.json``, ``model_type`` ``lfm2_moe``; the
layer equations are ISSUE 40's, written out in
``paddle_tpu/models/conv_moe.py``'s docstring too): the forward pass in
``jax.numpy``, float32, matmul precision "highest". No kernels, no cache,
no tail, no rows, no sorting, no batching, nothing imported from the
program. The helpers every reference shares (``fp8``, ``_mm``,
``rms_norm``, ``rope``, ``swiglu``, the head over alternates,
``coverage``) are ``reference/mla_moe.py``'s and the walk's bookkeeping of
alternates ``reference/kda_mla_moe.py``'s, loaded from the files beside
this one.

Plain pre-norm: ``x <- x + Op(N(x; ln_in))``, ``x <- x + FFN(N(x;
ln_post))``; the kinds of layer are told apart by ``lw``'s keys.

- A layer with ``conv_w`` is the GATED SHORT CONVOLUTION, written as a
  zero-padded causal sum over the WHOLE sequence: ``[B | C | z] = a
  in_proj``, ``g = B * z``, ``c_t = sum_j conv_w[:, j] g_{t - (L-1) + j}``
  with ``g`` zero before the sequence's start, no activation and no bias,
  out ``(C * c) out_proj`` — so the program's chunked form (a tail carried
  across chunk boundaries and calls, indexed by lane, set after a verify
  round to the rows that end at the last accepted position) is checked
  against what it has to equal.
- A layer with ``q_norm`` is rotary grouped-query attention: ``[q | k | v]
  = a qkv``, RMSNorm over the columns of every q head and every k head,
  THEN the rotary embedding on all columns (rotate-half), scores ``q.k /
  sqrt(head)``, plain causal softmax, over WHOLE sequences in blocks of
  query rows (32 heads x 9.7k x 9.7k float32 scores at once would be 12
  GB).
- A layer with ``router`` has the expert layer: float32 sigmoid scores,
  the ``k`` largest of ``s + router_bias``, gates ``s / (sum(s) + 1e-6)``
  over the chosen times ``routed_scaling_factor``; each held expert is run
  over every token, ONE EXPERT AT A TIME (no ``[tokens, experts, width]``
  array exists), and weighted by the gate the token gave it; what absent
  experts would add is left out (``reference/mla_moe.py``: the chip's
  share — here every expert is held and nothing is). One without has a
  dense SwiGLU (``gate_up`` / ``down``).

Departures from the published code, all exact re-arrangements or stated
assumptions (the configuration file lists the latter under ``assumed``):
W is ``[in, out]``, applied as ``x @ W``; the taps are ``conv_w`` [hidden,
L], tap ``L - 1`` on the position itself; the in-projection's thirds in
the order ``B | C | z``; q, k, v arrive as one matrix ``qkv`` (q first);
gate and up arrive fused (gate first), per expert too; the head is the
embedding, after one more RMSNorm (``norm``).

**Near-ties at the router** are followed as ``reference/mla_moe.py``
follows them (its docstring says why): the float32 chain of the harness's
walk carries ALTERNATES, ``x`` ``[T, STREAMS, hidden + 1]``, with the
undecided expert found on the selection score as
``reference/kda_mla_moe.py`` finds it. One case is new where EVERY expert
is held: the ``k``-th and the ``(k+1)``-th are then both held and both as
near the boundary (their midpoint), and ONE alternate covers the two — the
pair changing places; two undecided held experts that are not that pair
still leave the position out. An alternate row goes through the following
layers on its own: through attention it attends the sequence's own keys
before its position and its own at it; through a convolution it reads the
SEQUENCE's own ``g`` at the ``L - 1`` positions before its own.

``quant`` is the CONTROL: every linear layer's two operands fake-quantised
to an 8-bit float (e4m3, per-tensor scale) — the nearest precision below
the configuration's bfloat16. The router's product, the convolution's
taps, the rotary embedding and the attention products stay float32, as in
the other references. It has to fail the comparison that the program
passes.
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
F32, ROWS, TIE_MARGIN = _R.F32, _R.ROWS, _R.TIE_MARGIN
fp8, _mm, rms_norm, rope, swiglu = (_R.fp8, _R._mm, _R.rms_norm, _R.rope,
                                    _R.swiglu)
coverage = _R.coverage
GATE_EPS = 1e-6  # under the chosen scores' sum


def head_logits(x, top, *, m, quant):
    """``reference/mla_moe.py``'s head (the final norm, then the head;
    over alternates: per row the largest over its live streams of
    ``logits - max(logits)``) through the TIED table: the head is the
    embedding, transposed."""
    return _R.head_logits(
        x, {"norm": top["norm"], "lm_head": top["embed"].T},
        m={"rms_norm_eps": m["norm_eps"]}, quant=quant)


def short_conv(a, lw, m, quant, n_seq=None, alt=None):
    """The gated short convolution, ``a`` [R, hidden] already normed. The
    first ``n_seq`` rows are one sequence in order, zeros before its
    start; any further rows are alternates, ``alt`` = (their positions
    [N], with ``n_seq`` for a row that is nowhere; their slots; slots a
    position). An alternate's sum takes the SEQUENCE's ``g`` at the
    positions before its own, and its own at it."""
    R, h = a.shape
    T = R if n_seq is None else n_seq
    B, C, z = jnp.split(_mm(a, lw["in_proj"], quant), 3, axis=-1)
    g = B * z
    taps = lw["conv_w"]                                         # [h, L]
    L = taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((L - 1, h), F32), g[:T]])
    c = sum(taps[:, j] * padded[j:j + T] for j in range(L))
    if alt is not None:
        at = jnp.minimum(alt[0], T - 1)
        c = jnp.concatenate([c, taps[:, L - 1] * g[T:] + sum(
            taps[:, j] * padded[at + j] for j in range(L - 1))])
    return _mm(C * c, lw["out_proj"], quant)


def attention(a, lw, m, quant, pos=None, n_seq=None):
    """Rotary grouped-query attention with q/k head norms, ``a`` [R,
    hidden] already normed; rows as in ``reference/mla_moe.py``'s
    ``attention``: every row attends the sequence's keys BEFORE its
    position and its own entry at it. Blocked over query rows."""
    R = a.shape[0]
    T = R if n_seq is None else n_seq
    if pos is None:
        pos = jnp.arange(R)
    nh, G = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // nh
    g = nh // G
    eps, theta = m["norm_eps"], m["rope_parameters"]["rope_theta"]
    qkv = _mm(a, lw["qkv"], quant)
    q = rms_norm(qkv[:, :nh * d].reshape(R, nh, d), lw["q_norm"], eps)
    k = rms_norm(qkv[:, nh * d:(nh + G) * d].reshape(R, G, d),
                 lw["k_norm"], eps)
    v = qkv[:, (nh + G) * d:].reshape(R, G, d)
    q = rope(q, pos, theta).reshape(R, G, g, d)
    k = rope(k, pos, theta)
    scale = 1.0 / jnp.sqrt(F32(d))

    def block(start):
        r = jnp.minimum(start + jnp.arange(ROWS), R - 1)
        s = jnp.einsum("rkgd,tkd->kgrt", q[r], k[:T],
                       precision="highest") * scale
        seen = jnp.arange(T)[None, :] < pos[r][:, None]        # [rows, T]
        s = jnp.where(seen[None, None], s, -jnp.inf)
        own = jnp.einsum("rkgd,rkd->kgr", q[r], k[r],
                         precision="highest") * scale
        p = jax.nn.softmax(jnp.concatenate([s, own[..., None]], -1), -1)
        return jnp.einsum("kgrt,tkd->rkgd", p[..., :T], v[:T],
                          precision="highest") \
            + jnp.moveaxis(p[..., T], -1, 0)[..., None] * v[r][:, :, None]

    n_blocks = -(-R // ROWS)
    out = jax.lax.map(block, jnp.arange(n_blocks) * ROWS)
    return _mm(out.reshape(n_blocks * ROWS, nh * d)[:R], lw["o"], quant)


def _experts(u, lw, m, quant, alternate):
    """(the held experts' share of the routed sum; the same with the
    undecided held expert's membership toggled; whether there is exactly
    one such alternate; whether there would be more: module docstring).
    ``u`` [T, hidden] already normed. Without ``alternate``, or where
    every expert is chosen, the last three are None."""
    k = m["num_experts_per_tok"]
    first, held = m.get("first_held_expert", 0), m["num_experts"]
    scaling = m.get("routed_scaling_factor") or 1.0
    z = jnp.matmul(u, lw["router"], precision="highest")
    E = z.shape[-1]
    s = jax.nn.sigmoid(z)
    sel = s + lw["router_bias"]            # chooses; the gates are s alone
    alternate = alternate and k < E        # else nothing to change with
    sel_top, idx = jax.lax.top_k(sel, k + alternate)
    vals = jnp.take_along_axis(s, idx, -1)
    top_s, top_i = vals[:, :k], idx[:, :k]

    def gates(s_k):
        return s_k / (jnp.sum(s_k, -1, keepdims=True) + GATE_EPS) * scaling

    chosen = [(top_i, gates(top_s))]
    one = crowd = None
    if alternate:
        edge = jnp.mean(sel_top[:, k - 1:], -1)
        mine = (jnp.arange(E) >= first) & (jnp.arange(E) < first + held)
        # a score's distance from the boundary, in logits
        far = jnp.abs(sel - edge[:, None]) / (s * (1.0 - s))
        near = mine[None] & (far < TIE_MARGIN * jnp.std(z, -1)[:, None])
        n_near = jnp.sum(near, -1)
        # the k-th and the (k+1)-th both held: ONE alternate, the two
        # changing places
        pair = (n_near == 2) & jnp.all(
            jnp.take_along_axis(near, idx[:, k - 1:], -1), -1)
        one, crowd = (n_near == 1) | pair, (n_near > 1) & ~pair
        e = jnp.argmin(jnp.where(near, far, jnp.inf), -1)
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

    def run(e, ys):  # a loop: one expert over the tokens at a time
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
    """One layer on rows ``x`` [R, hidden] (the operators say what the
    rows are). Returns (the rows' output; their output with the undecided
    expert toggled, and ``_experts``' two flags, or None)."""
    eps = m["norm_eps"]
    a = rms_norm(x, lw["ln_in"], eps)
    x = x + (short_conv(a, lw, m, quant, n_seq, alt) if "conv_w" in lw
             else attention(a, lw, m, quant, pos, n_seq))
    u = rms_norm(x, lw["ln_post"], eps)
    if "router" not in lw:
        return x + swiglu(u, lw["gate_up"], lw["down"], quant), None, \
            None, None
    y, y_alt, one, crowd = _experts(u, lw, m, quant, alternate)
    if one is None:
        return x + y, None, None, None
    return x + y, x + y_alt, one, crowd


# ``reference/kda_mla_moe.py``'s walk (the sequence, each position's
# alternates and the marks: ``[T, hidden]`` from the embedding or the
# control's chain, ``[T, STREAMS, hidden + 1]`` from itself) around THIS
# file's ``_layer``: a copy of that module of this file's own
_WALK = _beside("kda_mla_moe")
_WALK._layer = _layer
layer_forward = _WALK.layer_forward


# -- whole-model form, for the CPU tests ----------------------------------------

def forward(params, ids, m, quant=False):
    """Logits [T, vocab] of one sequence, no alternates. ``params``:
    top-level leaves and ``layers`` (a list of leaf dicts), as
    ``chiplib.modelbuild.reference_params`` builds them."""
    x = params["embed"][ids]
    for lw in params["layers"]:
        x = _layer(x, lw, m, quant)[0]
    return head_logits(x, params, m=m, quant=quant)
