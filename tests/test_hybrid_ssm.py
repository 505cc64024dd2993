"""The hybrid state-space / attention model and its serving family against
the plain reference (``benchmarks/chip/reference/hybrid_ssm.py``, loaded
by path: ONE copy).

Tiny widths, CPU, seeded weights; the state-space leaves ``A_log`` /
``dt_bias`` / ``D`` keep the model's PUBLISHED initialisation (``A`` in
[1, 16], ``dt`` in [1e-3, 1e-1]): a slow decay, under which a state
carried wrongly from chunk to chunk, call to call or request to request
shows (the benchmark's seeded weights give a memory of a few tokens and
cannot show it). The program runs in float32 here, so what separates it
from the float32 reference is the order of summation: ``TOL`` = 2e-4 on
logits of magnitude ~1.
"""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework.errors import UnimplementedError
from paddle_tpu.models import (
    HybridSSMConfig, HybridSSMForCausalLM, generate, hybrid_ssm,
)
from paddle_tpu.ops.pallas import ssm_state
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.families import hybrid_ssm as family

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
VOCAB = 256


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "hybrid_ssm_reference",
        os.path.join(ROOT, "benchmarks/chip/reference/hybrid_ssm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(**kw):
    """3 state-space layers around 1 attention layer; 6 state-space heads
    in 2 groups; every width differs from every other where it can, every
    multiplier from 1, and the chunk (8) from every length tried."""
    base = dict(vocab_size=VOCAB, hidden_size=64,
                shared_intermediate_size=80, num_hidden_layers=4,
                layer_types=["mamba", "attention", "mamba", "mamba"],
                num_attention_heads=4, num_key_value_heads=2,
                mamba_n_heads=6, mamba_d_head=16, mamba_d_state=8,
                mamba_n_groups=2, mamba_d_conv=4, mamba_chunk_size=8,
                attention_multiplier=0.2, embedding_multiplier=3.0,
                residual_multiplier=0.5, logits_scaling=2.0)
    base.update(kw)
    return HybridSSMConfig(**base)


BORN = ("A_log", "dt_bias", "D")  # kept as the model is born


def seeded(model, seed=0, dtype="float32"):
    """Matrices N(0, 0.1) (the conv weight and bias too), norm weights 1
    +- 0.1 (so a dropped norm weight shows), from one generator in
    parameter order; the state-space leaves of ``BORN`` as published."""
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if name.rsplit(".", 1)[-1] in BORN:
            v = np.asarray(p._data, np.float32)
        elif name.endswith(("ln_in", "ln_post", "gate_norm", "norm")):
            v = 1 + 0.1 * rng.uniform(-1, 1, p.shape)
        else:
            v = rng.normal(0, 0.1, p.shape)
        p._data = jnp.asarray(v, dtype)
    model.eval()
    return model


def ref_params(model):
    out = {k: np.asarray(getattr(model, k)._data, np.float32)
           for k in ("embed", "norm")}
    out["layers"] = [{k: np.asarray(p._data, np.float32)
                      for k, p in blk.leaves().items()}
                     for blk in model.layers]
    return out


def ref_logits(ref, model, ids, quant=False):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(
            ref_params(model), jnp.asarray(ids), dict(vars(model.config)),
            quant=quant))


@pytest.fixture(scope="module")
def model():
    return seeded(HybridSSMForCausalLM(tiny_config()))


@pytest.fixture(scope="module")
def ids():
    return np.random.default_rng(1).integers(0, VOCAB, (2, 37)).astype(
        np.int32)


# -- the model against the reference -------------------------------------------

def test_whole_model_logits_match_the_reference(ref, model, ids):
    got = model(pt.to_tensor(ids)).numpy()
    for b in range(ids.shape[0]):
        want = ref_logits(ref, model, ids[b])
        assert np.abs(want).max() > 0.5
        assert np.abs(got[b] - want).max() < TOL


def test_a_lower_precision_fails_the_tolerance(ref, model, ids):
    """The same weights served in bfloat16, and the reference's own fp8
    control, miss ``TOL`` by an order of magnitude or more."""
    want = ref_logits(ref, model, ids[0])
    low = seeded(HybridSSMForCausalLM(tiny_config(dtype="bfloat16")),
                 dtype="bfloat16")
    got = low(pt.to_tensor(ids[:1])).numpy().astype(np.float32)[0]
    assert np.abs(got - want).max() > 10 * TOL
    assert np.abs(ref_logits(ref, model, ids[0], quant=True)
                  - want).max() > 10 * TOL


def test_published_initialisation_is_slow_decay():
    m = HybridSSMForCausalLM(tiny_config())
    for blk in m.layers:
        if blk.kind != hybrid_ssm.SSM:
            continue
        A = np.exp(blk.A_log.numpy())
        dt = np.log1p(np.exp(blk.dt_bias.numpy()))
        assert (A >= 1).all() and (A <= 16).all()
        assert (dt >= 0.99e-3).all() and (dt <= 1.01e-1).all()
        assert (blk.D.numpy() == 1).all()
        # a state halves after ln 2 / (dt A) positions: a few to hundreds
        # (the benchmark's seeded weights: one)
        half = np.log(2) / (dt * A)
        assert np.median(half) > 3 and half.max() > 20


def _scan_inputs(T, seed=0, b=2):
    c = tiny_config()
    rng = np.random.default_rng(seed)
    H, P, N, G = (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                  c.mamba_n_groups)
    f = lambda *s: jnp.asarray(rng.normal(0, 1, s), jnp.float32)  # noqa: E731
    born = hybrid_ssm._published_ssm_init(H, seed)
    dt = jax.nn.softplus(f(b, T, H) * 0.5 + jnp.asarray(born["dt_bias"],
                                                        jnp.float32))
    A = -jnp.exp(jnp.asarray(born["A_log"], jnp.float32))
    return f(b, T, H, P), f(b, T, G, N), f(b, T, G, N), dt, A, \
        f(b, H, P, N)


def _step_by_step(x, B, C, dt, A, S):
    ys = []
    for t in range(x.shape[1]):
        S = hybrid_ssm.ssm_step(S, x[:, t], B[:, t], dt[:, t], A)
        ys.append(hybrid_ssm.ssm_read(S, C[:, t]))
    return jnp.stack(ys, 1), S


@pytest.mark.parametrize("T", [1, 5, 8, 13, 37])
def test_chunked_form_equals_the_step_recurrence(T):
    """Lengths below, at and beyond the chunk (8), none but 8 a multiple
    of it, from a non-zero carried state, under slow decay."""
    x, B, C, dt, A, S0 = _scan_inputs(T, seed=T)
    y, S = hybrid_ssm.ssm_scan(x, B, C, dt, A, S0, 8)
    y_want, S_want = _step_by_step(x, B, C, dt, A, S0)
    np.testing.assert_allclose(y, y_want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(S, S_want, rtol=1e-4, atol=1e-4)
    # the carried state matters at this decay: dropping it is not close
    y0, _ = hybrid_ssm.ssm_scan(x, B, C, dt, A, jnp.zeros_like(S0), 8)
    assert np.abs(np.asarray(y0 - y_want)).max() > 0.1


def test_a_position_with_dt_zero_is_the_identity_on_the_state():
    x, B, C, dt, A, S0 = _scan_inputs(6, seed=3)
    S = hybrid_ssm.ssm_step(S0, x[:, 0], B[:, 0], jnp.zeros_like(dt[:, 0]),
                            A)
    assert (np.asarray(S) == np.asarray(S0)).all()
    keep = jnp.arange(6)[None, :, None] < 4
    _, S4 = hybrid_ssm.ssm_scan(x[:, :4], B[:, :4], C[:, :4], dt[:, :4], A,
                                S0, 8)
    _, S6 = hybrid_ssm.ssm_scan(x, B, C, jnp.where(keep, dt, 0.0), A, S0, 8)
    np.testing.assert_allclose(S6, S4, rtol=1e-6, atol=1e-6)


def test_train_step_runs_and_learns():
    """``jit.TrainStep`` differentiates through the chunked scan (several
    chunks, a padded last one) and the loss falls."""
    from paddle_tpu.jit.train_step import TrainStep

    pt.seed(0)
    m = HybridSSMForCausalLM(tiny_config(initializer_range=0.05))
    m.train()
    opt = pt.optimizer.AdamW(learning_rate=3e-3, parameters=m.parameters())
    step = TrainStep(m, opt)
    seq = np.random.default_rng(0).integers(0, VOCAB, (4, 22))
    x = pt.to_tensor(seq[:, :-1].astype(np.int32))
    y = pt.to_tensor(seq[:, 1:].astype(np.int64))
    losses = [float(step(x, y).numpy()) for _ in range(12)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] - 0.3, losses


def test_generate_raises_and_names_the_family(model):
    with pytest.raises(UnimplementedError, match="hybrid_ssm"):
        generate(model, pt.to_tensor(np.zeros((1, 4), np.int32)),
                 max_new_tokens=2)


# -- the one-pass state kernel (interpret mode) against the step recurrence ------

def _kernel_case(T, reads, n_keep, seed=0, b=3):
    """Inputs of one kernel call: a state, ``T`` owed positions of which
    each row keeps ``n_keep[row]``, ``reads`` read vectors."""
    c = tiny_config()
    H, P, N, G = (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                  c.mamba_n_groups)
    x, B, _, dt, A, S0 = _scan_inputs(T, seed=seed, b=b)
    rng = np.random.default_rng(seed + 1)
    C = jnp.asarray(rng.normal(0, 1, (b, reads, G, N)), jnp.float32)
    kept = jnp.arange(T)[None, :] < jnp.asarray(n_keep)[:, None]
    return (H, P, G), x, B, C, jnp.where(kept[..., None], dt, 0.0), A, S0


def _commit(x, B, dt, A, G):
    """The positions as the kernel takes them: their gains and their
    ``B`` rows as arrays of one layer, their ``x`` as planes."""
    return ssm_state.Commit(
        ssm_state.head_rows(ssm_state.gains(dt, A), G, x.shape[-1])[None],
        ssm_state.x_planes(x, G), ssm_state.b_rows(B)[None])


@pytest.mark.parametrize("n_keep", range(6))
@pytest.mark.parametrize("reads", [1, 5])
def test_state_kernel_equals_the_step_recurrence(reads, n_keep):
    """5 owed positions of which a row keeps ``n_keep`` (its neighbours
    another number), then ``reads`` read vectors: the state the kernel
    writes is ``ssm_step`` over the kept positions, its outputs
    ``ssm_read`` of that state. With one read (a plain round) the
    round's own position is committed in the same pass and the read
    comes from the result."""
    keeps = [n_keep, (n_keep + 2) % 6, 5 - n_keep]
    (H, P, G), x, B, C, dt, A, S0 = _kernel_case(5, reads, keeps,
                                                 seed=10 * reads + n_keep)
    owed = [_commit(x, B, dt, A, G)]
    S = S0
    for t in range(5):
        S = hybrid_ssm.ssm_step(S, x[:, t], B[:, t], dt[:, t], A)
    if reads == 1:  # the plain round's own position
        _, x1, B1, _, dt1, _, _ = _kernel_case(1, 1, [1, 1, 1], seed=99)
        owed.append(_commit(x1, B1, dt1, A, G))
        S = hybrid_ssm.ssm_step(S, x1[:, 0], B1[:, 0], dt1[:, 0], A)
    Y, Sn = ssm_state.state_round(ssm_state.to_slab(S0, G), owed, C)
    want = jnp.stack([hybrid_ssm.ssm_read(S, C[:, t])
                      for t in range(reads)], 1)
    np.testing.assert_allclose(ssm_state.from_slab(Sn, H, P), S, rtol=2e-5,
                               atol=2e-6)
    np.testing.assert_allclose(Y.reshape(want.shape), want, rtol=2e-5,
                               atol=2e-5)
    assert np.abs(np.asarray(S - S0)).max() > 1e-2 or max(keeps) == 0


@pytest.mark.parametrize("reads", [1, 5])
def test_state_kernel_with_nothing_owed_is_the_identity(reads):
    """A lane that owes nothing (``dt`` 0 at every owed position) gets
    its state back BIT FOR BIT, whatever the owed positions' inputs hold
    — a verify round's rejected drafts, a finished request's last round —
    and reads from exactly that state."""
    (H, P, G), x, B, C, dt, A, S0 = _kernel_case(5, reads, [0, 0, 0],
                                                 seed=reads)
    slab = ssm_state.to_slab(S0, G)
    Y, Sn = ssm_state.state_round(slab, [_commit(x, B, dt, A, G)], C)
    assert (np.asarray(Sn) == np.asarray(slab)).all()
    Y2, Sn2 = ssm_state.state_round(
        slab, [_commit(7 * x + 1, B[:, ::-1], dt, A, G)], C)
    assert (np.asarray(Sn2) == np.asarray(slab)).all()
    assert (np.asarray(Y2) == np.asarray(Y)).all()
    want = jnp.stack([hybrid_ssm.ssm_read(S0, C[:, t])
                      for t in range(reads)], 1)
    np.testing.assert_allclose(Y.reshape(want.shape), want, rtol=2e-5,
                               atol=2e-5)


def test_slab_layout_round_trips():
    c = tiny_config()
    _, _, _, _, _, S0 = _scan_inputs(1)
    slab = ssm_state.to_slab(S0, c.mamba_n_groups)
    assert slab.shape == ssm_state.slab_shape(
        2, c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
        c.mamba_n_groups)
    back = ssm_state.from_slab(slab, c.mamba_n_heads, c.mamba_d_head)
    assert (np.asarray(back) == np.asarray(S0)).all()
    # at the published sizes: a lane's 64 heads x 64 side by side, which
    # the kernel goes through in 32 chunks of a register's 128 lanes
    assert ssm_state.slab_shape(64, 64, 64, 128, 1) == (64, 1, 128, 4096)
    assert ssm_state.x_planes(jnp.zeros((64, 5, 64, 64)), 1).shape \
        == (64, 5, 1, 32, 128)


@pytest.mark.parametrize("T", [5, 13, 37])
def test_chunked_form_on_a_slab_equals_the_models(T):
    """The prefill chunk's scan reads and writes the state in the
    kernel's layout: the same outputs and the same state as the model's
    own, from a non-zero carried state, over one chunk and several."""
    c = tiny_config()
    x, B, C, dt, A, S0 = _scan_inputs(T, seed=T)
    y, S = hybrid_ssm.ssm_scan(x, B, C, dt, A, S0, 8)
    ys, Ss = hybrid_ssm.ssm_scan(
        x, B, C, dt, A, ssm_state.to_slab(S0, c.mamba_n_groups), 8,
        slab=True)
    assert Ss.shape == ssm_state.slab_shape(
        2, c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
        c.mamba_n_groups)
    np.testing.assert_allclose(ys, y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        ssm_state.from_slab(Ss, c.mamba_n_heads, c.mamba_d_head), S,
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_keep", [0, 3, 5])
def test_a_verify_rounds_outputs_are_the_step_recurrences(n_keep):
    """The verify round's call: 5 owed positions of which a row keeps
    ``n_keep``, then this round's 5 positions read with the family's
    scale and mix — ``y_t = S_t C_t + D x_t`` of the step recurrence
    advanced over the round's positions from the committed state, of
    which advance the kernel writes NOTHING."""
    keeps = [n_keep, (n_keep + 2) % 6, 5 - n_keep]
    (H, P, G), x, B, _, dt, A, S0 = _kernel_case(5, 5, keeps, seed=n_keep)
    _, x2, B2, C2, dt2, _, _ = _kernel_case(5, 5, [5, 5, 5],
                                            seed=70 + n_keep)
    x2 = x2.astype(jnp.bfloat16)
    D = jnp.linspace(0.5, 1.5, H)
    S = S0
    for t in range(5):
        S = hybrid_ssm.ssm_step(S, x[:, t], B[:, t], dt[:, t], A)
    committed, want = S, []
    for t in range(5):
        xt = x2[:, t].astype(jnp.float32)
        S = hybrid_ssm.ssm_step(S, xt, B2[:, t], dt2[:, t], A)
        want.append(hybrid_ssm.ssm_read(S, C2[:, t]) + D[:, None] * xt)
    cum = jnp.cumsum(dt2 * A, axis=1)
    rows = functools.partial(ssm_state.head_rows, groups=G, d_head=P)
    Y, Sn = ssm_state.state_round(
        ssm_state.to_slab(S0, G), [_commit(x, B, dt, A, G)], C2,
        scale=rows(jnp.exp(cum)),
        mix=(rows(family._own_mix(B2, C2, dt2, cum, D)),
             ssm_state.x_planes(x2, G)))
    np.testing.assert_allclose(ssm_state.from_slab(Sn, H, P), committed,
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(Y.reshape(3, 5, H, P), jnp.stack(want, 1),
                               rtol=1e-4, atol=1e-4)


# -- through ServingEngine ------------------------------------------------------

GEOM = dict(max_lanes=3, block_size=4, prefill_chunk=8, max_seq_len=96)


def engine(model, drafter=None, **kw):
    return ServingEngine(model, ServingConfig(**{**GEOM, **kw}),
                         drafter=drafter)


def prompts(n, seed=5, lo=5, hi=30):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, int(k)).astype(np.int32)
            for k in rng.integers(lo, hi, n)]


def served_gap(ref, model, prompt, out):
    """How far each served token's logit lies below the reference's best
    at its position (the benchmark's ``served_logit_gap``)."""
    full = np.concatenate([prompt, out])[:-1]
    logits = ref_logits(ref, model, full)[prompt.size - 1:]
    return (logits.max(-1) - logits[np.arange(len(out)), out]).max()


@pytest.mark.parametrize("chunk", [8, 128], ids=["chunk8", "chunk128"])
def test_chunked_prefill_and_plain_decode_equal_the_full_forward(
        ref, model, chunk):
    """Prompts shorter than, equal to and several times the chunk (8),
    decoded with speculation off: every served token is the reference's
    first choice to within ``TOL`` at its position. At chunk 128 every
    prompt is ONE padded call whose pad runs past the lane's table and
    ``max_seq_len`` (96)."""
    eng = engine(model, spec=False, prefill_chunk=chunk)
    work = prompts(5) + [np.arange(8, dtype=np.int32),
                         np.arange(3, dtype=np.int32)]
    reqs = [eng.submit(p, max_new_tokens=12) for p in work]
    eng.run()
    for p, r in zip(work, reqs):
        assert served_gap(ref, model, p, np.asarray(r.output)) < TOL
    st = eng.stats()
    assert st["ssm_slot_resets"] == len(work)
    assert st["ssm_state_passes"] == st["decode_steps"]
    assert st["verify_steps"] == 0 and st["spec_rolled_back_tokens"] == 0


class Oracle:
    """A drafter that knows the true continuation and, in the ONE round
    where the context is ``at`` tokens long, proposes ``right`` true
    tokens followed by ``wrong`` false ones."""

    def __init__(self, truth, at, right, wrong, shift=1):
        self.truth, self.at = np.asarray(truth, np.int32), at
        self.right, self.wrong, self.shift = right, wrong, shift

    def propose(self, context, k):
        n = len(context)
        if n != self.at:
            return np.zeros((0,), np.int32)
        d = self.truth[n:n + self.right + self.wrong].copy()
        d[self.right:] = (d[self.right:] + self.shift) % VOCAB
        return d[:k]


def lane_owes(eng, lane=0):
    """Positions of its last verify round the lane's state still owes."""
    return int(eng._pools[5][lane])


def lane_state(eng, lane=0, owed=True):
    """(state [state-space layers, H, P, N], conv tail) of one lane: what
    its state-space layers HOLD — each layer's slab with the positions
    the lane still owes applied (``owed=False``: the slab alone), by the
    step recurrence under the program's mask."""
    fam = eng._family
    cfg, pools = fam.gcfg, eng._pools
    states = pools[family.N_POOLS:family.N_POOLS + fam.n_ssm]
    pend_x = pools[family.N_POOLS + fam.n_ssm:]
    lps = [lp for kind, lp in zip(cfg.layer_types, fam.params["layers"])
           if kind == hybrid_ssm.SSM]
    out = []
    for si, (slab, lp) in enumerate(zip(states, lps)):
        S = ssm_state.from_slab(slab[lane:lane + 1], cfg.mamba_n_heads,
                                cfg.mamba_d_head)
        # the pending arrays hold the convolved x (as the kernel's
        # planes) and B | dt_raw of the lane's last verify round:
        # ssm_inputs wants x | B | C side by side (the C it returns is
        # not used)
        B, dt_raw = ssm_state.pending_rows(
            pools[4][si, lane][None], cfg.mamba_n_heads, cfg.mamba_d_state,
            cfg.mamba_n_groups)
        B = B.reshape(1, B.shape[1], -1)
        c = jnp.concatenate(
            [pend_x[si][lane].reshape(1, B.shape[1], -1), B, B], axis=-1)
        x, B, _, dt, A = hybrid_ssm.ssm_inputs(c, dt_raw, lp, cfg)
        for t in range(lane_owes(eng, lane) if owed else 0):
            S = hybrid_ssm.ssm_step(S, x[:, t], B[:, t], dt[:, t], A)
        out.append(np.asarray(S[0]))
    return (np.stack(out),
            np.asarray(pools[2][:, lane]).reshape(len(states), 3, -1))


def run_until(eng, req, n_out):
    while len(req.output) < n_out:
        eng.step()
    assert len(req.output) == n_out
    return lane_state(eng, req.lane)


K = 4


@pytest.fixture(scope="module")
def plain_run(model):
    """One request decoded plainly: its tokens, and the lane's state and
    conv tail after each number of emitted tokens."""
    prompt = prompts(1, seed=11, lo=13, hi=14)[0]
    eng = engine(model, spec=False)
    req = eng.submit(prompt, max_new_tokens=16)
    states = {}
    while not req.finished:
        eng.step()
        states[len(req.output)] = lane_state(eng, 0)
    return prompt, np.asarray(req.output), states


@pytest.mark.parametrize("a", range(K + 1))
def test_rejected_drafts_leave_no_trace_in_the_state(model, plain_run, a):
    """A verify round whose draft is right for ``a`` of ``k`` tokens: the
    lane emits ``a + 1`` tokens and owes its state ``a + 1`` positions;
    what it HOLDS (slab + owed positions) and its conv tail are BIT FOR
    BIT what the same round leaves with other rejected tokens, or with
    the ``a`` right tokens alone — nothing of a rejected position is in
    them — and equal plain decoding's after as many tokens up to the
    order of summation. ONE CALL LATER the slab itself holds them (the
    next round's pass committed what was owed, in its closed form, then
    its own position), and the lane owes nothing. Every later token is
    plain decoding's."""
    prompt, truth, states = plain_run
    seq = np.concatenate([prompt, truth])
    at = prompt.size + 3  # the round after 3 emitted tokens

    def spec_run(right, wrong, shift=1):
        eng = engine(model, Oracle(seq, at, right, wrong, shift), spec_k=K)
        req = eng.submit(prompt, max_new_tokens=16)
        got = run_until(eng, req, 3 + a + 1)
        assert eng.counters["verify_steps"] == 1
        assert lane_owes(eng, req.lane) == a + 1
        # the slab alone is still the state BEFORE the round
        np.testing.assert_allclose(lane_state(eng, req.lane, owed=False)[0],
                                   states[3][0], rtol=2e-5, atol=1e-6)
        rolled = eng.counters["spec_rolled_back_tokens"]
        eng.step()  # a plain round: commits what is owed, then its own
        assert lane_owes(eng, req.lane) == 0
        for got1, want1 in zip(lane_state(eng, req.lane, owed=False),
                               states[3 + a + 2]):
            np.testing.assert_allclose(got1, want1, rtol=2e-5, atol=1e-6)
        assert eng.counters["ssm_deferred_positions"] == a + 1
        eng.run()
        assert (np.asarray(req.output) == truth).all()
        # the engine's acceptance and the program's agree
        assert eng.counters["spec_rolled_back_tokens"] == rolled \
            == eng.counters["spec_proposed_tokens"] \
            - eng.counters["spec_accepted_tokens"]
        assert eng.counters["spec_accepted_tokens"] == a
        return got, rolled

    (S, tail), rolled = spec_run(a, K - a)
    assert rolled == K - a
    others = []
    if a < K:
        others.append(spec_run(a, K - a, shift=7)[0])
    if a >= 1:
        others.append(spec_run(a, 0)[0])
    for S2, tail2 in others:
        assert (S == S2).all() and (tail == tail2).all()
    S_plain, tail_plain = states[3 + a + 1]
    np.testing.assert_allclose(tail, tail_plain, rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(S, S_plain, rtol=2e-5, atol=1e-6)
    assert np.abs(S_plain).max() > 1e-3
    # a rejected position WOULD show: the state after one token more
    assert np.abs(states[3 + a + 2][0] - S_plain).max() > 1e-3


def test_a_reused_lane_gives_what_a_fresh_engine_gives(model):
    """One lane, two requests one after the other: the second starts from
    a zeroed slot, not from the first one's state."""
    first, second = prompts(2, seed=21)
    eng = engine(model, max_lanes=1, spec=False)
    eng.submit(first, max_new_tokens=10)
    eng.run()
    left = lane_state(eng, 0)[0]
    assert np.abs(left).max() > 1e-3  # the slot is NOT clean
    r2 = eng.submit(second, max_new_tokens=10)
    eng.run()
    fresh = engine(model, max_lanes=1, spec=False)
    f2 = fresh.submit(second, max_new_tokens=10)
    fresh.run()
    assert r2.output == f2.output
    for got, want in zip(lane_state(eng, 0), lane_state(fresh, 0)):
        assert (got == want).all()
    assert eng.stats()["ssm_slot_resets"] == 2


def test_a_prefilling_lane_never_carries_its_predecessors_pending_round(
        model, plain_run):
    """One lane, two requests. The first one's LAST round is a verify
    round, so it finishes owing its state 4 positions, which nothing
    applies; the second's first chunk zeroes the lane's count with the
    slot, its rounds start from what its prefill left, and it is served
    what a fresh engine serves."""
    prompt, truth, _ = plain_run
    seq = np.concatenate([prompt, truth])
    second = prompts(1, seed=23)[0]
    eng = engine(model, Oracle(seq, prompt.size + 6, 3, 0), max_lanes=1,
                 spec_k=K)
    first = eng.submit(prompt, max_new_tokens=10)
    eng.run()
    assert first.output == list(truth[:10])
    assert eng.counters["verify_steps"] == 1 and lane_owes(eng) == 4
    r2 = eng.submit(second, max_new_tokens=10)
    eng.step()  # its prefill, and nothing else yet
    assert lane_owes(eng) == 0
    fresh = engine(model, max_lanes=1, spec=False)
    f2 = fresh.submit(second, max_new_tokens=10)
    fresh.step()
    for got, want in zip(lane_state(eng, owed=False),
                         lane_state(fresh, owed=False)):
        assert (got == want).all()
    eng.run()
    fresh.run()
    assert r2.output == f2.output
    # the predecessor's 4 positions were never committed
    assert eng.counters["ssm_deferred_positions"] == 0


@pytest.mark.parametrize("chunk", [8, 128], ids=["chunk8", "chunk128"])
def test_a_preempted_request_resumes_token_identically(model, chunk):
    """A pool too small for three growing requests: the newest is
    preempted, its slot handed on, and its re-admission's prefill
    rebuilds state and K/V from chunk 0 — in calls of 8, or in one
    padded call of 128, against the roomy engine's calls of 8."""
    work = prompts(3, seed=31, lo=9, hi=12)
    tight = engine(model, num_blocks=13, spec=False, prefill_chunk=chunk)
    roomy = engine(model, spec=False)
    out = {}
    for name, eng in (("tight", tight), ("roomy", roomy)):
        reqs = [eng.submit(p, max_new_tokens=20) for p in work]
        eng.run()
        out[name] = [r.output for r in reqs]
    assert tight.counters["preemptions"] >= 1
    assert roomy.counters["preemptions"] == 0
    assert out["tight"] == out["roomy"]
    assert tight.stats()["ssm_slot_resets"] \
        == len(work) + tight.counters["preemptions"]


# a prefill call wider than what it is fed (PR 32): 1, W - 1, W, W + 1 and
# 3W + 5 tokens around a chunk of W = 8
_LENGTHS = [1, 7, 8, 9, 29]


@pytest.fixture(scope="module")
def chunk_engines(model):
    """One engine a prefill width: a block (the narrowest), 8, and 128 —
    wider than every prompt, a lane's table and ``max_seq_len`` (96)."""
    return {c: engine(model, spec=False, prefill_chunk=c)
            for c in (4, 8, 128)}


@pytest.mark.parametrize("chunk", [8, 128], ids=["chunk8", "chunk128"])
@pytest.mark.parametrize("length", _LENGTHS)
def test_a_wider_prefill_call_serves_the_same_tokens(ref, model,
                                                     chunk_engines, chunk,
                                                     length):
    """The same prompt through a ``chunk``-wide call and through
    block-wide ones: the same tokens, each the reference's first choice,
    and the lane's state and conv tail agree when the request is done —
    the pad is the identity on both (``dt`` 0; the tail ends at the last
    REAL position)."""
    prompt = prompts(1, seed=100 + length, lo=length, hi=length + 1)[0]
    got, left = {}, {}
    for c in (4, chunk):
        eng = chunk_engines[c]
        r = eng.submit(prompt, max_new_tokens=8)
        eng.step()
        lane = r.lane
        eng.run()
        got[c], left[c] = r, lane_state(eng, lane)
        eng.scheduler.pool.check_invariant()
    assert got[chunk].output == got[4].output
    assert served_gap(ref, model, prompt,
                      np.asarray(got[chunk].output)) < TOL
    for a, b in zip(left[chunk], left[4]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("padded", [16, 128, 8],
                         ids=["pad3", "pad115", "two_calls"])
def test_a_padded_final_chunk_leaves_the_exact_ones_state(model, padded):
    """A 13-token prompt prefilled (and nothing decoded: one new token)
    in ONE exact-width call of 13, against a padded call of 16, one of
    128 (past the table and ``max_seq_len``) and two calls of 8 with
    the second padded: the same first token, the same state to float32
    rounding (the chunked scan sums in another order), and the same conv
    tail: the 3 rows that end at position 12, not at the call's end."""
    prompt = prompts(1, seed=77, lo=13, hi=14)[0]
    out = {}
    for c in (13, padded):
        eng = engine(model, max_lanes=1, spec=False, prefill_chunk=c)
        r = eng.submit(prompt, max_new_tokens=1)
        eng.run()
        assert eng.counters["decode_steps"] == 0
        assert eng.counters["prefill_fed_tokens"] == -(-13 // c) * c
        out[c] = (r.output, *lane_state(eng, 0))
    assert out[padded][0] == out[13][0]
    np.testing.assert_allclose(out[padded][1], out[13][1], rtol=1e-4,
                               atol=1e-5)
    assert np.abs(out[13][1]).max() > 1e-3
    np.testing.assert_allclose(out[padded][2], out[13][2], rtol=1e-4,
                               atol=1e-5)
    assert np.abs(out[13][2]).max() > 1e-3


def test_a_wide_calls_pad_writes_the_null_block_alone(model):
    """5 real tokens in a 128-wide call on a fresh engine, and no round
    after it (a round advances every lane's slot, idle ones too): the
    pad's K/V lands in block 0, so besides it just the prompt's two
    blocks of 4 are written; and one lane's state and conv tail."""
    eng = engine(model, spec=False, prefill_chunk=128)
    eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=1)
    eng.run()
    assert eng.counters["prefill_chunks"] == 1 \
        and eng.counters["decode_steps"] == 0
    for pool in eng._pools[:2]:
        written = np.asarray(pool).any(axis=(0, 2, 3)).nonzero()[0]
        assert len(set(written) - {0}) == 2 and 0 in written
    touched = [lane for lane in range(3)
               if any(a.any() for a in lane_state(eng, lane))]
    assert len(touched) == 1
    eng.scheduler.pool.check_invariant()


def test_prefix_cache_on_acquires_nothing(model):
    """Two requests with the same prompt, prefix cache on (the default):
    a family with recurrent state acquires no shared block."""
    prompt = prompts(1, seed=41, lo=24, hi=25)[0]
    eng = engine(model, prefix_cache=True, spec=False)
    a = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    b = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    assert a.output == b.output
    assert a.cached_len == b.cached_len == 0
    st = eng.stats()
    assert st["prefix_hit_tokens"] == 0
    assert st["prefix_miss_tokens"] == 2 * prompt.size
    assert st["prefix_cache"] is True and st["prefix_reuse"] is False
    assert "recurrent state" in st["prefix_reuse_why"]
    assert st["indexed_blocks"] == 0


def test_speculation_is_token_identical_to_plain_decoding(model):
    """The default n-gram drafter on repeating prompts (so that it
    proposes and mostly misses) over several lanes with churn."""
    rng = np.random.default_rng(7)
    work = [np.tile(rng.integers(0, VOCAB, 4).astype(np.int32), 5)
            for _ in range(5)]
    outs = {}
    for spec in (False, True):
        eng = engine(model, spec=spec)
        reqs = [eng.submit(p, max_new_tokens=14) for p in work]
        eng.run()
        outs[spec] = [r.output for r in reqs]
        st = eng.stats()
    assert outs[True] == outs[False]
    assert st["verify_steps"] > 0
    assert st["spec_rolled_back_tokens"] \
        == st["spec_proposed_tokens"] - st["spec_accepted_tokens"] > 0
    # one pass through the state a round, plain or verify; every kept
    # position of a verify round entered the state a call late
    assert st["ssm_state_passes"] \
        == st["decode_steps"] + st["verify_steps"]
    assert st["ssm_state_lane_moves"] == 2 * st["ssm_lane_rounds"]
    assert 0 < st["ssm_deferred_positions"] \
        <= st["spec_accepted_tokens"] + st["ssm_lane_rounds"]


def test_stats_tell_pools_by_kind(model):
    eng = engine(model)
    st = eng.stats()
    c = model.config
    n_ssm = 3
    state = n_ssm * c.mamba_n_heads * c.mamba_d_head * c.mamba_d_state * 4
    tail = n_ssm * 3 * c.conv_dim * 4  # float32 here
    assert st["family"] == "hybrid_ssm"
    owed = n_ssm * 5 * (c.d_inner + c.mamba_n_groups * c.mamba_d_state
                        + c.mamba_n_heads) * 4 + 4
    assert st["ssm_state_bytes_per_lane"] == state
    assert st["ssm_pending_bytes_per_lane"] == owed
    assert st["lane_pool_bytes"] == GEOM["max_lanes"] * (state + tail
                                                         + owed)
    blocks = eng.scheduler.pool.num_blocks
    assert st["kv_pool_bytes"] == 1 * 2 * blocks * 4 * 2 * 16 * 4
    assert st["device_state_bytes"] \
        == st["kv_pool_bytes"] + st["lane_pool_bytes"]


@pytest.mark.parametrize("flag", ["kv_int8", "int8_weights"])
def test_unsupported_serving_modes_raise(model, flag):
    with pytest.raises(UnimplementedError, match=flag):
        engine(model, **{flag: True})


def test_other_families_keep_nothing_by_lane():
    """The dense family's device state is all token-indexed, and a prefix
    of its blocks is all a new request needs."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    dense = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
    dense.eval()
    st = ServingEngine(dense, ServingConfig(
        max_lanes=2, block_size=4, max_seq_len=32)).stats()
    assert st["family"] == "dense_gqa" and st["prefix_reuse"] is True
    assert st["lane_pool_bytes"] == 0
    assert st["device_state_bytes"] == st["kv_pool_bytes"] > 0
