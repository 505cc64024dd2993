"""The linear-attention / latent-attention sparse-expert model and its
serving family against the plain reference
(``benchmarks/chip/reference/kda_mla_moe.py``, loaded by path: ONE copy).

Tiny widths, CPU, seeded weights; the decay leaves ``A_log`` / ``dt_bias``
keep the model's PUBLISHED initialisation (``A`` in [1, 16], ``dt`` in
[1e-3, 1e-1]): a slow decay, under which a state carried wrongly from
chunk to chunk, call to call or request to request shows (the benchmark's
seeded weights halve a state every token and cannot show it). The program
runs in float32 here, so what separates it from the float32 reference is
the order of summation: ``TOL`` = 2e-4 on logits of magnitude ~1.
"""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework.errors import UnimplementedError
from paddle_tpu.incubate.distributed.models.moe.held_experts import (
    HeldExperts, route_top_k,
)
from paddle_tpu.models import (
    LinearLatentMoEConfig, LinearLatentMoEForCausalLM, generate,
    linear_latent_moe as M,
)
from paddle_tpu.ops.pallas import kda_state
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.families import linear_latent_moe as family

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
VOCAB = 256
LINEAR = {"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
          "num_heads": 3, "head_dim": 16, "short_conv_kernel_size": 4}


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "kda_mla_moe_reference",
        os.path.join(ROOT, "benchmarks/chip/reference/kda_mla_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_config(**kw):
    """4 linear-attention layers around 1 latent layer (the 4th, as
    published); layer 1 dense, 4 expert layers holding experts 2-5 of 8;
    every width differs from every other where it can, and the chunk (8)
    from every length tried."""
    base = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=80,
                moe_intermediate_size=24, num_hidden_layers=5,
                first_k_dense_replace=1, linear_attn_config=LINEAR,
                num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=12, num_experts=4,
                router_experts=8, first_held_expert=2,
                num_experts_per_token=3, routed_scaling_factor=2.446,
                kda_chunk_size=8)
    base.update(kw)
    return LinearLatentMoEConfig(**base)


BORN = ("A_log", "dt_bias")  # kept as the model is born


def seeded(model, seed=0, dtype="float32"):
    """Matrices N(0, 0.1) (the conv weights and the router's selection
    bias too), norm weights 1 +- 0.1 (so a dropped norm weight shows),
    from one generator in parameter order; the leaves of ``BORN`` as
    published."""
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        leaf = name.rsplit(".", 1)[-1]
        if leaf in BORN:
            v = np.asarray(p._data, np.float32)
        elif leaf in ("ln_in", "ln_post", "o_norm", "kv_norm", "norm"):
            v = 1 + 0.1 * rng.uniform(-1, 1, p.shape)
        else:
            v = rng.normal(0, 0.1, p.shape)
        p._data = jnp.asarray(v, dtype)
    model.eval()
    return model


def ref_params(model):
    out = {k: np.asarray(getattr(model, k)._data, np.float32)
           for k in ("embed", "norm", "lm_head")}
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
    return seeded(LinearLatentMoEForCausalLM(tiny_config()))


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
    low = seeded(LinearLatentMoEForCausalLM(tiny_config(dtype="bfloat16")),
                 dtype="bfloat16")
    got = low(pt.to_tensor(ids[:1])).numpy().astype(np.float32)[0]
    assert np.abs(got - want).max() > 10 * TOL
    assert np.abs(ref_logits(ref, model, ids[0], quant=True)
                  - want).max() > 10 * TOL


def test_the_layer_kinds_follow_the_published_lists():
    m = LinearLatentMoEForCausalLM(tiny_config())
    assert [b.kind for b in m.layers] == ["kda", "kda", "kda", "latent",
                                          "kda"]
    assert [b.ffn for b in m.layers] == ["dense"] + ["expert"] * 4
    with pytest.raises(ValueError, match="kda_layers"):
        tiny_config(linear_attn_config=dict(LINEAR, kda_layers=[1, 2, 3]))


def test_published_initialisation_is_slow_decay():
    m = LinearLatentMoEForCausalLM(tiny_config())
    for blk in m.layers:
        if blk.kind != M.KDA:
            continue
        A = np.exp(blk.A_log.numpy())
        dt = np.log1p(np.exp(blk.dt_bias.numpy()))
        assert (A >= 1).all() and (A <= 16).all()
        assert (dt >= 0.99e-3).all() and (dt <= 1.01e-1).all()
        # a channel halves after ln 2 / (dt A) positions: a few to
        # hundreds (the benchmark's seeded weights: one)
        half = np.log(2) / (dt.reshape(A.size, -1) * A[:, None])
        assert np.median(half) > 3 and half.max() > 20


def _kda_inputs(T, seed=0, b=2):
    c = tiny_config()
    rng = np.random.default_rng(seed)
    H, d = c.kda_heads, c.kda_head_dim
    f = lambda *s: jnp.asarray(rng.normal(0, 1, s), jnp.float32)  # noqa: E731
    born = M._published_kda_init(H, d, seed)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    g = -jnp.exp(jnp.asarray(born["A_log"], jnp.float32))[:, None] \
        * jax.nn.softplus(f(b, T, H, d) * 0.5 + jnp.asarray(
            born["dt_bias"], jnp.float32).reshape(H, d))
    return (unit(f(b, T, H, d)) * d ** -0.5, unit(f(b, T, H, d)),
            f(b, T, H, d), g, jax.nn.sigmoid(f(b, T, H)), f(b, H, d, d))


def _step_by_step(q, k, v, g, beta, S):
    os_ = []
    for t in range(q.shape[1]):
        S, o = M.kda_step(S, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        os_.append(o)
    return jnp.stack(os_, 1), S


@pytest.mark.parametrize("T", [1, 5, 8, 13, 37])
def test_chunked_form_equals_the_step_recurrence(T):
    """Lengths below, at and beyond the chunk (8), none but 8 a multiple
    of it, from a non-zero carried state, under slow decay."""
    q, k, v, g, beta, S0 = _kda_inputs(T, seed=T)
    o, S = M.kda_chunk(q, k, v, g, beta, S0, 8)
    o_want, S_want = _step_by_step(q, k, v, g, beta, S0)
    np.testing.assert_allclose(o, o_want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(S, S_want, rtol=1e-4, atol=1e-4)
    # the carried state matters at this decay: dropping it is not close
    o0, _ = M.kda_chunk(q, k, v, g, beta, jnp.zeros_like(S0), 8)
    assert np.abs(np.asarray(o0 - o_want)).max() > 0.1


def test_the_step_is_the_published_recurrence():
    """``kda_step`` reads the old state once for both products; held to
    the recurrence as written: decay, correct, then read the NEW state."""
    q, k, v, g, beta, S0 = _kda_inputs(1, seed=9)
    S, o = M.kda_step(S0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    Sd = jnp.exp(g[:, 0])[..., None] * S0
    pred = jnp.einsum("bhkv,bhk->bhv", Sd, k[:, 0])
    want = Sd + beta[:, 0, :, None, None] * k[:, 0][..., None] \
        * (v[:, 0] - pred)[:, :, None, :]
    np.testing.assert_allclose(S, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        o, jnp.einsum("bhkv,bhk->bhv", want, q[:, 0]), rtol=1e-4, atol=1e-5)


def test_a_masked_position_is_the_identity_on_the_state():
    """``g`` 0 and ``beta`` 0: bit for bit in the step and in the one-pass
    update; in the chunked form to float32 rounding."""
    q, k, v, g, beta, S0 = _kda_inputs(6, seed=3)
    zero = jnp.zeros_like
    S, _ = M.kda_step(S0, q[:, 0], k[:, 0], v[:, 0], zero(g[:, 0]),
                      zero(beta[:, 0]))
    assert (np.asarray(S) == np.asarray(S0)).all()
    hf = lambda a: jnp.swapaxes(a, 1, 2)  # noqa: E731
    S = M.kda_apply(S0, hf(k), zero(hf(g)), zero(hf(v)))
    assert (np.asarray(S) == np.asarray(S0)).all()
    keep = jnp.arange(6)[None, :, None] < 4
    _, S4 = M.kda_chunk(q[:, :4], k[:, :4], v[:, :4], g[:, :4], beta[:, :4],
                        S0, 8)
    _, S6 = M.kda_chunk(q, k, v, jnp.where(keep[..., None], g, 0.0),
                        jnp.where(keep, beta, 0.0), S0, 8)
    np.testing.assert_allclose(S6, S4, rtol=1e-6, atol=1e-6)


def _masked_steps(q, k, v, g, beta, S, n):
    """``kda_step`` position by position, a row's state advanced over its
    first ``n[row]`` positions alone; (every position's output, S)."""
    outs = []
    for t in range(q.shape[1]):
        S2, o = M.kda_step(S, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        S = jnp.where((t < n)[:, None, None, None], S2, S)
        outs.append(o)
    return jnp.stack(outs, 1), S


def _state_round(S, pend, layer, n, q, k, v, g, beta, own):
    """The kernel on ``[b, T, ..]`` arrays, as the family calls it (it
    takes and gives positions first)."""
    return family._round(S, pend, layer, n, (q, k, v, g), beta, own)


def _left_pending(S0, seed, layers=3, layer=1):
    """A verify round's call on ``S0``: (its inputs, the pending pool it
    leaves — ``layer``'s entries of ``layers``, the others ones)."""
    owed = _kda_inputs(5, seed=seed, b=3)[:5]
    ones = jnp.ones(kda_state.pending_shape(layers, 3, 5, *S0.shape[1:3]),
                    jnp.float32)
    o, S, pend = _state_round(S0, ones, layer, jnp.zeros((3,), int), *owed,
                              own=False)
    # nothing was owed: the state is as it was; the round's outputs are
    # the recurrence's; the other layers' entries are untouched
    assert (np.asarray(S) == np.asarray(S0)).all()
    want, _ = _masked_steps(*owed, S0, jnp.full((3,), 5))
    np.testing.assert_allclose(o, want, rtol=1e-4, atol=1e-5)
    assert (np.asarray(pend[0]) == 1).all() and (np.asarray(pend[2])
                                                  == 1).all()
    return owed, pend


@pytest.mark.parametrize("n_keep", range(6))
@pytest.mark.parametrize("reads", [1, 5])
def test_state_kernel_equals_the_step_recurrence(reads, n_keep):
    """5 owed positions of which a row keeps ``n_keep`` (its neighbours
    another number), then ``reads`` positions of this round: the state
    the kernel writes is ``kda_step`` over the kept positions, its outputs
    the recurrence's from there, position by position. With one read (a
    plain round) the round's own position is applied in the same call;
    with five (a verify round) none is, and they are left pending."""
    S0 = _kda_inputs(1, seed=50 + n_keep, b=3)[5]
    owed, pend = _left_pending(S0, seed=10 * reads + n_keep)
    n = jnp.asarray([n_keep, (n_keep + 2) % 6, 5 - n_keep])
    _, S_c = _masked_steps(*owed, S0, n)
    now = _kda_inputs(reads, seed=99 - n_keep, b=3)[:5]
    want, S_own = _masked_steps(*now, S_c, jnp.full((3,), reads))
    o, S, *left = _state_round(S0, pend, 1, n, *now, own=reads == 1)
    np.testing.assert_allclose(o, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(S, S_own if reads == 1 else S_c, rtol=1e-4,
                               atol=1e-5)
    assert np.abs(np.asarray(S_c - S0)).max() > 1e-2
    if reads == 5:  # what the round leaves is what ITS commit needs
        k, g, u = (left[0][1, :, i] for i in range(3))
        assert (np.asarray(k) == np.asarray(now[1])).all()
        assert (np.asarray(g) == np.asarray(now[3])).all()
        hf = lambda a: jnp.swapaxes(a, 1, 2)  # noqa: E731
        np.testing.assert_allclose(
            M.kda_apply(S_c, hf(k), hf(g), hf(u)), S_own, rtol=1e-4,
            atol=1e-5)


@pytest.mark.parametrize("reads", [1, 5])
def test_state_kernel_with_nothing_owed_is_the_identity(reads):
    """A lane that owes nothing gets its state back BIT FOR BIT, whatever
    the pending entries hold — a verify round's rejected drafts, a
    finished request's last round — and reads from exactly that state."""
    S0 = _kda_inputs(1, seed=60 + reads, b=3)[5]
    _, pend = _left_pending(S0, seed=reads)
    now = _kda_inputs(5, seed=70, b=3)[:5]
    none = jnp.zeros((3,), jnp.int32)
    o, S, _ = _state_round(S0, pend, 1, none, *now, own=False)
    assert (np.asarray(S) == np.asarray(S0)).all()
    o2, S2, _ = _state_round(S0, 7 * pend[:, :, :, ::-1] + 1, 1, none,
                             *now, own=False)
    assert (np.asarray(S2) == np.asarray(S0)).all()
    assert (np.asarray(o2) == np.asarray(o)).all()
    want, _ = _masked_steps(*now, S0, jnp.full((3,), 5))
    np.testing.assert_allclose(o, want, rtol=1e-4, atol=1e-5)


def test_the_selection_bias_chooses_and_does_not_weigh():
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.normal(0, 1, (9, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.3, (16, 8)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.3, (8,)), jnp.float32)
    idx0, g0 = route_top_k(u, w, 3, 2.0)
    idx, g = route_top_k(u, w, 3, 2.0, bias)
    s = np.asarray(jax.nn.sigmoid(u @ w))
    want = np.argsort(-(s + np.asarray(bias)), -1)[:, :3]
    assert (np.sort(np.asarray(idx), -1) == np.sort(want, -1)).all()
    assert (np.sort(np.asarray(idx), -1)
            != np.sort(np.asarray(idx0), -1)).any()
    top = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(g, top / top.sum(-1, keepdims=True) * 2.0,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g).sum(-1), 2.0, rtol=1e-5)


def _expert_layer(first, held, seed=3):
    layer = HeldExperts(64, 32, 16, held, first_held=first, top_k=4,
                        scaling=2.446, selection_bias=True)
    rng = np.random.default_rng(seed)  # the same draws for every share
    full = {"router": rng.normal(0, 0.3, (64, 16)),
            "experts_gate_up": rng.normal(0, 0.1, (16, 64, 64)),
            "experts_down": rng.normal(0, 0.1, (16, 32, 64)),
            "shared_gate_up": rng.normal(0, 0.1, (64, 64)),
            "shared_down": rng.normal(0, 0.1, (32, 64)),
            "router_bias": rng.normal(0, 0.2, (16,))}
    for k, v in full.items():
        if k.startswith("experts"):
            v = v[first:first + held]
        getattr(layer, k)._data = jnp.asarray(v, jnp.float32)
    return layer, full


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """The guide's test of the expert-parallel cut: 16 shares of 1 expert
    each (ranks 0-15 of 16 experts), the shared expert counted once, add
    up to the uncut layer's output as the reference computes it."""
    u = np.random.default_rng(5).normal(0, 1, (18, 64)).astype(np.float32)
    whole, full = _expert_layer(0, 16)
    lw = {k: jnp.asarray(v, jnp.float32) for k, v in full.items()}
    m = {"num_experts_per_token": 4, "num_experts": 16,
         "routed_scaling_factor": 2.446}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts(jnp.asarray(u), lw, m, False))
        shared = np.asarray(ref.swiglu(jnp.asarray(u), lw["shared_gate_up"],
                                       lw["shared_down"], False))
    total, counts = -15 * shared, []
    for rank in range(16):
        share, _ = _expert_layer(rank, 1)
        total = total + share(pt.to_tensor(u)).numpy()
        counts.append(share.last_counts.numpy())
    assert np.abs(total - want).max() < TOL
    # every token-expert assignment lands on exactly one share
    assert int(np.concatenate(counts).sum()) == 18 * 4
    # and one share alone is NOT the layer (the cut leaves something out)
    assert np.abs(whole(pt.to_tensor(u)).numpy() - want).max() < TOL
    assert np.abs(share(pt.to_tensor(u)).numpy() - want).max() > 0.01


def test_train_step_runs_and_learns():
    """``jit.TrainStep`` differentiates through the chunked delta rule
    (several chunks, a padded last one) and the loss falls."""
    from paddle_tpu.jit.train_step import TrainStep

    pt.seed(0)
    m = LinearLatentMoEForCausalLM(tiny_config(initializer_range=0.05))
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
    with pytest.raises(UnimplementedError, match="linear_latent_moe"):
        generate(model, pt.to_tensor(np.zeros((1, 4), np.int32)),
                 max_new_tokens=2)


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
    ``max_seq_len`` (96), in four query tiles of which the fed ones are
    attended."""
    eng = engine(model, spec=False, prefill_chunk=chunk)
    work = prompts(5) + [np.arange(8, dtype=np.int32),
                         np.arange(3, dtype=np.int32)]
    reqs = [eng.submit(p, max_new_tokens=12) for p in work]
    eng.run()
    for p, r in zip(work, reqs):
        assert served_gap(ref, model, p, np.asarray(r.output)) < TOL
    st = eng.stats()
    assert st["lin_slot_resets"] == len(work)
    assert st["lin_state_passes"] == st["decode_steps"]
    assert st["lin_state_lane_moves"] == 2 * st["lin_lane_rounds"]
    assert st["verify_steps"] == 0 and st["spec_rolled_back_tokens"] == 0
    assert st["moe_expert_calls"] == 4 * (st["decode_steps"]
                                          + st["prefill_chunks"])
    # held experts hit, counted in the rounds' calls: 1-4 of 4 a call
    assert 4 * st["decode_steps"] <= st["moe_round_experts_hit"] \
        <= 16 * st["decode_steps"]


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
    return int(eng._pools[-1][lane])


def lane_state(eng, lane=0, owed=True):
    """(state [linear-attention layers, H, d, d], conv tail) of a lane:
    what its linear-attention layers HOLD — each layer's array entry with
    the positions the lane still owes applied (``owed=False``: the entry
    alone), by the one-pass update under the program's mask."""
    *states, pend, _ = eng._pools[3:]
    n = len(states)
    S = jnp.stack([s[lane] for s in states])
    if owed:
        keep = (jnp.arange(pend.shape[3])
                < lane_owes(eng, lane))[None, None, :, None]
        k, g, u = (jnp.swapaxes(pend[:, lane, i], 1, 2) for i in range(3))
        S = M.kda_apply(S, k, jnp.where(keep, g, 0.0),
                        jnp.where(keep, u, 0.0))
    return (np.asarray(S),
            np.asarray(eng._pools[2][:, lane]).reshape(n, 3, -1))


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
    lane emits ``a + 1`` tokens, and its state and conv tail are BIT FOR
    BIT what the same round leaves with other rejected tokens, or with
    the ``a`` right tokens alone — nothing of a rejected position is in
    them — and equal plain decoding's after as many tokens up to the
    order of summation (the round reads the state once, in the chunked
    form over its positions, which is not the step recurrence bit for
    bit; ``lane_state``: the lane's array entry WITH the positions it
    still owes). Every later token is plain decoding's, and the engine's
    acceptance and the program's agree."""
    prompt, truth, states = plain_run
    seq = np.concatenate([prompt, truth])
    at = prompt.size + 3  # the round after 3 emitted tokens

    def spec_run(right, wrong, shift=1):
        eng = engine(model, Oracle(seq, at, right, wrong, shift), spec_k=K)
        req = eng.submit(prompt, max_new_tokens=16)
        got = run_until(eng, req, 3 + a + 1)
        assert eng.counters["verify_steps"] == 1
        rolled = eng.counters["spec_rolled_back_tokens"]
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
    np.testing.assert_allclose(tail, tail_plain, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(S, S_plain, rtol=1e-4, atol=1e-5)
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
    assert eng.stats()["lin_slot_resets"] == 2


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
    assert eng.stats()["lin_deferred_positions"] == 0


@pytest.mark.parametrize("chunk", [8, 128], ids=["chunk8", "chunk128"])
def test_a_preempted_request_resumes_token_identically(model, chunk):
    """A pool too small for three growing requests: the newest is
    preempted, its slot handed on, and its re-admission's prefill
    rebuilds state and latent entries from chunk 0 — in calls of 8, or in
    one padded call of 128, against the roomy engine's calls of 8."""
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
    assert tight.stats()["lin_slot_resets"] \
        == len(work) + tight.counters["preemptions"]


@pytest.mark.parametrize("length", [1, 7, 8, 9, 29])
def test_a_wider_prefill_call_serves_the_same_tokens(ref, model, length):
    """The same prompt through a 128-wide call and through block-wide
    ones: the same tokens, each the reference's first choice, and the
    lane's state and conv tail agree when the request is done — the pad is
    the identity on both (``g`` and ``beta`` 0; the tail ends at the last
    REAL position)."""
    prompt = prompts(1, seed=100 + length, lo=length, hi=length + 1)[0]
    got, left = {}, {}
    for c in (4, 128):
        eng = engine(model, spec=False, prefill_chunk=c, max_lanes=1)
        r = eng.submit(prompt, max_new_tokens=8)
        eng.run()
        got[c], left[c] = r, lane_state(eng, 0)
        eng.scheduler.pool.check_invariant()
    assert got[128].output == got[4].output
    assert served_gap(ref, model, prompt,
                      np.asarray(got[128].output)) < TOL
    for a, b in zip(left[128], left[4]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
        assert np.abs(b).max() > 1e-3


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
    assert st["lin_state_passes"] \
        == st["decode_steps"] + st["verify_steps"]
    assert st["lin_state_lane_moves"] == 2 * st["lin_lane_rounds"]
    # every accepted position entered its lane's state a call late, but
    # for the last round's of a request that then finished
    kept = st["spec_accepted_tokens"] + st["verify_steps"]
    assert 0 < st["lin_deferred_positions"] <= kept * GEOM["max_lanes"]


def test_stats_tell_pools_by_kind(model):
    eng = engine(model)
    st = eng.stats()
    c = model.config
    n_kda = 4
    state = n_kda * c.kda_heads * c.kda_head_dim * c.kda_head_dim * 4
    tail = n_kda * 3 * 3 * c.kda_width * 4  # float32 here
    assert st["family"] == "linear_latent_moe"
    assert st["lin_state_bytes_per_lane"] == state
    assert st["lin_conv_bytes_per_lane"] == tail
    # a verify round's k+1 positions' keys, log-decays, pseudo-values
    # (float32) a layer, and the lane's count
    owed = n_kda * 3 * (eng.config.spec_k + 1) * c.kda_width * 4 + 4
    assert st["lin_pending_bytes_per_lane"] == owed
    assert st["lane_pool_bytes"] == GEOM["max_lanes"] * (state + tail + owed)
    assert st["latent_kv_bytes_per_token"] == (32 + 8) * 4
    blocks = eng.scheduler.pool.num_blocks
    # ONE latent layer's entries, padded to a whole 128-lane tile
    assert st["kv_pool_bytes"] == 1 * blocks * 4 * 128 * 4
    assert st["device_state_bytes"] \
        == st["kv_pool_bytes"] + st["lane_pool_bytes"]


@pytest.mark.parametrize("flag", ["kv_int8", "int8_weights"])
def test_unsupported_serving_modes_raise(model, flag):
    with pytest.raises(UnimplementedError, match=flag):
        engine(model, **{flag: True})
