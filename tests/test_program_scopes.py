"""Program scopes (``paddle_tpu/monitor/scopes.py``): every instruction of
the serving families' step programs falls to the layer that issued it, a
scope changes no instruction, the registry that ``jit/exec_cache`` fills
outlives the programs, and the benchmark's reduction of a device trace by
scope (``benchmarks/chip/chiplib/devscopes.py``) puts op time where it
belongs."""
import contextlib
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.jit import exec_cache
from paddle_tpu.monitor import scopes
from paddle_tpu.serving import ServingConfig, ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KINDS = ("decode", "verify", "prefill")


# -- the six families at the sizes their own tests use -------------------------

def _dense():
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    model = LlamaForCausalLM(LlamaConfig.tiny(
        num_hidden_layers=3, hidden_size=128, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=256))
    return model, dict(max_lanes=4, block_size=16, num_blocks=37,
                       prefill_chunk=32, max_seq_len=80)


def _latent():
    from paddle_tpu.models import LatentMoEConfig, LatentMoEForCausalLM

    model = LatentMoEForCausalLM(LatentMoEConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=48,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=24, n_routed_experts=4, router_experts=16,
        first_held_expert=4, num_experts_per_tok=4,
        routed_scaling_factor=2.5, rope_theta=25600000.0))
    return model, dict(max_lanes=3, block_size=16, prefill_chunk=32,
                       max_seq_len=160)


def _hybrid():
    from paddle_tpu.models import HybridSSMConfig, HybridSSMForCausalLM

    model = HybridSSMForCausalLM(HybridSSMConfig(
        vocab_size=256, hidden_size=64, shared_intermediate_size=80,
        num_hidden_layers=4,
        layer_types=["mamba", "attention", "mamba", "mamba"],
        num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=6,
        mamba_d_head=16, mamba_d_state=8, mamba_n_groups=2, mamba_d_conv=4,
        mamba_chunk_size=8, attention_multiplier=0.2,
        embedding_multiplier=3.0, residual_multiplier=0.5,
        logits_scaling=2.0))
    return model, dict(max_lanes=3, block_size=4, prefill_chunk=8,
                       max_seq_len=96)


def _linear():
    from paddle_tpu.models import (
        LinearLatentMoEConfig, LinearLatentMoEForCausalLM,
    )

    model = LinearLatentMoEForCausalLM(LinearLatentMoEConfig(
        vocab_size=256, hidden_size=64, intermediate_size=80,
        moe_intermediate_size=24, num_hidden_layers=5,
        first_k_dense_replace=1, linear_attn_config={
            "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4],
            "num_heads": 3, "head_dim": 16, "short_conv_kernel_size": 4},
        num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=12, num_experts=4, router_experts=8,
        first_held_expert=2, num_experts_per_token=3,
        routed_scaling_factor=2.446, kda_chunk_size=8))
    return model, dict(max_lanes=3, block_size=4, prefill_chunk=8,
                       max_seq_len=96)


def _window():
    from paddle_tpu.models import WindowMoEConfig, WindowMoEForCausalLM

    model = WindowMoEForCausalLM(WindowMoEConfig(
        vocab_size=256, hidden_size=64, intermediate_size=80,
        moe_intermediate_size=24, num_hidden_layers=5,
        hybrid_layer_pattern=[0, 1, 1, 1, 0],
        moe_layer_freq=[0, 1, 1, 1, 1], num_attention_heads=8,
        num_key_value_heads=2, swa_num_key_value_heads=4, head_dim=24,
        v_head_dim=16, sliding_window=8, n_routed_experts=4,
        router_experts=8, first_held_expert=2, num_experts_per_tok=3))
    return model, dict(max_lanes=3, block_size=4, prefill_chunk=8,
                       max_seq_len=96)


def _conv():
    from paddle_tpu.models import ConvMoEConfig, ConvMoEForCausalLM

    model = ConvMoEForCausalLM(ConvMoEConfig(
        vocab_size=256, hidden_size=64, intermediate_size=80,
        moe_intermediate_size=24, num_hidden_layers=5,
        layer_types=["conv", "conv", "conv", "full_attention", "conv"],
        num_dense_layers=1, num_attention_heads=8, num_key_value_heads=2,
        num_experts=16, num_experts_per_tok=4))
    return model, dict(max_lanes=3, block_size=4, prefill_chunk=8,
                       max_seq_len=96)


FAMILIES = {"dense_gqa": _dense, "latent_moe": _latent,
            "hybrid_ssm": _hybrid, "linear_latent_moe": _linear,
            "window_moe": _window, "conv_moe": _conv}


def _engine(family):
    model, geom = FAMILIES[family]()
    model.eval()
    eng = ServingEngine(model, ServingConfig(**geom))
    assert eng._family.name == family
    return eng


def _program_texts(eng, kind):
    """Program ``kind`` as ``ServingEngine._ensure_compiled`` lowers it:
    (its lowered text, without debug info; its text compiled here and now
    — no cache between the caller and the trace)."""
    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    cfg = eng.config
    L, S, C = cfg.max_lanes, cfg.spec_k + 1, eng.prefill_chunk
    lanes, width, rest = {
        "decode": (L, 1, (i32(L), i32(L))),
        "verify": (L, S, (i32(L), i32(L, S), i32(L))),
        "prefill": (1, C, (i32(1, C), i32(), i32(), i32()))}[kind]
    fn, static = eng._family.program(kind)
    pools = jax.tree_util.tree_map(spec, (eng._params, *eng._pools))
    jax.clear_caches()  # a traced function keeps the names it was traced by
    # ... and so does a compiled one: the persistent compile cache keys a
    # program WITHOUT its metadata, so a hit hands back the names of
    # whichever tree compiled it first (tests/conftest.py turns it on)
    with exec_cache._fresh_compile():
        lowered = jax.jit(fn, static_argnames=tuple(static)).lower(
            *pools, eng._read_spec(kind, lanes, width), *rest, **static)
        return lowered.as_text(), lowered.compile().as_text()


def _program_text(eng, kind):
    return _program_texts(eng, kind)[1]


@pytest.fixture(scope="module", params=list(FAMILIES))
def texts(request):
    """{kind: (text with the scopes on, text with ``jax.named_scope`` a
    null context, the lowered text)} of one family's three programs."""
    eng = _engine(request.param)
    both = {kind: _program_texts(eng, kind) for kind in KINDS}
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        off = {kind: _program_text(eng, kind) for kind in KINDS}
    finally:
        jax.named_scope = real
        jax.clear_caches()
    return {kind: (both[kind][1], off[kind], both[kind][0])
            for kind in KINDS}


# a layer loop's own bookkeeping belongs to no layer: a scan's counter, its
# condition and its index vector, the loop-carried initial values whose
# metadata XLA merged down to ``while/body``, the pool copies of a backend
# that cannot donate. The dense family scans its layers: 8 such
# instructions in a program of ~80 on the CPU
LOOP_BOOKKEEPING = 8


@pytest.mark.parametrize("kind", KINDS)
def test_every_instruction_falls_to_a_declared_group(texts, kind):
    module, table = scopes.parse(texts[kind][0])
    assert module.startswith("jit_")
    work = {name: row for name, row in table.items()
            if row[3] not in scopes.PLUMBING
            and row[3] not in ("while", "conditional", "call")}
    assert len(work) > 50
    for name, (path, group, _, _, how) in work.items():
        assert (group in set(scopes.GROUPS.values())) == bool(path), name
        assert group == scopes.group_of(path)
        assert (how in ("own", "fused", "user", "operand")) == bool(path)
    unscoped = sorted(f"{name} ({row[3]}, {row[4] or 'no metadata'})"
                      for name, row in work.items() if not row[1])
    assert len(unscoped) <= max(0.02 * len(work), LOOP_BOOKKEEPING), \
        unscoped
    # the program's own lines wear their scopes themselves: what falls to
    # a group by dataflow alone is what XLA inserted. One exception, in
    # the hybrid family alone: its state kernel runs INTERPRETED on the
    # CPU, as loops around which this backend puts ``copy`` instructions
    # that fall to the kernel's scope by dataflow (97 of the decode
    # program's 345 rows, 89 of verify's 402; 4 of the kernel-less
    # prefill's 197 share the scope; none in the three other families,
    # which have no such scope, and none on the chip, where the kernel is
    # one custom call): those are not counted
    kernel_copies = sum(
        row[3] == "copy" and row[0] == "ssm/state_update"
        and row[4] in ("user", "operand") for row in work.values())
    own = sum(row[4] in ("own", "fused") for row in work.values())
    assert own >= 0.7 * (len(work) - kernel_copies), (own, len(work))
    # and nothing the program issued outside a loop is left without one
    stray = [name for name, row in work.items() if row[4] == "none"]
    assert len(stray) <= LOOP_BOOKKEEPING, stray


_METADATA = re.compile(r",? ?metadata=\{[^}]*\}")


def _instructions(text):
    """The module's computations with every ``metadata={...}`` taken out
    (and the tables of files and stack frames they index)."""
    body = text[text.index("\n\n", text.index("StackFrames")):] \
        if "StackFrames" in text else text
    return text.split("\n", 1)[0] + _METADATA.sub("", body)


@pytest.mark.parametrize("kind", KINDS)
def test_a_scope_changes_no_instruction(texts, kind):
    on, off, _ = texts[kind]
    assert any(row[4] == "own" for row in scopes.parse(on)[1].values())
    assert not any(row[0] for row in scopes.parse(off)[1].values())
    assert _instructions(on) == _instructions(off)


# sha256 (first 16 hex) of (a) each program's lowered text, without debug
# info, as ``tests/test_latent_moe.py`` takes the dense family's (JAX
# 0.9.0, matmul precision "highest" as tests/conftest.py sets it), and (b)
# the SORTED (opcode, scope path) pairs of ``scopes.parse``'s table of the
# compiled program: which scope every instruction falls to, with no
# instruction name, file name or line number in it — sorted, because XLA
# writes a module's computations in an order that differs from one compile
# of the same tree to the next (two runs on PR 43's tree agreed on the
# pairs and on no program's order of them). (b) is what the
# benchmark's ``dev_*_ms_per_round`` metrics and the ledger's ``breakdown``
# split device time by, and (a) does not see a ``jax.named_scope`` that
# moved. PR 44 read them on its parent (PR 43's tree) before it moved the
# families' common part to ``serving/families/common.py``: a refactor that
# keeps each program's operations, their order and their scopes keeps
# both. A change to a program changes its pins: read them again when that
# is meant. So does another JAX (a), or another XLA CPU compiler (b). PR 47
# read the six of ``latent_moe`` and ``linear_latent_moe`` again (their
# read became a call of the row kernel) and left the other twelve as they
# were: the K/V families' kernel is traced to the text it was. PR 48 read
# the three of ``linear_latent_moe`` again (its rounds' state path became a
# call of the state kernel a layer, its chunk zeroes a pending count) and
# no other family's.
_PINS = {
    ("dense_gqa", "decode"): ("17fda45b37bfd089", "bd105bf6f3a6258a"),
    ("dense_gqa", "verify"): ("16d6e470633adb07", "2328434dfb89c058"),
    ("dense_gqa", "prefill"): ("d735efcc21819e63", "920060828733a600"),
    ("latent_moe", "decode"): ("74316d22779a5326", "249a00a00fd232a7"),
    ("latent_moe", "verify"): ("7ae84f42f54b2605", "9818ef8f96572d63"),
    ("latent_moe", "prefill"): ("d8cdb221e9c3085f", "f8251b60435fb351"),
    ("hybrid_ssm", "decode"): ("d822b946bbebeb42", "4bac2dab009fa262"),
    ("hybrid_ssm", "verify"): ("a6321a93dd2cc162", "67f460d199d1d81e"),
    ("hybrid_ssm", "prefill"): ("9148517617aa9927", "5da29c8360572f25"),
    ("linear_latent_moe", "decode"): ("c75990560645e1e6", "8d3fc91cf900f7b7"),
    ("linear_latent_moe", "verify"): ("d3743f505309ddd3", "fac33b182adbadf0"),
    ("linear_latent_moe", "prefill"): ("85c9032de192465e", "827bdda560a782f8"),
    ("window_moe", "decode"): ("74fd0ded846c1d55", "9ce4de4981844279"),
    ("window_moe", "verify"): ("ae41f6cb315ed683", "05435ab5ad1119db"),
    ("window_moe", "prefill"): ("50b4f006d2675fc0", "850003b71665693e"),
    ("conv_moe", "decode"): ("c6397bf50adc653e", "514328ed7f578eb6"),
    ("conv_moe", "verify"): ("785c6b47cb6bd03a", "a6903243907b2b6f"),
    ("conv_moe", "prefill"): ("45cbc60c29394027", "3f8b6ab9f91799a7"),
}


@pytest.mark.parametrize("kind", KINDS)
def test_the_programs_are_the_ones_pinned(texts, kind, request):
    if jax.__version__ != "0.9.0":
        pytest.skip("the pins were read under JAX 0.9.0")
    family = request.node.callspec.params["texts"]
    compiled, _, lowered = texts[kind]
    pairs = sorted((row[3], row[0])
                   for row in scopes.parse(compiled)[1].values())
    got = tuple(hashlib.sha256(t.encode()).hexdigest()[:16]
                for t in (lowered, repr(pairs)))
    assert got == _PINS[family, kind], (family, kind, got)


# -- the scope path ---------------------------------------------------------------

def _layer(x, w):
    with jax.named_scope("attn/rows"):
        y = jax.nn.softmax(jnp.einsum("bd,de->be", x, w))
    with jax.named_scope("mlp"):
        return jnp.tanh(y) @ w


def _scanned(x, ws):
    y, _ = jax.lax.scan(lambda c, w: (_layer(c, w), None), x, ws)
    y = _layer(y, ws[0])  # and once outside any loop
    with jax.named_scope("head"):
        with jax.named_scope("norm"):
            y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True))
        return jnp.sum(y)


def _paths(fn):
    x, ws = jnp.ones((4, 8)), jnp.ones((3, 8, 8))
    with exec_cache._fresh_compile():
        text = jax.jit(fn).lower(x, ws).compile().as_text()
    return {scopes.path_of(m) for m in
            re.findall(r'op_name="([^"]*dot_general|[^"]*rsqrt)"', text)}


@pytest.mark.parametrize("how", ["scan", "checkpoint", "grad",
                                 "grad_of_checkpoint"])
def test_one_op_reads_the_same_path_however_it_is_transformed(how):
    fn = {"scan": _scanned,
          "checkpoint": jax.checkpoint(_scanned),
          "grad": jax.grad(_scanned, argnums=1),
          "grad_of_checkpoint": jax.grad(jax.checkpoint(_scanned),
                                         argnums=1)}[how]
    assert _paths(fn) == {"attn/rows", "mlp", "head/norm"}


@pytest.mark.parametrize("op_name,path", [
    ("jit(f)/jit(main)/while/body/closed_call/mla/q/norm/rsqrt",
     "mla/q/norm"),
    ("jit(f)/transpose(jvp(head))/norm/mul", "head/norm"),
    ("jit(f)/transpose(jvp(mla/q))/norm/bd,de->be/dot_general",
     "mla/q/norm"),
    ("jit(f)/jvp(jit(silu))/moe/shared/mul", "moe/shared"),
    ("jit(f)/attn/rows/bd,de->be/dot_general", "attn/rows"),
    ("jit(f)/jit(norm)/mul", ""),          # a function's name is no scope
    ("jit(f)/rows/mul", ""),               # a sub-scope under no scope
    ("jit(f)/while/body/add", ""),
    ("jit(_decode_step)/moe/experts/jit(silu)/logistic", "moe/experts"),
    ("jit(f)/norm", ""),                   # the last component: a primitive
])
def test_path_of(op_name, path):
    assert scopes.path_of(op_name) == path
    assert scopes.group_of(path) == (
        scopes.GROUPS[path.split("/")[0]] if path else "")


def test_every_declared_scope_has_a_group():
    assert set(scopes.SCOPES) == set(scopes.GROUPS)
    assert set(scopes.GROUPS.values()) == {"attn", "ffn", "state", "norm",
                                           "head"}


def test_what_xla_inserted_takes_its_users_scope():
    text = """HloModule jit_f, is_scheduled=true

%fused_a (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %m = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/attn/rows/mul"}
}

%fused_b (p: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %n = f32[4]{0} negate(%p.1), metadata={op_name="jit(f)/mlp/neg"}
  ROOT %s = f32[4]{0} add(%n, %p.1), metadata={op_name="jit(f)/norm/add"}
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %x = f32[4]{0} get-tuple-element(%t), index=1
  %in_loop = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_a, metadata={op_name="jit(f)/while/body/attn/rows/mul"}
  ROOT %r = (s32[], f32[4]{0}) tuple(%i, %in_loop)
}

%cond (t.1: (s32[], f32[4])) -> pred[] {
  %t.1 = (s32[], f32[4]{0}) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %start = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%a)
  %done = f32[4]{0} copy-done(%start)
  %mixed = f32[4]{0} fusion(%done), kind=kLoop, calls=%fused_b, metadata={op_name="jit(f)/norm/add"}
  %bare = f32[4]{0} add(%mixed, %mixed), metadata={op_name="jit(f)/add"}
  %zero = s32[] constant(0)
  %init = (s32[], f32[4]{0}) tuple(%zero, %bare)
  %loop = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body
  %out = f32[4]{0} get-tuple-element(%loop), index=1
  ROOT %tail = f32[4]{0} copy(%out)
}
"""
    module, table = scopes.parse(text)
    assert module == "jit_f"
    assert table["done"] == ["norm", "norm", False, "copy-done", "user"]
    assert table["start"][:2] == ["norm", "norm"]       # through its user
    assert table["mixed"] == ["norm", "norm", True, "fusion", "own"]
    assert table["in_loop"] == ["attn/rows", "attn", False, "fusion", "own"]
    assert table["bare"] == ["", "", False, "add", "none"]
    assert table["tail"][4] == ""                        # nothing to go by
    assert "m" not in table and "n" not in table         # fused: no events


# -- the registry -----------------------------------------------------------------

def test_the_registry_outlives_the_programs():
    eng = _engine("dense_gqa")
    with exec_cache._fresh_compile():  # no other tree's names (see above)
        eng.warmup()
    before = {m: p["label"] for m, p in scopes.compiled().items()}
    assert {"serving/decode", "serving/verify",
            "serving/prefill"} <= set(before.values())
    del eng
    exec_cache.clear()
    jax.clear_caches()
    after = scopes.compiled()
    assert {m: p["label"] for m, p in after.items()} == before
    decode = next(p for p in after.values()
                  if p["label"] == "serving/decode")
    assert decode["scoped"]
    assert {row[1] for row in decode["instructions"].values()} >= {
        "attn", "ffn", "norm", "head"}
    assert scopes.stale_programs() == 0


def users_of_the_packed_operand(text, size):
    """A compiled program's module name, its scope table, and the ENTRY
    instructions that read its one packed ``s32[size]`` parameter."""
    module, table = scopes.parse(text)
    entry = text[text.index("\nENTRY "):].split("\n}")[0]
    (packed,) = re.findall(
        rf"%?([\w.\-]+) = s32\[{size}\]\S* parameter\(", entry)
    users = [m.group(1) for m in map(scopes._INSTR.match, entry.splitlines())
             if m and m.group(1) != packed and packed in
             scopes._OPERAND.findall(m.group(2).split(", metadata=")[0])]
    return module, table, users


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_engines_programs_keep_their_names_and_scope_their_unpack(
        family, monkeypatch):
    """What the ENGINE compiles is the family's function behind one packed
    operand (``engine.packed_program``): it keeps the function's name —
    three modules, three registry entries, each under its
    ``serving/<kind>`` label — and the slices that cut the operand apart
    fall to a declared scope, none to "unscoped"."""
    monkeypatch.setattr(scopes, "_programs", {})
    eng = _engine(family)
    jax.clear_caches()
    with exec_cache._fresh_compile():
        eng.warmup()
    progs = scopes.compiled()
    assert {m: p["label"] for m, p in progs.items()} == {
        "jit__decode_step": "serving/decode",
        "jit__verify_step": "serving/verify",
        "jit__prefill_chunk": "serving/prefill"}
    assert scopes.stale_programs() == 0
    for kind, exe in (("decode", eng._decode_exec),
                      ("verify", eng._verify_exec),
                      ("prefill", eng._prefill_exec)):
        module, table, users = users_of_the_packed_operand(
            exe.compiled.as_text(), eng._layout(kind).size)
        assert users, module
        for name in users:
            assert table[name][1] and table[name][4] != "none", \
                (module, name, table[name])
        stray = [n for n, row in table.items() if row[4] == "none"]
        assert len(stray) <= LOOP_BOOKKEEPING, (module, stray)


def test_the_train_steps_program_is_recorded_too(monkeypatch):
    """Both compile sites go through ``exec_cache.get_or_compile``: the
    hook there records ``TrainStep``'s program at no further code. A
    differentiated, remat'd program reads the same scopes."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.jit.train_step import TrainStep
    from paddle_tpu.models import LatentMoEForCausalLM

    monkeypatch.setattr(scopes, "_programs", {})
    model = LatentMoEForCausalLM(_latent()[0].config)
    model.train()
    opt = pt.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
    ids = np.random.default_rng(0).integers(0, 256, (2, 12))
    with exec_cache._fresh_compile():
        TrainStep(model, opt)(pt.to_tensor(ids),
                              pt.to_tensor(ids.astype(np.int64)))
    (prog,) = [p for p in scopes.compiled().values()
               if p["label"] == "train_step/LatentMoEForCausalLM"]
    groups = {row[1] for row in prog["instructions"].values()}
    assert prog["scoped"] and groups >= {"attn", "ffn", "norm"}, groups
    assert scopes.stale_programs() == 0  # counts serving programs alone


def test_a_program_without_scopes_counts_as_stale(monkeypatch):
    monkeypatch.setattr(scopes, "_programs", {})

    class Bare:
        def as_text(self):
            return ("HloModule jit_old, is_scheduled=true\n\n"
                    "ENTRY %main (a: f32[4]) -> f32[4] {\n"
                    "  %a = f32[4]{0} parameter(0)\n"
                    "  ROOT %n = f32[4]{0} negate(%a), "
                    'metadata={op_name="jit(old)/neg"}\n}\n')

    scopes.record("serving/decode", Bare())
    scopes.record("train_step", Bare())  # the same name: replaced
    assert scopes.stale_programs() == 0
    scopes.record("serving/decode", Bare())
    assert scopes.stale_programs() == 1


def test_dump_writes_the_registry(tmp_path, monkeypatch):
    import json

    monkeypatch.setattr(scopes, "_programs", {})
    fn = jax.jit(_scanned)
    with exec_cache._fresh_compile():
        entry = exec_cache.get_or_compile(
            None, lambda: fn.lower(jnp.ones((4, 8)), jnp.ones((3, 8, 8))),
            label="toy")
    assert entry.source == "compile"
    path = tmp_path / "scopes.json"
    scopes.dump(str(path))
    (module, prog), = json.loads(path.read_text()).items()
    assert module == "jit__scanned" and prog["label"] == "toy"
    assert {row[0] for row in prog["instructions"].values()} >= {
        "attn/rows", "mlp", "head/norm"}


# -- device time by scope (benchmarks/chip/chiplib/devscopes.py) ------------------

@pytest.fixture(scope="module")
def devscopes():
    sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
    try:
        from chiplib import devscopes as mod
    finally:
        sys.path.pop(0)
    return mod


REGISTRY = {
    "jit__decode_step": {"label": "serving/decode", "instructions": {
        "fusion.1": ["attn/rows", "attn", False, "fusion", "own"],
        "while.2": ["", "", False, "while", "none"],
        "fusion.3": ["moe/experts", "ffn", False, "fusion", "own"],
        "fusion.4": ["norm", "norm", True, "fusion", "own"],
        "copy.5": ["", "", False, "copy", ""]}},
    "jit__prefill_chunk": {"label": "serving/prefill", "instructions": {
        "fusion.1": ["head", "head", False, "fusion", "own"]}},
}
MODULES = [(100.0, 200.0, "jit__decode_step"),
           (300.0, 400.0, "jit__decode_step"),
           (500.0, 560.0, "jit__prefill_chunk"),
           (900.0, 990.0, "jit__decode_step")]   # past the window
OPS = [("fusion.1", 100.0, 10.0),     # straddles nothing
       ("while.2", 110.0, 60.0),      # a container: its body's ops count
       ("fusion.3", 115.0, 20.0),     # an op in the loop's body
       ("fusion.3", 140.0, 20.0),
       ("fusion.4", 175.0, 5.0),      # a mixed fusion
       ("fusion.9", 185.0, 4.0),      # an instruction the map lacks
       ("copy.5", 190.0, 1.0),        # under no scope
       ("fusion.1", 300.0, 30.0),
       ("fusion.1", 500.0, 50.0),     # the prefill program's own fusion.1
       ("fusion.1", 250.0, 7.0),      # in no execution
       ("fusion.1", 900.0, 80.0)]     # past the window


def test_devscopes_puts_op_time_where_it_belongs(devscopes):
    red = devscopes.reduce(MODULES, OPS, REGISTRY, window=(0.0, 800.0),
                           dispatches=[90.0, 310.0, 880.0])
    ns = 1e-9
    assert red["rounds"] == 2 and red["prefill_calls"] == 1
    assert red["executions"] == {"decode": 2, "prefill": 1}
    dec = red["by_group"]["decode"]
    assert dec == pytest.approx({"attn": 40 * ns, "ffn": 40 * ns,
                                 "norm": 5 * ns, "unscoped": 5 * ns})
    assert red["by_group"]["prefill"] == pytest.approx({"head": 50 * ns})
    assert red["by_path"][("decode", "moe/experts")] \
        == pytest.approx(40 * ns)
    assert red["calls"][("decode", "moe/experts")] == 2
    assert red["by_path"][("decode", "unscoped")] == pytest.approx(5 * ns)
    assert red["mixed_s"] == pytest.approx({"decode": 5 * ns})
    assert red["unknown_s"] == pytest.approx({"decode": 4 * ns})
    # the groups and the unscoped time sum to the round programs' op time
    assert sum(dec.values()) == pytest.approx(red["seconds"]["decode"])
    assert devscopes.round_seconds(red) == pytest.approx(90 * ns)
    assert sum(devscopes.round_seconds(red, g)
               for g in (*devscopes.GROUPS, devscopes.UNSCOPED)) \
        == pytest.approx(devscopes.round_seconds(red))
    # the second dispatch opened 10 ns AFTER its execution started: skew
    assert red["host_device_skew_ms"] == pytest.approx(10e-6)


def test_devscopes_readers_on_an_observation(devscopes):
    red = devscopes.reduce(MODULES, OPS, REGISTRY, window=(0.0, 800.0))
    assert red["host_device_skew_ms"] is None
    obs = {"job": "serve", "loop": "backlog", "devscopes": red}
    assert devscopes.group_ms_per_round(obs, "backlog", "attn") \
        == pytest.approx(40e-6 / 2)
    assert devscopes.group_ms_per_round(obs, "backlog", "state") == 0.0
    assert devscopes.unscoped_pct(obs, "backlog") \
        == pytest.approx(100 * 5 / 90)
    assert devscopes.prefill_ms_per_round(obs, "backlog") \
        == pytest.approx(50e-6 / 2)
    assert devscopes.group_ms_per_round(obs, "open", "attn") is None
    # no known program in the window, no trace: nothing to read
    assert devscopes.reduce(MODULES, OPS, {}, window=(600.0, 800.0)) is None
    assert devscopes.table({"job": "serve", "trace": None}) is None
