"""The Pallas kernels held to the chip's own compiler, without the chip.

``check_lowering`` (``jax.export``) stops before the Mosaic / XLA-TPU
compile, so it cannot see a vector layout Mosaic refuses or a kernel
that overflows scoped VMEM — both happened (docs/KERNELS.md "Lowering
pre-flight"). The TPU compiler is installed here and compiles for a chip
that is described and not attached: every ``lowering_cases()`` entry of
every registered kernel — the shapes each ``check_lowering`` lists,
which include the main path's widths (Llama-2-7B training attention,
BERT-base) — is compiled for one chip of a ``v5e:2x2`` topology, one
parametrised case each. So are the serving engine's three programs,
held to what their K/V read may compile to, at a small geometry and at
the attention geometries engines are served at.
Nothing runs, so this says nothing about results or speed, and it is
never reported as a chip run.

The persistent compile cache is off around these compiles: an entry
written for a described chip cannot be read back without one, and the
next run would warn and compile again.
"""
import contextlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

import paddle_tpu.ops.pallas  # noqa: E402,F401 — registers the kernels
from paddle_tpu.ops import registry  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from paddle_tpu.serving.engine import PREFILL_CHUNK  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(autouse=True, scope="module")
def _as_on_the_chip():
    """Persistent cache off (module docstring), and the matmul precision
    the chip runs with: conftest pins 'highest' for the numpy-parity
    tests, under which the in-kernel fp32 dots take several MXU passes
    and the tuned 1024-wide flash blocks no longer fit VMEM — a program
    nobody runs."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_default_matmul_precision)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev[0])
    jax.config.update("jax_default_matmul_precision", prev[1])
    cc.reset_cache()


def _cases():
    out = []
    for name, fn in registry.platform_kernels("tpu"):
        for label, case_fn, specs in fn.lowering_cases():
            out.append(pytest.param(case_fn, specs, id=f"{name}-{label}"))
    return out


def test_every_registered_kernel_lists_its_cases():
    """A kernel without ``lowering_cases`` would silently escape the
    compiles below (same contract as ``check_lowering``)."""
    kernels = registry.platform_kernels("tpu")
    assert {n for n, _ in kernels} >= {
        "flash_attention", "flash_attention_headbatch"}
    for name, fn in kernels:
        assert fn.lowering_cases(), name


@pytest.mark.parametrize("fn,specs", _cases())
def test_kernel_compiles_for_described_v5e(topo, fn, specs):
    one_chip = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
            for s in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_under_a_mesh_compiles_per_shard(topo):
    """XLA refuses to partition a Mosaic kernel ("Mosaic kernels cannot
    be automatically partitioned") — what stopped the dp x mp train step
    on four chips. ``_per_shard`` wraps the call in a shard_map over the
    mesh (batch over 'dp', heads over 'mp'): compiled here for the
    described 2x2, each device's program holds the kernel at the LOCAL
    shape."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "mp"))
    b, s, h, d = 4, 1024, 8, 128
    spec = NamedSharding(mesh, P("dp", None, "mp", None))
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=spec)

    def local(q, k, v, kadd, seed):
        lb, _, lh, _ = q.shape
        assert (lb, lh) == (b // 2, h // 2)  # the shard, not the whole
        t = [x.transpose(0, 2, 1, 3).reshape(lb * lh, s, d)
             for x in (q, k, v)]
        out = fa._flash_bhsd(*t, True, d ** -0.5, False)
        return out.reshape(lb, lh, s, d).transpose(0, 2, 1, 3)

    def step(q, k, v):
        part = (mesh, "dp", "mp", b // 2, h // 2, h // 2)
        return jax.grad(lambda *a: fa._per_shard(
            local, part, *a, None, None).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(step).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_headbatch_refuses_what_mosaic_refuses():
    """d=64 is out of the head-batched family: a clear error at the
    entry, an empty candidate space in the search — never a Mosaic
    'unsupported shape cast' from inside a run."""
    from paddle_tpu.ops.pallas import head_flash, search

    q = jnp.zeros((2, 512, 12, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="head_dim 64"):
        head_flash.hb_flash(q, q, q, causal=False)
    fam = search.FAMILIES["flash_headbatch"]
    assert all(shape[5] % 128 == 0 for shape in fam.shapes())


# -- the serving engine's programs --------------------------------------------

_POOL = (37, 16, 2, 64)  # [num_blocks, block, kv_heads, head_dim]: no
#                          other value of the programs has this shape (the
#                          bf16 pool merges the heads into its last axis)


_LANES, _TABLE = 8, 72  # lanes, blocks a lane: 5 rows of 16 blocks, so
#                         rounds run 40 rows in tiles of 16 and a chunk 5
#                         rows in tiles of 4: no tile is a whole table


def _engines(pool, heads, lanes, table):
    """A 3-layer bf16 engine per pool dtype whose layer pool is ``pool``
    (scales: its first three dims) — built on the CPU, for its shapes."""
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingEngine

    nb, block, nkv, d = pool
    model = LlamaForCausalLM(LlamaConfig.tiny(
        num_hidden_layers=3, hidden_size=heads * d,
        num_attention_heads=heads, num_key_value_heads=nkv,
        intermediate_size=512, dtype="bfloat16"))
    for p in model.parameters():  # fp32 init, served in bf16
        p._data = p._data.astype("bfloat16")
    model.eval()
    return {kv_int8: ServingEngine(model, ServingConfig(
        max_lanes=lanes, block_size=block, num_blocks=nb,
        prefill_chunk=32, max_seq_len=table * block, kv_int8=kv_int8))
        for kv_int8 in (False, True)}


@pytest.fixture(scope="module")
def engines():
    return _engines(_POOL, 4, _LANES, _TABLE)


@contextlib.contextmanager
def _kernels_compiled():
    """The code's one rule for "am I on the chip" steered for a compile
    for the described chip: a program's Pallas kernels are compiled by
    Mosaic there, not interpreted."""
    import paddle_tpu.framework.device as device

    prev, device.platform = device.platform, lambda: "tpu"
    try:
        yield
    finally:
        device.platform = prev


def _compiled_program(topo, eng, kind, chunk=None):
    """Program ``kind`` of a rows-form family (the dense one, the hybrid
    state-space one) compiled for the described chip, lowered as
    ``ServingEngine._ensure_compiled`` lowers it there (pools donated,
    kernels compiled); the prefill program at ``chunk`` positions, the
    engine's own width unless given."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    cfg = eng.config
    L, S, C = cfg.max_lanes, cfg.spec_k + 1, chunk or eng.prefill_chunk
    lanes, width, rest = {
        "decode": (L, 1, (i32(L), i32(L))),
        "verify": (L, S, (i32(L), i32(L, S), i32(L))),
        "prefill": (1, C, (i32(1, C), i32(), i32(), i32())),
    }[kind]
    read = jax.tree_util.tree_map(spec, eng._read_spec(kind, lanes, width))
    fn, static = eng._family.program(kind)
    with _kernels_compiled():
        return jax.jit(
            fn, static_argnames=tuple(static),
            donate_argnums=eng._family.donate_argnums,
        ).lower(*jax.tree_util.tree_map(spec, (eng._params, *eng._pools)),
                read, *rest, **static).compile()


def _dense_program_text(topo, eng, kind, chunk=None):
    """The dense family's program ``kind`` as the chip's compiler leaves
    it."""
    return _compiled_program(topo, eng, kind, chunk).as_text()


def _program_names(compiled, loops=False):
    """A compiled program's instructions, each with its operands' shapes
    as the device trace names its events: the entry computation's and,
    with ``loops``, those of every ``while`` body and condition reachable
    from it (a loop body's operations are events of their own; a fused
    computation's are not)."""
    from jax._src.lib import xla_client as xc

    opts = xc._xla.HloPrintOptions()
    opts.print_operand_shape = True
    opts.print_backend_config = False
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    comps, name = {}, None
    for ln in text.splitlines():
        head = re.match(r"(ENTRY )?(%[\w.\-]+) \(.*\{$", ln)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            comps[name] = []
        elif name and " = " in ln:
            comps[name].append(ln.strip())
    out, todo = [], ["ENTRY"]
    while todo:
        lines = comps[todo.pop()]
        out += lines
        if loops:
            todo += [c for n in lines if re.search(r"[\s)]while\(", n)
                     for c in re.findall(r"(?:body|condition)=(%[\w.\-]+)",
                                         n)]
    return out


def _programs(family_width=None):
    """Every step program, the prefill chunk at the width the tests'
    engines pin (32), at the engine's default and — a family that names a
    width of its own (``prefill_chunk``) — at the family's."""
    own = [] if family_width is None else [
        pytest.param("prefill", family_width, id="prefill_family")]
    return [pytest.param("decode", None, id="decode"),
            pytest.param("verify", None, id="verify"),
            pytest.param("prefill", None, id="prefill"),
            pytest.param("prefill", PREFILL_CHUNK, id="prefill_default"),
            *own]


def _results_shaped(text, dims):
    """The instructions of ``text`` whose result has the shape ``dims``
    (a regex over the comma-separated dims)."""
    held = re.compile(rf"= \w+\[{dims}\]")
    return [ln.strip()[:200] for ln in text.splitlines() if held.search(ln)]


def _layer_pool(eng, pool):
    """One layer's pool as ``eng``'s family stores it: the int8 pool
    ``[num_blocks, block, kv_heads, head_dim]``, the bf16 pool with the
    heads merged into the last axis."""
    nb, block, nkv, d = pool
    return pool if eng.config.kv_int8 else (nb, block, nkv * d)


def _holds_no_layers_pool(text, eng, pool):
    """No instruction's result has one layer's pool shape (nor, in int8
    mode, one layer's scale-pool shape); the stacked pool is there."""
    stored = _layer_pool(eng, pool)
    assert eng._pools[0].shape[1:] == stored
    nb, block, nkv, d = pool
    dims = rf"{nb},{block},{nkv}(,{d})?" if eng.config.kv_int8 \
        else _dims(stored)
    lines = _results_shaped(text, dims)
    assert not lines, "\n".join(lines[:6])
    assert f"[3,{_dims(stored)}]" in text


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kind,chunk", _programs())
def test_engine_program_never_holds_one_layers_pool(
        topo, engines, kind, chunk, kv_int8):
    """The K/V read gathers from the STACKED pool by (layer, block).
    Indexing the layer first (``kp[li][tables]``) compiles, for the
    chip, to a fusion that writes out that layer's whole pool before the
    gather reads it — in every layer of every call (PERF.md section 6,
    PR 25). So: in the engine's programs as the chip's compiler leaves
    them, no instruction's result has one layer's pool shape (nor, in
    int8 mode, one layer's scale-pool shape)."""
    eng = engines[kv_int8]
    _holds_no_layers_pool(_dense_program_text(topo, eng, kind, chunk), eng,
                          _POOL)


# (``_engines``' arguments: pool [num_blocks, block, kv_heads,
# head_dim], heads, lanes, blocks a lane), the programs compiled: the
# benchmark's dense cells' attention (group 4 at head_dim 128, block 16:
# Mistral-7B's, two kv heads of its eight so that hidden stays 1024),
# all three programs; a 128-slot block with group 2, and every head its
# own K/V (the 7B engine chip_smoke.py serves), the decode program
_SERVED_AT = {
    "g4_d128_b16": (((37, 16, 2, 128), 8, 8, 72),
                    ("decode", "verify", "prefill")),
    "g2_d128_b128": (((37, 128, 2, 128), 4, 4, 8), ("decode",)),
    "mha_d128_b16": (((37, 16, 4, 128), 4, 8, 32), ("decode",)),
}


@pytest.fixture(scope="module")
def served_engines():
    return {g: _engines(*args) for g, (args, _) in _SERVED_AT.items()}


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("geometry,kind", [
    (g, k) for g, (_, kinds) in _SERVED_AT.items() for k in kinds])
def test_row_read_compiles_at_served_geometries(
        topo, served_engines, geometry, kind, kv_int8):
    """The engines above run head_dim 64 and group 2. The row read at
    the geometries engines are served at compiles for the chip, and
    there too no program holds one layer's pool."""
    pool = _SERVED_AT[geometry][0][0]
    eng = served_engines[geometry][kv_int8]
    text = _dense_program_text(topo, eng, kind)
    _holds_no_layers_pool(text, eng, pool)
    assert ("tpu_custom_call" in text) == (not kv_int8)


# the benchmark cells' full-attention geometries: (heads, kv heads, head
# dim, value dim, lanes, the prefill widths served: the family's own, and
# the engine's where a deployer may still give it) — Mistral-7B's,
# granite-4.0-h's (a head is half a 128-lane tile), MiMo-V2.5's full
# layers (192 is no multiple of 128, values narrower than keys; its
# family's 512 positions at group 16 are FOUR query tiles of the kernel's
# grid), LFM2's (granite's heads at its family's 512: 2,048 rows, one tile),
# and the two latent cells' (KV heads 0: ONE pool, one shared head as wide
# as an entry is stored, its value the entry's first 512 numbers — 640 /
# 160 query rows a lane a verify round, a 128-position chunk 16,384 /
# 4,096 rows in eight / two query tiles: PERF.md section 6, PR 47)
_ROW_READ_AT = {
    "mistral_g4_d128": (32, 8, 128, 128, 32, (PREFILL_CHUNK,)),
    "granite_g4_d64": (32, 8, 64, 64, 64, (PREFILL_CHUNK,)),
    "mimo_g16_d192_dv128": (64, 4, 192, 128, 64, (PREFILL_CHUNK, 256, 512)),
    "lfm2_g4_d64": (32, 8, 64, 64, 64, (512,)),
    "openpangu_latent_h128_d640": (128, 0, 640, 512, 64, (PREFILL_CHUNK,)),
    "kimi_latent_h32_d640": (32, 0, 640, 512, 64, (PREFILL_CHUNK,))}


def test_the_compiled_prefill_widths_are_the_families():
    from paddle_tpu.serving.families import conv_moe, window_moe

    assert _ROW_READ_AT["mimo_g16_d192_dv128"][5][-1] \
        == window_moe.WindowMoEFamily.prefill_chunk
    assert _ROW_READ_AT["lfm2_g4_d64"][5] \
        == (conv_moe.ConvMoEFamily.prefill_chunk,)


def _row_read_in_a_program(sd, row_attention, q_shape, nkv, dv, rows, pool):
    """The kernel's call lowered with its queries a product's result and
    its output a product's operand, as a family's program has them.
    Handed the kernel as parameters of their own the compiler places them
    otherwise, and a call that NO program can hold compiles alone (PR 42:
    MiMo's 512 positions as one query tile, 170 MB of VMEM in a program).
    ``nkv`` 0: the latent form — one pool, no value pool."""
    b, s, nh, d = q_shape

    def read(x, w, o, pos, rows, li, kpool, vpool=None):
        q = (x @ w).reshape(b, s, nh, d)
        out = row_attention(q, pos, rows, kpool, vpool, li, max(nkv, 1),
                            d ** -0.5, dv=dv)
        return out.reshape(b * s, nh * dv) @ o

    return jax.jit(read).lower(
        sd(jnp.bfloat16, b * s, 4096), sd(jnp.bfloat16, 4096, nh * d),
        sd(jnp.bfloat16, nh * dv, 4096), sd(jnp.int32, b, s),
        sd(jnp.int32, *rows), sd(jnp.int32),
        sd(jnp.bfloat16, *pool, max(nkv, 1) * d),
        *([sd(jnp.bfloat16, *pool, nkv * dv)] if nkv else []))


def test_one_query_tile_cannot_hold_the_widest_chunk(topo, monkeypatch):
    """Why the kernel's grid has its axis of query tiles: MiMo's 512
    positions at group 16 (8,192 query rows a KV head) in ONE grid step
    are refused by the chip's compiler inside a program."""
    from paddle_tpu.ops.pallas import row_attention as RA

    nh, nkv, d, dv, _, widths = _ROW_READ_AT["mimo_g16_d192_dv128"]
    monkeypatch.setattr(RA, "_Q_TILE_ROWS", max(widths) * (nh // nkv))
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with _kernels_compiled():
        lowered = _row_read_in_a_program(
            sd, RA.row_attention, (1, max(widths), nh, d), nkv, dv,
            (6, 18), (2, 2049, 16))
        with pytest.raises(Exception, match="vmem"):
            lowered.compile()


@pytest.mark.parametrize("geometry,kind", [
    (g, k) for g, at in sorted(_ROW_READ_AT.items())
    for k in ("decode", "verify", *(f"prefill{w}" for w in at[5]))])
def test_row_kernel_compiles_at_the_cells_geometries(topo, geometry, kind):
    """The live-rows read at the served head geometries and lanes, a
    plain round's, a verify round's and a prefill chunk's queries at
    every width its family serves: the fused kernel
    (``ops/pallas/row_attention.py``) compiles for the chip under its
    stated ``vmem_limit_bytes`` inside a program (its queries a
    product's result) — head slices at lane offsets of 64 and
    192, 2,048 query rows a KV head a grid step in MiMo's chunks (the 512
    positions of its family's call in four query tiles) and in LFM2's,
    the latent pool's one head of 640 (160) query rows a lane with its
    values out of the key's buffer —
    and the program holds no gathered tile ``[T, W x B, kv_heads, d]``
    (float32 or not, heads merged or not) and no value of one layer's
    pool shape."""
    from paddle_tpu.ops.pallas.row_attention import row_attention

    nh, nkv, d, dv, lanes, _ = _ROW_READ_AT[geometry]
    layers, nb, B, W, rows_a_lane = 2, 2049, 16, 16, 6
    b, s = {"decode": (lanes, 1), "verify": (lanes, 5)}.get(
        kind) or (1, int(kind[len("prefill"):]))
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sd(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with _kernels_compiled():
        text = _row_read_in_a_program(
            sd, row_attention, (b, s, nh, d), nkv, dv,
            (b * rows_a_lane, 2 + W), (layers, nb, B)).compile().as_text()
    assert "tpu_custom_call" in text and "row_attention" in text
    for dims in (rf"\d+,{W * B},({nkv},)?\d+(,1)?", rf"{nb},{B},\d+"):
        lines = _results_shaped(text, dims)
        assert not lines, "\n".join(lines[:6])


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("kind,chunk", _programs())
def test_engine_program_never_holds_every_lanes_table(
        topo, engines, kind, chunk, kv_int8):
    """The K/V read follows the rows the lanes hold (PERF.md section 6,
    PR 28): no instruction's result is shaped like every lane's whole
    table — ``[L * M, block, kv_heads, head_dim]`` as the full-table
    gather wrote it, or ``[L, M * block, ...]`` as the attention then
    read it (int8: the scales' shapes, without the head dim; bf16: the
    heads merged or not). The int8 program gathers a tile of rows at a
    time; the bf16 program's kernel copies a row's blocks into fast
    memory itself, and NO tile of gathered rows is a value of the
    program (PERF.md section 6, PR 39)."""
    eng = engines[kv_int8]
    lanes = 1 if kind == "prefill" else _LANES
    assert eng.blocks_per_lane == _TABLE
    w, tile, cap = eng._rows_form(kind, lanes)
    assert cap > tile  # several tiles: a tile is not the table
    text = _dense_program_text(topo, eng, kind, chunk)
    _, block, nkv, d = _POOL
    heads = rf"({nkv}(,{d})?|{nkv * d})"
    for dims in (rf"{lanes * _TABLE},{block},{heads}",
                 rf"{lanes},{_TABLE * block},{heads}"):
        lines = _results_shaped(text, dims)
        assert not lines, "\n".join(lines[:6])
    a_tile = _results_shaped(
        text, rf"({tile * w},{block}|{tile},{w * block}),{heads}")
    assert bool(a_tile) == kv_int8, "\n".join(a_tile[:6])
    assert ("tpu_custom_call" in text) == (not kv_int8)


# -- the latent-attention sparse-expert family's programs ----------------------

# [layers, num_blocks, block, width] at the published 512 + 64 = 576 numbers
# a token, which the family pads to whole 128-lane tiles: 640
_LATENT_POOL = (3, 2049, 16, 640)


# lanes, blocks a lane: the benchmark cell's 64 lanes, and 5 rows of 16
# blocks a lane (rounds are handed 320 rows, a chunk 5: the operand's
# length is no whole table). No width is 256 (a row's slots)
_LATENT_LANES, _LATENT_TABLE = 64, 80


@pytest.fixture(scope="module")
def latent_engine():
    """A 1-dense + 2-expert-layer bf16 engine, built on the CPU for its
    shapes."""
    from paddle_tpu.models import LatentMoEConfig, LatentMoEForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingEngine

    layers, nb, block, _ = _LATENT_POOL
    model = LatentMoEForCausalLM(LatentMoEConfig(
        vocab_size=512, hidden_size=384, intermediate_size=512,
        moe_intermediate_size=384, num_hidden_layers=layers,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=128,
        kv_lora_rank=512, qk_nope_head_dim=32, qk_rope_head_dim=64,
        v_head_dim=32, n_routed_experts=4, router_experts=16,
        num_experts_per_tok=4, initializer_range=0.0, dtype="bfloat16"))
    model.eval()
    return ServingEngine(model, ServingConfig(
        max_lanes=_LATENT_LANES, block_size=block, num_blocks=nb,
        prefill_chunk=32, max_seq_len=_LATENT_TABLE * block))


def _latent_program(topo, eng, kind, monkeypatch, chunk=None):
    """The family's program ``kind`` compiled for the described chip (the
    expert products as the Mosaic kernel, not its interpreter); the
    prefill program at ``chunk`` positions, the engine's own width unless
    given."""
    import paddle_tpu.framework.device as device

    monkeypatch.setattr(device, "platform", lambda: "tpu")
    return _compiled_program(topo, eng, kind, chunk)


def _latent_program_text(topo, eng, kind, monkeypatch, chunk=None):
    return _latent_program(topo, eng, kind, monkeypatch, chunk).as_text()


@pytest.mark.parametrize("kind,chunk", _programs())
def test_latent_program_never_copies_its_pool(topo, latent_engine, kind,
                                              chunk, monkeypatch):
    """A ``[layers, blocks, block, 576]`` pool the TPU lays out
    blocks-minor (576 is 4.5 lane tiles: row-major pads 11%, blocks-minor
    2049 -> 2176 only 6%), and each program then copied the whole pool
    into row-major and back, every call (2 x 1.95 ms at the benchmark's
    0.57 GB: PERF.md section 6, PR 27; with width 576 here this test
    finds both copies). The family pads the entry to 640, whose own
    layout is row-major: no instruction is a copy of the pool, the
    stacked pool is what the row kernel is handed (no one layer's pool
    is produced either: ``layer`` is a number the kernel is told), and
    the expert products are the grouped-matmul kernel, not its
    interpreter."""
    assert latent_engine._pools[0].shape == _LATENT_POOL
    compiled = _latent_program(topo, latent_engine, kind, monkeypatch, chunk)
    text = compiled.as_text()
    layers, nb, block, width = _LATENT_POOL
    whole = rf"\w+\[{layers},{nb},{block},{width}\]"
    copies = [ln.strip()[:200] for ln in text.splitlines()
              if re.search(rf"= {whole}\S* copy\(", ln)]
    assert not copies, "\n".join(copies[:4])
    one_layer = [ln.strip()[:200] for ln in text.splitlines()
                 if re.search(rf"= \w+\[{nb},{block},{width}\]", ln)]
    assert not one_layer, "\n".join(one_layer[:4])
    assert re.search(whole, text)
    # the read: one Mosaic kernel call a latent layer, handed the pool
    reads = [ln for ln in text.splitlines()
             if re.search(r"%row_attention[\.\d]* = [^\n]*custom_call_target="
                          r"\"tpu_custom_call\"", ln)]
    assert len(reads) == layers and all(
        re.search(whole, ln.split("custom-call(", 1)[1]) for ln in reads)
    # two grouped products an expert layer, as Mosaic kernels
    assert len(re.findall(r"%gmm[\.\d]* = [^\n]*custom_call_target="
                          r"\"tpu_custom_call\"", text)) == 4


def _holds_rows_not_tables(text, eng, kind, lanes, table, width,
                           a_tile=True):
    """No instruction's result is shaped like every lane's whole table —
    ``[lanes * M, block, width]`` as a full-table gather wrote it, or
    ``[lanes, M * block, width]`` as the attention then read it — while a
    tile of rows is (``a_tile`` False: nor a tile of rows — the program's
    kernel copies a row's blocks into fast memory itself)."""
    assert eng.blocks_per_lane == table
    w, tile, cap = eng._rows_form(kind, lanes)
    assert cap > tile  # several tiles: a tile is not the table
    block = eng.config.block_size
    for dims in (rf"{lanes * table},{block},{width}",
                 rf"{lanes},{table * block},{width}(,1)?"):
        lines = _results_shaped(text, dims)
        assert not lines, "\n".join(lines[:6])
    tiles = _results_shaped(
        text, rf"({tile * w},{block}|{tile},{w * block}),{width}")
    assert bool(tiles) == a_tile, "\n".join(tiles[:6])


@pytest.mark.parametrize("kind,chunk", _programs())
def test_latent_program_never_holds_every_lanes_table(
        topo, latent_engine, kind, chunk, monkeypatch):
    """The latent read follows the rows the lanes hold (PERF.md section
    6, PR 35), and since PR 47 the kernel copies a row's blocks into
    fast memory itself: no value of a program is shaped like every
    lane's whole table, nor like a tile of gathered rows."""
    lanes = 1 if kind == "prefill" else _LATENT_LANES
    _holds_rows_not_tables(
        _latent_program_text(topo, latent_engine, kind, monkeypatch, chunk),
        latent_engine, kind, lanes, _LATENT_TABLE, _LATENT_POOL[3],
        a_tile=False)


def _mla_reader():
    import importlib.util
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmarks", "chip"))
    try:
        spec = importlib.util.spec_from_file_location(
            "mla_attend_roofline_reader", os.path.join(
                root, "benchmarks/chip/metrics/mla_attend_roofline.py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
    finally:
        sys.path.pop(0)
    return reader


_OPERATION = re.compile(r"[\s)](fusion|copy|custom-call|gather|scatter|"
                        r"convolution|reduce|dynamic-update-slice)\(")


@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_latent_round_reads_each_layer_in_one_kernel_call(
        topo, latent_engine, kind, monkeypatch):
    """What is true of a latent round program since PR 47: under
    ``mla/attend`` a layer's read is ONE call of the row kernel (its
    queries the absorbed ones, one shared head: ``[lanes, 1, positions x
    heads, 640]``; its output the folded sums ``[.., 512]``, which go
    through ``W_v`` once), the scope holds no loop, and the program's text
    holds no gathered row tile ``[rows x blocks, block, 640]`` / ``[rows, slots,
    640]`` and no float32 tensor over a row's slots ``[.., slots]`` — the
    scores, their statistics and the weighted sums stay in the kernel's
    fast memory. (Until PR 47 this test held the benchmark's
    ``mla_attend_roofline`` reader to the XLA read's operations by role;
    what that reader picks now is PERF.md section 7 (r).)"""
    from paddle_tpu.serving.families import latent_moe as fam

    compiled = _latent_program(topo, latent_engine, kind, monkeypatch)
    names = _program_names(compiled, loops=True)
    layers, _, block, width = _LATENT_POOL
    lanes, slots = _LATENT_LANES, fam.ROW_BLOCKS * block
    s = 1 if kind == "decode" else latent_engine.config.spec_k + 1
    heads, dc = 4, 512
    reads = [n for n in names if "row_attention" in n
             and 'custom_call_target="tpu_custom_call"' in n]
    assert len(reads) == layers, [n[:160] for n in reads]
    for n in reads:
        assert re.search(r'op_name="[^"]*mla/attend/', n), n[:300]
        assert re.match(rf"%row_attention[\.\d]* = bf16\[{lanes},1,"
                        rf"{s * heads},{dc}\]", n), n[:160]
        assert f"bf16[{lanes},1,{s * heads},{width}]" in n, n[:400]
        assert f"bf16[{_dims(_LATENT_POOL)}]" in n  # the stacked pool
    assert not any("mla/attend" in n and "while" in n for n in names)
    text = compiled.as_text()
    for dims in (rf"\d+,{block},{width}",           # [T x W, block, 640]
                 rf"\d+,{slots},{width}(,1)?"):     # [T, slots, 640]
        lines = [ln for ln in _results_shaped(text, dims)
                 if not re.search(rf"= \w+\[{layers},", ln)]
        assert not lines, "\n".join(lines[:6])
    scores = re.compile(rf"f32\[(\d+,)+{slots}\]")
    held = [ln.strip()[:200] for ln in text.splitlines()
            if scores.search(ln)]
    assert not held, "\n".join(held[:6])
    # everything else under the scope is the absorbed query and ``W_v``
    under = [n for n in names if "mla/attend" in n and _OPERATION.search(n)
             and n not in reads]
    assert under and all("while" not in n for n in under)


@pytest.mark.parametrize("heads,width", [(128, 7680), (32, 2304)],
                         ids=["openpangu", "kimi"])
def test_latent_attention_reader_on_the_served_shapes(heads, width):
    """The same reader fed synthetic names of the served cells' shapes
    (64 lanes, 5 positions, rows of 256 slots): it returns the pattern
    ISSUE 35 reckoned and passes over the new entries, the pool scatter,
    the grouped products and the head."""
    reader = _mla_reader()
    picks = ["%f.1 = bf16[1024,16,640] fusion(bf16[21765,16,640] %p)",
             f"%f.2 = (f32[64,5,{heads}], f32[64,5,{heads},256]) "
             f"fusion(bf16[64,256,640,1] %b, bf16[64,5,{heads},640] %q)",
             f"%f.3 = f32[64,5,{heads},512] fusion(bf16[64,256,640] %b)",
             f"%f.4 = bf16[64,5,{heads},512] fusion(bf16[64,5,{heads},128])"]
    passed = ["%f.5 = bf16[64,5,640] fusion(bf16[64,5,576] %e)",
              "%f.6 = bf16[5,4353,16,640] scatter(bf16[5,4353,16,640] %p, "
              "bf16[64,5,640] %e)",
              f"%gmm.1 = bf16[2560,{width}] custom-call(bf16[2560,2048])",
              f"%f.7 = f32[64,5,19200] fusion(bf16[64,5,{width}] %x)"]
    m = {"kv_lora_rank": 512, "qk_rope_head_dim": 64,
         "num_attention_heads": heads}
    got = reader.pattern(picks + passed, 64, m)
    assert got == (rf"\[64,256,640(,1)?\]|\[64,(\d+,)*256\]|"
                   rf"\[64,(\d+,)*{heads},(512|640)\]|"
                   r"\[1024,16,640\]|\[64,256,640\]")
    assert [n for n in picks + passed if re.search(got, n)] == picks


# -- the scope map the compiled programs leave (monitor/scopes.py) -------------

def _fusions_without_a_group(compiled):
    """(the fusion instructions of the chip's program that fall to no
    declared group, all its fusion instructions) — what a device trace's
    busy time is joined to (benchmarks/chip/chiplib/devscopes.py)."""
    from paddle_tpu.monitor import scopes

    fusions = {name: row for name, row in
               scopes.parse(compiled.as_text())[1].items()
               if row[3] == "fusion"}
    return sorted(n for n, row in fusions.items() if not row[1]), fusions


@pytest.mark.parametrize("kind", ["decode", "verify", "prefill"])
@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
def test_dense_program_fusions_carry_a_group(topo, engines, kv_int8, kind):
    bare, fusions = _fusions_without_a_group(
        _compiled_program(topo, engines[kv_int8], kind))
    assert len(fusions) > 20
    assert len(bare) <= 0.05 * len(fusions), bare
    assert {row[1] for row in fusions.values()} >= {"attn", "ffn", "norm",
                                                    "head"}


@pytest.mark.parametrize("kind", ["decode", "verify", "prefill"])
@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_the_packed_program_compiles_for_the_chip(topo, engines,
                                                  hybrid_engine, family,
                                                  kind):
    """What the ENGINE compiles (``engine.packed_program``: the family's
    function behind ONE packed int32 operand) for the described chip: the
    row kernel takes its scalar-prefetch ``rows`` from a static slice of
    that vector, the module keeps the function's name, and the slices
    that are instructions of their own fall to ``embed`` — none of the
    operand's readers is left without a group."""
    from paddle_tpu.serving.engine import packed_program
    from test_program_scopes import users_of_the_packed_operand

    eng = engines[False] if family == "dense" else hybrid_engine
    one_chip = SingleDeviceSharding(topo.devices[0])

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    layout = eng._layout(kind)
    fn, static = eng._family.program(kind)
    with _kernels_compiled():
        text = jax.jit(
            packed_program(fn, layout), static_argnames=tuple(static),
            donate_argnums=eng._family.donate_argnums,
        ).lower(*jax.tree_util.tree_map(spec, (eng._params, *eng._pools)),
                jax.ShapeDtypeStruct((layout.size,), jnp.int32,
                                     sharding=one_chip),
                **static).compile().as_text()
    module, table, users = users_of_the_packed_operand(text, layout.size)
    assert module == "jit_" + fn.__name__
    assert "tpu_custom_call" in text  # the row kernel (and the state's)
    assert users
    for name in users:
        assert table[name][1] and table[name][4] != "none", \
            (name, table[name])
    assert any(row[0].startswith("embed") for row in table.values())


@pytest.mark.parametrize("kind", ["decode", "verify", "prefill"])
def test_latent_program_fusions_carry_a_group(topo, latent_engine, kind,
                                              monkeypatch):
    bare, fusions = _fusions_without_a_group(
        _latent_program(topo, latent_engine, kind, monkeypatch))
    assert len(fusions) > 100
    assert len(bare) <= 0.05 * len(fusions), bare
    paths = {row[0] for row in fusions.values()}
    # (no ``sample``: the chip fuses the greedy pick INTO the head
    # product, one ``iota_reduce_fusion`` whose root is ``head``'s)
    assert paths >= {"mla/attend", "mla/kv_write", "moe/route",
                     "moe/dispatch", "moe/combine", "moe/shared", "norm",
                     "head", "acc"}, paths


# -- the hybrid state-space / attention family's programs -----------------------

# lanes, state-space heads; d_head 64 and d_state 128 are the published
# sizes, and so is the K/V row of 8 heads x 64: what decides the layouts
_HYBRID_LANES, _HYBRID_HEADS = 8, 4
# a layer's state in the kernel's slab layout (ops/pallas/ssm_state.py):
# [lanes, groups, d_state, heads x d_head]
_HYBRID_STATE = (_HYBRID_LANES, 1, 128, _HYBRID_HEADS * 64)
_HYBRID_KV = (1, 2049, 16, 8 * 64)


@pytest.fixture(scope="module")
def hybrid_engine():
    """Two state-space layers around one attention layer, bf16, built on
    the CPU for its shapes."""
    from paddle_tpu.models import HybridSSMConfig, HybridSSMForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingEngine

    model = HybridSSMForCausalLM(HybridSSMConfig(
        vocab_size=512, hidden_size=512, shared_intermediate_size=512,
        num_hidden_layers=3, layer_types=["mamba", "attention", "mamba"],
        num_attention_heads=8, num_key_value_heads=8,
        mamba_n_heads=_HYBRID_HEADS, mamba_d_head=64, mamba_d_state=128,
        attention_multiplier=0.015625, embedding_multiplier=12,
        residual_multiplier=0.22, logits_scaling=8,
        initializer_range=0.0, dtype="bfloat16"))
    model.eval()
    return ServingEngine(model, ServingConfig(
        max_lanes=_HYBRID_LANES, block_size=16, num_blocks=_HYBRID_KV[1],
        prefill_chunk=32, max_seq_len=20 * 16))


def _hybrid_program_names(topo, eng, kind, monkeypatch, chunk=None):
    """The family's program ``kind`` as the chip's compiler leaves it
    (the state kernel compiled, not interpreted: the code's one rule for
    "am I on the chip" is steered here): the entry computation's
    instructions, each with its operands' shapes as the device trace
    names its events."""
    from jax._src.lib import xla_client as xc

    import paddle_tpu.framework.device as device

    monkeypatch.setattr(device, "platform", lambda: "tpu")
    compiled = _compiled_program(topo, eng, kind, chunk)
    opts = xc._xla.HloPrintOptions()
    opts.print_operand_shape = True
    opts.print_backend_config = False
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    return [ln.strip() for ln in text[text.index("\nENTRY"):].splitlines()
            if " = " in ln]


def _dims(shape):
    return ",".join(str(d) for d in shape)


@pytest.mark.parametrize("kind,chunk", _programs())
def test_hybrid_program_never_copies_a_pool(topo, hybrid_engine, kind,
                                            chunk, monkeypatch):
    """Device state of four kinds, none of which a program call may
    copy: K and V pools whose last axis is the 8 heads x 64 merged (with
    ``[.., 8, 64]`` the TPU lays the pool out blocks-minor and every call
    copied both pools in and out: 4 x 285 MB at the benchmark's size); a
    conv pool with a lane's 3 rows side by side; what a verify round
    leaves for the next call to commit (the small ``B | dt_raw`` pool,
    written layer by layer in place, and an array of ``x`` planes a
    state-space layer, which the next call's kernel reads where it lies
    and a verify round replaces whole — as ONE stacked pool the compiler
    moved all of it through fast memory and back a layer: PERF.md
    section 6, PR 37); one float32 state array a state-space layer,
    which the state kernel takes and returns in ONE buffer. The prefill
    chunk too, at both widths: it once copied its LAST state-space
    layer's array in and out at one pool size (PERF.md section 7 (aa))."""
    from paddle_tpu.serving.families.hybrid_ssm import N_POOLS

    eng = hybrid_engine
    states = eng._pools[N_POOLS:N_POOLS + 2]
    assert eng._pools[0].shape == _HYBRID_KV and len(eng._pools) \
        == N_POOLS + 4
    assert all(p.shape == _HYBRID_STATE for p in states)
    names = _hybrid_program_names(topo, eng, kind, monkeypatch, chunk)
    # (a verify round makes its arrays of planes anew: there a ``copy``
    # to that shape is the compiler's way of writing them)
    pools = "|".join(rf"\w+\[{_dims(p.shape)}\]" for p in
                     (eng._pools[0], eng._pools[2], eng._pools[4],
                      states[0], *eng._pools[-1:][:kind != "verify"]))
    copies = [n[:200] for n in names
              if re.search(rf"= ({pools})\S* copy\(", n)]
    assert not copies, "\n".join(copies[:4])


# what computes or copies on the chip: fusions, kernels, plain copies
# and in-place updates (at the test's size the compiler also stages a
# 1 MB state through fast memory, slice-start / slice-done / copy-start /
# copy-done around an unnamed concatenating custom-call: a 134 MB state
# has none, tools/rehearse_latent_serving.py)
_WORK = re.compile(r"[\s)](fusion|copy|convolution|dynamic-update-slice)\("
                   r"|custom_call_target=\"tpu_custom_call\"")


def _slab_sized(names, shape):
    """The working instructions (``_WORK``) that touch a float32 array
    with as many elements as ``shape``, whatever reshape the compiler
    made of it."""
    n = int(np.prod(shape))
    f32 = re.compile(r"f32\[([\d,]+)\]")
    return [ln for ln in names if _WORK.search(ln)
            and any(int(np.prod([int(d) for d in g.split(",")])) == n
                    for g in f32.findall(ln))]


@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_hybrid_round_touches_each_state_in_one_kernel_call(
        topo, hybrid_engine, kind, monkeypatch):
    """In a plain round AND in a verify round exactly one instruction a
    state-space layer touches a slab-sized float32 array: the state
    kernel's call, with the state aliased in and out (one buffer, read
    once and written once) and ``ssm/state_update`` in its metadata. No
    fusion reads the state a second time for the round's outputs and
    none applies a verify round's accepted positions after the head —
    they enter the state in the next call's pass (PERF.md section 6,
    PR 37; before it: one two-result fusion a layer in a plain round,
    two fusions a layer, 3 x 134 MB, in a verify round)."""
    names = _hybrid_program_names(topo, hybrid_engine, kind, monkeypatch)
    touching = _slab_sized(names, _HYBRID_STATE)
    assert len(touching) == 2, [n[:160] for n in touching]
    state = rf"f32\[{_dims(_HYBRID_STATE[0:1] + _HYBRID_STATE[2:])}\]"
    for n in touching:
        assert re.search(r"[\s)]custom-call\(", n) \
            and 'custom_call_target="tpu_custom_call"' in n, n[:200]
        assert "ssm/state_update" in n and "ssm_state_round" in n, n[:300]
        # the call's second result IS its last operand's buffer
        assert re.match(rf"%\S+ = \(f32\[[\d,]+\]\S*, {state}", n), n[:200]
        aliased = re.search(
            r"output_to_operand_aliasing=\{\{1\}: \((\d+), \{\}\)\}", n)
        operands = re.findall(r"%[\w.\-]+", n[n.index("custom-call("):n.index(
            "), custom_call_target")])
        assert aliased and int(aliased.group(1)) == len(operands) - 1, \
            n[:400]


@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_ssm_update_reader_picks_the_state_and_nothing_else(
        topo, hybrid_engine, kind, monkeypatch):
    """The benchmark's ``ssm_update_roofline`` picks operations by the
    state's element count in their instruction text (the device trace's
    events are named by it). Held here to the compiled programs' own
    instructions: a layer's one kernel call in a plain round and in a
    verify round, nothing of the attention layer, the MLPs or the
    head."""
    import importlib.util
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmarks", "chip"))
    try:
        spec = importlib.util.spec_from_file_location(
            "ssm_update_roofline_reader", os.path.join(
                root, "benchmarks/chip/metrics/ssm_update_roofline.py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
    finally:
        sys.path.pop(0)
    names = _hybrid_program_names(topo, hybrid_engine, kind, monkeypatch)
    m = {"mamba_n_heads": _HYBRID_HEADS, "mamba_d_head": 64,
         "mamba_d_state": 128}
    picked = reader.pattern(names, _HYBRID_LANES, m, 2)
    assert picked is not None
    ops = _WORK
    hit = [n for n in names if re.search(picked, n) and ops.search(n)]
    assert len(hit) == 2, [n[:200] for n in hit]
    assert all("ssm/state_update" in n and "ssm_state_round" in n
               for n in hit), [n[:200] for n in hit]
    # a trace holds the prefill chunk's events too: with a chunk of the
    # default width among the names (no ``[W, ...]`` intermediate of it
    # has a slab's element count) the round's picks are the same
    wide = _hybrid_program_names(topo, hybrid_engine, "prefill",
                                 monkeypatch, PREFILL_CHUNK)
    assert any(re.search(rf"\[1,{PREFILL_CHUNK},", n) for n in wide)
    with_chunk = reader.pattern(names + wide, _HYBRID_LANES, m, 2)
    assert [n for n in names if re.search(with_chunk, n)
            and ops.search(n)] == hit
    # a program without a state (the parent's): nothing to read
    assert reader.pattern([n for n in names
                           if f",128,{_HYBRID_HEADS * 64}]" not in n],
                          _HYBRID_LANES, m, 2) is None


def test_state_kernel_compiles_at_the_served_sizes(topo, monkeypatch):
    """The state kernel at the benchmark cell's sizes — 64 lanes, 64
    heads x 64, state 128, a verify round's 5 owed (the last layer's
    gains and ``B`` rows, its ``x`` planes in bfloat16) and 5 read
    positions with their scale and mix, and a plain round's 5 + 1 owed
    and 1 read — compiled by Mosaic for the described chip: the 2 MB
    block a lane fits its stated VMEM, the in-kernel transposes, lane
    broadcasts and the chunk's slices at a computed lane offset lower,
    and the state is aliased (no second 134 MB buffer: temporaries
    0)."""
    import paddle_tpu.framework.device as device
    from paddle_tpu.ops.pallas import ssm_state

    monkeypatch.setattr(device, "platform", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sd(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L, H, P, N, T, layers = 64, 64, 64, 128, 5, 36

    def rows(a):
        return ssm_state.head_rows(a, 1, P)

    def planes(t):
        return sd(L, t, 1, 32, 128, dtype=jnp.bfloat16)

    owed = (sd(layers, L, 1 + T, H), planes(T), sd(layers, L, 1, 8, N))

    def verify(S, C, g, x, B, scale, mix, x_now):
        return ssm_state.state_round(
            S, [ssm_state.Commit(rows(g), x, B, 35)], C,
            scale=rows(scale), mix=(rows(mix), x_now))

    def decode(S, C, g, x, B, g1, x1, B1, D):
        own = ssm_state.Commit(rows(g1)[None], x1,
                               ssm_state.b_rows(B1)[None])
        return ssm_state.state_round(
            S, [ssm_state.Commit(rows(g), x, B, 35), own], C,
            mix=(rows(D), x1))

    slab = sd(*ssm_state.slab_shape(L, H, P, N, 1))
    for fn, args in (
            (verify, (slab, sd(L, T, 1, N), *owed, sd(L, T, H),
                      sd(L, T * T, H), planes(T))),
            (decode, (slab, sd(L, 1, 1, N), *owed, sd(L, 2, H), planes(1),
                      sd(L, 1, 1, N), sd(L, 1, H)))):
        compiled = jax.jit(fn, donate_argnums=0).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == L * H * P * N * 4
        assert mem.temp_size_in_bytes < 32 << 20


# -- the linear-attention / latent-attention family's programs ------------------

# lanes, linear-attention heads; the head size 128 and the latent entry of
# 512 + 64 numbers (stored as 640) are the published sizes: what decides
# the layouts
_LINEAR_LANES, _LINEAR_HEADS = 8, 2
_LINEAR_STATE = (_LINEAR_LANES, _LINEAR_HEADS, 128, 128)
_LINEAR_POOL = (1, 2049, 16, 640)
_LINEAR_TABLE = 144  # blocks a lane: 9 rows of 16 (rounds are handed 72
#                      rows, a chunk 9: more than one ``tile`` of either)


@pytest.fixture(scope="module")
def linear_engine():
    """Two gated delta-rule layers around one latent layer, the first
    layer dense, bf16, built on the CPU for its shapes."""
    from paddle_tpu.models import (
        LinearLatentMoEConfig, LinearLatentMoEForCausalLM,
    )
    from paddle_tpu.serving import ServingConfig, ServingEngine

    model = LinearLatentMoEForCausalLM(LinearLatentMoEConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=3,
        linear_attn_config={
            "kda_layers": [1, 3], "full_attn_layers": [2],
            "num_heads": _LINEAR_HEADS, "head_dim": 128,
            "short_conv_kernel_size": 4},
        num_attention_heads=4, kv_lora_rank=512, qk_nope_head_dim=32,
        qk_rope_head_dim=64, v_head_dim=32, num_experts=4,
        router_experts=16, num_experts_per_token=4,
        initializer_range=0.0, dtype="bfloat16"))
    model.eval()
    return ServingEngine(model, ServingConfig(
        max_lanes=_LINEAR_LANES, block_size=16,
        num_blocks=_LINEAR_POOL[1], prefill_chunk=32,
        max_seq_len=_LINEAR_TABLE * 16))


def _linear_program_names(topo, eng, kind, monkeypatch, chunk=None,
                          loops=False):
    """The family's program ``kind`` as the chip's compiler leaves it:
    the entry computation's instructions (with ``loops``: the latent
    read's loop bodies' too), each with its operands' shapes as the
    device trace names its events."""
    import paddle_tpu.framework.device as device

    monkeypatch.setattr(device, "platform", lambda: "tpu")
    return _program_names(_compiled_program(topo, eng, kind, chunk), loops)


@pytest.mark.parametrize("kind,chunk", _programs())
def test_linear_program_never_copies_a_pool(topo, linear_engine, kind,
                                            chunk, monkeypatch):
    """Device state of three kinds, none of which a program call may
    copy: the latent family's padded pool (for the latent layers alone),
    a conv pool with a lane's 3 rows side by side, one float32 state
    array a linear-attention layer. The prefill chunk (told its state
    slot beside its lane's rows) too, at both widths. And the latent
    layer reads its live rows in ONE call of the row kernel under
    ``mla/attend``: no value shaped like every lane's whole table, nor
    like a tile of gathered rows (PERF.md section 6, PR 35 and PR 47)."""
    eng = linear_engine
    assert eng._pools[0].shape == _LINEAR_POOL
    assert all(p.shape == _LINEAR_STATE for p in eng._pools[3:-2])
    names = _linear_program_names(topo, eng, kind, monkeypatch, chunk,
                                  loops=True)
    reads = [n for n in names if "row_attention" in n
             and 'custom_call_target="tpu_custom_call"' in n]
    assert len(reads) == 1 and "mla/attend" in reads[0], reads
    _holds_rows_not_tables(
        "\n".join(names), eng, kind,
        1 if kind == "prefill" else _LINEAR_LANES, _LINEAR_TABLE,
        _LINEAR_POOL[3], a_tile=False)
    pools = "|".join(rf"\w+\[{_dims(p.shape)}\]" for p in
                     (eng._pools[0], eng._pools[2], eng._pools[3]))
    copies = [n[:200] for n in names
              if re.search(rf"= ({pools})\S* copy\(", n)]
    assert not copies, "\n".join(copies[:4])
    # the expert products are the grouped-matmul kernel (2 expert layers)
    assert len([n for n in names if re.match(r"%gmm[\.\d]* = ", n)]) == 4


@pytest.mark.parametrize("kind", ["decode", "verify"])
def test_kda_update_reader_picks_the_state_and_nothing_else(
        topo, linear_engine, kind, monkeypatch):
    """The benchmark's ``kda_update_roofline`` picks operations by the
    state's shape in their instruction text. Held here to the compiled
    programs' own instructions: ONE a linear-attention layer in a plain
    round and in a verify round — the state kernel's call under
    ``kda/state_update``, with the state aliased in and out (one buffer,
    read once and written once) — nothing of the latent layer, the expert
    layers or the head. No fusion goes over the state's shape (before PR
    48: two a layer a round — a read for the products with the old state,
    then the update) and no ``[lanes, heads, k+1, k+1]`` value is left
    (the chunked form's 5 x 5 algebra: it runs inside the kernel, on
    ``[heads, d]`` vectors)."""
    import importlib.util
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "benchmarks", "chip"))
    try:
        spec = importlib.util.spec_from_file_location(
            "kda_update_roofline_reader", os.path.join(
                root, "benchmarks/chip/metrics/kda_update_roofline.py"))
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
    finally:
        sys.path.pop(0)
    names = _linear_program_names(topo, linear_engine, kind, monkeypatch)
    m = {"linear_attn_config": {"num_heads": _LINEAR_HEADS,
                                "head_dim": 128}}
    picked = reader.pattern(names, _LINEAR_LANES, m)
    state = rf"f32\[{_dims(_LINEAR_STATE)}\]"
    assert picked.replace("\\,", ",") == state  # that shape and no other
    hit = [n for n in names if re.search(picked, n) and _WORK.search(n)]
    assert len(hit) == 2, [n[:200] for n in hit]
    for n in hit:
        assert re.search(r"[\s)]custom-call\(", n) \
            and 'custom_call_target="tpu_custom_call"' in n, n[:200]
        assert "kda/state_update" in n and "kda_state_round" in n, n[:300]
        # the call's second result IS its last operand's buffer
        assert re.match(rf"%\S+ = \(f32\[[\d,]+\]\S*, {state}", n), n[:200]
        aliased = re.search(
            r"output_to_operand_aliasing=\{\{1\}: \((\d+), \{\}\)", n)
        operands = re.findall(r"%[\w.\-]+", n[n.index("custom-call("):n.index(
            "), custom_call_target")])
        assert aliased and int(aliased.group(1)) == len(operands) - 1, \
            n[:400]
    # nothing else over the state's shape, in any reshape of it
    assert _slab_sized(names, _LINEAR_STATE) == hit
    # the chunked form's T x T algebra is nowhere in the program
    T = linear_engine.config.spec_k + 1
    square = rf"f32\[{_LINEAR_LANES},{_LINEAR_HEADS},{T},{T}[\],]"
    assert not [n[:200] for n in names if re.search(square, n)]
    # a trace holds the prefill chunk's events too: the round's picks are
    # the same with a chunk of the default width among the names
    wide = _linear_program_names(topo, linear_engine, "prefill",
                                 monkeypatch, PREFILL_CHUNK)
    with_chunk = reader.pattern(names + wide, _LINEAR_LANES, m)
    assert [n for n in names if re.search(with_chunk, n)
            and _WORK.search(n)] == hit
    # a program without a state (the parent's): nothing to read
    assert reader.pattern([n for n in names if ",128,128]" not in n],
                          _LINEAR_LANES, m) is None


def test_kda_state_kernel_compiles_at_the_served_sizes(topo, monkeypatch):
    """The delta rule's state kernel at the benchmark cell's sizes — 64
    lanes, 32 heads of 128 x 128, layer 19 of 20, a verify round's 5 owed
    and 5 read positions and a plain round's 5 owed, 1 read and 1 own —
    compiled by Mosaic for the described chip: the 2 MB block a lane fits
    its stated VMEM, the strided stores into a head's tiles, the
    in-kernel transposes and the float32 products on the matrix unit
    lower, and state and pending pool are aliased (no second 134 MB
    buffer: temporaries 0)."""
    import paddle_tpu.framework.device as device
    from paddle_tpu.ops.pallas import kda_state

    monkeypatch.setattr(device, "platform", lambda: "tpu")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sd(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L, H, d, T, layers = 64, 32, 128, 5, 20
    for reads, own in ((T, False), (1, True)):
        def call(S, pend, n, q, k, v, g, beta):
            return kda_state.state_round(S, pend, layers - 1, n, q, k, v,
                                         g, beta, own=own)

        compiled = jax.jit(call, donate_argnums=(0, 1)[:2 - own]).lower(
            sd(L, H, d, d), sd(*kda_state.pending_shape(layers, L, T, H, d)),
            sd(L, dtype=jnp.int32), *(sd(reads, L, H, d),) * 4,
            sd(L, reads, H)).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# -- the window-attention / full-attention family's programs --------------------

# lanes; the head sizes (keys 192, values 128), the key/value heads (4 full,
# 8 window) and the window (128) are the published ones: what decides the
# layouts of the pools and the rings
_WINDOW_LANES, _WINDOW_TABLE = 8, 144  # 9 rows of 16 blocks a lane
_WINDOW_K = (1, 2049, 16, 4 * 192)
_WINDOW_V = (1, 2049, 16, 4 * 128)
_WINDOW_RINGS = ((_WINDOW_LANES, 144, 8 * 192), (_WINDOW_LANES, 144, 8 * 128))


@pytest.fixture(scope="module")
def window_engine():
    """One full layer over a dense SwiGLU, then two window layers over
    expert layers, bf16, built on the CPU for its shapes."""
    from paddle_tpu.models import WindowMoEConfig, WindowMoEForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingEngine

    model = WindowMoEForCausalLM(WindowMoEConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512,
        moe_intermediate_size=128, num_hidden_layers=3,
        hybrid_layer_pattern=[0, 1, 1], moe_layer_freq=[0, 1, 1],
        num_attention_heads=16, num_key_value_heads=4,
        swa_num_key_value_heads=8, head_dim=192, v_head_dim=128,
        sliding_window=128, n_routed_experts=4, router_experts=16,
        num_experts_per_tok=4, initializer_range=0.0, dtype="bfloat16"))
    model.eval()
    return ServingEngine(model, ServingConfig(
        max_lanes=_WINDOW_LANES, block_size=16, num_blocks=_WINDOW_K[1],
        prefill_chunk=32, max_seq_len=_WINDOW_TABLE * 16))


@pytest.mark.parametrize("kind,chunk", _programs(512))
def test_window_program_never_copies_a_pool(topo, window_engine, kind,
                                            chunk, monkeypatch):
    """Device state of two kinds by LAYER TYPE, none of which a program
    call may copy: the full layer's K and V pools, of DIFFERENT last axis
    (4 heads x 192 and 4 x 128 merged: 6 and 4 lane tiles), and a K ring
    and a V ring a window layer by lane (144 slots at a window of 128 and
    4 drafts a round). Every one is donated and written where it lies: a
    round scatters its fed positions, the prefill chunk (told its lane
    beside its rows) updates its lane's slice. The full layer reads its
    live rows in ONE call of the row kernel, with ``attn/rows`` in its
    metadata: no value shaped like every lane's whole table, nor like a
    tile of gathered rows; the expert products are the grouped-matmul
    kernel."""
    import paddle_tpu.framework.device as device

    monkeypatch.setattr(device, "platform", lambda: "tpu")
    eng = window_engine
    assert (eng._pools[0].shape, eng._pools[1].shape) \
        == (_WINDOW_K, _WINDOW_V)
    assert [p.shape for p in eng._pools[3:]] \
        == [_WINDOW_RINGS[0]] * 2 + [_WINDOW_RINGS[1]] * 2
    compiled = _compiled_program(topo, eng, kind, chunk)
    state = [p for i, p in enumerate(eng._pools) if i != 2]
    # donation holds: every pool and ring comes back in its own buffer
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= sum(p.nbytes for p in state)
    names = _program_names(compiled, loops=True)
    reads = [n for n in names if "row_attention" in n
             and 'custom_call_target="tpu_custom_call"' in n]
    assert len(reads) == 1 and "attn/rows" in reads[0], reads
    lanes = 1 if kind == "prefill" else _WINDOW_LANES
    for width in (_WINDOW_K[3], _WINDOW_V[3]):
        _holds_rows_not_tables("\n".join(names), eng, kind, lanes,
                               _WINDOW_TABLE, width, a_tile=False)
    pools = "|".join(rf"\w+\[{_dims(s)}\]" for s in
                     (_WINDOW_K, _WINDOW_V, *_WINDOW_RINGS))
    copies = [n[:200] for n in names
              if re.search(rf"= ({pools})\S* copy\(", n)]
    assert not copies, "\n".join(copies[:4])
    assert len([n for n in names if re.match(r"%gmm[\.\d]* = ", n)]) == 4


# -- the short-convolution / grouped-query family's programs --------------------

# lanes; the hidden size (2048: a tail of 2 x 2048 a conv layer), the head
# size (64) and the key/value heads (8: a pool row of 512) are the
# published ones: what decides the layouts of the pools and the tails
_CONV_LANES, _CONV_TABLE = 8, 144  # 9 rows of 16 blocks a lane
_CONV_KV = (1, 2049, 16, 8 * 64)
_CONV_TAILS = (3, _CONV_LANES, 2 * 2048)


@pytest.fixture(scope="module")
def conv_engine():
    """A conv layer over a dense SwiGLU, then conv / attention / conv over
    expert layers that hold every expert, bf16, built on the CPU for its
    shapes."""
    from paddle_tpu.models import ConvMoEConfig, ConvMoEForCausalLM
    from paddle_tpu.serving import ServingConfig, ServingEngine

    model = ConvMoEForCausalLM(ConvMoEConfig(
        vocab_size=512, hidden_size=2048, intermediate_size=256,
        moe_intermediate_size=128, num_hidden_layers=4,
        layer_types=["conv", "conv", "full_attention", "conv"],
        num_dense_layers=1, num_attention_heads=32, num_key_value_heads=8,
        num_experts=8, num_experts_per_tok=4, initializer_range=0.0,
        dtype="bfloat16"))
    model.eval()
    return ServingEngine(model, ServingConfig(
        max_lanes=_CONV_LANES, block_size=16, num_blocks=_CONV_KV[1],
        prefill_chunk=32, max_seq_len=_CONV_TABLE * 16))


@pytest.mark.parametrize("kind,chunk", _programs(512))
def test_conv_program_never_copies_a_pool(topo, conv_engine, kind, chunk,
                                          monkeypatch):
    """Device state of two kinds by LAYER TYPE, none of which a program
    call may copy: the attention layer's K and V pools (8 heads x 64
    merged: 4 lane tiles) and ONE pool of tails by (conv layer, lane), 2 x
    2048 numbers a row. Every one is donated and written where it lies: a
    plain round shifts a layer's tails, a verify round sets them from the
    window it kept until the head, the prefill chunk (told its lane beside
    its rows) updates its lane's row. The attention layer reads its live
    rows in ONE call of the row kernel, with ``attn/rows`` in its
    metadata; the expert products are the grouped-matmul kernel."""
    import paddle_tpu.framework.device as device

    monkeypatch.setattr(device, "platform", lambda: "tpu")
    eng = conv_engine
    assert (eng._pools[0].shape, eng._pools[1].shape) == (_CONV_KV,) * 2
    assert eng._pools[3].shape == _CONV_TAILS and len(eng._pools) == 4
    compiled = _compiled_program(topo, eng, kind, chunk)
    state = [p for i, p in enumerate(eng._pools) if i != 2]
    # donation holds: both pools and the tails come back in their buffers
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= sum(p.nbytes for p in state)
    names = _program_names(compiled, loops=True)
    reads = [n for n in names if "row_attention" in n
             and 'custom_call_target="tpu_custom_call"' in n]
    assert len(reads) == 1 and "attn/rows" in reads[0], reads
    lanes = 1 if kind == "prefill" else _CONV_LANES
    _holds_rows_not_tables("\n".join(names), eng, kind, lanes, _CONV_TABLE,
                           _CONV_KV[3], a_tile=False)
    pools = "|".join(rf"\w+\[{_dims(s)}\]" for s in (_CONV_KV, _CONV_TAILS))
    copies = [n[:200] for n in names
              if re.search(rf"= ({pools})\S* copy\(", n)]
    assert not copies, "\n".join(copies[:4])
    # the tails are written under the convolution's own scope (what the
    # compiler adds is a prefetch of the small pool into fast memory and
    # back, ``copy-start`` / ``copy-done``: no change of layout)
    writes = [n for n in names
              if re.match(rf"%\S+ = \w+\[{_dims(_CONV_TAILS)}\]", n)
              and not re.search(r"[\s)](parameter|get-tuple-element|"
                                r"copy-start|copy-done)\(", n)]
    assert writes and all("sconv/conv" in n for n in writes), \
        [n[:200] for n in writes if "sconv/conv" not in n]
    assert len([n for n in names if re.match(r"%gmm[\.\d]* = ", n)]) == 6
