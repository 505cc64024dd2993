"""Compile a serving cell's three step programs — the latent-attention
sparse-expert family's, the hybrid state-space family's, the
linear-attention family's, the window-attention family's or the
short-convolution family's, by the configuration's ``arch`` — at the configuration's real sizes for ONE chip
of a described ``v5e:2x2`` — no chip attached, nothing runs — and print
what each needs of the device's memory. By hand, before chip calls:

    JAX_PLATFORMS=cpu python tools/rehearse_latent_serving.py \
        [--config benchmarks/chip/configs/<name>.json] [--text-dir DIR]

The parameters are shapes only (the benchmark adapter's leaf list laid
out as the family lays its collected parameters out), so no weight is
made. What the TPU compiler refuses (a program
that does not fit 16 GB, a layout it cannot tile) shows here and costs no
chip time; a compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _row_reads(form, g, i32, geom, slot):
    """(static kwargs by program, read operands by program) of a family
    whose programs read their lanes' live rows through the row kernel:
    ``form(kind)`` its ``(W, tile)``; ``slot``: the prefill chunk is told
    its state slot too."""
    from paddle_tpu.serving.engine import fit_rows

    L, _, C, K, M = geom
    reads, statics = {}, {}
    for kind, lanes, width in (("decode", L, 1), ("verify", L, K + 1),
                               ("prefill", 1, C)):
        w, _, cap = fit_rows(form(kind), lanes, M)
        reads[kind] = (i32(cap, 2 + w), i32(lanes, width))
        statics[kind] = {"cfg": g}
    if slot:
        reads["prefill"] += (i32(1),)
    return statics, reads


def _latent(arch, cfg, layers, s, sds, i32, geom):
    """(family module, static kwargs by program, pools, read operands by
    program)
    of ``serving/families/latent_moe.py``: one padded latent pool, the
    lanes' live rows for every program."""
    import jax.numpy as jnp

    from paddle_tpu.models import LatentMoEConfig
    from paddle_tpu.serving.families import latent_moe as fam

    B = geom[1]
    static = LatentMoEConfig(**arch.config_kwargs(
        cfg, layers, s["max_seq_len"])).static()
    width = cfg["model"]["kv_lora_rank"] + cfg["model"]["qk_rope_head_dim"]
    width = -(-width // fam.LANES) * fam.LANES  # as make_pools pads it
    pools = (sds((layers, s["num_blocks"], B, width)),
             sds((len(fam.ACC),), jnp.int32))
    statics, reads = _row_reads(fam.read_form, static, i32, geom, False)
    return fam, statics, pools, reads


def _hybrid(arch, cfg, layers, s, sds, i32, geom):
    """The same of ``serving/families/hybrid_ssm.py``: K and V pools by
    block for the attention layers, a conv pool, the small pending pool
    with the lanes' counts, and one float32 state array (the kernel's
    slab layout) and one array of pending planes a state-space layer by
    LANE, the dense family's live-rows read (the prefill chunk's with its
    state slot)."""
    import jax.numpy as jnp

    from paddle_tpu.models import HybridSSMConfig
    from paddle_tpu.ops.pallas import ssm_state
    from paddle_tpu.serving.families import hybrid_ssm as fam

    L, B, C, K, M = geom
    g = HybridSSMConfig(**arch.config_kwargs(
        cfg, layers, s["max_seq_len"])).static()
    n_ssm = sum(k == "mamba" for k in g.layer_types)
    kv = sds((layers - n_ssm, s["num_blocks"], B,
              g.num_key_value_heads * g.head_dim))
    sizes = (g.mamba_n_heads, g.mamba_d_head, g.mamba_d_state,
             g.mamba_n_groups)
    planes, small = ssm_state.pending_shapes(L, K + 1, *sizes)
    pools = (kv, kv, sds((n_ssm, L, (g.mamba_d_conv - 1) * g.conv_dim)),
             sds((len(fam.ACC),), jnp.int32), sds((n_ssm, *small)),
             sds((L,), jnp.int32),
             *(sds(ssm_state.slab_shape(L, *sizes), jnp.float32)
               for _ in range(n_ssm)),
             *(sds(planes) for _ in range(n_ssm)))

    statics, reads = _row_reads(
        lambda kind: (fam.ROW_BLOCKS, fam.PREFILL_TILE if kind == "prefill"
                      else fam.ROW_TILE), g, i32, geom, True)
    return fam, statics, pools, reads


def _linear(arch, cfg, layers, s, sds, i32, geom):
    """The same of ``serving/families/linear_latent_moe.py``: the latent
    family's padded pool for the latent layers, a conv pool, one
    float32 state array a linear-attention layer by LANE, the pending
    pool with the lanes' counts, the lanes' live rows for every program
    (the prefill chunk's with its state slot)."""
    import jax.numpy as jnp

    from paddle_tpu.models import LinearLatentMoEConfig
    from paddle_tpu.ops.pallas import kda_state
    from paddle_tpu.serving.families import linear_latent_moe as fam

    L, B, _, K, M = geom
    g = LinearLatentMoEConfig(**arch.config_kwargs(
        cfg, layers, s["max_seq_len"])).static()
    n_kda = sum(k == "kda" for k in g.layer_kinds)
    width = -(-(g.kv_lora_rank + g.qk_rope_head_dim) // fam.LANES) \
        * fam.LANES
    pools = (sds((layers - n_kda, s["num_blocks"], B, width)),
             sds((len(fam.ACC),), jnp.int32),
             sds((n_kda, L, (g.kda_taps - 1) * 3 * g.kda_width)),
             *(sds((L, g.kda_heads, g.kda_head_dim, g.kda_head_dim),
                   jnp.float32) for _ in range(n_kda)),
             sds(kda_state.pending_shape(n_kda, L, K + 1, g.kda_heads,
                                         g.kda_head_dim), jnp.float32),
             sds((L,), jnp.int32))
    statics, reads = _row_reads(fam.read_form, g, i32, geom, True)
    return fam, statics, pools, reads


def _window(arch, cfg, layers, s, sds, i32, geom):
    """The same of ``serving/families/window_moe.py``: K and V pools by
    block for the full layers (keys wider than values), and a K ring and
    a V ring a window layer by LANE; the dense family's live-rows read
    (the prefill chunk's with its lane)."""
    import jax.numpy as jnp

    from paddle_tpu.models import WindowMoEConfig
    from paddle_tpu.serving.families import window_moe as fam

    L, B, _, K, _ = geom
    c = WindowMoEConfig(**arch.config_kwargs(cfg, layers, s["max_seq_len"]))
    g = c.static()
    n_win = sum(g.hybrid_layer_pattern)
    R = c.window_ring_len or fam.ring_len(c, K)
    full, swa = g.num_key_value_heads, g.swa_num_key_value_heads
    pools = (sds((layers - n_win, s["num_blocks"], B, full * g.head_dim)),
             sds((layers - n_win, s["num_blocks"], B, full * g.v_head_dim)),
             sds((len(fam.ACC),), jnp.int32),
             *(sds((L, R, swa * g.head_dim)) for _ in range(n_win)),
             *(sds((L, R, swa * g.v_head_dim)) for _ in range(n_win)))
    statics, reads = _row_reads(fam.read_form, g, i32, geom, True)
    return fam, statics, pools, reads


def _conv(arch, cfg, layers, s, sds, i32, geom):
    """The same of ``serving/families/conv_moe.py``: K and V pools by
    block for the attention layers and ONE pool of tails by (conv layer,
    LANE); the dense family's live-rows read (the prefill chunk's with
    its lane)."""
    import jax.numpy as jnp

    from paddle_tpu.models import ConvMoEConfig
    from paddle_tpu.serving.families import conv_moe as fam

    L, B, _, _, _ = geom
    g = ConvMoEConfig(**arch.config_kwargs(
        cfg, layers, s["max_seq_len"])).static()
    n_conv = sum(k == "conv" for k in g.layer_types)
    kv = sds((layers - n_conv, s["num_blocks"], B,
              g.num_key_value_heads * g.head_dim))
    pools = (kv, kv, sds((len(fam.ACC),), jnp.int32),
             sds((n_conv, L, (g.conv_L_cache - 1) * g.hidden_size)))
    statics, reads = _row_reads(
        lambda kind: (fam.ROW_BLOCKS, fam.PREFILL_TILE if kind == "prefill"
                      else fam.ROW_TILE), g, i32, geom, True)
    return fam, statics, pools, reads


# architecture -> (its family's module under serving/families, the
# function above that describes its pools and reads)
FAMILIES = {"mla_moe": ("latent_moe", _latent),
            "hybrid_ssm": ("hybrid_ssm", _hybrid),
            "kda_mla_moe": ("linear_latent_moe", _linear),
            "swa_gqa_moe": ("window_moe", _window),
            "conv_gqa_moe": ("conv_moe", _conv)}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        ROOT, "benchmarks/chip/configs/openpangu-ultra-moe-718b-ep16.json"))
    ap.add_argument("--text-dir", default="",
                    help="write each compiled module's text here")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu.framework.device as device
    from paddle_tpu.serving import ServingConfig
    from paddle_tpu.serving.engine import (PREFILL_CHUNK,
                                           default_prefill_chunk)

    # steer the code's ONE rule for "am I on the chip" here, in the
    # script: the described chip gets the real kernels, not interpret mode
    device.platform = lambda: "tpu"
    cfg = json.load(open(args.config))
    spec = importlib.util.spec_from_file_location(
        "arch", os.path.join(ROOT, "benchmarks/chip/arch",
                             cfg["arch"] + ".py"))
    arch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(arch)
    layers, s = cfg["num_hidden_layers"]["serve"], cfg["serve"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    dt = jnp.dtype(cfg["model"]["torch_dtype"])

    def sds(shape, dtype=dt):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=chip)

    params = {"layers": [dict() for _ in range(layers)]}
    weights = 0
    for li, name, shape, _ in arch.leaf_specs(cfg["model"], layers):
        (params if li < 0 else params["layers"][li])[name] = sds(shape)
        weights += math.prod(shape) * dt.itemsize
    params["layers"] = tuple(params["layers"])
    sc = ServingConfig(max_lanes=s["max_lanes"],
                       max_seq_len=s["max_seq_len"],
                       num_blocks=s["num_blocks"])
    L, B, K = sc.max_lanes, sc.block_size, sc.spec_k
    module, describe = FAMILIES[cfg["arch"]]
    # the width the engine would take: the family object's own, as
    # ``ServingEngine.__init__`` reads it (a family module exports its
    # family class alone)
    families = importlib.import_module(
        "paddle_tpu.serving.families." + module)
    (family,) = families.__all__
    C = sc.prefill_chunk or default_prefill_chunk(
        s["max_seq_len"], B, getattr(getattr(families, family),
                                     "prefill_chunk", PREFILL_CHUNK))
    M = -(-s["max_seq_len"] // B)

    def i32(*shape):
        return sds(shape, jnp.int32)

    fam, statics, pools, reads = describe(
        arch, cfg, layers, s, sds, i32, (L, B, C, K, M))
    programs = {
        "decode": (fam._decode_step, (reads["decode"], i32(L), i32(L))),
        "verify": (fam._verify_step,
                   (reads["verify"], i32(L), i32(L, K + 1), i32(L))),
        "prefill": (fam._prefill_chunk,
                    (reads["prefill"], i32(1, C), i32(), i32(), i32())),
    }
    print(json.dumps({"weights_bytes": weights, "pools_bytes": sum(
        math.prod(p.shape) * p.dtype.itemsize for p in pools), "lanes": L, "blocks_per_lane": M, "chunk": C,
        "spec_k": K}))
    for kind, (fn, rest) in programs.items():
        t = time.perf_counter()
        compiled = jax.jit(
            fn, static_argnames=tuple(statics[kind]),
            donate_argnums=tuple(range(1, 1 + len(pools)))).lower(
            params, *pools, *rest, **statics[kind]).compile()
        ma = compiled.memory_analysis()
        print(json.dumps({
            "program": kind, "compile_s": round(time.perf_counter() - t, 1),
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "total_live_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes
            + ma.temp_size_in_bytes}), flush=True)
        if args.text_dir:
            os.makedirs(args.text_dir, exist_ok=True)
            with open(os.path.join(args.text_dir, kind + ".txt"), "w") as f:
                f.write(compiled.as_text())


if __name__ == "__main__":
    main()
