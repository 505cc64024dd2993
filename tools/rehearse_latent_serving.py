"""Compile a latent-attention sparse-expert serving cell's three step
programs at the configuration's real sizes for ONE chip of a described
``v5e:2x2`` — no chip attached, nothing runs — and print what each needs
of the device's memory. By hand, before chip calls:

    JAX_PLATFORMS=cpu python tools/rehearse_latent_serving.py \
        [--config benchmarks/chip/configs/<name>.json] [--text-dir DIR]

The parameters are shapes only (the benchmark adapter's leaf list laid
out as ``serving/families/latent_moe.py`` lays its collected parameters
out), so no weight is made. What the TPU compiler refuses (a program
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
    from paddle_tpu.models import LatentMoEConfig
    from paddle_tpu.serving import ServingConfig
    from paddle_tpu.serving.families import latent_moe as fam

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
    static = LatentMoEConfig(**arch.config_kwargs(
        cfg, layers, s["max_seq_len"])).static()
    sc = ServingConfig(max_lanes=s["max_lanes"],
                       max_seq_len=s["max_seq_len"],
                       num_blocks=s["num_blocks"])
    L, B, C, K = sc.max_lanes, sc.block_size, sc.prefill_chunk, sc.spec_k
    M = -(-s["max_seq_len"] // B)
    width = cfg["model"]["kv_lora_rank"] + cfg["model"]["qk_rope_head_dim"]
    width = -(-width // fam.LANES) * fam.LANES  # as make_pools pads it
    pool = sds((layers, s["num_blocks"], B, width))
    acc = sds((len(fam.ACC),), jnp.int32)

    def i32(*shape):
        return sds(shape, jnp.int32)

    programs = {
        "decode": (fam._decode_step, (i32(L, M), i32(L), i32(L))),
        "verify": (fam._verify_step,
                   (i32(L, M), i32(L), i32(L, K + 1), i32(L))),
        "prefill": (fam._prefill_chunk,
                    (i32(1, M), i32(1, C), i32(), i32(), i32())),
    }
    print(json.dumps({"weights_bytes": weights,
                      "pool_bytes": layers * s["num_blocks"] * B * width
                      * dt.itemsize, "lanes": L, "blocks_per_lane": M,
                      "chunk": C, "spec_k": K}))
    for kind, (fn, rest) in programs.items():
        t = time.perf_counter()
        compiled = jax.jit(fn, static_argnames=("cfg",),
                           donate_argnums=(1, 2)).lower(
            params, pool, acc, *rest, cfg=static).compile()
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
