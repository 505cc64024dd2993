"""Compiles each cell's programs at the configuration's real sizes for
one chip of a DESCRIBED ``v5e:2x2`` (no chip attached) and prints
``memory_analysis()``, so that a cell that cannot compile or does not fit
is found without chip time. Run by hand before a chip call:

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [--only train|serve|reference]

Nothing runs, so this says nothing about results or times; a compile that
passes is not a chip run. The program's model is built on the CPU (its
arrays only give shapes) and its one rule for "am I on the chip" is
steered here, in the script, never through an option of the program.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def gb(x):
    return round(x / 1e9, 2)


def report(name, compiled):
    ma = compiled.memory_analysis()
    print({"program": name,
           "argument_gb": gb(ma.argument_size_in_bytes),
           "output_gb": gb(ma.output_size_in_bytes),
           "alias_gb": gb(ma.alias_size_in_bytes),
           "temp_gb": gb(ma.temp_size_in_bytes),
           "peak_estimate_gb": gb(ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  - ma.alias_size_in_bytes
                                  + ma.temp_size_in_bytes)}, flush=True)


def reference_train(files, cfg, traffic, topo_devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    ref = files.reference(cfg["reference"])
    one = SingleDeviceSharding(topo_devices[0])
    layers = cfg["num_hidden_layers"]["train"]
    tree = {"layers": [{} for _ in range(layers)]}
    for li, name, shape, _ in files.arch(cfg["arch"]).leaf_specs(
            cfg["model"], layers):
        s = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
        if li < 0:
            tree[name] = s
        else:
            tree["layers"][li][name] = s
    ids = jax.ShapeDtypeStruct(
        (traffic["batch_per_replica"], traffic["seq_len"]), jnp.int32,
        sharding=one)
    n = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    fn = jax.jit(lambda p, a, b, i, l, k: ref.train_step(
        p, a, b, i, l, k, m=cfg["model"], o=cfg["train"]),
        donate_argnums=(0, 1, 2))
    with jax.default_matmul_precision("highest"):
        report("reference train step (float32)",
               fn.lower(tree, tree, tree, ids, ids, n).compile())


def reference_serve(files, cfg, topo_devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    ref = files.reference(cfg["reference"])
    one = SingleDeviceSharding(topo_devices[0])
    m = cfg["model"]
    lw = {name: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
          for li, name, shape, _ in files.arch(cfg["arch"]).leaf_specs(m, 1)
          if li == 0}
    T = cfg["serve"]["max_seq_len"]
    x = jax.ShapeDtypeStruct((T, m["hidden_size"]), jnp.float32,
                             sharding=one)
    with jax.default_matmul_precision("highest"):
        report(f"reference layer, one sequence of {T} (float32)",
               jax.jit(lambda x, lw: ref.layer_forward(x, lw, li=0, m=m))
               .lower(x, lw).compile())


def program_train(files, cfg, traffic, topo_devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu.framework.device as device_mod
    from chiplib import train

    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import env as env_mod

    device_mod.platform = lambda: "tpu"  # the program's one rule, steered
    replicas = train.init_mesh(jax.devices())
    model, step, specs, keys, params = train.build_step(
        files.arch(cfg["arch"]), cfg, traffic, 0, {})
    step._ensure_state()
    # built on the CPU for its shapes; lowered against the described chip
    env_mod.reset_env()
    tmesh = env_mod.init_mesh(dp=1, devices=list(topo_devices[:1])).mesh

    def spec(a):
        s = getattr(a.sharding, "spec", None)
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(tmesh, s if s is not None else P()))

    rep = NamedSharding(tmesh, P())
    rows = traffic["batch_per_replica"] * replicas
    ids = jax.ShapeDtypeStruct((rows, traffic["seq_len"]), jnp.int32,
                               sharding=rep)
    key = jax.eval_shape(lambda: jax.random.key(0))
    lowered = step._build(None).lower(
        [spec(p._data) for p in step._params],
        [spec(a) for a in step._flatten_state()],
        [spec(b._data) for b in step._buffers],
        jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep),
        [ids, ids])
    compiled = lowered.compile()
    text = compiled.as_text()
    print({"flash_kernel_in_program": "tpu_custom_call" in text},
          flush=True)
    report("program train step", compiled)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="")
    ap.add_argument("--config", default="mistral-7b-v0.3")
    ap.add_argument("--traffic", default="pretrain-s4096-b2")
    args = ap.parse_args()
    from jax.experimental import topologies

    from chiplib import manifest

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    files = manifest.Files()
    cfg = manifest._json(os.path.join(HERE, "configs",
                                      args.config + ".json"))
    traffic = files.traffic(args.traffic)
    todo = args.only.split(",") if args.only else [
        "reference_train", "reference_serve", "program_train"]
    if "reference_train" in todo:
        reference_train(files, cfg, traffic, topo.devices)
    if "reference_serve" in todo:
        reference_serve(files, cfg, topo.devices)
    if "program_train" in todo:
        program_train(files, cfg, traffic, topo.devices)


if __name__ == "__main__":
    main()
