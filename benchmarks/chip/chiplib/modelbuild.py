"""Builds the program's model at a configuration's widths and hands it
the benchmark's seeded weights. The model comes from the configuration's
architecture adapter (``arch/<name>.py``), through the program's public
classes; only the values of its parameters are replaced."""
from __future__ import annotations

from . import weights as W


def build(arch, cfg, layers, max_positions, seed, **flags):
    """(model, specs, keys, params): the program's model with every
    parameter replaced, in ONE jitted call, by the seeded weights in the
    served type. Under a mesh each leaf lands in its parameter's own
    sharding."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt

    dtype = cfg["model"]["torch_dtype"]
    pt.set_default_dtype(dtype)  # parameters are born in the served type
    try:
        model = arch.build_model(cfg, layers, max_positions, **flags)
    finally:
        pt.set_default_dtype("float32")
    specs = arch.leaf_specs(cfg["model"], layers)
    named = dict(model.named_parameters())
    params = [named[arch.param_name(li, name)] for li, name, _, _ in specs]
    # the leaf list covers every parameter, each once, in its own shape
    assert len({id(p) for p in params}) == len(params) == len(named), (
        len(params), len(named))
    for p, (_, name, shape, _) in zip(params, specs):
        assert tuple(p.shape) == tuple(shape), (name, p.shape, shape)
    shardings = [p._data.sharding for p in params]
    keys = W.keys_for(seed, specs)

    def make(keys):
        return [W.make_leaf(keys[i], shape, kind, dtype)
                for i, (_, _, shape, kind) in enumerate(specs)]

    # the model's own initial values go before the seeded ones are made:
    # two sets alive at once would be the run's memory peak, and
    # ``memory_peak_bytes`` is to be the program's, not the benchmark's
    for p in params:
        p._data.delete()
    leaves = jax.jit(make, out_shardings=shardings)(jnp.asarray(keys))
    for p, a in zip(params, leaves):
        p._data = a
    return model, specs, keys, params


def reference_leaf(cfg, spec, key):
    """One leaf for the reference: the float32 values of the leaf as the
    program got it (rounded to the served type)."""
    import jax
    import jax.numpy as jnp

    _, _, shape, kind = spec
    dtype = cfg["model"]["torch_dtype"]
    return jax.jit(lambda k: W.make_leaf(k, shape, kind, dtype)
                   .astype(jnp.float32))(jnp.uint32(key))


def reference_params(cfg, specs, keys):
    """The same weights for the reference, as {<top-level name>: leaf,
    ..., 'layers': [{<name>: leaf}, ...]}."""
    out = {"layers": []}
    for spec, key in zip(specs, keys):
        li, name = spec[:2]
        leaf = reference_leaf(cfg, spec, key)
        if li < 0:
            out[name] = leaf
        else:
            while len(out["layers"]) <= li:
                out["layers"].append({})
            out["layers"][li][name] = leaf
    return out
