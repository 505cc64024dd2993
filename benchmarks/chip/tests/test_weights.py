"""The seeded weights did not move when ``hashed_uniform`` took any
rank: every leaf of ``tiny-llama`` against checksums recorded from the
parent's code (``data/weights_pin.json``), and a stacked leaf against the
flat index it is defined by."""
import json
import os
import zlib

import numpy as np
import pytest

import tiny
from chiplib import weights as W


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 9])
def test_seeded_weights_did_not_move(seed):
    import jax.numpy as jnp

    files = tiny.files()
    cfg = files.config(tiny.MANIFEST, "tiny-llama")
    with open(os.path.join(tiny.DATA, "weights_pin.json")) as f:
        pin = json.load(f)["seeds"][str(seed)]
    specs = files.arch(cfg["arch"]).leaf_specs(cfg["model"], 2)
    keys = W.keys_for(seed, specs)
    assert len(specs) == len(pin)
    for (li, name, shape, kind), key in zip(specs, keys):
        leaf = W.make_leaf(jnp.uint32(key), shape, kind, "bfloat16")
        a = np.ascontiguousarray(np.asarray(leaf.astype(jnp.float32)))
        assert [int(key), zlib.crc32(a.tobytes())] == pin[f"{li}.{name}"], (
            li, name)


@pytest.mark.parametrize("shape", [(24,), (4, 6), (2, 3, 4), (2, 1, 3, 4)])
def test_a_leaf_of_any_rank_hashes_its_row_major_flat_index(shape):
    import jax.numpy as jnp

    key = jnp.uint32(0x9E3779B9)
    flat = np.asarray(W.hashed_uniform(key, (24,)))
    assert np.array_equal(np.asarray(W.hashed_uniform(key, shape)),
                          flat.reshape(shape))
    assert len(set(flat.tolist())) == 24 and np.all(np.abs(flat) < 1)
