"""Reference against program at a tiny size on the CPU — and the
lower-precision control, which has to FAIL the same comparison."""
import contextlib
import io
import json

import numpy as np
import pytest

import tiny


def run(workload, seed, **kw):
    import run as runner

    tiny.interpret_flash()
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        result = runner.run_cell(workload, seed, 2.0, 0, files=tiny.files(),
                                 require_chip=False, **kw)
    lines = [json.loads(x) for x in buf.getvalue().splitlines()
             if x.startswith("{")]
    return result, {ln["line"]: ln for ln in lines if "line" in ln}


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 9])
def test_train_program_matches_reference_and_fp8_control_does_not(seed):
    result, lines = run("tiny-train", seed, control=True)
    assert result["correct"]
    prog = {r["name"]: r for r in lines["compare"]["numbers"]}
    ctrl = {r["name"]: r for r in lines["control"]["numbers"]}
    assert all(r["ok"] for r in prog.values())
    # the control fails one of the cell's numbers, by a wide margin
    assert not ctrl["grad_norm_rel_gap"]["ok"]
    assert ctrl["grad_norm_rel_gap"]["value"] > \
        3 * prog["grad_norm_rel_gap"]["value"]


@pytest.mark.parametrize("workload", ["tiny-backlog", "tiny-steady"])
def test_served_tokens_match_reference_and_fp8_control_does_not(workload):
    result, lines = run(workload, 5, control=True)
    assert result["correct"] and result["failed"] == 0
    cmp_ = lines["compare"]
    gap = cmp_["numbers"][0]
    assert gap["name"] == "served_logit_gap" and gap["ok"]
    assert cmp_["served_tokens_compared"] > 0
    assert cmp_["control_gap"] > gap["limit"]
    assert cmp_["control_gap"] > 3 * max(gap["value"], 1e-3)


def test_reference_rope_and_attention_by_hand():
    """The reference's pieces against direct formulas."""
    import jax.numpy as jnp

    from chiplib import manifest

    ref = manifest.Files().reference("llama_dense")
    rng = np.random.default_rng(0)
    T, nh, nkv, d = 6, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(T, nh, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(T, nkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(T, nkv, d)), jnp.float32)
    out = np.asarray(ref.attention(q, k, v)).reshape(T, nh, d)
    for h in range(nh):
        kh, vh = np.asarray(k[:, h // 2]), np.asarray(v[:, h // 2])
        s = np.asarray(q[:, h]) @ kh.T / np.sqrt(d)
        s[np.triu_indices(T, 1)] = -np.inf
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        assert np.allclose(out[:, h], p @ vh, atol=1e-5)
    x = jnp.asarray(rng.normal(size=(T, 1, d)), jnp.float32)
    r = np.asarray(ref.rope(x, jnp.arange(T), 10000.0))
    assert np.allclose(r[0], np.asarray(x)[0], atol=1e-6)  # position 0
    assert np.allclose(np.linalg.norm(r, axis=-1),
                       np.linalg.norm(np.asarray(x), axis=-1), atol=1e-5)
