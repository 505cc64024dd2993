"""The hybrid state-space / attention adapter (``arch/hybrid_ssm.py``,
``reference/hybrid_ssm.py``) through the serve runner at a tiny size on the
CPU (``hybrid_ssm/``: 3 state-space layers around 1 attention layer,
served in bfloat16): the program's served tokens pass the comparison and
the fp8 control fails it; a family that does not start a slot from zero,
and one whose rejected drafts stay in the state, make ``correct`` false;
the configuration keeps every published key; the three new readers on a
synthetic ``obs``."""
import contextlib
import io
import json
import math
import os
import shutil

import pytest

import tiny

ADDED = os.path.join(tiny.HERE, "hybrid_ssm")
CONFIG = "tiny-hybrid-ssm-bf16"
CELL = CONFIG + "-backlog"
REAL = "granite-4.0-h-micro"


def _files(tmp_path, short_prompts=False):
    from chiplib import manifest

    data = str(tmp_path / "data")
    shutil.copytree(tiny.DATA, data)
    for kind, name in (("configs", CONFIG), ("limits", CELL)):
        shutil.copy(os.path.join(ADDED, kind, name + ".json"),
                    os.path.join(data, kind, name + ".json"))
    traffic = "tiny-backlog"
    if short_prompts:
        # prompts of 2-6 tokens: the seeded weights' state halves with
        # every token (A about -1, dt about 0.7), so what a slot's last
        # request left shows only in the first few positions of the next
        mix = json.load(open(os.path.join(data, "traffic",
                                          traffic + ".json")))
        mix["name"] = traffic = "tiny-short-backlog"
        mix["classes"][0]["new_tokens"] = [[0.0, 2], [1.0, 6]]
        json.dump(mix, open(os.path.join(data, "traffic",
                                         traffic + ".json"), "w"))
    man = json.loads(json.dumps(tiny.MANIFEST))
    man["configs"].append({"name": CONFIG,
                           "file": f"configs/{CONFIG}.json"})
    man["workloads"].append({"name": CELL, "config": CONFIG,
                             "traffic": traffic, "chips": 1})
    return manifest.Files(root=data, data=data, manifest=man)


def _run(files, seed=7, control=True):
    import run as runner

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        result = runner.run_cell(CELL, seed, 1.5, 0, files=files,
                                 require_chip=False, control=control)
    return result, {ln["line"]: ln for ln in map(json.loads,
                                                 buf.getvalue().splitlines())}


@pytest.mark.parametrize("seed", [7, 2 ** 31 + 11])
def test_tiny_cell_passes_and_its_control_fails(tmp_path, seed):
    result, lines = _run(_files(tmp_path), seed)
    cmp_ = lines["compare"]
    assert result["correct"] is True and result["failed"] == 0, cmp_
    assert cmp_["arch_file"].endswith("arch/hybrid_ssm.py")
    assert cmp_["reference_file"].endswith("reference/hybrid_ssm.py")
    gap = cmp_["numbers"][0]
    assert gap["name"] == "served_logit_gap"
    assert cmp_["served_tokens_compared"] > 0
    assert cmp_["control_gap"] > 2 * gap["limit"]
    # the family's counters are in the window's counters, where the
    # reader finds them; the compared requests went through verify rounds
    # with rejections, and the program's count of them is the engine's
    c = lines["window"]["counters"]
    rounds = c["decode_steps"] + c["verify_steps"]
    assert c["ssm_state_passes"] == c["decode_steps"] + 2 * c["verify_steps"]
    assert rounds <= c["ssm_lane_rounds"] <= 4 * rounds
    assert 2 * c["ssm_lane_rounds"] <= c["ssm_state_lane_moves"] \
        <= 3 * c["ssm_lane_rounds"]
    assert c["spec_rolled_back_tokens"] \
        == c["spec_proposed_tokens"] - c["spec_accepted_tokens"] > 0
    assert c["ssm_slot_resets"] > 0 and c["prefix_hit_tokens"] == 0


def _broken(monkeypatch, name, fn):
    from paddle_tpu.serving.families import hybrid_ssm

    monkeypatch.setattr(hybrid_ssm, name, fn)


def test_a_slot_that_is_not_reset_fails(tmp_path, monkeypatch):
    """A request that starts from what its lane's last request left."""
    files = _files(tmp_path, short_prompts=True)
    assert _run(files, control=False)[0]["correct"] is True
    _broken(monkeypatch, "_carried", lambda fresh, kept: kept)
    result, lines = _run(files, control=False)
    gap = lines["compare"]["numbers"][0]
    assert result["correct"] is False and gap["value"] > 2 * gap["limit"]
    assert result["failed"] == 0


def test_rejected_drafts_left_in_the_state_fail(tmp_path, monkeypatch):
    """A verify round that advances state and conv tail over every
    position it fed, accepted or not."""
    import jax.numpy as jnp

    _broken(monkeypatch, "_keeps",
            lambda live, accepted: jnp.where(live, 5, 0))
    result, lines = _run(_files(tmp_path), control=False)
    gap = lines["compare"]["numbers"][0]
    assert lines["window"]["counters"]["spec_rolled_back_tokens"] > 0
    assert result["correct"] is False and gap["value"] > 2 * gap["limit"]
    assert result["failed"] == 0


def _real():
    from chiplib import manifest

    files = manifest.Files()
    man = files.load()
    entry = [c for c in man["configs"] if c["name"] == REAL][0]
    cfg = files.config(man, REAL)
    return files, man, entry, cfg


def test_the_configuration_keeps_every_published_key():
    import test_manifest

    files, man, entry, cfg = _real()
    test_manifest.check_widths(entry, cfg)
    # the depth is listed for its FORM alone: the harness reads the group
    # {published, serve} where the source holds a number; nothing is cut
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == {"published": 40, "serve": 40}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [r for r in map(json.loads, open(catalog))
               if r["name"] == REAL][0]
        assert cfg["published"] == row["config"]
        # the driver's check: every key of the catalog's config stands at
        # the top level with the catalog's value, unless `reduced` has it
        for k, v in row["config"].items():
            assert cfg[k] == v or k in entry["reduced"], k
        assert entry["source"] == cfg["source"] == row["source_url"]
    for k, v in cfg["published"].items():
        if k != "num_hidden_layers":
            assert cfg[k] == cfg["model"][k] == v, k
    assert set(cfg["model"]) - set(cfg["published"]) \
        == {"torch_dtype", "head_dim"}
    (cell,) = [w for w in man["workloads"] if w["config"] == REAL]
    assert cell["name"] == "serve-ssm-hybrid-backlog" \
        and cell["traffic"] == "reason-backlog" and cell["chips"] == 1
    assert cell["name"] not in [
        m for m in man["per_layer"]
        if m["name"] == "decode_step_roofline"][0]["workloads"]


def test_cost_functions_give_the_configurations_arithmetic():
    files, _, _, cfg = _real()
    arch = files.arch(cfg["arch"])
    m, layers = cfg["model"], cfg["num_hidden_layers"]["serve"]
    n = sum(math.prod(shape) for _, _, shape, _ in
            arch.leaf_specs(m, layers))
    assert n == 3_191_396_096  # 6.38 GB in bfloat16
    per = {}
    for li, name, shape, _ in arch.leaf_specs(m, layers):
        per[li] = per.get(li, 0) + math.prod(shape)
    assert per[0] == 76_182_976 and per[5] == 60_821_504
    assert arch.ssm_layers(m, layers) == 36
    assert arch.weight_bytes(m, layers) == 2 * n  # tied: read as the head
    assert arch.ssm_state_bytes_per_lane(m, layers) == 75_497_472
    assert arch.kv_bytes_per_token(m, layers) == 8192
    assert arch.ssm_update_bytes(m, layers, 64) == 2 * 64 * 75_497_472
    total = arch.hybrid_round_bytes(m, layers, 45_000, 64)
    assert total == 2 * n + 45_000 * 8192 + 2 * 64 * 75_497_472
    assert 19.9 < total / 819e9 * 1e3 < 20.2  # ms at the HBM rate
    assert 0.58 < arch.ssm_update_bytes(m, layers, 64) / total < 0.60
    kinds = {name: kind for _, name, _, kind in arch.leaf_specs(m, layers)}
    assert {k for k, v in kinds.items() if v == "norm"} \
        == {"ln_in", "ln_post", "gate_norm", "D", "norm", "conv_w"}
    assert {len(s) for _, _, s, _ in arch.leaf_specs(m, layers)} \
        == {1, 2, 3}


def _reader(name):
    from chiplib import manifest

    return manifest.metric_reader(name)


def test_state_bytes_per_token_reader():
    files, _, _, cfg = _real()
    obs = {"job": "serve", "arch": files.arch(cfg["arch"]),
           "model": cfg["model"], "layers": 40, "tokens_out": 64 * 100,
           "counters": {"ssm_state_lane_moves": 2 * 64 * 100}}
    read = _reader("ssm_state_bytes_per_token")
    assert read(obs) == 2 * 75_497_472  # a plain round, a token a lane
    obs["counters"]["ssm_state_lane_moves"] = 3 * 64 * 100
    obs["tokens_out"] = 5 * 64 * 100     # every draft accepted
    assert read(obs) == 3 * 75_497_472 / 5
    obs["counters"] = {}                 # the parent's program: no counter
    assert read(obs) is None


def _synthetic_obs(files, cfg, ms_state, ms_other, lanes=64, rounds=3):
    """A trace of ``rounds`` pure decode rounds: one span each, inside it
    36 operations on a layer's state (``ms_state`` in all) and one other
    (``ms_other``)."""
    m = cfg["model"]
    slab = f"f32[{lanes},64,64,128]"
    names = [f"%multiply_reduce_fusion.{i} = (f32[{lanes},64,64]{{2,1,0}}, "
             f"{slab}{{3,2,1,0}}) fusion(%p.{i}, {slab} %args_{i})"
             for i in range(36)]
    other = "%fusion.9 = bf16[64,16384]{1,0} fusion(bf16[64,2048] %x)"
    host, full, short = [], [], []
    t = 1e6
    for _ in range(rounds):
        start = t
        t += 1e5
        for nm in names:
            d = ms_state * 1e6 / 36
            full.append((nm, t, d))
            short.append((nm.split(" = ")[0].lstrip("%") + " fusion", t, d))
            t += d
        full.append((other, t, ms_other * 1e6))
        short.append(("fusion.9 fusion", t, ms_other * 1e6))
        t += ms_other * 1e6 + 1e5
        host.append(("bench/engine_step", start, t - start))
        t += 1e5
    r = {"ms": 0.0, "live_kv_tokens": 45_000, "lanes": lanes,
         "traced": True, "prefill_chunks": 0, "decode_steps": 1,
         "verify_steps": 0, "decoded_tokens": lanes}
    return {"job": "serve", "loop": "backlog", "arch": files.arch(
        cfg["arch"]), "model": m, "layers": 40, "lanes": lanes,
        "rounds": [dict(r) for _ in range(rounds)],
        "counters": {"spec_proposed_tokens": 0, "verify_steps": 0},
        "trace": {"devices": {0: short}, "host": host},
        "optext_events": full,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_roofline_readers_on_a_synthetic_trace():
    files, _, _, cfg = _real()
    arch = files.arch(cfg["arch"])
    m = cfg["model"]
    least_state = arch.ssm_update_bytes(m, 40, 64) / 819e9 * 1e3   # ms
    least_round = arch.hybrid_round_bytes(m, 40, 45_000, 64) / 819e9 * 1e3
    assert 11.7 < least_state < 11.9 and 19.9 < least_round < 20.2
    # a device that runs AT the roofline reads 100%, never more
    obs = _synthetic_obs(files, cfg, least_state, least_round - least_state)
    assert _reader("ssm_update_roofline")(obs) == pytest.approx(100.0)
    assert _reader("hybrid_round_roofline")(obs) == pytest.approx(100.0)
    # at the times one would expect of a real run: below
    obs = _synthetic_obs(files, cfg, 16.0, 10.0)
    assert _reader("ssm_update_roofline")(obs) \
        == pytest.approx(100 * least_state / 16.0)
    assert _reader("hybrid_round_roofline")(obs) \
        == pytest.approx(100 * least_round / 26.0)
    # the pattern is the pool's shape, by element count, whatever reshape
    pattern = _reader("ssm_update_roofline").__globals__["pattern"]
    picked = pattern(
        ["%a = f32[64,1,1,64,64,128]{5,4,3,2,1,0} bitcast(f32[64,64,64,128])",
         "%b = f32[64,64,64]{2,1,0} fusion(f32[64,64] %c)",
         "%d = bf16[64,64,64,128]{3,2,1,0} fusion()"], 64, m, 36)
    assert picked.count("|") == 1 and "f32\\[64,64,64\\]" not in picked
    # a program without the state (the parent), or another architecture
    assert pattern(["%b = f32[64,64,64]{2,1,0} fusion()"], 64, m,
                   36) is None
    obs["model"] = {"hidden_size": 4096}
    obs["arch"] = files.arch("llama_dense")
    assert _reader("ssm_update_roofline")(obs) is None
    assert _reader("hybrid_round_roofline")(obs) is None
