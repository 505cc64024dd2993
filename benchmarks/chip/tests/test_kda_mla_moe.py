"""The linear-attention / latent-attention sparse-expert adapter
(``arch/kda_mla_moe.py``, ``reference/kda_mla_moe.py``) through the serve
runner at a tiny size on the CPU (``kda_mla_moe/``: 4 gated delta-rule
layers around 1 latent layer, layer 1 dense, top-2 of 8 experts with 4
held, served in bfloat16): the program's served tokens pass the
comparison and the fp8 control fails it; a family that does not start a
slot from zero, and one whose rejected drafts stay in the state, make
``correct`` false; the configuration keeps every published key; the four
new readers on a synthetic ``obs``."""
import contextlib
import io
import json
import math
import os
import shutil

import pytest

import tiny

ADDED = os.path.join(tiny.HERE, "kda_mla_moe")
CONFIG = "tiny-kda-mla-moe-bf16"
CELL = CONFIG + "-backlog"
REAL = "kimi-linear-48b-a3b-ep16"
REAL_CELL = "serve-linear-moe-backlog"
STATE = 20 * 32 * 128 * 128 * 4  # bytes a lane, at the published sizes


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
        # every token, so what a slot's last request left shows only in
        # the first few positions of the next
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
    assert cmp_["arch_file"].endswith("arch/kda_mla_moe.py")
    assert cmp_["reference_file"].endswith("reference/kda_mla_moe.py")
    gap = cmp_["numbers"][0]
    assert gap["name"] == "served_logit_gap"
    assert cmp_["served_tokens_compared"] > 0
    assert cmp_["control_gap"] > 2 * gap["limit"]
    # both accumulators' counters are in the window's counters, where the
    # readers find them; the compared requests went through verify rounds
    # with rejections, and the program's count of them is the engine's
    c = lines["window"]["counters"]
    rounds = c["decode_steps"] + c["verify_steps"]
    assert c["lin_state_passes"] == c["decode_steps"] + 2 * c["verify_steps"]
    assert rounds <= c["lin_lane_rounds"] <= 4 * rounds
    assert 2 * c["lin_lane_rounds"] <= c["lin_state_lane_moves"] \
        <= 3 * c["lin_lane_rounds"]
    assert c["spec_rolled_back_tokens"] \
        == c["spec_proposed_tokens"] - c["spec_accepted_tokens"] > 0
    assert c["lin_slot_resets"] > 0 and c["prefix_hit_tokens"] == 0
    assert 0 < c["moe_assignments_held"] < c["moe_assignments"]
    assert c["moe_expert_calls"] == 4 * (rounds + c["prefill_chunks"])
    # held experts hit, counted in the rounds' calls alone: 1-4 of 4 a call
    assert 4 * rounds <= c["moe_round_experts_hit"] <= 4 * 4 * rounds


def _broken(monkeypatch, name, fn):
    from paddle_tpu.serving.families import linear_latent_moe

    monkeypatch.setattr(linear_latent_moe, name, fn)


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
    # the depth is listed for its FORM alone: nothing of it is cut
    assert entry["reduced"] == cfg["reduced"] \
        == ["num_experts", "vocab_size", "num_hidden_layers"]
    assert cfg["num_hidden_layers"] == {"published": 27, "serve": 27}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [r for r in map(json.loads, open(catalog))
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct"][0]
        assert cfg["published"] == row["config"]
        # the driver's check: every key of the catalog's config stands at
        # the top level with the catalog's value, unless `reduced` has it
        for k, v in row["config"].items():
            assert cfg[k] == v or k in entry["reduced"], k
        assert entry["source"] == cfg["source"] == row["source_url"]
    for k, v in cfg["published"].items():
        if k not in entry["reduced"]:
            assert cfg[k] == cfg["model"][k] == v, k
    assert (cfg["model"]["num_experts"], cfg["model"]["vocab_size"]) \
        == (16, 20480) == (cfg["num_experts"], cfg["vocab_size"])
    assert set(cfg["model"]) - set(cfg["published"]) \
        == {"torch_dtype", "router_experts", "first_held_expert",
            "kda_chunk_size"}
    la = cfg["model"]["linear_attn_config"]
    assert len(la["kda_layers"]) == 20 and la["full_attn_layers"] \
        == [4, 8, 12, 16, 20, 24, 27]
    (cell,) = [w for w in man["workloads"] if w["config"] == REAL]
    assert cell["name"] == REAL_CELL \
        and cell["traffic"] == "reason-backlog" and cell["chips"] == 1
    listed = {m["name"] for m in man["per_layer"]
              if REAL_CELL in m.get("workloads", [])}
    assert listed == {
        "decode_round_ms_p50", "tokens_per_round", "spec_accept_pct",
        "idle_draft_ms_per_round", "idle_launch_ms_per_round",
        "idle_fetch_ms_per_round", "idle_sched_ms_per_round",
        "idle_prefill_ms_per_round", "idle_unattributed_pct",
        "kda_update_roofline", "linear_round_roofline",
        "linear_expert_mm_roofline", "lin_state_bytes_per_token"}


def test_cost_functions_give_the_configurations_arithmetic():
    files, _, _, cfg = _real()
    arch = files.arch(cfg["arch"])
    m, layers = cfg["model"], cfg["num_hidden_layers"]["serve"]
    per = {}
    for li, name, shape, _ in arch.leaf_specs(m, layers):
        per[li] = per.get(li, 0) + math.prod(shape)
    n = sum(per.values())
    assert n == 4_296_057_728  # 8.59 GB in bfloat16
    assert (per[0], per[1], per[3]) \
        == (103_219_872, 160_433_056, 150_033_664)
    assert (arch.kda_layers(m, layers), arch.latent_layers(m, layers),
            arch.expert_layers(m, layers)) == (20, 7, 26)
    assert arch.lin_state_bytes_per_lane(m, layers) == STATE == 41_943_040
    assert arch.latent_bytes_per_token(m, layers) == 7 * 1152
    assert arch.kda_update_bytes(m, layers, 64) == 2 * 64 * STATE
    # every held expert hit: everything but the embedding is read
    embed = 20480 * 2304
    assert arch.weight_bytes(m, layers, 16) == 2 * (n - embed)
    # 6 of a layer's 16 held experts hit: 10 experts a layer are not read
    some = arch.weight_bytes(m, layers, 6)
    assert 2 * (n - embed) - some == 26 * 10 * 7_077_888 * 2
    total = arch.linear_round_bytes(m, layers, 45_000, 64, 6)
    assert total == some + 45_000 * 8064 + 2 * 64 * STATE
    assert 12.8 < total / 819e9 * 1e3 < 13.0  # ms at the HBM rate
    # the experts hit a call: the engine's count over the rounds' calls
    c = {"decode_steps": 10, "verify_steps": 90,
         "moe_round_experts_hit": 100 * 26 * 6}
    assert arch.round_experts_hit(m, layers, c) == 6
    assert arch.round_experts_hit(m, layers, {"verify_steps": 3}) is None
    kinds = {name: kind for _, name, _, kind in arch.leaf_specs(m, layers)}
    assert {k for k, v in kinds.items() if v == "norm"} \
        == {"ln_in", "ln_post", "o_norm", "kv_norm", "norm", "conv_w"}


def _reader(name):
    from chiplib import manifest

    return manifest.metric_reader(name)


def test_state_bytes_per_token_reader():
    files, _, _, cfg = _real()
    obs = {"job": "serve", "arch": files.arch(cfg["arch"]),
           "model": cfg["model"], "layers": 27, "tokens_out": 64 * 100,
           "counters": {"lin_state_lane_moves": 2 * 64 * 100}}
    read = _reader("lin_state_bytes_per_token")
    assert read(obs) == 2 * STATE  # a plain round, a token a lane: 83.9 MB
    obs["counters"]["lin_state_lane_moves"] = 3 * 64 * 100
    obs["tokens_out"] = 5 * 64 * 100     # every draft accepted
    assert read(obs) == 3 * STATE / 5
    obs["counters"] = {}                 # the parent's program: no counter
    assert read(obs) is None


def _synthetic_obs(files, cfg, ms_state, ms_experts, ms_other, lanes=64,
                   rounds=3):
    """A trace of ``rounds`` pure decode rounds: one span each, inside it
    40 operations on a layer's state (``ms_state`` in all), 52 grouped
    products on the stacked expert weights (``ms_experts``) and one other
    (``ms_other``)."""
    m = cfg["model"]
    slab = f"f32[{lanes},32,128,128]"
    names = [f"%fusion.{i} = {slab}{{3,2,1,0}} fusion({slab} %args_{i}, "
             f"f32[{lanes},32,128] %u.{i})" for i in range(40)]
    gmm = [f"%gmm.{i} = bf16[512,{n}]{{1,0}} custom-call(bf16[512,{k}] %r, "
           f"bf16[16,{k},{n}] %w.{i})"
           for i in range(26) for k, n in ((2304, 2048), (1024, 2304))]
    other = "%fusion.9 = bf16[64,20480]{1,0} fusion(bf16[64,2304] %x)"
    host, full, short = [], [], []
    t = 1e6
    for _ in range(rounds):
        start = t
        t += 1e5
        for group, ms in ((names, ms_state), (gmm, ms_experts),
                          ([other], ms_other)):
            for nm in group:
                d = ms * 1e6 / len(group)
                full.append((nm, t, d))
                short.append((nm.split(" = ")[0].lstrip("%") + " fusion",
                              t, d))
                t += d
        t += 1e5
        host.append(("bench/engine_step", start, t - start))
        t += 1e5
    r = {"ms": 0.0, "live_kv_tokens": 45_000, "lanes": lanes,
         "traced": True, "prefill_chunks": 0, "decode_steps": 1,
         "verify_steps": 0, "decoded_tokens": lanes}
    return {"job": "serve", "loop": "backlog", "arch": files.arch(
        cfg["arch"]), "model": m, "layers": 27, "lanes": lanes,
        "rounds": [dict(r) for _ in range(rounds)],
        "counters": {"spec_proposed_tokens": 0, "verify_steps": 0,
                     "decode_steps": rounds, "moe_assignments": 1600,
                     "moe_assignments_held": 100,
                     "moe_round_experts_hit": rounds * 26 * 6},
        "trace": {"devices": {0: short}, "host": host},
        "optext_events": full,
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_roofline_readers_on_a_synthetic_trace():
    files, _, _, cfg = _real()
    arch = files.arch(cfg["arch"])
    m = cfg["model"]
    least_state = arch.kda_update_bytes(m, 27, 64) / 819e9 * 1e3   # ms
    least_round = arch.linear_round_bytes(m, 27, 45_000, 64, 6) \
        / 819e9 * 1e3
    _, nbytes = arch.expert_mm_flops_bytes(m, 1, 64 * 8 / 16, 6)
    least_experts = 26 * nbytes / 819e9 * 1e3
    assert 6.5 < least_state < 6.6 and 12.8 < least_round < 13.0
    assert 2.6 < least_experts < 2.8  # 26 layers x 6 of 16 experts hit
    # a device that runs AT the roofline reads 100%, never more
    obs = _synthetic_obs(files, cfg, least_state, least_experts,
                         least_round - least_state - least_experts)
    assert _reader("kda_update_roofline")(obs) == pytest.approx(100.0)
    assert _reader("linear_round_roofline")(obs) == pytest.approx(100.0)
    assert _reader("linear_expert_mm_roofline")(obs) == pytest.approx(100.0)
    # at the times one would expect of a real run: below
    obs = _synthetic_obs(files, cfg, 10.0, 8.0, 6.0)
    assert _reader("kda_update_roofline")(obs) \
        == pytest.approx(100 * least_state / 10.0)
    assert _reader("linear_round_roofline")(obs) \
        == pytest.approx(100 * least_round / 24.0)
    assert _reader("linear_expert_mm_roofline")(obs) \
        == pytest.approx(100 * least_experts / 8.0)
    # without the engine's count of experts hit (the parent): nothing
    del obs["counters"]["moe_round_experts_hit"]
    assert _reader("linear_round_roofline")(obs) is None
    assert _reader("linear_expert_mm_roofline")(obs) is None
    # the pattern is the state's shape, by element count, whatever reshape
    pattern = _reader("kda_update_roofline").__globals__["pattern"]
    picked = pattern(
        ["%a = f32[64,1,32,128,128]{4,3,2,1,0} bitcast(f32[64,32,128,128])",
         "%b = f32[64,32,128]{2,1,0} fusion(f32[64,32] %c)",
         "%d = bf16[64,32,128,128]{3,2,1,0} fusion()"], 64, m)
    assert picked.count("|") == 1 and "f32\\[64,32,128\\]" not in picked
    # a program without the state (the parent), or another architecture
    assert pattern(["%b = f32[64,32,128]{2,1,0} fusion()"], 64, m) is None
    obs["model"] = {"hidden_size": 4096}
    obs["arch"] = files.arch("llama_dense")
    for name in ("kda_update_roofline", "linear_round_roofline",
                 "linear_expert_mm_roofline"):
        assert _reader(name)(obs) is None
    # and the accepted state / expert readers find nothing in this cell
    obs = _synthetic_obs(files, cfg, 10.0, 8.0, 6.0)
    assert _reader("ssm_update_roofline")(obs) is None
    assert _reader("hybrid_round_roofline")(obs) is None
