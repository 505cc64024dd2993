"""The window-attention / full-attention sparse-expert adapter
(``arch/swa_gqa_moe.py``, ``reference/swa_gqa_moe.py``) through the serve
runner at a tiny size on the CPU (``swa_gqa_moe/``: a full layer over a
dense SwiGLU, then 3 window layers and 1 full layer over expert layers,
top-2 of 8 experts with 4 held, keys wider than values, a ring of 16
slots, served in bfloat16): the program's served tokens pass the
comparison and the fp8 control fails it; a family that leaves the sink
out, and one whose ring shows what a lane's predecessor left, make
``correct`` false; the configuration keeps every published key; the five
new readers on a synthetic ``obs``."""
import contextlib
import io
import json
import math
import os
import shutil

import pytest

import tiny

ADDED = os.path.join(tiny.HERE, "swa_gqa_moe")
CONFIG = "tiny-swa-gqa-moe-bf16"
CELL = CONFIG + "-backlog"
REAL = "mimo-v2.5-ep16"
REAL_CELL = "serve-swa-moe-backlog"
RING = 5 * 144 * 8 * 320 * 2  # bytes a lane, at the published sizes


def _files(tmp_path, short_prompts=False):
    from chiplib import manifest

    data = str(tmp_path / "data")
    shutil.copytree(tiny.DATA, data)
    for kind, name in (("configs", CONFIG), ("limits", CELL)):
        shutil.copy(os.path.join(ADDED, kind, name + ".json"),
                    os.path.join(data, kind, name + ".json"))
    traffic = "tiny-backlog"
    if short_prompts:
        # prompts of 2-6 tokens: what a lane's last request left in the
        # ring lies inside the band of the next one's first positions
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
    assert cmp_["arch_file"].endswith("arch/swa_gqa_moe.py")
    assert cmp_["reference_file"].endswith("reference/swa_gqa_moe.py")
    gap = cmp_["numbers"][0]
    assert gap["name"] == "served_logit_gap"
    assert cmp_["served_tokens_compared"] > 0
    assert cmp_["control_gap"] > 2 * gap["limit"]
    # the accumulator's counters are in the window's counters, where the
    # readers find them; the compared requests went through verify rounds
    # with rejections, and the program's count of them is the engine's
    c = lines["window"]["counters"]
    rounds = c["decode_steps"] + c["verify_steps"]
    assert c["spec_rolled_back_tokens"] \
        == c["spec_proposed_tokens"] - c["spec_accepted_tokens"] > 0
    assert c["win_slot_resets"] > 0 and c["prefix_hit_tokens"] == 0
    assert 0 < c["moe_assignments_held"] < c["moe_assignments"]
    assert c["moe_expert_calls"] == 4 * (rounds + c["prefill_chunks"])
    # held experts hit, counted in the rounds' calls alone: 1-4 of 4 a call
    assert 4 * rounds <= c["moe_round_experts_hit"] <= 4 * 4 * rounds


def _no_sink(scores, sink):
    import jax

    return jax.nn.softmax(scores, axis=-1)


def test_a_sink_left_out_fails(tmp_path, monkeypatch):
    from paddle_tpu.models import window_moe

    monkeypatch.setattr(window_moe, "softmax_with_sink", _no_sink)
    result, lines = _run(_files(tmp_path), control=False)
    gap = lines["compare"]["numbers"][0]
    assert result["correct"] is False and gap["value"] > 2 * gap["limit"]
    assert result["failed"] == 0


def test_a_ring_that_shows_its_last_request_fails(tmp_path, monkeypatch):
    """A request whose first positions see what its lane's last request
    left in the ring (the mask without ``held >= 0``)."""
    from paddle_tpu.models import window_moe

    files = _files(tmp_path, short_prompts=True)
    assert _run(files, control=False)[0]["correct"] is True

    def never_empty(q_pos, k_pos, window):
        back = q_pos - k_pos
        return (back >= 0) & (back < window)

    monkeypatch.setattr(window_moe, "band_mask", never_empty)
    result, lines = _run(files, control=False)
    gap = lines["compare"]["numbers"][0]
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
    assert entry["reduced"] == cfg["reduced"] \
        == ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
            "n_routed_experts", "vocab_size"]
    assert cfg["num_hidden_layers"] == {"published": 48, "serve": 7}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [r for r in map(json.loads, open(catalog))
               if r["name"] == "MiMo-V2.5"][0]
        assert cfg["published"] == row["config"]
        # the driver's check: every key of the catalog's config stands at
        # the top level with the catalog's value, unless `reduced` has it
        for k, v in row["config"].items():
            assert cfg[k] == v or k in entry["reduced"], k
        assert entry["source"] == cfg["source"] == row["source_url"]
    for k, v in cfg["published"].items():
        if k not in entry["reduced"]:
            assert cfg[k] == cfg["model"][k] == v, k
    m = cfg["model"]
    assert (m["n_routed_experts"], m["vocab_size"]) == (16, 19072) \
        == (cfg["n_routed_experts"], cfg["vocab_size"])
    # layer 0, then ONE whole period of the published pattern
    pub = cfg["published"]
    assert m["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0] \
        == pub["hybrid_layer_pattern"][:1] + pub["hybrid_layer_pattern"][6:12]
    assert m["moe_layer_freq"] == [0] + [1] * 6 \
        == pub["moe_layer_freq"][:1] + pub["moe_layer_freq"][6:12]
    assert set(m) - set(pub) == {"torch_dtype", "router_experts",
                                 "first_held_expert", "window_ring_len"}
    assert set(pub) - set(m) == {"num_hidden_layers"}
    assert m["window_ring_len"] >= m["sliding_window"] + 4 + 1
    (cell,) = [w for w in man["workloads"] if w["config"] == REAL]
    assert cell["name"] == REAL_CELL \
        and cell["traffic"] == "agent-backlog" and cell["chips"] == 1
    assert cfg["serve"]["max_seq_len"] == 8192 + 1536
    listed = {x["name"] for x in man["per_layer"]
              if REAL_CELL in x.get("workloads", [])}
    # (at least: a later PR may list the cell under further metrics)
    assert listed >= {
        "decode_round_ms_p50", "tokens_per_round", "spec_accept_pct",
        "idle_draft_ms_per_round", "idle_launch_ms_per_round",
        "idle_fetch_ms_per_round", "idle_sched_ms_per_round",
        "idle_prefill_ms_per_round", "idle_unattributed_pct",
        "dev_attn_ms_per_round", "dev_ffn_ms_per_round",
        "dev_norm_ms_per_round", "dev_head_ms_per_round",
        "dev_unscoped_pct", "moe_tokens_per_held_expert",
        "expert_load_max_over_mean", "window_attend_roofline",
        "full_attend_roofline", "swa_expert_mm_roofline",
        "swa_round_roofline", "kv_bytes_per_live_token"}
    mix = files.traffic("agent-backlog")
    (cls,) = mix["classes"]
    assert cls["new_tokens"] == [[0, 256], [0.5, 2048], [0.9, 6144],
                                 [1.0, 8192]]
    assert cls["output_tokens"] == [[0, 32], [0.5, 512], [0.9, 1024],
                                    [1.0, 1536]]
    assert (mix["loop"], mix["cycle_requests"], mix["min_waiting_per_lane"],
            mix["ramp_s"], mix["traced_seconds"], cls["turns"]) \
        == ("backlog", 256, 2, 24, 8, 1)


def test_cost_functions_give_the_configurations_arithmetic():
    files, _, _, cfg = _real()
    arch = files.arch(cfg["arch"])
    m, layers = cfg["model"], cfg["num_hidden_layers"]["serve"]
    per = {}
    for li, name, shape, _ in arch.leaf_specs(m, layers):
        per[li] = per.get(li, 0) + math.prod(shape)
    n = sum(per.values())
    assert n == 3_429_955_392  # 6.86 GB in bfloat16
    assert (per[0], per[1], per[6]) \
        == (290_463_744, 498_082_112, 492_839_168)
    assert (arch.window_layers(m, layers), arch.full_layers(m, layers),
            arch.expert_layers(m, layers)) == (5, 2, 6)
    assert arch.full_kv_bytes_per_token(m, layers) == 5_120
    assert arch.every_layer_kv_bytes_per_token(m, layers) == 30_720
    assert arch.ring_bytes_per_lane(m, layers) == RING == 3_686_400
    # 64 lanes of 3,300 tokens: the window's 128 live slots a lane
    assert arch.window_live_bytes(m, layers, 64 * 3300, 64) \
        == 5 * 64 * 128 * 5120
    # a lane shorter than the window reads what it has
    assert arch.window_live_bytes(m, layers, 64 * 32, 64) \
        == 5 * 64 * 32 * 5120
    embed = 19072 * 4096
    assert arch.weight_bytes(m, layers, 16) == 2 * (n - embed)
    some = arch.weight_bytes(m, layers, 6)
    assert 2 * (n - embed) - some == 6 * 10 * 25_165_824 * 2
    total = arch.swa_round_bytes(m, layers, 64 * 3300, 64, 6)
    assert total == some + 64 * 3300 * 5120 + 5 * 64 * 128 * 5120
    assert 5.9 < total / 819e9 * 1e3 < 6.1  # ms at the HBM rate
    held = arch.cache_bytes_held(m, layers, 64 * 3300, 64)
    assert held == 5120 * (64 * 3300 + 64 * 8) + 64 * RING
    assert 6_200 < held / (64 * 3300) < 6_300
    c = {"decode_steps": 10, "verify_steps": 90,
         "moe_round_experts_hit": 100 * 6 * 7}
    assert arch.round_experts_hit(m, layers, c) == 7
    assert arch.round_experts_hit(m, layers, {"verify_steps": 3}) is None
    kinds = {name: kind for _, name, _, kind in arch.leaf_specs(m, layers)}
    assert {k for k, v in kinds.items() if v == "norm"} \
        == {"ln_in", "ln_post", "norm", "sink"}


def _reader(name):
    from chiplib import manifest

    return manifest.metric_reader(name)


def _synthetic_obs(files, cfg, ms, lanes=64, rounds=3, live=64 * 3300):
    """A traced run of ``rounds`` pure decode rounds whose operations
    under ``attn/window``, ``attn/rows``, ``moe/experts`` and everything
    else took ``ms`` (a dict by scope path) a round, as
    ``devscopes.table`` would reduce them."""
    host, dev = [], []
    t = 1e6
    busy = sum(ms.values()) * 1e6
    for _ in range(rounds):
        host.append(("bench/engine_step", t, busy + 2e5))
        dev.append(("fusion", t + 1e5, busy))
        t += busy + 3e5
    r = {"ms": 0.0, "live_kv_tokens": live, "lanes": lanes, "traced": True,
         "prefill_chunks": 0, "decode_steps": 1, "verify_steps": 0,
         "decoded_tokens": lanes}
    red = {"rounds": rounds, "by_path": {
        ("decode", path): v * rounds / 1e3 for path, v in ms.items()}}
    return {"job": "serve", "loop": "backlog", "arch": files.arch(
        cfg["arch"]), "model": cfg["model"], "layers": 7, "lanes": lanes,
        "rounds": [dict(r) for _ in range(rounds)], "devscopes": red,
        "counters": {"spec_proposed_tokens": 0, "verify_steps": 0,
                     "decode_steps": rounds, "moe_assignments": 1600,
                     "moe_assignments_held": 100,
                     "moe_round_experts_hit": rounds * 6 * 6},
        "trace": {"devices": {0: dev}, "host": host},
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_roofline_readers_on_a_synthetic_trace():
    files, _, _, cfg = _real()
    arch = files.arch(cfg["arch"])
    m = cfg["model"]
    live = 64 * 3300
    least = {
        "attn/window": arch.window_attend_flops_bytes(
            m, 7, live, 64, 64)[1] / 819e9 * 1e3,
        "attn/rows": arch.full_attend_flops_bytes(
            m, 7, live, 1)[1] / 819e9 * 1e3,
        "moe/experts": 6 * arch.expert_mm_flops_bytes(
            m, 1, 64 * 8 / 16, 6)[1] / 819e9 * 1e3}
    whole = arch.swa_round_bytes(m, 7, live, 64, 6) / 819e9 * 1e3
    assert 0.25 < least["attn/window"] < 0.27   # 5 x 64 x (128 + 1) rows
    assert 1.3 < least["attn/rows"] < 1.35      # 211k tokens x 5,120 B
    assert 2.2 < least["moe/experts"] < 2.25    # 6 layers x 6 experts hit
    names = {"attn/window": "window_attend_roofline",
             "attn/rows": "full_attend_roofline",
             "moe/experts": "swa_expert_mm_roofline"}
    # a device that runs AT the roofline reads 100%, never more
    obs = _synthetic_obs(files, cfg, dict(
        least, mlp=whole - sum(least.values())))
    for path, name in names.items():
        assert _reader(name)(obs) == pytest.approx(100.0)
    assert _reader("swa_round_roofline")(obs) == pytest.approx(100.0)
    # at the times one would expect of a real run: below
    ms = {"attn/window": 1.5, "attn/rows": 4.0, "moe/experts": 5.0,
          "mlp": 4.5}
    obs = _synthetic_obs(files, cfg, ms)
    for path, name in names.items():
        assert _reader(name)(obs) \
            == pytest.approx(100 * least[path] / ms[path])
    assert _reader("swa_round_roofline")(obs) \
        == pytest.approx(100 * whole / 15.0)
    assert _reader("kv_bytes_per_live_token")(obs) == pytest.approx(
        arch.cache_bytes_held(m, 7, live, 64) / live)
    # a scope's sub-scopes count with it; another kind of program does not
    obs["devscopes"]["by_path"][("decode", "attn/rows/norm")] = 0.003
    obs["devscopes"]["by_path"][("prefill", "attn/rows")] = 1.0
    assert _reader("full_attend_roofline")(obs) \
        == pytest.approx(100 * least["attn/rows"] / 5.0)
    # without the engine's count of experts hit: nothing
    del obs["counters"]["moe_round_experts_hit"]
    assert _reader("swa_round_roofline")(obs) is None
    assert _reader("swa_expert_mm_roofline")(obs) is None
    # a program without the scope registry, or a run without a trace
    obs["devscopes"] = None
    assert _reader("window_attend_roofline")(obs) is None
    assert _reader("full_attend_roofline")(obs) is None
    # another architecture: nothing to read, and nothing raised
    obs = _synthetic_obs(files, cfg, ms)
    obs["model"] = {"hidden_size": 4096}
    obs["arch"] = files.arch("llama_dense")
    for name in (*names.values(), "swa_round_roofline",
                 "kv_bytes_per_live_token"):
        assert _reader(name)(obs) is None
    # and the accepted state / expert readers find nothing in this cell
    obs = _synthetic_obs(files, cfg, ms)
    for name in ("ssm_update_roofline", "hybrid_round_roofline",
                 "kda_update_roofline", "linear_round_roofline",
                 "linear_expert_mm_roofline"):
        assert _reader(name)(obs) is None
