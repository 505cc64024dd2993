"""The short-convolution / grouped-query sparse-expert adapter
(``arch/conv_gqa_moe.py``, ``reference/conv_gqa_moe.py``) through the serve
runner at a tiny size on the CPU (``conv_gqa_moe/``: a convolution over a
dense SwiGLU, then conv / conv / attention / conv over expert layers,
top-4 of 16 experts ALL held, 3 taps, a tied head, served in bfloat16):
the program's served tokens pass the comparison and the fp8 control fails
it; a family that hands a request its lane's last tail, and one whose two
gates are swapped, make ``correct`` false; the configuration keeps every
published key; the four new readers and the three joined ones on a
synthetic ``obs``.

The seeded matrices are made with std 0.1 here (``weights.STD`` steered in
the test): at 128 columns a matrix of std 0.02 gives every layer an output
a tenth of the embedding it is added to, and a TIED head then scores the
fed token's own row highest at every position — a model that repeats its
prompt's last token, on which every comparison reads 0, the control's too.
At the published 2048 columns the layers' outputs are 50x the embedding
(the configuration's ``assumed.weights``)."""
import contextlib
import io
import json
import math
import os
import shutil

import pytest

import tiny

ADDED = os.path.join(tiny.HERE, "conv_gqa_moe")
CONFIG = "tiny-conv-gqa-moe-bf16"
CELL = CONFIG + "-backlog"
REAL = "lfm2-24b-a2b"
REAL_CELL = "serve-conv-moe-backlog"
TAIL = 7 * 2 * 2048 * 2  # bytes a lane, at the published sizes
CUT = ["conv", "conv", "conv", "full_attention", "conv", "conv", "conv",
       "full_attention", "conv"]


@pytest.fixture(autouse=True)
def wide_weights(monkeypatch):
    from chiplib import weights

    monkeypatch.setattr(weights, "STD", 0.1)


def _files(tmp_path, short_prompts=False):
    from chiplib import manifest

    data = str(tmp_path / "data")
    shutil.copytree(tiny.DATA, data)
    for kind, name in (("configs", CONFIG), ("limits", CELL)):
        shutil.copy(os.path.join(ADDED, kind, name + ".json"),
                    os.path.join(data, kind, name + ".json"))
    traffic = "tiny-backlog"
    if short_prompts:
        # prompts of 1-3 tokens: what a lane's last request left in the
        # tail is a large part of what the next one's first tokens see
        mix = json.load(open(os.path.join(data, "traffic",
                                          traffic + ".json")))
        mix["name"] = traffic = "tiny-short-backlog"
        mix["classes"][0]["new_tokens"] = [[0.0, 1], [1.0, 3]]
        mix["classes"][0]["output_tokens"] = [[0.0, 4], [1.0, 8]]
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
    assert cmp_["arch_file"].endswith("arch/conv_gqa_moe.py")
    assert cmp_["reference_file"].endswith("reference/conv_gqa_moe.py")
    gap = cmp_["numbers"][0]
    assert gap["name"] == "served_logit_gap" and gap["value"] > 0
    assert cmp_["served_tokens_compared"] > 0
    assert cmp_["control_gap"] > 1.5 * gap["limit"]
    # the accumulator's counters are in the window's counters, where the
    # readers find them; the compared requests went through verify rounds
    # with rejections, and the program's count of them is the engine's
    c = lines["window"]["counters"]
    rounds = c["decode_steps"] + c["verify_steps"]
    assert c["spec_rolled_back_tokens"] \
        == c["spec_proposed_tokens"] - c["spec_accepted_tokens"] > 0
    assert c["conv_slot_resets"] > 0 and c["prefix_hit_tokens"] == 0
    # every expert is held: every assignment is
    assert c["moe_assignments_held"] == c["moe_assignments"] > 0
    assert c["moe_expert_calls"] == 4 * (rounds + c["prefill_chunks"])
    # held experts hit, counted in the rounds' calls alone: 4-16 of 16
    assert 4 * 4 * rounds <= c["moe_round_experts_hit"] <= 4 * 16 * rounds


def _tail_not_reset(fresh, kept):
    return kept


def _gates_swapped(u, lp):
    import jax.numpy as jnp

    B, C, z = jnp.split(u @ lp["in_proj"], 3, axis=-1)
    return C * z, B


def test_a_tail_that_shows_its_last_request_fails(tmp_path, monkeypatch):
    """A request whose first positions convolve over what its lane's last
    request left in the tail (a chunk at position 0 that does not start
    from zero)."""
    from paddle_tpu.serving.families import conv_moe

    files = _files(tmp_path, short_prompts=True)
    assert _run(files, control=False)[0]["correct"] is True
    monkeypatch.setattr(conv_moe, "_carried", _tail_not_reset)
    result, lines = _run(files, control=False)
    gap = lines["compare"]["numbers"][0]
    assert result["correct"] is False and gap["value"] > 1.5 * gap["limit"]
    assert result["failed"] == 0


def test_swapped_gates_fail(tmp_path, monkeypatch):
    from paddle_tpu.models import conv_moe

    monkeypatch.setattr(conv_moe, "sconv_project", _gates_swapped)
    result, lines = _run(_files(tmp_path), control=False)
    gap = lines["compare"]["numbers"][0]
    assert result["correct"] is False and gap["value"] > 1.5 * gap["limit"]
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
        == ["num_hidden_layers", "layer_types", "num_dense_layers"]
    assert cfg["num_hidden_layers"] == {"published": 40, "serve": 9}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = [r for r in map(json.loads, open(catalog))
               if r["name"] == "LFM2-24B-A2B"][0]
        assert cfg["published"] == row["config"]
        # the driver's check: every key of the catalog's config stands at
        # the top level with the catalog's value, unless `reduced` has it
        for k, v in row["config"].items():
            assert cfg[k] == v or k in entry["reduced"], k
        assert entry["source"] == cfg["source"] == row["source_url"]
    for k, v in cfg["published"].items():
        if k not in entry["reduced"]:
            assert cfg[k] == cfg["model"][k] == v, k
    m, pub = cfg["model"], cfg["published"]
    # every expert, the whole vocabulary: as published, and not listed
    assert (m["num_experts"], m["router_experts"], m["vocab_size"]) \
        == (64, 64, 65536) == (pub["num_experts"],) * 2 + (pub["vocab_size"],)
    # layer 0, then TWO whole periods of the published pattern (4-11)
    assert m["layer_types"] == CUT == cfg["layer_types"] \
        == pub["layer_types"][:1] + pub["layer_types"][4:12]
    assert pub["layer_types"][4:8] == pub["layer_types"][8:12] \
        == ["conv", "conv", "full_attention", "conv"]
    assert (m["num_dense_layers"], pub["num_dense_layers"]) == (1, 2)
    assert set(m) - set(pub) == {"torch_dtype", "router_experts",
                                 "first_held_expert", "tie_word_embeddings"}
    assert set(pub) - set(m) == {"num_hidden_layers"}
    for words in ("five-stage pipeline", "ALL 64 experts", "65,536-row",
                  "layers 4-11"):
        assert words in cfg["stands_for"], words
    assert "5,177,950,976" in cfg["arithmetic"]["serve"]
    (cell,) = [w for w in man["workloads"] if w["config"] == REAL]
    assert cell["name"] == REAL_CELL and cell["chips"] == 1
    assert cfg["serve"]["max_seq_len"] == 8192 + 1536
    assert cfg["serve"]["max_lanes"] == 64
    listed = {x["name"] for x in man["per_layer"]
              if REAL_CELL in x.get("workloads", [])}
    # (at least: a later PR may list the cell under further metrics)
    assert listed >= {
        "decode_round_ms_p50", "tokens_per_round", "spec_accept_pct",
        "idle_draft_ms_per_round", "idle_launch_ms_per_round",
        "idle_fetch_ms_per_round", "idle_sched_ms_per_round",
        "idle_prefill_ms_per_round", "idle_unattributed_pct",
        "dev_attn_ms_per_round", "dev_ffn_ms_per_round",
        "dev_state_ms_per_round", "dev_norm_ms_per_round",
        "dev_head_ms_per_round", "dev_unscoped_pct",
        "full_attend_roofline", "swa_expert_mm_roofline",
        "kv_bytes_per_live_token", "sconv_roofline", "conv_round_roofline",
        "conv_chunk_roofline", "tokens_per_expert_call"}
    # the two readers that index a key this configuration lacks
    assert not listed & {"moe_tokens_per_held_expert",
                         "expert_load_max_over_mean"}
    mix = files.traffic(cell["traffic"])
    (cls,) = mix["classes"]
    assert cls["new_tokens"] == [[0, 256], [0.5, 2048], [0.9, 6144],
                                 [1.0, 8192]]
    assert cls["output_tokens"] == [[0, 32], [0.5, 512], [0.9, 1024],
                                    [1.0, 1536]]
    assert (mix["loop"], mix["cycle_requests"], mix["min_waiting_per_lane"],
            mix["ramp_s"], mix["traced_seconds"], mix["check_requests"],
            cls["turns"]) == ("backlog", 256, 2, 24, 8, 6, 1)


def test_cost_functions_give_the_configurations_arithmetic():
    files, _, _, cfg = _real()
    arch = files.arch(cfg["arch"])
    m, layers = cfg["model"], cfg["num_hidden_layers"]["serve"]
    per = {}
    for li, name, shape, _ in arch.leaf_specs(m, layers):
        per[li] = per.get(li, 0) + math.prod(shape)
    n = sum(per.values())
    assert n == 5_177_950_976  # 10.36 GB in bfloat16
    assert (per[0], per[1], per[3], per[-1]) \
        == (89_139_200, 620_898_368, 614_600_896, 134_217_728 + 2048)
    assert (arch.conv_layers(m, layers), arch.attn_layers(m, layers),
            arch.expert_layers(m, layers)) == (7, 2, 8)
    assert arch.kv_bytes_per_token(m, layers) == 4_096
    assert arch.tail_bytes_per_lane(m, layers) == TAIL == 57_344
    assert arch.sconv_weights(m, layers) == 7 * 16_783_360
    # every expert hit: everything held is read, the tied table ONCE
    assert arch.weight_bytes(m, layers, 64) == 2 * n
    some = arch.weight_bytes(m, layers, 60)
    assert 2 * n - some == 4 * 8 * 9_437_184 * 2
    total = arch.conv_round_bytes(m, layers, 64 * 3300, 64, 60)
    assert total == some + 64 * 3300 * 4096 + 64 * TAIL
    assert 12.9 < total / 819e9 * 1e3 < 13.1  # ms at the HBM rate
    # a 128-token chunk hits every expert; its bytes bound it
    assert 63.9 < arch.chunk_experts_hit(m, 128) <= 64
    assert 62.8 < arch.chunk_experts_hit(m, 64) < 63.0  # a plain round's
    flops, nbytes = arch.conv_chunk_flops_bytes(m, layers, 128, 2048)
    assert 12.6 < nbytes / 819e9 * 1e3 < 12.7
    assert 0.6 < flops / 197e12 * 1e3 < 0.8
    # operators, the dense layer, routers, 4 experts a layer: 0.51B a token
    assert arch.active_params(m, layers) == 7 * 16_783_360 \
        + 2 * (10_485_888 - 128) + 72_351_744 + 8 * 131_072 \
        + 8 * 4 * 9_437_184 == 513_845_248
    sf, sb = arch.sconv_flops_bytes(m, layers, 64, 320)
    assert sb == 7 * 16_783_360 * 2 + 2 * 64 * TAIL
    assert sf == 2 * 320 * 7 * 16_783_360
    assert sf / 197e12 > sb / 819e9  # 5 positions a lane: the MXU's
    held = arch.cache_bytes_held(m, layers, 64 * 3300, 64)
    assert held == 4096 * (64 * 3300 + 64 * 8) + 64 * TAIL
    assert 4_100 < held / (64 * 3300) < 4_200
    c = {"decode_steps": 10, "verify_steps": 90,
         "moe_round_experts_hit": 100 * 8 * 63}
    assert arch.round_experts_hit(m, layers, c) == 63
    assert arch.round_experts_hit(m, layers, {"verify_steps": 3}) is None
    kinds = {name: kind for _, name, _, kind in arch.leaf_specs(m, layers)}
    assert {k for k, v in kinds.items() if v == "norm"} \
        == {"ln_in", "ln_post", "norm", "q_norm", "k_norm", "conv_w"}
    assert "lm_head" not in kinds  # the head is the embedding


def _reader(name):
    from chiplib import manifest

    return manifest.metric_reader(name)


def _synthetic_obs(files, cfg, ms, chunk_ms=14.0, lanes=64, rounds=3,
                   live=64 * 3300):
    """A traced run of ``rounds`` pure verify-less decode rounds whose
    operations under ``sconv``, ``attn/rows``, ``moe/experts`` and
    everything else took ``ms`` (a dict by scope path) a round, and of 2
    prefill chunks of ``chunk_ms``, as ``devscopes.table`` would reduce
    them."""
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
    red = {"rounds": rounds, "prefill_calls": 2,
           "seconds": {"decode": sum(ms.values()) * rounds / 1e3,
                       "prefill": 2 * chunk_ms / 1e3},
           "by_path": {("decode", path): v * rounds / 1e3
                       for path, v in ms.items()}}
    return {"job": "serve", "loop": "backlog", "arch": files.arch(
        cfg["arch"]), "model": cfg["model"], "layers": 9, "lanes": lanes,
        "rounds": [dict(r) for _ in range(rounds)], "devscopes": red,
        "requests": [{"prompt_len": 2048}, {"prompt_len": 6144}],
        "counters": {"spec_proposed_tokens": 0, "verify_steps": 0,
                     "decode_steps": rounds, "prefill_chunks": 64,
                     "prefix_miss_tokens": 64 * 120,
                     "moe_assignments": 8 * (rounds * 256 + 64 * 480),
                     "moe_assignments_held": 8 * (rounds * 256 + 64 * 480),
                     "moe_expert_calls": 8 * (rounds + 64),
                     "moe_round_experts_hit": rounds * 8 * 63},
        "trace": {"devices": {0: dev}, "host": host},
        "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}}


def test_roofline_readers_on_a_synthetic_trace():
    files, _, _, cfg = _real()
    arch = files.arch(cfg["arch"])
    m = cfg["model"]
    live = 64 * 3300
    least = {
        "sconv": arch.sconv_flops_bytes(m, 9, 64, 64)[1] / 819e9 * 1e3,
        "attn/rows": arch.full_attend_flops_bytes(
            m, 9, live, 1)[1] / 819e9 * 1e3,
        "moe/experts": 8 * arch.expert_mm_flops_bytes(
            m, 1, 64 * 4, 63)[1] / 819e9 * 1e3}
    whole = arch.conv_round_bytes(m, 9, live, 64, 63) / 819e9 * 1e3
    assert 0.29 < least["sconv"] < 0.30         # 235 MB + the tails
    assert 1.05 < least["attn/rows"] < 1.06     # 211k tokens x 4,096 B
    assert 11.6 < least["moe/experts"] < 11.7   # 8 layers x 63 experts hit
    names = {"sconv": "sconv_roofline", "attn/rows": "full_attend_roofline",
             "moe/experts": "swa_expert_mm_roofline"}
    # the chunk: 120 real tokens, a mean live context of (2048^2 +
    # 6144^2) / (2 x 8192) = 2560 tokens
    chunk = arch.conv_chunk_flops_bytes(m, 9, 120, 2560)[1] / 819e9 * 1e3
    # a device that runs AT the roofline reads 100%, never more
    obs = _synthetic_obs(files, cfg, dict(
        least, mlp=whole - sum(least.values())), chunk_ms=chunk)
    for path, name in names.items():
        assert _reader(name)(obs) == pytest.approx(100.0)
    assert _reader("conv_round_roofline")(obs) == pytest.approx(100.0)
    assert _reader("conv_chunk_roofline")(obs) == pytest.approx(100.0)
    # at the times one would expect of a real run: below
    ms = {"sconv/in_proj": 0.3, "sconv/conv": 0.1, "sconv/out_proj": 0.1,
          "attn/rows": 2.0, "moe/experts": 13.0, "mlp": 1.5}
    obs = _synthetic_obs(files, cfg, ms)
    assert _reader("sconv_roofline")(obs) \
        == pytest.approx(100 * least["sconv"] / 0.5)  # sub-scopes count
    assert _reader("full_attend_roofline")(obs) \
        == pytest.approx(100 * least["attn/rows"] / 2.0)
    assert _reader("swa_expert_mm_roofline")(obs) \
        == pytest.approx(100 * least["moe/experts"] / 13.0)
    assert _reader("conv_round_roofline")(obs) \
        == pytest.approx(100 * whole / 17.0)
    assert _reader("conv_chunk_roofline")(obs) \
        == pytest.approx(100 * chunk / 14.0)
    assert _reader("kv_bytes_per_live_token")(obs) == pytest.approx(
        arch.cache_bytes_held(m, 9, live, 64) / live)
    # rows a group of a grouped product gets: 4 a round, 7.5 a chunk
    assert _reader("tokens_per_expert_call")(obs) == pytest.approx(
        (3 * 256 + 64 * 480) / (64 * (3 + 64)))
    # another kind of program's time under the scope does not count
    obs["devscopes"]["by_path"][("prefill", "sconv/in_proj")] = 1.0
    assert _reader("sconv_roofline")(obs) \
        == pytest.approx(100 * least["sconv"] / 0.5)
    # without the engine's count of experts hit: nothing
    del obs["counters"]["moe_round_experts_hit"]
    assert _reader("conv_round_roofline")(obs) is None
    assert _reader("swa_expert_mm_roofline")(obs) is None
    # a program without the scope registry, or a run without a trace
    obs["devscopes"] = None
    for name in ("sconv_roofline", "conv_chunk_roofline",
                 "full_attend_roofline"):
        assert _reader(name)(obs) is None
    # another architecture: nothing to read, and nothing raised
    obs = _synthetic_obs(files, cfg, ms)
    obs["model"] = {"hidden_size": 4096}
    obs["arch"] = files.arch("llama_dense")
    for name in ("sconv_roofline", "conv_round_roofline",
                 "conv_chunk_roofline", "tokens_per_expert_call",
                 "kv_bytes_per_live_token"):
        assert _reader(name)(obs) is None
    # and the accepted state / window readers find nothing in this cell
    obs = _synthetic_obs(files, cfg, ms)
    for name in ("ssm_update_roofline", "hybrid_round_roofline",
                 "kda_update_roofline", "linear_round_roofline",
                 "linear_expert_mm_roofline", "window_attend_roofline",
                 "swa_round_roofline"):
        assert _reader(name)(obs) is None
