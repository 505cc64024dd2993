"""The latent-attention sparse-expert adapter (``arch/mla_moe.py``,
``reference/mla_moe.py``) through the serve runner at a tiny size on the
CPU: one dense and two expert layers. Three configurations under
``mla_moe/``: ``routed`` (top-2 of 8, experts 2-5 held, served in
float32), ``bf16`` (served in bfloat16, every expert chosen and held)
and ``routed-bf16`` (the first, served in bfloat16 — the case that
matters on the chip: a near-tie at the router sends a token to another
expert than the float32 reference, and under a plain comparison that one
flip outweighs the fp8 control's error; the reference's alternates take
it out of the reading, and with ``TIE_MARGIN`` 0 the program fails its
limit as the control does). In each, the program's served tokens pass
the comparison and the fp8 control fails it; the engine's expert counters
reach the result's readers, and the cost functions give the
configuration file's own arithmetic."""
import contextlib
import io
import json
import math
import os
import shutil

import pytest

import tiny

ADDED = os.path.join(tiny.HERE, "mla_moe")


def _files(tmp_path, config):
    from chiplib import manifest

    data = str(tmp_path / "data")
    shutil.copytree(tiny.DATA, data)
    for kind, name in (("configs", config),
                       ("limits", config + "-backlog")):
        shutil.copy(os.path.join(ADDED, kind, name + ".json"),
                    os.path.join(data, kind, name + ".json"))
    man = json.loads(json.dumps(tiny.MANIFEST))
    man["configs"].append({"name": config,
                           "file": f"configs/{config}.json"})
    man["workloads"].append({"name": config + "-backlog", "config": config,
                             "traffic": "tiny-backlog", "chips": 1})
    return manifest.Files(root=data, data=data, manifest=man)


def _run(files, config, seed=7):
    import run as runner

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        result = runner.run_cell(config + "-backlog", seed, 1.5, 0,
                                 files=files, require_chip=False,
                                 control=True)
    return result, {ln["line"]: ln for ln in map(json.loads,
                                                 buf.getvalue().splitlines())}


def test_without_alternates_a_router_flip_fails_the_bf16_program(
        tmp_path, monkeypatch):
    """What the alternates are for: the same run compared plainly
    (``TIE_MARGIN`` 0: no expert is ever undecided) reads the program
    0.27 where it reads 0.005 with them, over the limit its control is
    held to — a flip's coin toss, not the arithmetic."""
    config = "tiny-mla-moe-routed-bf16"
    files = _files(tmp_path, config)
    ref = files.reference("mla_moe")
    ref.TIE_MARGIN = 0.0
    monkeypatch.setattr(files, "reference", lambda name: ref)
    result, lines = _run(files, config)
    gap = lines["compare"]["numbers"][0]
    assert result["correct"] is False and gap["value"] > 2 * gap["limit"]
    assert result["failed"] == 0


@pytest.mark.parametrize("config", ["tiny-mla-moe-routed",
                                    "tiny-mla-moe-bf16",
                                    "tiny-mla-moe-routed-bf16"])
def test_tiny_cell_passes_and_its_control_fails(tmp_path, config):
    files = _files(tmp_path, config)
    held = files.config(files.load(), config)["model"]["n_routed_experts"]
    result, lines = _run(files, config)
    cmp_ = lines["compare"]
    assert result["correct"] is True and result["failed"] == 0, cmp_
    assert cmp_["arch_file"].endswith("arch/mla_moe.py")
    assert cmp_["reference_file"].endswith("reference/mla_moe.py")
    gap = cmp_["numbers"][0]
    assert gap["name"] == "served_logit_gap"
    assert cmp_["served_tokens_compared"] > 0
    assert cmp_["control_gap"] > gap["limit"]
    assert cmp_["control_gap"] > 3 * max(gap["value"], 1e-3)
    # the family's counters are in the window's counters, where the two
    # counter readers find them
    c = lines["window"]["counters"]
    assert c["moe_expert_calls"] == 2 * (
        c["prefill_chunks"] + c["decode_steps"] + c["verify_steps"])
    assert 0 < c["moe_assignments_held"] <= c["moe_assignments"]
    assert (c["moe_assignments_held"] == c["moe_assignments"]) \
        == (held == 8)  # every expert held, or half of them
    from chiplib import manifest

    obs = {"job": "serve", "counters": c,
           "model": files.config(files.load(), config)["model"]}
    per_expert = manifest.metric_reader("moe_tokens_per_held_expert")(obs)
    uneven = manifest.metric_reader("expert_load_max_over_mean")(obs)
    assert per_expert == c["moe_assignments_held"] / (
        c["moe_expert_calls"] * held)
    assert 1.0 <= uneven <= held
    # a program without the counters (the parent): the readers give None
    obs["counters"] = {k: v for k, v in c.items()
                       if not k.startswith("moe_")}
    assert manifest.metric_reader("moe_tokens_per_held_expert")(obs) is None
    assert manifest.metric_reader("expert_load_max_over_mean")(obs) is None


def test_cost_functions_give_the_configurations_arithmetic():
    from chiplib import manifest

    files = manifest.Files()
    cfg = files.config(files.load(), "openpangu-ultra-moe-718b-ep16")
    arch = files.arch(cfg["arch"])
    m, layers = cfg["model"], cfg["num_hidden_layers"]["serve"]
    n = sum(math.prod(shape) for _, _, shape, _ in
            arch.leaf_specs(m, layers))
    assert round(n / 1e6) == 4919  # 9.84 GB in bfloat16
    assert arch.latent_bytes_per_token(m) == 1152
    embed = m["vocab_size"] * m["hidden_size"] * 2
    assert arch.weight_bytes(m, layers) == 2 * n - embed
    assert arch.decode_round_bytes(m, layers, 1000) \
        == arch.weight_bytes(m, layers) + 1000 * 5 * 1152
    # 512 pairs a layer call at 64 lanes hit 86-87% of 16 experts' ...
    pairs = 64 * 8 * 16 / 256
    flops, nbytes = arch.expert_mm_flops_bytes(m, 1, pairs)
    per_expert = 3 * 7680 * 2048 * 2
    assert flops == 2 * 3 * 7680 * 2048 * pairs
    assert 13.5 * per_expert < nbytes < 14.2 * per_expert
    flops, nbytes = arch.mla_attend_flops_bytes(m, 64, 64 * 1000, 64 * 1000)
    assert nbytes == 64 * 1000 * 1152 + 512 * 128 * 256 * 2
    assert flops == 2 * 128 * 512 * 256 * 64 + 2 * 128 * 1088 * 64000


def test_the_configuration_keeps_every_published_width():
    from chiplib import manifest

    import test_manifest

    files = manifest.Files()
    man = files.load()
    entry = [c for c in man["configs"]
             if c["name"] == "openpangu-ultra-moe-718b-ep16"][0]
    cfg = files.config(man, entry["name"])
    test_manifest.check_widths(entry, cfg)
    # the catalog's keys stand at the top level too, with `model`'s values
    for k, v in cfg["published"].items():
        if k != "num_hidden_layers":
            assert cfg[k] == cfg["model"][k], k
            assert v == cfg[k] or k in entry["reduced"], k
    assert cfg["num_hidden_layers"] == {"published": 61, "serve": 5}
    assert set(entry["reduced"]) == set(cfg["reduced"])


def test_the_cells_traffic_is_the_issues_in_one_order_for_every_seed():
    """``reason-backlog`` keeps what ISSUE 27 gives it, and the order of
    its cycle is the file's: the driver's check read ``serve_tok_s`` 2-3%
    apart from seed to seed while the order came from ``--seed`` (which
    prompts a 51 s window prefills, and how old its answers are, is
    work). ``--seed`` makes the token ids alone."""
    from chiplib import manifest, traffic

    mix = manifest.Files().traffic("reason-backlog")
    assert {k: mix[k] for k in ("loop", "cycle_requests", "ramp_s",
                                "min_waiting_per_lane", "traced_seconds",
                                "check_requests")} == {
        "loop": "backlog", "cycle_requests": 256, "ramp_s": 6,
        "min_waiting_per_lane": 2, "traced_seconds": 8, "check_requests": 6}
    (cls,) = mix["classes"]
    chat = manifest.Files().traffic("chat-backlog")["classes"][0]
    assert cls["new_tokens"] == chat["new_tokens"] \
        == [[0.0, 16], [0.5, 256], [0.9, 1024], [1.0, 2048]]
    assert cls["output_tokens"] \
        == [[0.0, 32], [0.5, 512], [0.9, 1024], [1.0, 1536]]
    assert cls["share"] == 1.0 and cls["turns"] == 1 \
        and not cls.get("shared_prefix_tokens")
    a, b = (traffic.schedule(mix, seed, 51, 19200)[0]
            for seed in (7, 2 ** 31 + 12345))
    assert [(r["prompt_len"], r["out"]) for r in a] \
        == [(r["prompt_len"], r["out"]) for r in b]
    assert not any((x["prompt"][-8:] == y["prompt"][-8:]).all()
                   for x, y in zip(a, b))
    free = traffic.schedule({k: v for k, v in mix.items()
                             if k != "order_seed"}, 7, 51, 19200)[0]
    assert sorted((r["prompt_len"], r["out"]) for r in free) \
        == sorted((r["prompt_len"], r["out"]) for r in a)
    assert [r["out"] for r in free] != [r["out"] for r in a]
