"""The prefill call's width is the model's FAMILY's (PERF.md section 6, PR
42): an engine built without ``prefill_chunk`` takes its family's
``prefill_chunk`` — 512 for the window-attention and the short-convolution
families, whose call reads every held expert whatever its width — or the
engine's ``PREFILL_CHUNK`` (128) for a family that names none, fitted to
whole blocks under ``max_seq_len`` either way; a width given in
``ServingConfig`` or ``PT_SERVE_PREFILL_CHUNK`` wins, taken as given. And
a prompt fed at the family's 512 — one full call, one part-padded — serves
the tokens a narrow engine serves."""
import numpy as np
import pytest

import test_program_scopes as PS
from paddle_tpu.serving import ServingConfig, ServingEngine
from paddle_tpu.serving.engine import PREFILL_CHUNK

WIDTH = {"dense_gqa": PREFILL_CHUNK, "latent_moe": PREFILL_CHUNK,
         "hybrid_ssm": PREFILL_CHUNK, "linear_latent_moe": PREFILL_CHUNK,
         "window_moe": 512, "conv_moe": 512}


def _model(family):
    model, geom = PS.FAMILIES[family]()
    model.eval()
    return model, {**geom, "num_blocks": None, "prefill_chunk": None}


@pytest.mark.parametrize("family", sorted(PS.FAMILIES))
def test_an_engines_default_width_is_its_familys(family, monkeypatch):
    monkeypatch.delenv("PT_SERVE_PREFILL_CHUNK", raising=False)
    model, geom = _model(family)
    own = WIDTH[family]
    assert PREFILL_CHUNK == 128

    def width(**kw):
        eng = ServingEngine(model, ServingConfig(**{**geom, **kw}))
        assert eng._family.name == family
        assert getattr(eng._family, "prefill_chunk", PREFILL_CHUNK) == own
        assert eng.stats()["prefill_chunk"] == eng.prefill_chunk
        return eng.prefill_chunk

    # the family's width as it is, then fitted: whole blocks, never past
    # max_seq_len rounded down to whole blocks, at least one block
    assert width(block_size=16, max_seq_len=4096) == own
    assert width(block_size=16, max_seq_len=300) == min(own, 288)
    assert width(block_size=48, max_seq_len=4096) == own // 48 * 48
    assert width(block_size=4, max_seq_len=50) == 48
    assert width(block_size=16, max_seq_len=10) == 16
    # a width that is given wins, taken as given: the argument, the
    # environment, and the argument over the environment
    assert width(block_size=16, max_seq_len=4096, prefill_chunk=24) == 24
    monkeypatch.setenv("PT_SERVE_PREFILL_CHUNK", "40")
    assert width(block_size=16, max_seq_len=4096,
                 prefill_chunk=ServingConfig().prefill_chunk) == 40
    assert width(block_size=16, max_seq_len=4096, prefill_chunk=200) == 200


@pytest.mark.parametrize("family", ["window_moe", "conv_moe"])
def test_a_prompt_fed_at_the_familys_width_serves_the_narrow_engines_tokens(
        family, monkeypatch):
    """530 tokens through an engine left to its family's width: one FULL
    call of 512 positions (a window layer's band a window of queries at a
    time over a ring many times shorter; a conv tail carried into the
    next call) and one padded call of 18 real tokens, against the same
    prompt fed 32 positions a call."""
    monkeypatch.delenv("PT_SERVE_PREFILL_CHUNK", raising=False)
    model, geom = _model(family)
    geom.update(block_size=16, max_seq_len=576, max_lanes=2, spec=False)
    prompt = np.random.default_rng(42).integers(0, 256, 530).astype(np.int32)
    outs = {}
    for chunk in (None, 32):
        eng = ServingEngine(model, ServingConfig(**{**geom,
                                                    "prefill_chunk": chunk}))
        req = eng.submit(prompt, max_new_tokens=6)
        eng.run()
        outs[chunk] = list(req.output)
        st = eng.stats()
        width = chunk or 512
        assert st["prefill_chunk"] == width
        assert st["prefill_chunks"] == -(-530 // width)
        assert st["prefill_fed_tokens"] == st["prefill_chunks"] * width
        assert st["prefix_miss_tokens"] == 530
    assert len(outs[None]) == 6 and outs[None] == outs[32]


CONV_CELL = "tiny-conv-gqa-moe-bf16-backlog"


@pytest.fixture
def replay_tool(tmp_path, monkeypatch):
    """(``tools/replay_prefill_width.py``, the benchmark's tiny
    short-convolution cell as a ``chiplib.manifest.Files``)."""
    import json
    import os
    import shutil

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    chip = os.path.join(root, "benchmarks", "chip")
    for p in (os.path.join(root, "tools"), os.path.join(chip, "tests"), chip):
        monkeypatch.syspath_prepend(p)
    import replay_prefill_width as replay
    import tiny
    from chiplib import manifest, weights

    monkeypatch.delenv("PT_SERVE_PREFILL_CHUNK", raising=False)
    # (why 0.1: benchmarks/chip/tests/test_conv_gqa_moe.py's docstring)
    monkeypatch.setattr(weights, "STD", 0.1)
    config = CONV_CELL[:-len("-backlog")]
    data = str(tmp_path / "data")
    shutil.copytree(tiny.DATA, data)
    for kind, name in (("configs", config), ("limits", CONV_CELL)):
        shutil.copy(os.path.join(tiny.HERE, "conv_gqa_moe", kind,
                                 name + ".json"),
                    os.path.join(data, kind, name + ".json"))
    man = json.loads(json.dumps(tiny.MANIFEST))
    man["configs"].append({"name": config, "file": f"configs/{config}.json"})
    man["workloads"].append({"name": CONV_CELL, "config": config,
                             "traffic": "tiny-backlog", "chips": 1})
    return replay, manifest.Files(root=data, data=data, manifest=man)


def test_the_replay_tool_says_where_two_widths_differ(replay_tool, tmp_path):
    """``tools/replay_prefill_width.py`` (what answered REVIEW.md's question
    on the conv cell's widest gap, on the chip) at the benchmark's tiny
    short-convolution configuration on the CPU: one request served alone
    at 16 and at 64 positions a call, the router's choices and the logits
    of the two prefill programs compared, the reference's reading of
    both."""
    import json

    replay, files = replay_tool
    out = str(tmp_path / "lines.jsonl")
    said = replay.replay(files, CONV_CELL, 7, [16, 64], serial=11, out=out)
    lines = [json.loads(ln) for ln in open(out)]
    assert [ln["line"] for ln in lines] == [
        "request", "served", "forced", "served", "forced", "widths",
        "reference", "reference"]
    n, n_out = lines[0]["prompt_len"], lines[0]["out"]
    assert lines[0]["serial"] == 11 and n > 64
    for w in (16, 64):
        assert said["served"][w]["prefill_chunks"] == -(-n // w)
        # the program fed the served tokens chooses them again, nearly
        # everywhere (a round's arithmetic is not a chunk's)
        assert said["forced"][w]["positions"] == n + n_out - 1
        assert said["forced"][w]["first_choice_is_not_the_served_token"] \
            <= n_out // 10
        assert said["reference"][w]["n"] == n_out
        assert said["reference"][w]["widest"] <= said["reference"][w]["limit"]
    both = said["widths"][64]
    assert both["against"] == 16
    assert both["of"] == 4 * (n + n_out - 1)  # expert layers x positions
    # two widths round alike: few choices flip, each between two scores
    # far closer than scores mostly are, and the logits stay close
    assert both["route_flips"] <= both["of"] // 50
    assert all(m < both["margin_p50_everywhere"] / 4
               for _, _, m in both["first_flips"])
    assert both["widest_logit_difference"] < 0.1


def test_the_replay_tool_says_which_sampled_request_read_widest(replay_tool):
    """Its ``--window``: one run of the cell as the benchmark makes it, in
    which every sampled request says which submitted prompt it is and
    where the reference's widest gap of it lies; the run's ``compared``
    number is the widest of them."""
    replay, files = replay_tool
    result, sampled = replay.window(files, CONV_CELL, 7, 1.5,
                                    require_chip=False)
    assert result["correct"] and len(sampled) > 1
    assert sampled[0]["widest"] \
        == result["compared"]["served_logit_gap"]["value"]
    assert [s["widest"] for s in sampled] \
        == sorted((s["widest"] for s in sampled), reverse=True)
    serials = [s["serial"] for s in sampled]
    assert None not in serials and len(set(serials)) == len(serials)
    for s in sampled:
        assert s["n"] == s["served"] > 0
        assert s["prompt_len"] <= s["widest_at_position"] \
            < s["prompt_len"] + s["served"]
