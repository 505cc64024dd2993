"""An architecture is added by files and entries only: an adapter, a
reference, a configuration, a cell's limits, one ``configs`` and one
``workloads`` entry — and no file that exists is written. The two
examples under ``added_arch/`` run through the two runners on the CPU:
their leaves differ from ``llama_dense``'s in name, count and rank
(stacked experts, a router), or their reference in its mask."""
import contextlib
import hashlib
import io
import json
import os
import shutil

import pytest

import tiny

ADDED = os.path.join(tiny.HERE, "added_arch")
CASES = {
    # the train runner on LlamaConfig(moe_num_experts=4): rank-3 leaves
    "moe-train": {"config": "tiny-moe", "arch": "llama_moe",
                  "cell": "tiny-moe-train", "traffic": "tiny-train"},
    # the serve runner on a non-zero sliding_window: its own mask
    "window-serve": {"config": "tiny-window", "arch": "llama_window",
                     "cell": "tiny-window-backlog",
                     "traffic": "tiny-backlog"},
}


def digests(root):
    out = {}
    for d, _, names in os.walk(root):
        for n in names:
            p = os.path.join(d, n)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def add(case, data):
    """Copies the case's files into ``data`` — each to a path that does
    not exist yet — and returns the manifest with its two entries."""
    for kind, name, ext in (("arch", case["arch"], ".py"),
                            ("reference", case["arch"], ".py"),
                            ("configs", case["config"], ".json"),
                            ("limits", case["cell"], ".json")):
        dst = os.path.join(data, kind, name + ext)
        assert not os.path.exists(dst), dst
        os.makedirs(os.path.dirname(dst), exist_ok=True)
        shutil.copy(os.path.join(ADDED, kind, name + ext), dst)
    man = json.loads(json.dumps(tiny.MANIFEST))
    man["configs"].append({"name": case["config"],
                           "file": f"configs/{case['config']}.json"})
    man["workloads"].append({"name": case["cell"], "config": case["config"],
                             "traffic": case["traffic"], "chips": 1})
    return man


@pytest.mark.parametrize("case", sorted(CASES))
def test_an_architecture_is_added_by_files_and_one_entry_only(tmp_path,
                                                              case):
    import run as runner
    from chiplib import manifest

    case = CASES[case]
    data = str(tmp_path / "data")
    shutil.copytree(tiny.DATA, data)
    before = {"data": digests(data), "chip": digests(os.path.dirname(
        tiny.HERE))}
    man = add(case, data)
    files = manifest.Files(root=data, data=data, manifest=man)
    specs = files.arch(case["arch"]).leaf_specs(
        files.config(man, case["config"])["model"], 2)
    dense = manifest.Files().arch("llama_dense").leaf_specs(
        files.config(man, "tiny-llama")["model"], 2)
    if case["arch"] == "llama_moe":
        assert len(specs) != len(dense)
        assert {n for _, n, _, _ in specs} - {n for _, n, _, _ in dense} \
            == {"router", "w_in", "w_out"}
        assert {len(s) for _, _, s, _ in specs} == {1, 2, 3}

    tiny.interpret_flash()
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        result = runner.run_cell(case["cell"], 7, 1.5, 0, files=files,
                                 require_chip=False, control=True)
    lines = {ln["line"]: ln for ln in map(json.loads,
                                          buf.getvalue().splitlines())}
    cmp_ = lines["compare"]
    assert result["correct"] is True and result["failed"] == 0, cmp_
    assert cmp_["arch_file"] == f"arch/{case['arch']}.py"
    assert cmp_["reference_file"] == f"reference/{case['arch']}.py"
    assert set(result["compared"]) == {r["name"] for r in cmp_["numbers"]}
    # the fp8 control fails the comparison that the program passes
    if case["traffic"] == "tiny-train":
        ctrl = {r["name"]: r for r in lines["control"]["numbers"]}
        prog = {r["name"]: r for r in cmp_["numbers"]}
        assert not ctrl["grad_norm_rel_gap"]["ok"]
        assert ctrl["grad_norm_rel_gap"]["value"] > \
            3 * prog["grad_norm_rel_gap"]["value"]
    else:
        gap = cmp_["numbers"][0]
        assert gap["name"] == "served_logit_gap"
        assert cmp_["served_tokens_compared"] > 0
        assert cmp_["control_gap"] > gap["limit"]
        assert cmp_["control_gap"] > 3 * max(gap["value"], 1e-3)
    # nothing that was there was written: every old file reads as before
    after = digests(data)
    assert {k: after[k] for k in before["data"]} == before["data"]
    assert digests(os.path.dirname(tiny.HERE)) == before["chip"]
