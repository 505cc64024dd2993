"""BENCHMARK.json against the contract's schema, and every entry resolved
to its files by name."""
import json
import os
import re

import pytest

from chiplib import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what a reference must have, by the kind of the cell's traffic
PATH_NEEDS = {"train": ("train_step",),
              "serve": ("layer_forward", "head_logits")}


@pytest.fixture(scope="module")
def man():
    return manifest.Files().load()


def test_top_level_keys(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51 and isinstance(
        man["run_seconds"], int)
    assert 1 <= len(man["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in man["paths"])
    assert len(man["command"]) <= 32
    size = os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_full_check_fits_with_24_cells(man):
    cells = 24
    total = ((2 + 14 * cells) * (man["run_seconds"] + 60)
             + cells * 2 * 90 + 1200)
    assert total <= 43200, total


def test_names_units_and_keys(man):
    seen = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic",
                                       "chips", "why"})):
        for e in man[group]:
            assert set(e) == keys, (group, e)
            assert NAME.match(e["name"]), e["name"]
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for w in man["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}, m
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}, m
        assert m["source"] in SOURCES
        assert 1 <= len(m["layer"]) <= 200
    for m in man["end_to_end"] + man["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
    assert "setup_s" in seen
    pairs = [(w["config"], w["traffic"]) for w in man["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in man["workloads"])
    assert four <= max(1, len(man["workloads"]) // 4)


def test_roofline_and_moves(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = {w["name"] for w in man["workloads"]}
    for m in man["per_layer"]:
        assert m["moves"] in e2e, m
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        for w in m.get("workloads", []):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells), (m, w)


def test_every_cell_reports_enough(man):
    for w in man["workloads"]:
        e = manifest.metrics_for(man, w["name"], "end_to_end")
        p = manifest.metrics_for(man, w["name"], "per_layer")
        assert "setup_s" in e and len(e) >= 2 and len(p) >= 1, w["name"]


def test_every_entry_resolves_to_files_by_name(man):
    files = manifest.Files()
    used = {w["config"] for w in man["workloads"]}
    assert used == {c["name"] for c in man["configs"]}
    for c in man["configs"]:
        assert any(c["file"].startswith(p + "/") for p in man["paths"])
        cfg = files.config(man, c["name"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        # the reference's file is named where a reader can find it
        assert cfg["reference_file"] == os.path.relpath(
            files.path("reference", cfg["reference"]), manifest.ROOT)
        assert any(cfg["reference_file"].startswith(p + "/")
                   for p in man["paths"])
        arch = files.arch(cfg["arch"])
        for fn in ("build_model", "param_name", "leaf_specs"):
            assert callable(getattr(arch, fn)), (cfg["arch"], fn)
    for w in man["workloads"]:
        kind = files.traffic(w["traffic"])["kind"]
        assert files.limits(w["name"])
        # a reference needs only the functions of the paths its
        # configuration's cells use
        cfg = files.config(man, w["config"])
        ref = files.reference(cfg["reference"])
        for fn in PATH_NEEDS[kind]:
            assert callable(getattr(ref, fn)), (cfg["reference"], fn)
    for m in man["end_to_end"] + man["per_layer"]:
        if m["name"] != "setup_s":
            assert callable(manifest.metric_reader(m["name"]))


WIDTHS = ("hidden_size", "intermediate", "latent", "state_size", "head_dim",
          "_dim", "_rank", "experts_per_tok", "head_size", "expansion",
          "proj")


def check_widths(entry, cfg):
    """No width is in ``reduced``, and every key that ``model`` (what the
    chip runs) shares with the configuration's OWN ``published`` block
    (the source's config.json) is equal unless ``reduced`` lists it;
    what ``model`` adds to the published keys is listed in ``assumed``."""
    assert len(entry["reduced"]) <= 16
    for k in entry["reduced"]:
        assert not any(w in k for w in WIDTHS), k
    pub, m = cfg["published"], cfg["model"]
    for k, v in m.items():
        if k in pub:
            assert v == pub[k] or k in entry["reduced"], (k, v, pub[k])
        else:
            assert k in cfg["assumed"], k
    depth = cfg["num_hidden_layers"]
    assert depth["published"] == pub["num_hidden_layers"]
    assert all(v == depth["published"] for v in depth.values()) \
        or "num_hidden_layers" in entry["reduced"]


def test_no_width_is_reduced(man):
    for c in man["configs"]:
        check_widths(c, manifest.Files().config(man, c["name"]))


def test_a_changed_width_is_caught():
    cfg = {"published": {"hidden_size": 8, "num_hidden_layers": 4},
           "model": {"hidden_size": 8}, "assumed": {},
           "num_hidden_layers": {"published": 4, "serve": 2}}
    check_widths({"reduced": ["num_hidden_layers"]}, cfg)
    with pytest.raises(AssertionError):
        check_widths({"reduced": []}, cfg)  # a cut depth not listed
    cfg["model"]["hidden_size"] = 4
    with pytest.raises(AssertionError):
        check_widths({"reduced": ["num_hidden_layers"]}, cfg)
    with pytest.raises(AssertionError):  # and a width may not be listed
        check_widths({"reduced": ["num_hidden_layers", "hidden_size"]}, cfg)


def test_a_cell_is_added_by_files_and_one_entry_only(tmp_path):
    """The README's worked example: a new traffic mix and a cell on it,
    with nothing edited — one data file, one limits file, one entry."""
    import shutil

    import tiny

    data = tmp_path / "data"
    shutil.copytree(tiny.DATA, data)
    mix = json.load(open(data / "traffic" / "tiny-backlog.json"))
    mix["name"] = "tiny-short-only"
    mix["classes"][0]["new_tokens"] = [[0.0, 4], [1.0, 16]]
    json.dump(mix, open(data / "traffic" / "tiny-short-only.json", "w"))
    shutil.copy(data / "limits" / "tiny-backlog.json",
                data / "limits" / "tiny-short.json")
    man = json.loads(json.dumps(tiny.MANIFEST))
    man["workloads"].append({"name": "tiny-short", "config": "tiny-llama",
                             "traffic": "tiny-short-only", "chips": 1})
    files = manifest.Files(root=str(data), data=str(data), manifest=man)
    cell = manifest.cell(files.load(), "tiny-short")
    assert files.traffic(cell["traffic"])["classes"][0]["new_tokens"][1] \
        == [1.0, 16]
    assert files.limits("tiny-short") and files.config(man, cell["config"])
