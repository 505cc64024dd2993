"""Persistent hardware-measurement store (utils/measurements.py)."""
import json
import os

import pytest

from paddle_tpu.utils import measurements as meas


@pytest.fixture()
def store(tmp_path, monkeypatch):
    path = str(tmp_path / "PERF_MEASUREMENTS.json")
    monkeypatch.setenv("PT_MEASUREMENTS_PATH", path)
    return path


def test_record_stamps_provenance(store):
    rec = meas.record("m1", 123.4, "tok/s", backend="tpu",
                      device="TPU v5 lite", extra={"mfu": 0.6})
    assert rec["metric"] == "m1" and rec["value"] == 123.4
    assert rec["backend"] == "tpu" and rec["device"] == "TPU v5 lite"
    assert "timestamp" in rec
    # provenance lands on disk, atomically, as valid json
    with open(store) as f:
        data = json.load(f)
    assert data["records"][-1]["extra"] == {"mfu": 0.6}
    # the repo is a git checkout, so commit provenance must be present
    assert "commit" in data["records"][-1]


def test_last_good_skips_cpu_records(store):
    meas.record("m1", 1.0, "tok/s", backend="tpu", device="TPU v5 lite")
    meas.record("m1", 2.0, "tok/s", backend="cpu", device="cpu")
    lg = meas.last_good("m1")
    assert lg is not None and lg["value"] == 1.0 and lg["backend"] == "tpu"
    assert meas.last_good("missing") is None


def test_last_good_returns_most_recent_hw(store):
    meas.record("m1", 1.0, "tok/s", backend="tpu", device="d")
    meas.record("m1", 3.0, "tok/s", backend="tpu", device="d")
    assert meas.last_good("m1")["value"] == 3.0


def test_all_latest(store):
    meas.record("a", 1.0, "u", backend="tpu", device="d")
    meas.record("b", 2.0, "u", backend="cpu", device="cpu")
    meas.record("a", 5.0, "u", backend="tpu", device="d")
    latest = meas.all_latest()
    assert latest["a"]["value"] == 5.0 and "b" not in latest
    latest_all = meas.all_latest(hardware_only=False)
    assert latest_all["b"]["value"] == 2.0


def test_corrupt_store_recovers(store):
    with open(store, "w") as f:
        f.write("{not json")
    meas.record("m", 1.0, "u", backend="tpu", device="d")
    assert meas.last_good("m")["value"] == 1.0


def test_bench_emits_last_good_inline(store, monkeypatch):
    """The last-good TPU record (the perf guard's baseline) keeps its
    provenance: device kind and the extras it was recorded with."""
    meas.record("llama_train_tokens_per_sec_per_chip", 39595.0, "tokens/s",
                backend="tpu", device="TPU v5 lite",
                extra={"mfu": 0.574, "vs_baseline": 1.2756})
    lg = meas.last_good("llama_train_tokens_per_sec_per_chip")
    assert lg["extra"]["mfu"] == 0.574
    assert lg["device"] == "TPU v5 lite"


def test_dirty_headline_marked_and_digest(tmp_path, monkeypatch):
    from paddle_tpu.utils import measurements as m

    monkeypatch.setenv("PT_MEASUREMENTS_PATH", str(tmp_path / "s.json"))
    monkeypatch.setattr(m, "_git_commit", lambda: {
        "commit": "abc123", "dirty": True, "diff_digest": "deadbeefcafe"})
    rec = m.record("llama_train_tokens_per_sec_per_chip", 1.0, "tokens/s",
                   backend="tpu", device="TPU v5 lite")
    assert rec["dirty_headline"] is True
    assert rec["diff_digest"] == "deadbeefcafe"
    # non-headline dirty records are stored without the loud mark
    rec2 = m.record("some_micro_metric", 2.0, "s", backend="tpu",
                    device="TPU v5 lite")
    assert "dirty_headline" not in rec2
    # cpu records never headline-mark
    rec3 = m.record("llama_train_tokens_per_sec_per_chip", 1.0,
                    "tokens/s", backend="cpu", device="cpu")
    assert "dirty_headline" not in rec3


def test_dirty_headline_refused_in_strict_mode(tmp_path, monkeypatch):
    import pytest

    from paddle_tpu.utils import measurements as m

    monkeypatch.setenv("PT_MEASUREMENTS_PATH", str(tmp_path / "s.json"))
    monkeypatch.setenv("PT_REFUSE_DIRTY_HEADLINE", "1")
    monkeypatch.setattr(m, "_git_commit", lambda: {
        "commit": "abc123", "dirty": True, "diff_digest": "deadbeefcafe"})
    with pytest.raises(RuntimeError, match="refusing dirty-tree"):
        m.record("llama_train_tokens_per_sec_per_chip", 1.0, "tokens/s",
                 backend="tpu", device="TPU v5 lite")


def test_diff_digest_real_git_when_dirty(monkeypatch, tmp_path):
    # live _git_commit: digest present iff dirty
    from paddle_tpu.utils import measurements as m

    out = m._git_commit()
    if out.get("dirty"):
        assert len(out.get("diff_digest", "")) == 12
    else:
        assert "diff_digest" not in out


def test_annotate_last_backfills_extra(store):
    """bench.py back-fills peak_hbm_gib onto its already-persisted record
    (XLA's executable memory accounting is only computed after
    the record landed — the perf guard's HBM gate reads it from the
    baseline's extra)."""
    meas.record("m1", 100.0, "tok/s", backend="tpu", device="d",
                extra={"mfu": 0.5})
    meas.record("m1", 200.0, "tok/s", backend="tpu", device="d",
                extra={"mfu": 0.6})
    assert meas.annotate_last("m1", {"peak_hbm_gib": 11.3}, value=200.0)
    recs = json.load(open(store))["records"]
    assert recs[-1]["extra"] == {"mfu": 0.6, "peak_hbm_gib": 11.3}
    assert "peak_hbm_gib" not in recs[-2]["extra"]  # only the match
    # value mismatch / unknown metric: no write, False
    assert not meas.annotate_last("m1", {"x": 1}, value=999.0)
    assert not meas.annotate_last("nope", {"x": 1})
    # extra-less record gains one
    meas.record("m2", 1.0, "s", backend="tpu", device="d")
    assert meas.annotate_last("m2", {"peak_hbm_gib": 2.0})
    assert json.load(open(store))["records"][-1]["extra"] == {
        "peak_hbm_gib": 2.0}
