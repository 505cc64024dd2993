"""Persistent hardware-measurement records with provenance.

Every successful benchmark measurement taken on real hardware is appended
to ``PERF_MEASUREMENTS.json`` at the repo root *the moment it is taken*,
stamped with the git commit (where the tree is a git checkout — a copy
that is not simply carries no commit), timestamp, device kind and
backend. The perf guard (``tools/perf_guard.py``) reads its baselines
from here; no entry point splices an old record into a new line.

Reference analogue: the reference keeps its benchmark truth in CI-side
artifacts (``tools/ci_op_benchmark.sh`` gates against stored results); on
this side the store is a committed JSON file so provenance survives the
session.
"""
from __future__ import annotations

import json
import os
import subprocess
import tempfile
import time
from typing import Any, Dict, List, Optional

__all__ = ["measurements_path", "record", "record_or_warn",
           "record_rec_or_warn", "annotate_last", "last_good",
           "all_latest"]

_ENV_PATH = "PT_MEASUREMENTS_PATH"


class DirtyHeadlineRefused(RuntimeError):
    """Strict-mode refusal of a dirty-tree headline record. Deliberately
    NOT swallowed by record_or_warn: under PT_REFUSE_DIRTY_HEADLINE=1
    the operator asked for a hard stop, and silently dropping a real
    hardware number would be the worst of both worlds."""


def measurements_path() -> str:
    """Path of the persistent store (repo-root ``PERF_MEASUREMENTS.json``)."""
    override = os.environ.get(_ENV_PATH)
    if override:
        return override
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(here))
    return os.path.join(root, "PERF_MEASUREMENTS.json")


# metrics whose records are the repo's headline claims: a dirty-tree
# record for one of these pins a commit whose tree is NOT what ran, so
# it is loudly marked (`dirty_headline`) and stamped with a digest of
# the uncommitted diff so the exact tree is checkable; set
# PT_REFUSE_DIRTY_HEADLINE=1 to make it a hard error instead
# (round-4 verdict weak #5).
HEADLINE_METRICS = frozenset({
    "llama_train_tokens_per_sec_per_chip",
    "llama_longcontext_train_tokens_per_sec_per_chip",
    "llama_decode_tokens_per_sec_per_chip",
    "llama7b_geometry_tokens_per_sec_per_chip",
    "llama_train_loss_curve",
    "bert_base_mlm_tokens_per_sec_per_chip",
    "resnet50_train_imgs_per_sec_per_chip",
    "ernie_pretrain_tokens_per_sec_per_chip",
})


def _git_commit() -> Dict[str, Any]:
    # always stamp the commit of the code that measured, not of wherever
    # the store file happens to live
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(os.path.dirname(here))
    out: Dict[str, Any] = {}
    try:
        head = subprocess.run(
            ["git", "-C", root, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10)
        if head.returncode == 0:
            out["commit"] = head.stdout.strip()
        dirty = subprocess.run(
            ["git", "-C", root, "status", "--porcelain"],
            capture_output=True, text=True, timeout=10)
        if dirty.returncode == 0:
            out["dirty"] = bool(dirty.stdout.strip())
        if out.get("dirty"):
            # digest over the tracked diff + untracked file list: two
            # runs from the same dirty tree hash alike, any source
            # change changes the digest
            import hashlib

            diff = subprocess.run(
                ["git", "-C", root, "diff", "HEAD"],
                capture_output=True, text=True, timeout=30)
            h = hashlib.sha256()
            h.update(diff.stdout.encode())
            h.update(dirty.stdout.encode())
            out["diff_digest"] = h.hexdigest()[:12]
    except (OSError, subprocess.SubprocessError):
        # no git binary / not a repository (a chiprun copy is neither):
        # the record simply carries no commit
        pass
    return out


def _load() -> Dict[str, Any]:
    path = measurements_path()
    if not os.path.exists(path):
        return {"records": []}
    try:
        with open(path) as f:
            data = json.load(f)
        if isinstance(data, dict) and isinstance(data.get("records"), list):
            return data
    except (OSError, ValueError):
        pass
    return {"records": []}


def _atomic_write(data: Dict[str, Any]) -> None:
    path = measurements_path()
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               prefix=".perf_meas_", suffix=".json")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


class _StoreLock:
    """fcntl lock on a sidecar file: concurrent benches (hwbench during a
    round + the driver's bench.py at round end) must not drop each other's
    records in the read-modify-write."""

    def __init__(self, path: str):
        self._path = path + ".lock"
        self._fd: Optional[int] = None

    def __enter__(self):
        try:
            import fcntl

            self._fd = os.open(self._path, os.O_CREAT | os.O_RDWR)
            fcntl.flock(self._fd, fcntl.LOCK_EX)
        except Exception:  # noqa: BLE001 — lock is protection, not a gate
            if self._fd is not None:
                os.close(self._fd)
                self._fd = None
        return self

    def __exit__(self, *exc):
        if self._fd is not None:
            try:
                import fcntl

                fcntl.flock(self._fd, fcntl.LOCK_UN)
            finally:
                os.close(self._fd)
                self._fd = None
        return False


def record(metric: str, value: float, unit: str, *,
           backend: Optional[str] = None,
           device: Optional[str] = None,
           extra: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Append one measurement with provenance; returns the stored record.

    ``backend``/``device`` default to the live jax backend and device kind;
    pass them explicitly to avoid re-touching a flaky backend after the
    measurement is already in hand.
    """
    if backend is None or device is None:
        try:
            import jax

            backend = backend or jax.default_backend()
            device = device or getattr(
                jax.devices()[0], "device_kind", backend)
        except Exception:  # noqa: BLE001
            backend = backend or "unknown"
            device = device or "unknown"
    rec: Dict[str, Any] = {
        "metric": metric,
        "value": value,
        "unit": unit,
        "backend": backend,
        "device": device,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    rec.update(_git_commit())
    if rec.get("dirty") and metric in HEADLINE_METRICS and _is_hw(rec):
        if os.environ.get("PT_REFUSE_DIRTY_HEADLINE") == "1":
            raise DirtyHeadlineRefused(
                f"refusing dirty-tree record for headline metric "
                f"{metric!r}: commit the tree first. The store's "
                f"contract is that a headline record's commit is the "
                f"tree that ran.")
        # default: record, but loudly marked + digest-stamped (a hard
        # refusal could drop a real hardware number when the driver
        # benches an end-of-round uncommitted tree)
        import sys

        rec["dirty_headline"] = True
        print(f"measurements: DIRTY-TREE headline record for {metric} "
              f"(diff_digest={rec.get('diff_digest')}) — re-measure on "
              f"a clean tree for a publishable number",
              file=sys.stderr, flush=True)
    if extra:
        rec["extra"] = extra
    with _StoreLock(measurements_path()):
        data = _load()
        data["records"].append(rec)
        _atomic_write(data)
    return rec


def record_or_warn(metric: str, value: float, unit: str,
                   **kw) -> Optional[Dict[str, Any]]:
    """`record`, but an unwritable store must never crash a bench after a
    successful hardware measurement — warn on stderr and carry on."""
    import sys

    try:
        return record(metric, value, unit, **kw)
    except DirtyHeadlineRefused:
        raise  # strict mode asked for a hard stop
    except Exception as e:  # noqa: BLE001 — persistence is best-effort
        print(f"measurements: persist failed for {metric}: {e}",
              file=sys.stderr, flush=True)
        return None


def record_rec_or_warn(rec: Dict[str, Any], **kw) -> Optional[Dict[str, Any]]:
    """Persist a bench's one-line JSON dict: metric/value/unit become the
    record head, every other key lands in ``extra``. Keeps the persist
    contract in one place for all benchmark scripts."""
    extra = {k: v for k, v in rec.items()
             if k not in ("metric", "value", "unit")}
    return record_or_warn(rec["metric"], rec["value"], rec["unit"],
                          extra=extra or None, **kw)


def annotate_last(metric: str, extra_updates: Dict[str, Any],
                  value: Optional[float] = None) -> bool:
    """Merge ``extra_updates`` into the MOST RECENT record for ``metric``
    (optionally matching ``value`` so only the run's own record is
    touched). How benches back-fill expensive statistics — e.g. XLA's
    executable memory accounting, which is only computed AFTER
    the throughput record was persisted (records land the moment the
    number exists; the peak-HBM baseline must still end up on them or
    the perf guard's HBM gate can never fire). Returns True when a
    record was updated."""
    with _StoreLock(measurements_path()):
        data = _load()
        for rec in reversed(data["records"]):
            if rec.get("metric") != metric:
                continue
            if value is not None and rec.get("value") != value:
                continue
            ex = rec.get("extra") or {}
            ex.update(extra_updates)
            rec["extra"] = ex
            _atomic_write(data)
            return True
    return False


def _is_hw(rec: Dict[str, Any]) -> bool:
    return rec.get("backend") not in (None, "cpu", "unknown")


def last_good(metric: str,
              match: Optional[Dict[str, Any]] = None
              ) -> Optional[Dict[str, Any]]:
    """Most recent real-hardware record for ``metric`` (None if none).

    ``match`` filters on extra fields — e.g. ``{"batch": 8, "seq": 1024}``
    skips over sweep points at other configs instead of returning them.
    A key ABSENT from a record's extra is a wildcard, not a mismatch:
    records persisted before a config knob existed must stay eligible
    baselines (same rule as ``tools/perf_guard.py:last_good``, this
    function's stdlib twin — keep the two in lockstep)."""
    for rec in reversed(_load()["records"]):
        if rec.get("metric") != metric or not _is_hw(rec):
            continue
        ex = rec.get("extra") or {}
        if match and any(k in ex and ex[k] != v
                         for k, v in match.items()):
            continue
        return rec
    return None


def all_latest(hardware_only: bool = True) -> Dict[str, Dict[str, Any]]:
    """Latest record per metric (hardware-backed only by default)."""
    out: Dict[str, Dict[str, Any]] = {}
    for rec in _load()["records"]:
        if hardware_only and not _is_hw(rec):
            continue
        out[rec["metric"]] = rec
    return out
