"""Measurement-driven kernel search harness (ROADMAP item 3).

``autotune.py`` tunes ONE family (flash block sizes). This module is the
general harness grown out of it, in the spirit of automatic kernel
generation (PAPERS.md: 2006.12645) and learned tuning (CUDA-L2,
2512.02551), at Pallas scale:

- **Declarative candidate spaces**: each kernel family registers a
  :class:`KernelFamily` describing its search shapes, its candidate
  configurations (block sizes, grid layouts, variant flags — with
  family-owned pruning, e.g. a VMEM-budget bound), how to build a
  runnable kernel for a (shape, config) pair, and the XLA-composite
  baseline it must beat.
- **Mandatory parity pre-filter**: every candidate runs in CPU
  interpret mode against the composite BEFORE it is ever timed — a
  config that cannot reproduce the math is rejected, never measured
  (``search/rejects``), so a fast-but-wrong tiling cannot win.
- **The timing discipline**: candidates are timed with
  ``autotune._time_compiled`` — two compiled fori_loops of different
  lengths with a REAL data dependence, difference-divided so the
  constant dispatch + fence overhead cancels.
- **One persisted tune table** (``kernel_tune.json`` next to this
  module — TRACKED, and empty until a hardware search writes a row:
  which kernel runs may depend only on files git would commit):
  per-family namespaces, device + commit provenance on every
  row, fcntl-locked read-modify-write with atomic tmp/rename
  (``utils/measurements.py`` discipline — the old ``flash_tune.json``
  writer could tear under concurrent hwbench/autotune writers).
  Legacy ``flash_tune.json`` entries are migrated in through a
  one-shot loader fallback (:func:`load_table` merges them under the
  ``flash`` namespace).
- **Engagement = measured-faster-than-composite only**: a kernel
  engages for a shape exactly when a HARDWARE row at that exact key
  says ratio > 1.0 (CPU/interpret rows never engage — their
  wall-clock is meaningless). No row → the caller's default path.

Monitor contract: this module carries a ``_monitor`` None-slot
(``pallas/engaged``, ``pallas/fallback_composite``, ``search/*`` —
``monitor.INSTRUMENTED_MODULES``); when monitoring is off no monitor
callable is ever invoked.
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from typing import Any, Dict, Iterable, List, Optional

__all__ = [
    "KernelFamily", "register_family", "FAMILIES",
    "table_path", "load_table", "save_table", "update_table",
    "family_entries", "lookup", "best_config", "engaged", "decide",
    "search_family", "search_shape",
]

_ENV_PATH = "PT_KERNEL_TUNE_PATH"

# telemetry slot (paddle_tpu.monitor None-slot contract): None unless
# PT_MONITOR wired it
_monitor = None

FAMILIES: Dict[str, "KernelFamily"] = {}


def register_family(family: "KernelFamily") -> "KernelFamily":
    """Register a kernel family under ``family.name`` (idempotent by
    name: re-import replaces)."""
    FAMILIES[family.name] = family
    return family


class KernelFamily:
    """One searchable kernel family. Subclasses declare the candidate
    space and how to build/verify/compare; the harness owns enumeration,
    the parity pre-filter, timing, and persistence."""

    #: tune-table namespace + monitor label
    name = "family"
    #: time fwd+bwd (training kernels) rather than fwd only (decode)
    grad = False
    #: interpret-mode parity tolerance vs the composite (fp32 inputs)
    parity_atol = 2e-5

    def shapes(self) -> List[Any]:
        """The standard search shapes (hardware run)."""
        return []

    def smoke_shapes(self) -> List[Any]:
        """Tiny shapes for the CPU interpret-mode smoke pipeline."""
        return self.shapes()

    def key(self, shape) -> str:
        """Tune-table key for ``shape`` — exact-match engagement rides
        on it, so it must encode every engagement-relevant parameter."""
        raise NotImplementedError

    def shape_info(self, shape) -> Dict[str, Any]:
        """Human-readable shape fields for the persisted row."""
        return {"shape": list(shape) if isinstance(shape, tuple)
                else shape}

    def candidates(self, shape) -> Iterable[Dict[str, Any]]:
        """Candidate configurations for ``shape`` (already pruned by
        family-owned feasibility rules, e.g. VMEM budget)."""
        raise NotImplementedError

    def make_inputs(self, shape):
        """Deterministic input arrays for parity + timing."""
        raise NotImplementedError

    def build(self, shape, config, interpret: bool):
        """A callable ``fn(*make_inputs(shape))`` running the kernel at
        ``config``."""
        raise NotImplementedError

    def build_composite(self, shape):
        """The XLA-composite baseline ``fn(*make_inputs(shape))`` the
        family must measure faster than to engage."""
        raise NotImplementedError


# -- unified tune table -------------------------------------------------------

_table_cache: Optional[Dict[str, Any]] = None


def table_path() -> str:
    override = os.environ.get(_ENV_PATH)
    if override:
        return override
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "kernel_tune.json")


def _store_lock(path: str):
    """The fcntl sidecar lock from utils/measurements.py — one
    discipline for every persisted measurement artifact."""
    from ...utils.measurements import _StoreLock

    return _StoreLock(path)


def _read_disk(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            data = json.load(f)
        if isinstance(data, dict) and isinstance(data.get("families"),
                                                 dict):
            return data
    except (OSError, ValueError):
        pass
    return {"families": {}}


def _atomic_write(path: str, data: Dict[str, Any]) -> None:
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               prefix=".kernel_tune_", suffix=".json")
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


def _migrate_flash(data: Dict[str, Any]) -> Dict[str, Any]:
    """One-shot loader fallback: legacy ``flash_tune.json`` rows appear
    under the ``flash`` namespace (unified rows win on key collision).
    Purely additive and in-memory — the merged view persists the next
    time the table is saved."""
    try:
        from . import autotune

        legacy = autotune.load_cache().get("entries", {})
    except Exception:  # noqa: BLE001 — a broken legacy cache must not
        return data  # poison the unified table
    if not legacy:
        return data
    fam = data.setdefault("families", {}).setdefault(
        "flash", {"entries": {}})
    for key, e in legacy.items():
        if key in fam["entries"]:
            continue
        row = dict(e)
        row.setdefault("migrated_from", "flash_tune.json")
        if "ratio_fwd_bwd" in row:
            row.setdefault("ratio", row["ratio_fwd_bwd"])
        if "block_q" in row:
            row.setdefault("config", {"block_q": row["block_q"],
                                      "block_k": row.get("block_k")})
        fam["entries"][key] = row
    return data


def load_table(refresh: bool = False) -> Dict[str, Any]:
    global _table_cache
    if _table_cache is None or refresh:
        _table_cache = _migrate_flash(_read_disk(table_path()))
    return _table_cache


def save_table(data: Dict[str, Any]) -> None:
    """Full-table write (locked + atomic). Prefer :func:`update_table`
    for read-modify-write — it re-reads under the lock so concurrent
    writers cannot drop each other's rows."""
    global _table_cache
    path = table_path()
    with _store_lock(path):
        _atomic_write(path, data)
    _table_cache = data


def update_table(mutator) -> Dict[str, Any]:
    """Locked read-modify-write: reload from disk under the fcntl lock,
    apply ``mutator(data)``, write atomically. The ONLY safe way to add
    rows when hwbench and a manual search can run concurrently."""
    global _table_cache
    path = table_path()
    with _store_lock(path):
        data = _migrate_flash(_read_disk(path))
        mutator(data)
        _atomic_write(path, data)
    _table_cache = data
    return data


def _device_kind() -> Optional[str]:
    try:
        import jax

        return getattr(jax.devices()[0], "device_kind", None)
    except Exception:  # noqa: BLE001 — no backend, no filtering
        return None


def family_entries(family: str) -> Dict[str, Any]:
    """Rows for ``family`` measured on the RUNNING device generation
    (same rule as ``autotune._device_entries``: a v5e row must not
    drive decisions on v6e)."""
    entries = load_table().get("families", {}).get(
        family, {}).get("entries", {})
    kind = _device_kind()
    if kind is None:
        return entries
    return {k: e for k, e in entries.items()
            if e.get("device") in (None, kind)}


def lookup(family: str, key: str) -> Optional[Dict[str, Any]]:
    """Exact-key row or None — engagement never transfers across shapes
    (the flash crossover lesson: the win/lose verdict flips with shape;
    see autotune.kernel_beats_composite)."""
    return family_entries(family).get(key)


def best_config(family: str, key: str) -> Optional[Dict[str, Any]]:
    e = lookup(family, key)
    return e.get("config") if e else None


def engaged(family: str, key: str) -> Optional[bool]:
    """Measured engagement verdict; None when no measurement applies.

    A row only counts when it was measured on real hardware (CPU /
    interpret rows carry meaningless wall-clock and never engage) and
    carries a kernel-vs-composite ratio. True iff measured faster.
    """
    e = lookup(family, key)
    if e is None or "ratio" not in e:
        return None
    if e.get("backend") in (None, "cpu") or e.get("interpret"):
        return None
    return e["ratio"] > 1.0


def note_engaged(family: str) -> None:
    m = _monitor
    if m is not None:
        m.on_pallas_engaged(family)


def note_fallback(family: str) -> None:
    m = _monitor
    if m is not None:
        m.on_pallas_fallback(family)


def decide(family: str, key: str) -> bool:
    """The runtime entry: engagement verdict + monitor accounting.
    Returns True only on a measured-faster hardware row."""
    v = bool(engaged(family, key))
    if v:
        note_engaged(family)
    else:
        note_fallback(family)
    return v


def engagement_report() -> Dict[str, bool]:
    """``{family: any-shape-engaged}`` for EVERY registered family on
    the current device — the sub-object benches embed (``kernels``) so
    the perf guard's engagement-regression gate can compare runs. A
    family with no hardware rows reports False, NOT absent: the
    deleted-row / regenerated-table regression must read as a lost
    engagement against a True baseline (absent means only "this bench
    didn't embed the map at all" — the guard's wildcard)."""
    out: Dict[str, bool] = {}
    for name in sorted(FAMILIES):
        hw = [e for e in family_entries(name).values()
              if e.get("backend") not in (None, "cpu")
              and not e.get("interpret") and "ratio" in e]
        out[name] = any(e["ratio"] > 1.0 for e in hw)
    return out


# -- the search ---------------------------------------------------------------

def _parity_check(fam: KernelFamily, shape, config, args, ref_out):
    """Interpret-mode parity vs the composite — the mandatory
    pre-filter. Returns (ok, max_abs_err)."""
    import numpy as np

    try:
        out = fam.build(shape, config, interpret=True)(*args)
    except Exception:  # noqa: BLE001 — a config that cannot run is a reject
        return False, float("inf")
    outs = out if isinstance(out, (tuple, list)) else (out,)
    refs = ref_out if isinstance(ref_out, (tuple, list)) else (ref_out,)
    err = 0.0
    for o, r in zip(outs, refs):
        err = max(err, float(np.max(np.abs(
            np.asarray(o, dtype=np.float64)
            - np.asarray(r, dtype=np.float64)))))
    return err <= fam.parity_atol, err


def search_shape(fam: KernelFamily, shape, iters: int = 20,
                 verbose: bool = True,
                 interpret: Optional[bool] = None) -> Dict[str, Any]:
    """Run the full pipeline for one shape: enumerate -> interpret-mode
    parity filter -> time survivors + composite -> persist the best row
    (device/commit provenance). Returns the persisted entry."""
    import jax

    from . import autotune
    from ...framework.device import on_tpu
    from ...utils import measurements as _meas

    if interpret is None:
        interpret = not on_tpu()
    key = fam.key(shape)
    args = fam.make_inputs(shape)
    # parity runs on dedicated (fp32) inputs when the family provides
    # them: the filter must see math errors, not bf16 rounding noise
    pargs = getattr(fam, "make_parity_inputs", fam.make_inputs)(shape)
    composite = fam.build_composite(shape)
    ref_out = composite(*pargs)
    cands = list(fam.candidates(shape))
    if not cands:
        raise RuntimeError(f"{fam.name}: empty candidate space for "
                           f"{key}")
    m = _monitor
    survivors = []
    rejects = 0
    for cand in cands:
        ok, err = _parity_check(fam, shape, cand, pargs, ref_out)
        if ok:
            survivors.append((cand, err))
        else:
            rejects += 1
            if m is not None:
                m.on_search_reject(fam.name)
            if verbose:
                print(f"  {fam.name}[{key}] reject {cand}: "
                      f"parity err {err:g} > {fam.parity_atol:g}",
                      flush=True)
    if not survivors:
        raise RuntimeError(
            f"{fam.name}: every candidate failed interpret-mode parity "
            f"for {key} — the kernel is wrong, not slow")

    def timefn(f):
        return autotune._gradify(f) if fam.grad else f

    try:
        t_comp = autotune._time_compiled(timefn(composite), args, iters)
    except Exception as e:  # noqa: BLE001 — composite OOM: no ratio
        if verbose:
            print(f"  {fam.name}[{key}] composite failed "
                  f"({type(e).__name__}); no engagement ratio",
                  flush=True)
        t_comp = None

    results = []
    hint: Dict[str, Any] = {}  # shared fori-loop calibration per shape
    for cand, perr in survivors:
        fn = fam.build(shape, cand, interpret=interpret)
        try:
            t = autotune._time_compiled(timefn(fn), args, iters,
                                        n_hint=hint)
        except Exception as e:  # noqa: BLE001 — a bad config skips
            rejects += 1
            if m is not None:
                m.on_search_reject(fam.name)
            if verbose:
                print(f"  {fam.name}[{key}] {cand}: failed "
                      f"{type(e).__name__}", flush=True)
            continue
        if m is not None:
            m.on_search_timed(fam.name)
        results.append((t, cand, perr))
        if verbose:
            print(f"  {fam.name}[{key}] {cand}: "
                  f"{t * 1e3:.3f} ms"
                  + (f"  (composite {t_comp * 1e3:.3f} ms)"
                     if t_comp is not None else ""), flush=True)
    if not results:
        raise RuntimeError(f"{fam.name}: no candidate survived timing "
                           f"for {key}")
    results.sort(key=lambda r: r[0])
    t_best, best_cand, best_err = results[0]
    entry: Dict[str, Any] = {
        "family": fam.name, "key": key,
        "config": best_cand,
        "t_kernel_ms": round(t_best * 1e3, 4),
        "parity_max_err": best_err,
        "candidates": len(cands),
        "candidates_timed": len(results),
        "rejects": rejects,
        "grad": fam.grad,
        "device": _device_kind(),
        "backend": jax.default_backend(),
        "interpret": bool(interpret),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    entry.update(fam.shape_info(shape))
    entry.update(_meas._git_commit())
    if t_comp is not None:
        entry["t_composite_ms"] = round(t_comp * 1e3, 4)
        entry["ratio"] = round(t_comp / max(t_best, 1e-12), 4)
        if m is not None:
            m.on_search_best_ratio(fam.name, entry["ratio"])

    def put(data):
        data.setdefault("families", {}).setdefault(
            fam.name, {"entries": {}}).setdefault(
            "entries", {})[key] = entry

    update_table(put)
    on_persist = getattr(fam, "on_persist", None)
    if on_persist is not None:
        on_persist(shape, entry)
    return entry


def search_family(fam_or_name, shapes=None, iters: int = 20,
                  verbose: bool = True,
                  interpret: Optional[bool] = None,
                  smoke: bool = False) -> List[Dict[str, Any]]:
    """Search every shape of a family; returns the persisted entries.
    ``smoke`` selects the family's tiny CPU shapes."""
    fam = FAMILIES[fam_or_name] if isinstance(fam_or_name, str) \
        else fam_or_name
    if shapes is None:
        shapes = fam.smoke_shapes() if smoke else fam.shapes()
    out = []
    for shape in shapes:
        if verbose:
            print(f"searching {fam.name}[{fam.key(shape)}] "
                  f"({len(list(fam.candidates(shape)))} candidate(s))",
                  flush=True)
        out.append(search_shape(fam, shape, iters=iters, verbose=verbose,
                                interpret=interpret))
    return out


from ...monitor import _register as _monitor_register  # noqa: E402

_monitor_register(sys.modules[__name__])
