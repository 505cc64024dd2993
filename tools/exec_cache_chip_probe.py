#!/usr/bin/env python
"""Cold-vs-warm compile wall-time through the exec cache on the chip
(ROADMAP A10: does ``serialize_executable`` round-trip on the real
device? — still open; never run there).

Runs ``bench.py`` twice in child processes, one after the other, against
a fresh ``PT_EXEC_CACHE`` directory: the COLD run must compile and
serialize, the WARM run must deserialize and pay ~zero fresh XLA
compiles. The delta is the cold-start saving the cache buys on this
backend, and the warm run's disk-hit count is the proof that the TPU's
PJRT client supports ``serialize_executable`` — which the CPU-only
proof in tests/test_exec_cache.py cannot establish.

Note for whoever runs it in a fresh machine: the cache key hashes the
package's file sizes and MTIMES (``jit/exec_cache.py:_code_fingerprint``)
and mtimes change on every copy of the tree, so the disk tier only hits
within one checkout — cold and warm must run from the same copy, as
they do here.

Usage: python tools/exec_cache_chip_probe.py
Prints one JSON line: {"metric": "exec_cache_cold_warm_compile_ms", ...}
with ``serialize_executable_ok`` as the plugin-support verdict.
Wired as an hwbench row; persists to PERF_MEASUREMENTS.json on hardware.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _find_bench_line(text: str):
    """perf_guard.find_bench_line by path (tools/ is not a package)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "perf_guard.py")
    spec = importlib.util.spec_from_file_location("perf_guard", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.find_bench_line(text)


def summarize(cold: dict, warm: dict) -> dict:
    """The probe's verdict from the two bench lines (pure — unit-tested
    without subprocesses). ``serialize_executable_ok`` requires the cold
    run to have SERIALIZED artifacts and the warm run to have LOADED
    them (disk hits) — a backend whose executables don't round-trip
    fails the second leg (every load error falls back to a fresh
    compile and counts in ``errors``)."""
    tel_c = cold.get("telemetry") or {}
    tel_w = warm.get("telemetry") or {}
    ec_c = tel_c.get("exec_cache") or {}
    ec_w = tel_w.get("exec_cache") or {}
    cold_ms = tel_c.get("compile_ms_total")
    warm_ms = tel_w.get("compile_ms_total")
    ok = bool(ec_c.get("serialized", 0) > 0
              and ec_w.get("disk_hits", 0) > 0)
    rec = {
        "metric": "exec_cache_cold_warm_compile_ms",
        "value": (round(cold_ms - warm_ms, 1)
                  if cold_ms is not None and warm_ms is not None
                  else None),
        "unit": "ms",
        "compile_ms_cold": cold_ms,
        "compile_ms_warm": warm_ms,
        "serialized_cold": ec_c.get("serialized", 0),
        "disk_hits_warm": ec_w.get("disk_hits", 0),
        "deserialize_errors_warm": ec_w.get("errors", 0),
        "serialize_executable_ok": ok,
        "headline_metric": cold.get("metric"),
    }
    note = cold.get("note") or warm.get("note")
    if note:
        rec["note"] = note
    return rec


def main() -> int:
    cache_dir = os.environ.get(
        "PT_EXEC_CACHE_PROBE_DIR",
        os.path.join(ROOT, ".jax_cache", "exec_cache_probe"))
    # cold must be COLD: wipe any artifacts from a previous probe
    shutil.rmtree(cache_dir, ignore_errors=True)
    env = dict(os.environ)
    env["PT_EXEC_CACHE"] = cache_dir
    lines = []
    # One process for each chip: this parent starts, one after the
    # other, the children that hold the chip, so it must never
    # initialise a JAX backend itself (importing jax or paddle_tpu is
    # fine; jax.devices()/default_backend() is not).
    for phase in ("cold", "warm"):
        proc = subprocess.run(
            [sys.executable, "bench.py"], cwd=ROOT, env=env,
            capture_output=True, text=True)
        line = _find_bench_line(proc.stdout)
        if proc.returncode != 0 or line is None:
            tail = (proc.stderr or "").strip().splitlines()[-3:]
            print(json.dumps({
                "metric": "exec_cache_cold_warm_compile_ms",
                "value": None, "unit": "ms",
                "error": f"{phase} bench failed rc={proc.returncode}: "
                         f"{' | '.join(tail)}"}), flush=True)
            return 1
        print(f"probe: {phase} compile_ms_total="
              f"{(line.get('telemetry') or {}).get('compile_ms_total')}",
              file=sys.stderr, flush=True)
        lines.append(line)
    rec = summarize(*lines)
    if "note" not in rec:  # hardware lines persist with provenance
        sys.path.insert(0, ROOT)
        from paddle_tpu.utils import measurements as _meas

        # device facts come from the CHILD's line: this parent must not
        # touch the backend (see the spawn above)
        _meas.record_rec_or_warn(
            rec, backend=lines[0].get("platform", "tpu"),
            device=lines[0].get("device_kind", "unknown"))
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
