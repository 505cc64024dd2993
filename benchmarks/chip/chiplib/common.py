"""What every cell's runner shares: the device look, the compile cache,
compile counting, memory readings, printing."""
from __future__ import annotations

import json
import os
import statistics
import sys


def emit(obj):
    print(json.dumps(obj), flush=True)


def note(kind, **kw):
    """An earlier line of the run's output (never the last)."""
    emit({"line": kind, **kw})


def device_info(need_chips, require_chip=True):
    """The device as JAX reports it. Without an accelerator, or with
    fewer chips than the cell asks for, the run ends here with a non-zero
    exit code and no result line."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_chip and info["platform"] != "tpu":
        print(f"benchmarks/chip: JAX found platform "
              f"{info['platform']!r}, not 'tpu' — this benchmark "
              f"measures only on the chip", file=sys.stderr)
        raise SystemExit(3)
    if info["count"] < need_chips:
        print(f"benchmarks/chip: the cell needs {need_chips} chip(s), "
              f"{info['count']} visible", file=sys.stderr)
        raise SystemExit(3)
    info["count"] = need_chips  # the chips this cell uses
    return info, devs[:need_chips]


def enable_compile_cache():
    """``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``
    — the program's own rule (paddle_tpu/utils/xla_cache.py), so program
    and benchmark agree on one fixed directory. Every program is cached,
    however short its compile."""
    import jax

    from paddle_tpu.utils.xla_cache import enable_compilation_cache

    where = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class JaxEvents:
    """XLA backend compiles and persistent-cache hits and misses, from
    JAX's own monitoring events: every jit in the process."""

    def __init__(self):
        import jax.monitoring as jm

        self.counts = {"backend_compiles": 0, "cache_hits": 0,
                       "cache_misses": 0}
        jm.register_event_listener(self._on_event)
        jm.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.counts["cache_misses"] += 1

    def _on_duration(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.counts["backend_compiles"] += 1

    def compiles(self):
        """Programs built or loaded from the persistent cache (JAX times
        both as a backend compile) — in the window it must read 0."""
        return self.counts["backend_compiles"]


def memory_peak(devices):
    """Peak bytes in use on the fullest chip."""
    return memory_stat(devices, "peak_bytes_in_use")


def bytes_in_use(devices):
    return memory_stat(devices, "bytes_in_use")


def drop_program_state():
    """After the window: free what the program left on the device, so the
    reference has the chip to itself."""
    import gc

    import jax

    from paddle_tpu.jit import exec_cache

    exec_cache.clear()
    jax.clear_caches()
    gc.collect()


def quantile(values, q):
    """Nearest-rank quantile (q in 0..1) of a non-empty list."""
    s = sorted(values)
    k = max(0, min(len(s) - 1, int(-(-q * len(s) // 1)) - 1))
    return s[k]


def memory_stat(devices, key):
    """The largest ``memory_stats()[key]`` over the chips, or None where
    the backend reports none (the CPU)."""
    vals = [(d.memory_stats() or {}).get(key) for d in devices]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


def request_quantile(obs, loop, key, q):
    """Nearest-rank quantile of one per-request fact of a serving cell
    with that loop, or None."""
    if obs["job"] != "serve" or obs["loop"] != loop:
        return None
    v = [r[key] for r in obs["requests"] if key in r]
    return quantile(v, q) if v else None


def pure_round_ms(obs, loop):
    """Median of the benchmark's span around ``engine.step`` over the
    rounds that decoded and prefilled nothing."""
    if obs["job"] != "serve" or obs["loop"] != loop:
        return None
    v = [r["ms"] for r in obs["rounds"]
         if r["prefill_chunks"] == 0
         and r["decode_steps"] + r["verify_steps"] > 0]
    return statistics.median(v) if v else None


def trace_dir(workload, seed):
    """Where a traced run's profile goes: under the ignored
    ``chiprun_out/`` of the checkout."""
    from . import manifest

    return os.path.join(manifest.ROOT, "chiprun_out", "traces",
                        f"{workload}-{seed}")
