"""Device memory observatory: HBM accounting for the runtime.

The flight recorder (`monitor/spans.py`) made *host* wall time legible;
this module does the same for *device* memory — the other resource a run
silently dies on. Three views, cheapest first:

1. **Allocator stats** — ``device.memory_stats()`` where the PJRT plugin
   exposes them (``peak_bytes_in_use`` is the honest per-device peak).
   The TPU reports them; the CPU test backend returns ``None``.
2. **Live-buffer census** — ``jax.live_arrays()`` summed (global bytes +
   per-device via addressable shards). Works on every backend; taken at
   StepLogger step boundaries and hapi phase brackets, so peak-HBM-per-
   step lands in the JSONL sink and (through the ``memory/*`` gauges) in
   the profiler's chrome-trace ``ph:"C"`` counter tracks.
3. **Executable accounting** — ``TrainStep.memory_analysis()``
   (jit/train_step.py:331) structured into per-executable records
   (argument/output/temp/generated-code bytes). For SPMD executables XLA
   reports the *per-device* partitioned module, so these numbers are
   per-shard when a mesh is active — the basis of
   ``tools/memory_planner.py``'s fits/doesn't-fit preflight verdicts.

Reference parity: ``paddle.device.cuda.max_memory_allocated`` and the
``fluid/memory`` stats interface — here the allocator is XLA's, so peak
truth comes from the census + executable analysis instead of a custom
allocator hook.

Zero-overhead-when-off contract (same as the counter/span slots): the
module-global :data:`_ledger` is ``None`` unless :func:`enable` filled it
(``PT_MONITOR_MEM=1`` at import, or programmatic). Call sites
(StepLogger, hapi fit/evaluate) guard with ``memory._ledger is not None``
— off, they pay one attribute load + ``is None`` check and no census ever
runs (asserted by ``tests/test_memory_numerics.py``).
"""
from __future__ import annotations

import threading

__all__ = [
    "MemoryLedger", "enable", "disable", "enabled", "ledger",
    "live_census", "executable_record", "analysis_to_dict",
    "device_peak_gib",
]

# the None-slot: the observatory is off unless enable() filled it
_ledger = None

# per-executable records kept in a ledger snapshot before the oldest are
# dropped (a long sweep must not grow the JSONL run_end line unboundedly)
_MAX_EXECUTABLES = 32


def enabled() -> bool:
    return _ledger is not None


def ledger() -> "MemoryLedger | None":
    """The live ledger (None when the observatory is off)."""
    return _ledger


def enable() -> "MemoryLedger":
    """Install the ledger (idempotent). Same effect as starting the
    process with ``PT_MONITOR_MEM=1``."""
    global _ledger
    if _ledger is None:
        _ledger = MemoryLedger()
    return _ledger


def disable() -> None:
    """Clear the slot: census call sites go back to a single ``is None``
    check."""
    global _ledger
    _ledger = None


# -- raw views ---------------------------------------------------------------

def _backend_stats() -> dict:
    """Allocator stats of device 0, ``{}`` where the plugin exposes none
    (the CPU test backend)."""
    try:
        import jax

        return dict(jax.devices()[0].memory_stats() or {})
    except Exception:  # noqa: BLE001 — stats are a bonus, never a gate
        return {}


def device_peak_gib() -> float | None:
    """``peak_bytes_in_use`` of device 0 in GiB, or None where the
    backend reports no allocator stats."""
    peak = _backend_stats().get("peak_bytes_in_use")
    return round(peak / 2**30, 3) if peak is not None else None


def live_census(per_device: bool = False) -> dict:
    """One walk over ``jax.live_arrays()``: total live bytes + buffer
    count. ``per_device=True`` additionally sums each array's worst
    single-device cost (``distributed.shard.per_shard_bytes`` —
    replicated arrays bill full size, sharded ones their largest shard)
    into ``max_device_bytes``: the per-device HBM bound that OOMs first.
    Backend allocator peak rides along when available. O(live arrays) —
    which is why the observatory is opt-in rather than riding
    ``PT_MONITOR``."""
    import jax

    total = 0
    buffers = 0
    per_dev = 0
    if per_device:
        from ..distributed.shard import per_shard_bytes
    for a in jax.live_arrays():
        try:
            nb = int(a.nbytes)
        except Exception:  # noqa: BLE001 — deleted/donated buffers raise
            continue
        total += nb
        buffers += 1
        if per_device:
            try:
                per_dev += per_shard_bytes(a)
            except Exception:  # noqa: BLE001
                per_dev += nb
    out = {"live_bytes": total, "live_buffers": buffers}
    if per_device:
        out["max_device_bytes"] = per_dev
    peak = _backend_stats().get("peak_bytes_in_use")
    if peak is not None:
        out["backend_peak_bytes"] = int(peak)
    return out


def analysis_to_dict(ma, name: str | None = None) -> dict:
    """``CompiledMemoryStats`` -> plain dict. ``peak_bytes`` is
    arguments + temporaries — the live-HBM high-water mark while the
    executable runs (outputs alias into temp space; donated inputs are
    still arguments). For SPMD executables XLA reports the per-device
    partitioned module, so every field is per-shard under a mesh."""
    rec = {}
    if name:
        rec["name"] = name
    for key, attr in (
            ("args_bytes", "argument_size_in_bytes"),
            ("output_bytes", "output_size_in_bytes"),
            ("temp_bytes", "temp_size_in_bytes"),
            ("alias_bytes", "alias_size_in_bytes"),
            ("generated_code_bytes", "generated_code_size_in_bytes")):
        rec[key] = int(getattr(ma, attr, 0) or 0)
    rec["peak_bytes"] = rec["args_bytes"] + rec["temp_bytes"]
    rec["peak_gib"] = round(rec["peak_bytes"] / 2**30, 4)
    return rec


def executable_record(train_step, *batch, name: str | None = None) -> dict:
    """Structured memory record of a TrainStep's compiled executable for
    these batch shapes (pays one AOT compile — shared XLA cache applies).
    Annotated with the active mesh shape when one is up (the byte fields
    are then per-shard — see :func:`analysis_to_dict`); appended to the
    live ledger when the observatory is on."""
    rec = analysis_to_dict(train_step.memory_analysis(*batch), name=name)
    try:
        from ..distributed import env as env_mod

        e = env_mod.get_env()
        if e is not None and e.mesh.size > 1:
            # degenerate (size-1) axes add noise, not information
            rec["mesh"] = {k: v for k, v in zip(
                e.mesh.axis_names, e.mesh.devices.shape) if v > 1}
            rec["devices"] = int(e.mesh.size)
            rec["per_shard"] = True
    except Exception:  # noqa: BLE001 — mesh annotation is best-effort
        pass
    led = _ledger
    if led is not None:
        led.add_executable(rec)
    return rec


# -- the ledger --------------------------------------------------------------

class MemoryLedger:
    """Running peak-HBM account: censuses at step/phase boundaries, plus
    the per-executable records taken while it was live. Thread-safe (the
    prefetch producer and the stepping thread may both trigger
    censuses)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.census_count = 0
        self.peak_live_bytes = 0
        self.peak_backend_bytes = 0
        self.last = {}
        self.executables: list = []
        self._dropped_executables = 0

    def _gauges(self):
        # shared registry objects — the profiler exports every monitor
        # gauge as a chrome-trace ph:"C" counter track, which is how
        # peak-HBM-per-step lands on the Perfetto timeline
        from . import gauge

        return (gauge("memory/live_bytes"),
                gauge("memory/peak_live_bytes"),
                gauge("memory/live_buffers"))

    def census(self, tag: str | None = None) -> dict:
        """Take one live-buffer census, update peaks and gauges; returns
        the census dict (plus running peaks)."""
        c = live_census()
        with self._lock:
            self.census_count += 1
            self.peak_live_bytes = max(self.peak_live_bytes,
                                       c["live_bytes"])
            self.peak_backend_bytes = max(
                self.peak_backend_bytes, c.get("backend_peak_bytes", 0))
            self.last = c
            peak = self.peak_live_bytes
        try:
            g_live, g_peak, g_bufs = self._gauges()
            g_live.set(c["live_bytes"])
            g_peak.set(peak)
            g_bufs.set(c["live_buffers"])
        except Exception:  # noqa: BLE001 — gauges must not break a step
            pass
        out = dict(c)
        out["peak_live_bytes"] = peak
        if tag:
            out["tag"] = tag
        return out

    def step_census(self) -> dict:
        """The compact per-step line StepLogger embeds."""
        c = self.census()
        out = {"live_bytes": c["live_bytes"],
               "peak_live_bytes": c["peak_live_bytes"]}
        if "backend_peak_bytes" in c:
            out["backend_peak_bytes"] = c["backend_peak_bytes"]
        return out

    def add_executable(self, rec: dict) -> None:
        with self._lock:
            self.executables.append(rec)
            if len(self.executables) > _MAX_EXECUTABLES:
                self.executables.pop(0)
                self._dropped_executables += 1

    @property
    def peak_gib(self) -> float:
        """Best available peak in GiB: allocator peak where the backend
        reports one, live-census peak otherwise."""
        peak = self.peak_backend_bytes or self.peak_live_bytes
        return round(peak / 2**30, 4)

    def snapshot(self) -> dict:
        """The run_end / bench ``memory`` sub-object."""
        with self._lock:
            out = {
                "peak_live_bytes": self.peak_live_bytes,
                "peak_live_gib": round(self.peak_live_bytes / 2**30, 4),
                "censuses": self.census_count,
                "executables": list(self.executables),
            }
            if self.peak_backend_bytes:
                out["peak_backend_bytes"] = self.peak_backend_bytes
                out["peak_hbm_gib"] = round(
                    self.peak_backend_bytes / 2**30, 4)
            if self._dropped_executables:
                out["executables_dropped"] = self._dropped_executables
        return out
