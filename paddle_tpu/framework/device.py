"""Device management.

Reference parity: `paddle.set_device` / `paddle.get_device`
(reference `python/paddle/device/__init__.py:244`) and the DeviceManager
plugin registry (`paddle/phi/backends/device_manager.h:128`).

TPU-first design: a "device" is a JAX device (PJRT). There are no streams to
manage — XLA owns ordering — so the reference's DeviceContext/stream machinery
collapses to "which jax.Device do creation ops place onto". Sharded (multi-
device) placement is handled by the distributed layer via `jax.sharding`.
"""
from __future__ import annotations

import threading

import jax

_state = threading.local()


def _platform_of(name: str) -> str:
    # normalize paddle-style device strings: "tpu", "tpu:0", "cpu", "gpu:1"
    return name.split(":")[0].lower()


def _index_of(name: str) -> int:
    parts = name.split(":")
    return int(parts[1]) if len(parts) > 1 else 0


_PLATFORM_ALIASES = {
    "tpu": ("tpu",),
    "cpu": ("cpu",),
    "gpu": ("gpu", "cuda", "rocm"),
}


def platform() -> str:
    """Platform of the first device JAX reports (``"tpu"``, ``"cpu"``)."""
    return jax.devices()[0].platform


def on_tpu() -> bool:
    """THE rule for "am I on the chip". Kernel dispatch
    (ops/registry.py), Pallas interpret mode (everything that is not the
    chip interprets — here that is the CPU, and only because the
    platform is the CPU, never as a rescue after a failed compile),
    buffer donation and the entry points' smoke/real split all answer
    from here."""
    return platform() == "tpu"


def require_tpu(who: str) -> None:
    """Entry points call this when they were not asked for a CPU smoke:
    no chip is an error, never a quiet switch to the CPU."""
    if not on_tpu():
        raise RuntimeError(
            f"{who}: JAX reports platform {platform()!r}, not a TPU; this "
            f"run was not asked to smoke on the CPU and will not fall "
            f"back to it")


def _available_platforms():
    plats = {}
    for d in jax.devices():
        plats.setdefault(d.platform.lower(), []).append(d)
    return plats


def set_device(device: str):
    """Select the device that subsequent tensor-creation ops place data on.

    Accepts ``"tpu"``, ``"tpu:0"``, ``"cpu"``, ``"gpu:1"``.
    """
    platform = _platform_of(device)
    index = _index_of(device)
    plats = _available_platforms()
    candidates = _PLATFORM_ALIASES.get(platform, (platform,))
    for cand in candidates:
        if cand in plats:
            devs = plats[cand]
            if index >= len(devs):
                raise ValueError(
                    f"device index {index} out of range for platform {cand!r} "
                    f"({len(devs)} devices)"
                )
            _state.device = devs[index]
            _state.name = f"{platform}:{index}"
            return _state.device
    # fall back to jax.devices('cpu') which always exists even when the
    # default platform is tpu
    if platform == "cpu":
        devs = jax.devices("cpu")
        _state.device = devs[index]
        _state.name = f"cpu:{index}"
        return _state.device
    raise ValueError(
        f"device {device!r} not available; present platforms: {sorted(plats)}"
    )


def get_device() -> str:
    """Paddle-style device string for the current device."""
    if not hasattr(_state, "name"):
        _init_default()
    return _state.name


def current_device() -> jax.Device:
    """The jax.Device creation ops place onto."""
    if not hasattr(_state, "device"):
        _init_default()
    return _state.device


def _init_default():
    # local_devices, not devices: under a multi-process runtime
    # (launcher + jax.distributed.initialize) jax.devices()[0] belongs to
    # process 0 and is non-addressable from the others
    d = jax.local_devices()[0]
    platform = d.platform.lower()
    for public, aliases in _PLATFORM_ALIASES.items():
        if platform in aliases:
            platform = public
            break
    _state.device = d
    _state.name = f"{platform}:0"


# ---- memory observability -------------------------------------------------
# Reference parity: `paddle/fluid/memory/stats.cc` and the
# `paddle.device.cuda.{memory,max_memory}_{allocated,reserved}` API. On TPU
# allocation is owned by PJRT; these surface its per-device stats
# (bytes_in_use / peak_bytes_in_use / bytes_limit). PJRT peaks are
# process-monotonic, so reset_* records a baseline and subsequent maxima are
# reported relative to observations after it (best effort, documented).

_mem_baseline: dict = {}


def _resolve(device=None) -> jax.Device:
    if device is None:
        return current_device()
    if isinstance(device, jax.Device):
        return device
    return _lookup(device)


def _lookup(name: str) -> jax.Device:
    platform = _platform_of(str(name))
    index = _index_of(str(name))
    plats = _available_platforms()
    for cand in _PLATFORM_ALIASES.get(platform, (platform,)):
        if cand in plats:
            return plats[cand][index]
    raise ValueError(f"device {name!r} not available")


def memory_stats(device=None) -> dict:
    """Raw PJRT allocator stats for ``device`` (empty dict if the backend
    does not expose them, e.g. some CPU builds)."""
    d = _resolve(device)
    try:
        return dict(d.memory_stats() or {})
    except Exception:
        return {}


def memory_allocated(device=None) -> int:
    """Bytes currently held by live buffers on ``device``."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """Peak bytes in use on ``device`` (since process start, or since the
    last :func:`reset_max_memory_allocated`)."""
    d = _resolve(device)
    stats = memory_stats(d)
    peak = int(stats.get("peak_bytes_in_use", 0))
    base = _mem_baseline.get(id(d))
    if base is not None and peak <= base:
        # PJRT peaks are monotonic; after a reset report the live number
        return int(stats.get("bytes_in_use", 0))
    return peak


def memory_reserved(device=None) -> int:
    """Bytes reserved by the allocator pool (PJRT: limit-tracked pool)."""
    stats = memory_stats(device)
    return int(stats.get("bytes_reserved",
                         stats.get("pool_bytes", stats.get("bytes_in_use", 0))))


def max_memory_reserved(device=None) -> int:
    stats = memory_stats(device)
    return int(stats.get("peak_bytes_reserved",
                         stats.get("peak_pool_bytes",
                                   stats.get("peak_bytes_in_use", 0))))


def reset_max_memory_allocated(device=None) -> None:
    d = _resolve(device)
    _mem_baseline[id(d)] = int(
        memory_stats(d).get("peak_bytes_in_use", 0))


def reset_max_memory_reserved(device=None) -> None:
    reset_max_memory_allocated(device)


def empty_cache() -> None:
    """Parity no-op: PJRT owns its pools; XLA frees donated/dead buffers."""


def get_device_properties(device=None):
    """Total/free memory and identity of ``device`` (parity:
    `paddle.device.cuda.get_device_properties`)."""
    d = _resolve(device)
    stats = memory_stats(d)
    return {
        "name": getattr(d, "device_kind", d.platform),
        "platform": d.platform,
        "index": d.id,
        "total_memory": int(stats.get("bytes_limit", 0)),
        "bytes_in_use": int(stats.get("bytes_in_use", 0)),
    }


def is_compiled_with_tpu() -> bool:
    return bool(_available_platforms().get("tpu"))


def device_count(platform: str | None = None) -> int:
    if platform is None:
        return len(jax.devices())
    candidates = _PLATFORM_ALIASES.get(platform.lower(), (platform.lower(),))
    plats = _available_platforms()
    return sum(len(plats.get(c, ())) for c in candidates)
