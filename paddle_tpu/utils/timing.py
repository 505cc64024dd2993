"""Device-timing helper: a fence made of a device->host fetch.

JAX dispatch is asynchronous, so a wall-clock measurement must end in a
fence. `device_sync` fetches one element of every leaf to the host: the
value cannot arrive before the work that produces it has finished, on
any backend. `jax.block_until_ready` is the other fence; on the TPU v5e
the two agree (chip_smoke.py times the same train step both ways and
prints the pair — PERF.md "Bring-up"). This one stays because its
latency is observable (`sync/fence_ms`) and because the engine's rounds
need the fetched token anyway.
"""
from __future__ import annotations

import sys
import time

import jax

from ..monitor import _register as _monitor_register

# Telemetry slots (see paddle_tpu.monitor): when wired, every device_sync
# reports its transfer-fence latency to the sync/fence_ms histogram and a
# `sync`-category span to the flight recorder (monitor/spans.py) on the
# logical "sync_fences" lane — fences from any thread collect on one
# timeline row. The measurement is the host transfer itself.
_monitor = None
_spans = None


def device_sync(out):
    """Block until `out` (any pytree of arrays) has actually been
    computed, by fetching one element of EVERY leaf to the host (leaves
    may come from separate dispatches, so fencing only the first would
    leave the rest in flight; one scalar per leaf is cheap).
    Returns `out` so it can wrap expressions inline."""
    fetch = []
    for leaf in jax.tree_util.tree_leaves(out):
        if not hasattr(leaf, "dtype"):
            continue
        if getattr(leaf, "size", 1) == 0:
            continue  # nothing to fetch; indexing would raise
        if getattr(leaf, "ndim", 0):
            leaf = leaf[(0,) * leaf.ndim]
        fetch.append(leaf)
    if fetch:
        m = _monitor
        if m is not None:
            t0 = time.perf_counter()
            jax.device_get(fetch)
            m.on_device_sync((time.perf_counter() - t0) * 1e3)
            sp = _spans
            if sp is not None:
                sp.record("sync/device_sync", "sync", t0,
                          lane="sync_fences")
        else:
            jax.device_get(fetch)
    return out


_monitor_register(sys.modules[__name__])
