"""The few JAX surfaces this repo reaches through one name.

Written for the installed JAX (0.9.0) only — no branch for another
release. Each entry is either a plain alias or a one-line spelling kept
here so that the next JAX move touches one file:

- ``shard_map`` / ``export``: ``jax.shard_map`` and the ``jax.export``
  module.
- ``pvary``: ``jax.lax.pcast(..., to="varying")`` (``jax.lax.pvary`` is
  deprecated).
- ``tpu_compiler_params``: ``pltpu.CompilerParams``, imported lazily
  (Pallas is heavy and optional).
- ``serialize_executable`` / ``deserialize_executable``:
  ``jax.experimental.serialize_executable`` — the on-disk tier of
  ``jit/exec_cache.py``.
"""
from __future__ import annotations

import jax

__all__ = ["shard_map", "export", "pvary", "tpu_compiler_params",
           "serialize_executable", "deserialize_executable"]

shard_map = jax.shard_map
export = jax.export


def tpu_compiler_params(**kwargs):
    """Pallas-TPU compiler params (``pltpu.CompilerParams``)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(**kwargs)


def pvary(x, axis_names):
    """Mark ``x`` varying over ``axis_names`` inside shard_map (literals
    that feed varying outputs need the cast under ``check_vma``)."""
    return jax.lax.pcast(x, tuple(axis_names), to="varying")


def serialize_executable(compiled):
    """``(payload, in_tree, out_tree, device_ids)`` for a
    ``jax.stages.Compiled`` — the persistable form of an AOT-compiled
    executable (lazy import: the module drags in pickle glue callers may
    never need). ``device_ids`` are the devices the executable was
    compiled for: ``deserialize_and_load`` otherwise loads it across
    EVERY device of the backend, and a one-device program then refuses
    its arguments ("expected 8 shards") on a host that shows eight."""
    from jax.experimental import serialize_executable as _se

    payload, in_tree, out_tree = _se.serialize(compiled)
    devices = compiled._executable._unloaded_executable.device_list
    return payload, in_tree, out_tree, tuple(d.id for d in devices)


def deserialize_executable(payload, in_tree, out_tree, device_ids):
    """Rehydrate :func:`serialize_executable` output into a loaded,
    callable executable on the same device ids of the current backend.
    Raises on any payload/topology mismatch — callers treat that as a
    cache miss."""
    from jax.experimental import serialize_executable as _se

    by_id = {d.id: d for d in jax.devices()}
    return _se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])
