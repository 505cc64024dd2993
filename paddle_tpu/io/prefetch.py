"""Device prefetch: keep K batches ahead of the compiled step in HBM.

The DataLoader's workers overlap *host-side* batch production (decode,
augment, collate); the final host→device copy still happens on consume.
That copy's enqueue is cheap but the data only starts moving when
`device_put` is dispatched — so a synchronous loop pays the copy latency
inside the step gap. :class:`DevicePrefetchIterator`
closes that gap: a producer thread pulls batches from any iterable and
issues async ``device_put`` K batches ahead, so batch k+1's host→HBM copy
overlaps step k's compute (``device_put`` is asynchronous under PJRT; the
returned arrays are futures). This is the same discipline as
``jax.data``-style double buffering / flax prefetch_to_device.

Sharded staging: when a mesh is active (``distributed.env.get_env()``) or
an explicit ``sharding`` is passed, leaves are placed with that sharding —
a *sharded* ``device_put`` that writes each device's slice directly,
instead of replicating through one chip.

Telemetry (``paddle_tpu/monitor``, zero-overhead off): buffer depth after
each stage (``io/prefetch_depth``), batches staged
(``io/prefetch_batches``), and starvation events with their host-blocked
wait (``io/prefetch_starvations``, ``io/prefetch_wait_ms``). Span lanes
(``monitor/spans.py``): producer ``device_put`` staging on the
``prefetch_producer`` lane, consumer starved waits as
``prefetch_starvation`` attribution spans on the consuming thread's lane.
"""
from __future__ import annotations

import queue
import sys
import threading
import time

import numpy as np

from ..framework.core import Tensor
from ..monitor import _register as _monitor_register

# Telemetry slots (see paddle_tpu.monitor): None unless PT_MONITOR wired
# them. `_spans` is the flight-recorder ring (monitor/spans.py).
_monitor = None
_spans = None

__all__ = ["DevicePrefetchIterator"]


def _default_place(leaf, sharding):
    import jax

    if sharding is not None:
        return jax.device_put(leaf, sharding)
    return jax.device_put(leaf)


class DevicePrefetchIterator:
    """Wrap any batch iterable; stage up to ``depth`` batches device-ward.

    Args:
        iterable: anything yielding batches — a ``paddle.io.DataLoader``,
            a generator of numpy arrays / Tensors, or nested tuples/dicts
            of them.
        depth: max batches staged ahead (the HBM budget: each staged batch
            is live on device until consumed + freed by the step).
        sharding: optional ``jax.sharding.Sharding`` applied to every
            array leaf (e.g. batch-dim sharding for data parallelism).
            Default: when a mesh is active, batches are replicated onto it
            (``distributed.env.put_replicated`` — multihost-safe);
            otherwise a plain single-device ``device_put``.
        to_tensor: wrap staged leaves back into ``Tensor`` (default True,
            matching DataLoader output).

    Iteration contract (tests/test_async_pipeline.py): batches come out in
    input order; an exception raised by the inner iterable is re-raised at
    the position it occurred (after all earlier batches); iteration after
    exhaustion or error raises a clean ``StopIteration``.
    """

    _DONE = ("done",)
    _ERR = ("err",)
    _ITEM = ("item",)

    def __init__(self, iterable, depth=2, sharding=None, to_tensor=True):
        if depth < 1:
            from ..framework.errors import InvalidArgumentError

            raise InvalidArgumentError(
                f"DevicePrefetchIterator: depth must be >= 1 (got {depth})")
        self._depth = int(depth)
        self._sharding = sharding
        self._to_tensor = to_tensor
        self._q: queue.Queue = queue.Queue(maxsize=self._depth)
        self._stop = threading.Event()
        self._exhausted = False
        self._thread = threading.Thread(
            target=self._produce, args=(iter(iterable),), daemon=True)
        self._thread.start()

    # -- staging -------------------------------------------------------------

    def _place_leaf(self, leaf):
        if isinstance(leaf, Tensor):
            arr = leaf._data
        elif isinstance(leaf, (np.ndarray, np.generic)):
            arr = leaf
        else:
            return leaf  # strings/ints/None pass through untouched
        if self._sharding is not None:
            out = _default_place(arr, self._sharding)
        else:
            from ..distributed import env as env_mod

            e = env_mod.get_env()
            if e is not None and e.mesh.size > 1:
                out = env_mod.put_replicated(arr, e.mesh)
            else:
                out = _default_place(arr, None)
        return Tensor(out) if self._to_tensor else out

    def _place(self, item):
        if isinstance(item, dict):
            return {k: self._place(v) for k, v in item.items()}
        if isinstance(item, (tuple, list)):
            return type(item)(self._place(v) for v in item)
        return self._place_leaf(item)

    def _offer(self, kind, payload) -> bool:
        # the bounded queue is the in-flight cap: put blocks once `depth`
        # staged batches are unconsumed (timeout polls the stop flag so
        # close() never strands the producer)
        while not self._stop.is_set():
            try:
                self._q.put((kind, payload), timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self, it):
        while not self._stop.is_set():
            try:
                batch = next(it)
            except StopIteration:
                self._offer(self._DONE, None)
                return
            except BaseException as e:  # noqa: BLE001 — crosses the thread
                self._offer(self._ERR, e)
                return
            sp = _spans
            t_stage = time.perf_counter() if sp is not None else None
            try:
                staged = self._place(batch)
            except BaseException as e:  # noqa: BLE001 — device_put failed
                self._offer(self._ERR, e)
                return
            if sp is not None:
                # the producer's async device_put enqueue, on its own lane
                sp.record("prefetch/stage", "prefetch_stage", t_stage,
                          lane="prefetch_producer")
            if self._offer(self._ITEM, staged):
                m = _monitor
                if m is not None:
                    m.on_prefetch_put(self._q.qsize())

    # -- consumption ---------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._exhausted:
            raise StopIteration
        m = _monitor
        try:
            kind, payload = self._q.get_nowait()
        except queue.Empty:
            # timed waits so a close()'d iterator (stopped producer, no
            # sentinel coming) ends in clean StopIteration, not a hang
            t0 = time.perf_counter()
            while True:
                if self._stop.is_set():
                    self._exhausted = True
                    raise StopIteration
                try:
                    kind, payload = self._q.get(timeout=0.1)
                    break
                except queue.Empty:
                    continue
            if m is not None:
                m.on_prefetch_starved((time.perf_counter() - t0) * 1e3)
            sp = _spans
            if sp is not None:
                # consumer-side host-blocked wait: the input pipeline was
                # the bottleneck for this slice of the step gap
                sp.record("prefetch/starved_wait", "prefetch_starvation", t0)
        if kind is self._ITEM:
            return payload
        self._exhausted = True
        self._stop.set()
        if kind is self._ERR:
            raise payload
        raise StopIteration

    def close(self):
        """Stop the producer and drop staged batches (frees their HBM)."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass

    def __del__(self):
        try:
            self._stop.set()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


_monitor_register(sys.modules[__name__])
