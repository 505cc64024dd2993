"""Process groups and collective communication.

Reference parity: the `ProcessGroup` abstraction and its per-collective
Python API — `paddle/fluid/distributed/collective/process_group.h:53`,
`python/paddle/distributed/communication/{all_reduce,all_gather,...}.py`,
group management `python/paddle/distributed/collective.py:178` (`new_group`).

TPU-first design: a "group" is a set of mesh axes, not an NCCL ring. Eager
collectives are tiny compiled shard_map programs over those axes (SURVEY §5.8:
"Eager-mode collectives = tiny compiled programs"); collectives that appear
inside a traced program (jit / shard_map) lower directly to XLA collective
HLOs (`psum`, `all_gather`, `ppermute`, …) and ride ICI. There are no
streams, events, or ncclUniqueId bootstrap — XLA owns ordering, and the mesh
is the membership.

Semantics note (single-controller): an eager Tensor is a *global* array. A
collective over a group reads the tensor's per-shard view along the group's
axes: `all_reduce` on an axis-sharded tensor sums the shards (replicating the
result); on a replicated tensor each participant holds the same value, so the
sum is value × group size — identical to what N identical NCCL ranks would
produce.
"""
from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ..monitor import _register as _monitor_register

# Telemetry slots (see paddle_tpu.monitor): when wired, each collective
# reports one call + payload bytes, and `_spans` (monitor/spans.py) gets
# one `dispatch` span per eager collective's host-side enqueue. In-trace
# collectives count once per *trace*, not per execution — XLA owns the
# executed schedule.
_monitor = None
_spans = None


def _mon_collective(name, arr, axes=()):
    m = _monitor
    if m is not None:
        # axes = the group's mesh axes: the monitor splits the byte
        # counter per axis (collective/bytes/<axis>) so the planner's
        # per-axis cost model has a measured twin (docs/AUTOSHARD.md)
        m.on_collective(name, int(getattr(arr, "nbytes", 0) or 0),
                        axes=axes)


def _traced_collective(fn):
    """Span-record the collective's host-side wall time (program-cache
    lookup + dispatch enqueue; compile on a fresh shape). Off, the wrapper
    costs one ``is None`` check — the counter path (`_mon_collective`)
    stays where it is, past the trivial early returns."""
    name = f"collective/{fn.__name__}"

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        sp = _spans
        if sp is None:
            return fn(*args, **kwargs)
        t0 = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            sp.record(name, "dispatch", t0)

    return wrapper


def shard_map(fn, mesh, in_specs, out_specs, check_rep=False):
    from ..framework.jax_compat import shard_map as _shard_map

    return _shard_map(fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=check_rep)

from . import env as env_mod
from ..framework.core import Tensor
from ..ops.dispatch import apply


class ReduceOp:
    """Parity: `paddle.distributed.ReduceOp`."""

    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communicator: one or more mesh axes.

    Parity: the `Group` returned by `paddle.distributed.new_group`
    (`collective.py:178`). `axes` is the mesh-axis tuple the collectives
    run over; `nranks` is the product of those axis sizes.
    """

    def __init__(self, axes, name=None):
        self.axes = tuple(axes)
        self.name = name or "_".join(self.axes)

    @property
    def nranks(self) -> int:
        e = env_mod.ensure_env()
        n = 1
        for a in self.axes:
            n *= e.degree(a)
        return n

    world_size = nranks

    @property
    def rank(self) -> int:
        """Single-controller semantics: the python process is not one rank
        of the group — it drives ALL shards of the mesh at once, so "this
        process's rank" is 0 by convention (the reference's per-process
        rank does not map onto GSPMD). Code that branches per-rank should
        instead shard by mesh axis; see `get_group_rank`."""
        if self.nranks <= 0:
            return -1
        return 0

    def get_group_rank(self, rank):
        """Identity under the single-controller model: global rank == group
        rank because there is exactly one controller. Reference code that
        uses this to pick a subset of data must use sharding instead —
        raise loudly if the caller asks for a rank this controller does
        not own (anything other than its own world)."""
        if not isinstance(rank, int) or rank < 0 or rank >= max(self.nranks, 1):
            raise ValueError(
                f"rank {rank} out of range for single-controller group "
                f"with {self.nranks} shards; per-rank branching does not "
                f"exist under GSPMD — express the split as a sharding")
        return rank

    def __repr__(self):
        return f"Group(axes={self.axes}, nranks={self.nranks})"


_WORLD: Group | None = None


def _world_group() -> Group:
    global _WORLD
    if _WORLD is None:
        env_mod.ensure_env()
        _WORLD = Group(env_mod.AXIS_ORDER, name="world")
    return _WORLD


def get_group(group=None) -> Group:
    if group is None:
        return _world_group()
    if isinstance(group, Group):
        return group
    if isinstance(group, str):
        return Group((group,))
    return Group(tuple(group))


def new_group(ranks=None, backend=None, timeout=None, axes=None, name=None):
    """Parity: `paddle.distributed.new_group`. In SPMD the membership is a
    mesh-axis set; rank lists (a multi-controller concept) are accepted when
    they exactly cover one axis of the current mesh, otherwise axes must be
    given explicitly."""
    if axes is not None:
        return Group(axes if isinstance(axes, (tuple, list)) else (axes,), name)
    e = env_mod.ensure_env()
    if ranks is None or len(ranks) == e.world_size:
        return _world_group()
    matching = [ax for ax in env_mod.AXIS_ORDER
                if e.degree(ax) == len(ranks)]
    if len(matching) == 1:
        return Group((matching[0],), name)
    raise ValueError(
        f"cannot map ranks {ranks} unambiguously onto mesh axes "
        f"{e.degrees} (matching axes: {matching}); pass axes=... explicitly"
    )


# ---------------------------------------------------------------------------
# in-trace detection: inside shard_map the group's axes are bound axis names
# ---------------------------------------------------------------------------

def _axes_in_scope(axes) -> bool:
    try:
        for a in axes:
            jax.lax.axis_index(a)  # raises NameError outside shard_map
        return True
    except (NameError, Exception):
        return False


# ---------------------------------------------------------------------------
# eager collectives: cached compiled shard_map programs
# ---------------------------------------------------------------------------

def _spec_on(ndim, axes, dim):
    parts = [None] * ndim
    parts[dim] = axes if len(axes) > 1 else axes[0]
    return PartitionSpec(*parts)


@functools.lru_cache(maxsize=512)
def _reduce_program(mesh, axes, op, shape, dtype, in_spec_key):
    in_spec = PartitionSpec(*in_spec_key)
    red = {
        "sum": jax.lax.psum, "avg": jax.lax.pmean,
        "max": jax.lax.pmax, "min": jax.lax.pmin,
        "prod": _prod_reduce,
    }[op]
    ax = axes if len(axes) > 1 else axes[0]

    # result replicated over the reduced axes
    out_parts = [p if not _mentions(p, axes) else None for p in in_spec_key]
    out_spec = PartitionSpec(*out_parts)

    def shard_fn(x):
        return red(x, ax)

    fn = shard_map(shard_fn, mesh=mesh, in_specs=(in_spec,),
                   out_specs=out_spec, check_rep=False)
    return jax.jit(fn)


def _mentions(part, axes):
    if part is None:
        return False
    if isinstance(part, (tuple, list)):
        return any(p in axes for p in part)
    return part in axes


def _current_spec(arr) -> tuple:
    s = getattr(arr, "sharding", None)
    if isinstance(s, NamedSharding):
        spec = tuple(s.spec)
        spec = spec + (None,) * (arr.ndim - len(spec))
        return spec
    return (None,) * arr.ndim


def _on_mesh(arr):
    """Place an off-mesh (single-device) array onto the mesh replicated;
    mesh-resident arrays pass through with their layout."""
    e = env_mod.ensure_env()
    s = getattr(arr, "sharding", None)
    if isinstance(s, NamedSharding) and s.mesh.shape == e.mesh.shape:
        return arr
    return jax.device_put(arr, NamedSharding(e.mesh, PartitionSpec()))


def _prod_reduce(x, ax):
    # jax.lax has no pprod: |x| in log space + sign parity + zero sweep
    mag = jnp.exp(jax.lax.psum(jnp.log(jnp.maximum(jnp.abs(x), 1e-38)), ax))
    n_neg = jax.lax.psum((x < 0).astype(jnp.int32), ax)
    sign = 1.0 - 2.0 * (n_neg % 2).astype(jnp.float32)
    any_zero = jax.lax.pmin(jnp.abs(x), ax) == 0
    return jnp.where(any_zero, 0.0, mag * sign).astype(x.dtype)


@_traced_collective
def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """Parity: `paddle.distributed.all_reduce`. In-place on the Tensor shell
    (rebinds the buffer), also returns it."""
    g = get_group(group)
    if g.nranks == 1:
        return tensor
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)
    _mon_collective("all_reduce", t._data, g.axes)
    if _axes_in_scope(g.axes):
        ax = g.axes if len(g.axes) > 1 else g.axes[0]
        red = {"sum": jax.lax.psum, "avg": jax.lax.pmean,
               "max": jax.lax.pmax, "min": jax.lax.pmin,
               "prod": _prod_reduce}[op]
        out = apply(f"all_reduce_{op}", lambda x: red(x, ax), (t,))
        t._replace_(out._data)
        t._grad_node = out._grad_node
        t._out_index = out._out_index
        t.stop_gradient = out.stop_gradient and t.stop_gradient
        return t
    arr = _on_mesh(t._data)
    prog = _reduce_program(env_mod.get_env().mesh, g.axes, op,
                           tuple(arr.shape), str(arr.dtype),
                           _current_spec(arr))
    t._replace_(prog(arr))
    return t


@functools.lru_cache(maxsize=512)
def _gather_program(mesh, axes, dim, shape, dtype, in_spec_key):
    in_spec = PartitionSpec(*in_spec_key)
    ax = axes if len(axes) > 1 else axes[0]
    out_parts = [p if not _mentions(p, axes) else None for p in in_spec_key]
    out_spec = PartitionSpec(*out_parts)

    def shard_fn(x):
        return jax.lax.all_gather(x, ax, axis=dim, tiled=True)

    fn = shard_map(shard_fn, mesh=mesh, in_specs=(in_spec,),
                   out_specs=out_spec, check_rep=False)
    return jax.jit(fn)


@_traced_collective
def all_gather(tensor_or_list, tensor=None, group=None, sync_op=True, axis=0):
    """Parity: `paddle.distributed.all_gather(tensor_list, tensor)`. Also
    callable functional-style: `all_gather(tensor)` returns the gathered
    Tensor (concatenated along ``axis``)."""
    g = get_group(group)
    out_list = None
    if isinstance(tensor_or_list, list) and tensor is not None:
        out_list, x = tensor_or_list, tensor
    else:
        x = tensor_or_list
    t = x if isinstance(x, Tensor) else Tensor(x)
    if g.nranks > 1:
        _mon_collective("all_gather", t._data, g.axes)
    if g.nranks == 1:
        gathered = t
    elif _axes_in_scope(g.axes):
        ax = g.axes if len(g.axes) > 1 else g.axes[0]
        gathered = apply(
            "all_gather",
            lambda a: jax.lax.all_gather(a, ax, axis=axis, tiled=True),
            (t,),
        )
    else:
        arr = _on_mesh(t._data)
        prog = _gather_program(env_mod.get_env().mesh, g.axes, axis,
                               tuple(arr.shape),
                               str(arr.dtype), _current_spec(arr))
        gathered = Tensor(prog(arr))
    if out_list is not None:
        from ..tensor.manipulation import split as _split

        out_list.extend(_split(gathered, g.nranks, axis=axis))
        return out_list
    return gathered


@_traced_collective
def broadcast(tensor, src=0, group=None, sync_op=True):
    """Parity: `paddle.distributed.broadcast`. SPMD: a global array is
    already consistent across the mesh; replicate it over the group's axes."""
    g = get_group(group)
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)
    if g.nranks == 1 or _axes_in_scope(g.axes):
        return t
    _mon_collective("broadcast", t._data, g.axes)
    e = env_mod.ensure_env()
    spec = _current_spec(t._data)
    parts = [None if _mentions(p, g.axes) else p for p in spec]
    t._replace_(jax.device_put(
        _on_mesh(t._data), NamedSharding(e.mesh, PartitionSpec(*parts))))
    return t


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """SPMD reduce == all_reduce (every participant holds the result)."""
    return all_reduce(tensor, op=op, group=group)


@_traced_collective
def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """Parity: `paddle.distributed.scatter`. SPMD: shard dim 0 over the
    group's axes (src is irrelevant — data is global)."""
    g = get_group(group)
    if tensor_list is not None:
        from ..tensor.manipulation import concat

        tensor = concat([x if isinstance(x, Tensor) else Tensor(x)
                         for x in tensor_list], axis=0)
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)
    if g.nranks == 1 or _axes_in_scope(g.axes):
        return t
    _mon_collective("scatter", t._data, g.axes)
    e = env_mod.ensure_env()
    t._replace_(jax.device_put(
        _on_mesh(t._data), NamedSharding(e.mesh, _spec_on(t.ndim, g.axes, 0))))
    return t


@_traced_collective
def all_to_all(out_tensor_list, in_tensor_list=None, group=None, sync_op=True,
               split_axis=0, concat_axis=0):
    """Parity: `paddle.distributed.alltoall`. Functional form
    `all_to_all(x, split_axis=, concat_axis=)` is the EP dispatch primitive
    (reference `global_scatter`/`global_gather` ops); inside shard_map it
    lowers to the XLA AllToAll HLO."""
    g = get_group(group)
    if isinstance(out_tensor_list, list) and in_tensor_list is not None:
        from ..tensor.manipulation import concat, split as _split

        x = concat([t if isinstance(t, Tensor) else Tensor(t)
                    for t in in_tensor_list], axis=0)
        res = all_to_all(x, group=group, split_axis=0, concat_axis=0)
        out_tensor_list.extend(_split(res, g.nranks, axis=0))
        return out_tensor_list
    x = out_tensor_list
    t = x if isinstance(x, Tensor) else Tensor(x)
    if g.nranks == 1:
        return t
    _mon_collective("all_to_all", t._data, g.axes)
    ax = g.axes if len(g.axes) > 1 else g.axes[0]
    if _axes_in_scope(g.axes):
        return apply(
            "all_to_all",
            lambda a: jax.lax.all_to_all(a, ax, split_axis=split_axis,
                                         concat_axis=concat_axis, tiled=True),
            (t,),
        )
    e = env_mod.ensure_env()
    fn = _a2a_program(e.mesh, g.axes, t.ndim, split_axis, concat_axis)
    in_spec = _spec_on(t.ndim, g.axes, concat_axis)
    sharding = NamedSharding(e.mesh, in_spec)

    # route through the tape (placement inside the traced fn): an eager
    # all-to-all is linear, and jax derives its vjp — the transposed
    # all-to-all — from the shard_map program
    def _placed_a2a(a):
        return fn(jax.device_put(a, sharding))

    return apply("all_to_all", _placed_a2a, (t,))


@functools.lru_cache(maxsize=512)
def _a2a_program(mesh, axes, ndim, split_axis, concat_axis):
    ax = axes if len(axes) > 1 else axes[0]
    in_spec = _spec_on(ndim, axes, concat_axis)
    out_spec = _spec_on(ndim, axes, split_axis)

    def shard_fn(a):
        return jax.lax.all_to_all(a, ax, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)

    return jax.jit(shard_map(shard_fn, mesh=mesh, in_specs=(in_spec,),
                             out_specs=out_spec))


alltoall = all_to_all


@_traced_collective
def reduce_scatter(tensor, op=ReduceOp.SUM, group=None, sync_op=True, axis=0):
    """Parity: `paddle.distributed.reduce_scatter` — XLA ReduceScatter HLO
    in-trace; eager form shards the summed result along ``axis``."""
    g = get_group(group)
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)
    if g.nranks == 1:
        return t
    _mon_collective("reduce_scatter", t._data, g.axes)
    ax = g.axes if len(g.axes) > 1 else g.axes[0]
    if _axes_in_scope(g.axes):
        return apply(
            "reduce_scatter",
            lambda a: jax.lax.psum_scatter(a, ax, scatter_dimension=axis,
                                           tiled=True),
            (t,),
        )
    red = all_reduce(Tensor(t._data), op=op, group=group)
    e = env_mod.ensure_env()
    red._replace_(jax.device_put(
        _on_mesh(red._data), NamedSharding(e.mesh, _spec_on(t.ndim, g.axes, axis))))
    return red


@_traced_collective
def ppermute(tensor, perm, group=None):
    """`jax.lax.ppermute` exposed for pipeline schedules (reference p2p
    send/recv, `pp_utils/p2p_communication.py`). In-trace only."""
    g = get_group(group)
    ax = g.axes if len(g.axes) > 1 else g.axes[0]
    t = tensor if isinstance(tensor, Tensor) else Tensor(tensor)
    _mon_collective("ppermute", t._data, g.axes)
    return apply("ppermute", lambda a: jax.lax.ppermute(a, ax, perm), (t,))


def send(tensor, dst=0, group=None, sync_op=True):
    raise NotImplementedError(
        "point-to-point send/recv is expressed as ppermute inside pipeline "
        "schedules on TPU (XLA CollectivePermute); host-level p2p is not a "
        "TPU primitive"
    )


recv = send


@_traced_collective
def barrier(group=None):
    """Parity: `paddle.distributed.barrier`.

    Multi-host: a real rendezvous — every process must reach this point
    before any continues (host-side effects ordered around it, e.g. rank-0
    writes a file the others read). Uses the jax.distributed coordination
    service when initialized; a compiled psum over the mesh only orders
    *device* work, not hosts, so it is not sufficient (round-1 ADVICE).
    Single-process: a device round-trip flushes dispatched work.
    """
    _mon_collective("barrier", None)
    e = env_mod.ensure_env()
    if jax.process_count() > 1:
        try:
            from jax._src import distributed as _jd

            client = getattr(_jd.global_state, "client", None)
            if client is not None:
                client.wait_at_barrier(
                    f"paddle_tpu_barrier_{_barrier_seq[0]}", 60_000)
                _barrier_seq[0] += 1
                return None
        except Exception:
            pass
        # fallback: an all-reduce across the world mesh — devices of every
        # host participate, so completion implies every host dispatched it
        f = _barrier_fns.get(e.mesh)
        if f is None:
            from ..framework.jax_compat import shard_map
            from jax.sharding import PartitionSpec as P

            ax = tuple(e.mesh.axis_names)
            f = jax.jit(shard_map(lambda x: jax.lax.psum(x, ax), mesh=e.mesh,
                                  in_specs=P(), out_specs=P()))
            _barrier_fns[e.mesh] = f
        from ..utils.timing import device_sync

        # transfer-backed fence (utils/timing.py): the fetched value
        # cannot arrive before every host's all-reduce has run
        device_sync(f(jnp.ones(())))
        return None
    from ..utils.timing import device_sync

    device_sync(jnp.zeros(()))
    return None


_barrier_seq = [0]
_barrier_fns: dict = {}


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor):
        from ..utils.timing import device_sync

        device_sync(tensor._data)


# ---- object collectives (host-side; parity communication/all_gather_object) ----

def all_gather_object(object_list, obj, group=None):
    """Single-controller: every "rank" holds the same object graph."""
    g = get_group(group)
    object_list.extend([obj] * g.nranks)
    return object_list


def broadcast_object_list(object_list, src=0, group=None):
    return object_list


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    """Parity: paddle.distributed.alltoall_single — single-tensor
    all-to-all with optional uneven splits. Equal splits ride the XLA
    AllToAll HLO; uneven splits are unsupported under SPMD static shapes
    (same constraint the reference documents for its equal-split fast
    path)."""
    if in_split_sizes is not None or out_split_sizes is not None:
        raise NotImplementedError(
            "alltoall_single with uneven split sizes needs dynamic shapes, "
            "which a compiled SPMD program cannot express; pad to equal "
            "splits (the reference's fast path has the same requirement)")
    res = all_to_all(in_tensor, group=group, split_axis=0, concat_axis=0)
    if isinstance(out_tensor, Tensor):
        # inplace-adopt (same pattern as tensor inplace ops): the out=
        # form must stay differentiable through the collective
        out_tensor._data = res._data
        out_tensor._grad_node = res._grad_node
        out_tensor._out_index = res._out_index
        out_tensor.stop_gradient = (res.stop_gradient
                                    and out_tensor.stop_gradient)
        return out_tensor
    return res


def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """Parity: paddle.distributed.gather. Single-controller SPMD holds one
    logical value per mesh: gather materializes the per-shard slices the
    way all_gather does, delivered on every host (dst is advisory)."""
    g = get_group(group)
    if gather_list is None:
        gather_list = []
    parts = []
    all_gather(parts, tensor, group=group)
    if len(parts) != g.nranks:
        raise RuntimeError(
            f"gather produced {len(parts)} shards for a "
            f"{g.nranks}-rank group")
    # a reference-style caller preallocates nranks placeholders and
    # expects them *replaced*, not appended after
    gather_list[:] = parts
    return gather_list


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    """Parity: paddle.distributed.scatter_object_list (single-controller:
    every rank sees the same object graph, so rank r's slot is
    in_object_list[r] — with one logical process that is slot 0)."""
    g = get_group(group)
    if in_object_list is None:
        raise ValueError("scatter_object_list needs in_object_list")
    if len(in_object_list) != g.nranks:
        raise ValueError(
            f"in_object_list must have nranks={g.nranks} entries")
    out_object_list.append(in_object_list[g.rank])
    return out_object_list


def isend(tensor, dst=0, group=None):
    """Parity: paddle.distributed.isend — same TPU constraint as send."""
    return send(tensor, dst, group)


def irecv(tensor, src=0, group=None):
    """Parity: paddle.distributed.irecv — same TPU constraint as recv."""
    return recv(tensor, src, group)


def destroy_process_group(group=None):
    """Parity: paddle.distributed.destroy_process_group. Mesh-axis groups
    hold no OS resources (they are sharding annotations); world teardown
    resets the mesh env."""
    if group is None:
        from . import env as _env

        _env.reset_env()
    return None


def get_backend(group=None):
    """Parity: paddle.distributed.get_backend — this build's collectives
    are XLA HLOs over the PJRT runtime."""
    return "XLA"


def is_available():
    """Parity: paddle.distributed.is_available."""
    return True


_monitor_register(sys.modules[__name__])
