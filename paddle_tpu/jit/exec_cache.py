"""Unified AOT executable cache with on-disk serialized compilation.

Every compile site in the runtime — ``TrainStep`` (and through it
``AsyncStepper``, ``tools/memory_planner.py`` candidates and
``dryrun_multichip``) plus the inference ``Predictor`` — routes its
trace/lower/compile through :func:`get_or_compile`. GSPMD-partitioned
executables are deterministic functions of (fn, input avals, shardings,
mesh topology) — exactly a cache key (PAPERS.md: GSPMD 2105.04663) — so
the same artifact the runtime executes also serves XLA's own memory
accounting (``TrainStep.memory_analysis`` reuses the cached executable
instead of paying a second AOT compile).

Two tiers, both armed only while the cache is enabled
(``PT_EXEC_CACHE=<dir>`` in the environment, or :func:`enable`):

1. **In-memory** — a process-wide ``key-hash -> ExecEntry`` map, shared
   across TrainStep instances and the Predictor, so a planner sweep or a
   multi-model server compiles each distinct signature once per process.
2. **On-disk** — the compiled executable serialized via the
   ``framework/jax_compat.py`` shim (``jax.experimental
   .serialize_executable``) into ``<dir>/<key-hash>.ptxc``; a cold
   process deserializes instead of recompiling — zero fresh XLA compiles
   for a warm signature. Any mismatch (format version, key, platform,
   corrupt file, backend that can't deserialize) falls back to a fresh
   compile; the cache can only ever cost a retry, never correctness.

Key anatomy (see ``TrainStep._cache_key`` for the train-step instance):
callers build a plain nested structure of scalars/tuples; this module
wraps it with the global invalidators — jax version, backend + device
kind + device count, and a size+mtime fingerprint of the installed
``paddle_tpu`` package (ANY source edit invalidates the disk tier: model
code is baked into executables, so staleness here would be silent wrong
numerics). The full key repr is stored in the artifact and compared on
load — a hash collision cannot alias two programs.

Off-is-free contract: when the cache is disabled (the default),
:func:`get_or_compile` is a straight timed compile — no key is built
(callers pass ``key=None``), no tier is consulted, and the monitor
counters follow the ``None``-slot pattern
(``jit/exec_cache_{hit,miss,deserialize_ms,serialize_ms}`` — this module
is in ``monitor.INSTRUMENTED_MODULES``). ``jit/compiles`` /
``jit/compile_ms`` fire here on every true compile regardless of the
cache state (this is THE compile chokepoint now). Details:
``docs/EXEC_CACHE.md``.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import hashlib
import json
import os
import pickle
import re
import sys
import threading
import time
import types
import weakref

import jax
import numpy as np

from ..framework import jax_compat as _jc
from ..monitor import _register as _monitor_register
from ..monitor import scopes as _scopes

__all__ = [
    "get_or_compile", "ExecEntry", "enable", "disable", "enabled",
    "cache_dir", "clear", "stats", "key_hash", "array_spec",
    "array_digest", "freeze_attrs", "fingerprint_callable", "mesh_spec",
    "meta_get", "meta_put", "FORMAT",
]

# bump on any change to the artifact layout or key schema
FORMAT = 2

# telemetry slot (paddle_tpu.monitor None-slot contract): None unless
# PT_MONITOR wired it
_monitor = None

# compiled-program audit slot (analysis/program_audit.py): None unless
# PT_PROGRAM_AUDIT armed it — same zero-overhead-off contract; every
# fresh compile (and every cache hit, for sidecar re-reporting) at this
# chokepoint is offered to the auditor when the slot is live
_audit = None

# -- state -------------------------------------------------------------------

# on-disk tier directory; None = cache disabled (both tiers)
_dir: str | None = os.environ.get("PT_EXEC_CACHE") or None

# in-memory tier: key-hash -> ExecEntry (process-wide, cross-instance).
# LRU-bounded: callers (TrainStep._cache, Predictor) hold their own
# reference to the entries they use, so eviction here only drops
# cross-instance sharing — it never invalidates a live executable.
_mem: "collections.OrderedDict" = collections.OrderedDict()

# mem-tier bound: without one, every distinct signature ever compiled
# (each pinning an XLA executable's host+device program memory) lives
# until process exit — a multi-model server could never free an
# unloaded model's executables
_MAX_MEM_ENTRIES = int(os.environ.get("PT_EXEC_CACHE_MEM_LIMIT", "64") or 64)

# serializes the enabled-path compile+store: _fresh_compile toggles the
# GLOBAL jax compilation-cache flag, so two threads warming models
# concurrently could re-enable it under each other's compile and
# resurface the "Symbols not found" poisoned-artifact bug
_compile_lock = threading.Lock()

# disk-tier bound: every source edit orphans all artifacts under new
# hashes, so an iterating developer accumulates them — prune oldest past
# this many files on store
_MAX_DISK_ENTRIES = int(os.environ.get("PT_EXEC_CACHE_LIMIT", "256") or 256)

# plain-int bookkeeping, always on (read by tools / the dryrun proof
# line; independent of the monitor so the numbers exist without it)
_stats = {"mem_hits": 0, "disk_hits": 0, "misses": 0, "serialized": 0,
          "errors": 0, "compile_ms_saved": 0.0}

_warned: set = set()


def _warn_once(msg: str) -> None:
    if msg not in _warned:
        _warned.add(msg)
        print(f"exec_cache: {msg}", file=sys.stderr, flush=True)


def enabled() -> bool:
    return _dir is not None


def cache_dir() -> str | None:
    return _dir


def enable(directory: str) -> None:
    """Arm both tiers at ``directory`` (same effect as starting the
    process with ``PT_EXEC_CACHE=<directory>``)."""
    global _dir
    _dir = os.path.expanduser(str(directory))


def disable() -> None:
    """Disarm both tiers; compiled-but-cached entries stay referenced by
    their TrainStep owners, the process-wide map is dropped."""
    global _dir
    _dir = None
    _mem.clear()


def clear() -> None:
    """Drop the in-memory tier (the disk tier is left on disk) and zero
    the plain-int stats — test isolation hook."""
    _mem.clear()
    _meta_mem.clear()
    for k in _stats:
        _stats[k] = 0.0 if k == "compile_ms_saved" else 0


def stats() -> dict:
    out = dict(_stats)
    out["enabled"] = enabled()
    out["dir"] = _dir
    out["mem_entries"] = len(_mem)
    return out


# -- key building ------------------------------------------------------------

def _freeze(obj):
    """Canonical hashable form of a caller key: dicts sort, sequences
    become tuples, scalars pass through, anything else reprs."""
    if isinstance(obj, dict):
        return tuple((str(k), _freeze(v))
                     for k, v in sorted(obj.items(), key=lambda kv: str(kv[0])))
    if isinstance(obj, (list, tuple)):
        return tuple(_freeze(v) for v in obj)
    if isinstance(obj, (set, frozenset)):
        return tuple(sorted(repr(v) for v in obj))
    if isinstance(obj, (int, float, bool, str, bytes, type(None))):
        return obj
    # default object reprs differ across processes ONLY by address —
    # strip it or the disk tier never hits again for that key
    return re.sub(r" at 0x[0-9a-f]+", "", repr(obj))


def array_spec(x) -> tuple:
    """(shape, dtype, sharding, memory_kind) of an array — the aval +
    placement facts an executable is specialized on."""
    sh = getattr(x, "sharding", None)
    return (tuple(int(d) for d in getattr(x, "shape", ())),
            str(getattr(x, "dtype", "?")),
            str(sh) if sh is not None else None,
            getattr(sh, "memory_kind", None))


# id(arr) -> (weakref, spec, digest): arrays are immutable in jax, so a
# digest is valid as long as the SAME object is alive (the weakref +
# spec re-check guards id reuse after GC)
_digest_memo: dict = {}


def array_digest(x) -> tuple:
    """Content hash of an array that gets BAKED into a program as a
    constant (frozen params, ASP masks) — value changes must re-key.

    ``np.asarray`` is a full device→host transfer (expensive for big
    arrays), so digests are memoized per array
    OBJECT: each frozen param is fetched at most once per process, not
    once per signature miss."""
    spec = array_spec(x)
    hit = _digest_memo.get(id(x))
    if hit is not None and hit[0]() is x and hit[1] == spec:
        return hit[2]
    try:
        b = np.asarray(x).tobytes()
    except Exception:  # noqa: BLE001 — undigestable: key on the spec only
        return ("nodigest",) + spec
    dig = (hashlib.sha256(b).hexdigest()[:16],) + spec
    try:
        if len(_digest_memo) > 4096:  # purge dead entries, bound the map
            for k in [k for k, v in _digest_memo.items() if v[0]() is None]:
                del _digest_memo[k]
        _digest_memo[id(x)] = (weakref.ref(x), spec, dig)
    except TypeError:
        pass  # not weakref-able: recompute next call
    return dig


def _stable(v, depth: int = 3):
    """Address-free form of an attribute value: scalars by value, plain
    containers structurally (nn loss layers keep their hyperparams in a
    ``self._args`` dict), anything else by type qualname — NEVER repr,
    whose ``0x7f...`` addresses would flip disk-tier keys per process."""
    if isinstance(v, (int, float, bool, str, bytes, type(None))):
        return v
    if depth <= 0:
        return type(v).__qualname__
    if isinstance(v, dict):
        return tuple((str(k), _stable(x, depth - 1))
                     for k, x in sorted(v.items(), key=lambda kv: str(kv[0])))
    if isinstance(v, (list, tuple)):
        return tuple(_stable(x, depth - 1) for x in v)
    if isinstance(v, (set, frozenset)):
        return tuple(sorted(str(_stable(x, depth - 1)) for x in v))
    return type(v).__qualname__


def freeze_attrs(obj, exclude: tuple = ()) -> tuple | None:
    """Type qualname + the scalar and scalar-container attributes of
    ``obj.__dict__`` — the hyperparameters (betas, eps, weight-decay
    coeffs, a loss layer's ``_args`` dict...) that are traced into a
    program as constants. Arrays and arbitrary objects contribute only
    their type (they either arrive as runtime args or get keyed
    explicitly — TrainStep does for frozen params and ASP masks)."""
    if obj is None:
        return None
    out = [type(obj).__module__ + "." + type(obj).__qualname__]
    for k in sorted(getattr(obj, "__dict__", {})):
        if k in exclude:
            continue
        out.append((k, _stable(obj.__dict__[k])))
    return tuple(out)


def _const_fp(c):
    """Structural form of a code const: ``repr()`` of a nested code
    object embeds its memory address ('<code object ... at 0x7f...>'),
    which would flip the disk-tier key every process — hash nested code
    recursively instead."""
    if isinstance(c, types.CodeType):
        return ("code", c.co_name,
                hashlib.sha256(c.co_code).hexdigest()[:16],
                _const_fp(c.co_consts), ",".join(c.co_names))
    if isinstance(c, tuple):
        return tuple(_const_fp(v) for v in c)
    if isinstance(c, frozenset):
        return tuple(sorted(repr(v) for v in c))
    return repr(c)


def _callable_attrs(obj, _seen) -> tuple:
    """Fingerprints of the callable instance attrs of ``obj`` — a bound
    method or ``__call__`` object reads them at trace time, so they are
    program identity (hapi's ``Model._loss_fn`` reads ``self._loss``:
    two Models differing only in loss layer must not share a key)."""
    out = []
    for k in sorted(getattr(obj, "__dict__", {})):
        v = obj.__dict__[k]
        if callable(v) and not isinstance(v, type):
            out.append((k, fingerprint_callable(v, _seen)))
    return tuple(out)


def _value_fp(v, _seen):
    """Fingerprint of one trace-time-constant value (a closure cell, a
    default, a partial arg): scalars by value, arrays by content digest,
    callables recursively, anything else by type name."""
    if isinstance(v, (int, float, bool, str, bytes, type(None))):
        return repr(v)
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        # baked into the trace as a constant
        return array_digest(v)
    if callable(v):
        return fingerprint_callable(v, _seen)
    return type(v).__qualname__


def fingerprint_callable(fn, _seen=None) -> tuple | str:
    """Best-effort identity of a traced callable: bytecode + consts +
    names + closure cells + argument defaults (scalars by value, arrays
    by content digest, callables recursively), ``functools.partial``
    structurally (inner fn + bound args), plus the scalar instance state
    of bound methods and ``__call__`` objects — anything the trace bakes
    in as a constant. Lambdas with equal code hash equal — exactly what
    the planner's and bench's loss lambdas need.

    Residual under-keying: non-scalar, non-array, non-callable state
    read at trace time (a dict attr, a nested data object) contributes
    only its type name. Callers that bake such state must key it
    explicitly — TrainStep does for frozen params, ASP masks, and
    optimizer/regularizer scalars."""
    if _seen is None:
        _seen = set()
    if id(fn) in _seen:  # e.g. a recursive lambda closing over itself
        return ("cycle",)
    _seen.add(id(fn))
    bound = getattr(fn, "__func__", None)
    if bound is not None:
        # a bound method's instance attrs are trace-time constants:
        # scalars by value via freeze_attrs, callables (a loss Layer on
        # hapi's Model._loss_fn, a sub-step) by their own fingerprint
        return ("bound", fingerprint_callable(bound, _seen),
                freeze_attrs(fn.__self__),
                _callable_attrs(fn.__self__, _seen))
    if isinstance(fn, functools.partial):
        # a partial's bound args are trace-time constants exactly like
        # closure cells; the bare type name would alias EVERY partial
        return ("partial", fingerprint_callable(fn.func, _seen),
                tuple(_value_fp(a, _seen) for a in fn.args),
                tuple((k, _value_fp(v, _seen))
                      for k, v in sorted(fn.keywords.items())))
    code = getattr(fn, "__code__", None)
    if code is None:
        # callable object: its __call__ bytecode + its scalar attrs +
        # its callable attrs (same baked-constant argument as above)
        call = getattr(type(fn), "__call__", None)
        if call is not None and getattr(call, "__code__", None) is not None:
            return ("obj", fingerprint_callable(call, _seen),
                    freeze_attrs(fn), _callable_attrs(fn, _seen))
        return type(fn).__module__ + "." + type(fn).__qualname__
    h = hashlib.sha256(code.co_code)
    h.update(repr(_const_fp(code.co_consts)).encode())
    h.update(",".join(code.co_names).encode())
    cells = []
    for name, cell in zip(code.co_freevars, fn.__closure__ or ()):
        try:
            v = cell.cell_contents
        except ValueError:
            cells.append((name, "<empty>"))
            continue
        cells.append((name, _value_fp(v, _seen)))
    # defaults are trace-time constants too: `lambda m, x, y, w=w: ...`
    # built in a hyperparam loop differs ONLY here
    dflt = tuple(_value_fp(v, _seen) for v in fn.__defaults__ or ())
    kwd = tuple((k, _value_fp(v, _seen))
                for k, v in sorted((fn.__kwdefaults__ or {}).items()))
    return (code.co_name, h.hexdigest()[:16], tuple(cells), dflt, kwd)


@functools.lru_cache(maxsize=None)
def fingerprint_class(cls) -> tuple:
    """Bytecode fingerprint of a class's own methods, for classes
    defined OUTSIDE the installed package: ``_code_fingerprint``'s
    size+mtime walk cannot see a user's ``model.py``, so an edited
    ``forward()`` must invalidate through the key instead (model code is
    baked into the executable — staleness here is silent wrong
    numerics). In-package and builtin classes contribute nothing (the
    package walk already covers them)."""
    out = []
    for klass in cls.__mro__:
        mod = klass.__module__ or ""
        if mod == "builtins" or mod == "paddle_tpu" \
                or mod.startswith("paddle_tpu."):
            continue
        for name in sorted(vars(klass)):
            v = vars(klass)[name]
            if isinstance(v, (staticmethod, classmethod)):
                v = v.__func__
            if isinstance(v, types.FunctionType):
                out.append((klass.__qualname__, name,
                            fingerprint_callable(v)))
    return tuple(out)


def mesh_spec() -> tuple | None:
    """Axis names + shape of the active mesh (None when single-device) —
    partitioned executables are topology-specific."""
    try:
        from ..distributed import env as env_mod

        e = env_mod.get_env()
        if e is None:
            return None
        return (tuple(e.mesh.axis_names),
                tuple(int(d) for d in e.mesh.devices.shape))
    except Exception:  # noqa: BLE001
        return None


def _platform_spec() -> tuple:
    devs = jax.devices()
    # codegen-relevant jax config is executable identity too: a
    # matmul-precision or x64 flip produces a different program for the
    # same caller key (conftest pins precision 'highest'; bench doesn't)
    cfg = tuple(
        (name, str(getattr(jax.config, name, None)))
        for name in ("jax_default_matmul_precision", "jax_enable_x64",
                     "jax_numpy_dtype_promotion"))
    return (jax.__version__, jax.default_backend(),
            getattr(devs[0], "device_kind", "?"), len(devs), cfg)


@functools.lru_cache(maxsize=1)
def _code_fingerprint() -> str:
    """size+mtime walk of the installed package: ANY source edit flips
    the fingerprint, so a code change can never serve a stale executable
    (mtime-only churn — e.g. a git checkout — costs one recompile, which
    is the safe direction)."""
    import paddle_tpu

    root = os.path.dirname(os.path.abspath(paddle_tpu.__file__))
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            p = os.path.join(dirpath, fn)
            try:
                st = os.stat(p)
            except OSError:
                continue
            h.update(f"{os.path.relpath(p, root)}:{st.st_size}:"
                     f"{st.st_mtime_ns};".encode())
    return h.hexdigest()[:16]


def key_hash(key) -> tuple[str, str]:
    """(full key repr, sha256 hex) with the global invalidators — format
    version, platform, package fingerprint — folded in."""
    full = (FORMAT, _platform_spec(), _code_fingerprint(), _freeze(key))
    rep = repr(full)
    return rep, hashlib.sha256(rep.encode()).hexdigest()


# -- entries -----------------------------------------------------------------

class ExecEntry:
    """One cached executable: callable, introspectable, provenance-
    stamped. ``source`` is ``compile`` | ``mem`` | ``disk`` (how THIS
    process obtained it); ``compile_ms`` is the wall time the original
    trace+lower+compile cost (carried through the disk tier — the
    'saved' number on a warm hit)."""

    __slots__ = ("compiled", "key_hash", "source", "compile_ms")

    def __init__(self, compiled, key_hash, source, compile_ms):
        self.compiled = compiled
        self.key_hash = key_hash
        self.source = source
        self.compile_ms = compile_ms

    def __call__(self, *args):
        return self.compiled(*args)

    def memory_analysis(self):
        """XLA's own accounting of the executable — works on deserialized
        executables too, so warm starts get HBM numbers compile-free."""
        return self.compiled.memory_analysis()


# -- meta sidecar ------------------------------------------------------------

# derived facts about a cached executable (the planner's per-axis
# collective bytes parsed from its post-SPMD HLO), keyed by the SAME
# key as the executable itself — the facts and the artifact invalidate
# together (any source edit, jax bump, or topology change flips the key
# hash for both). In-memory tier always works; the JSON disk tier rides
# the cache dir so a warm planner sweep re-reads its comms account with
# zero fresh traces. Bounded like the mem tier.
_meta_mem: "collections.OrderedDict" = collections.OrderedDict()


def _meta_path(sha: str) -> str:
    return os.path.join(_dir, sha[:32] + ".meta.json")


def meta_get(key) -> dict | None:
    """Sidecar facts stored under ``key`` (None = no key / no facts)."""
    if key is None:
        return None
    _rep, sha = key_hash(key)
    hit = _meta_mem.get(sha)
    if hit is not None:
        return hit
    if not enabled():
        return None
    try:
        with open(_meta_path(sha)) as f:
            blob = json.load(f)
        if not (isinstance(blob, dict) and blob.get("format") == FORMAT
                and blob.get("key_sha") == sha):
            return None
        meta = blob.get("meta")
    except (OSError, ValueError):
        return None
    if isinstance(meta, dict):
        _meta_mem[sha] = meta
        while len(_meta_mem) > _MAX_MEM_ENTRIES:
            with contextlib.suppress(KeyError):
                _meta_mem.popitem(last=False)
        return meta
    return None


def meta_put(key, meta: dict) -> None:
    """Store sidecar facts under ``key`` (JSON-able dict); disk write is
    atomic and best-effort — losing it only costs a re-derivation."""
    if key is None or not isinstance(meta, dict):
        return
    _rep, sha = key_hash(key)
    _meta_mem[sha] = meta
    while len(_meta_mem) > _MAX_MEM_ENTRIES:
        with contextlib.suppress(KeyError):
            _meta_mem.popitem(last=False)
    if not enabled():
        return
    try:
        os.makedirs(_dir, exist_ok=True)
        path = _meta_path(sha)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"format": FORMAT, "key_sha": sha, "meta": meta}, f)
        os.replace(tmp, path)
    except OSError:
        pass  # an unwritable dir must never break planning


# -- the cache ---------------------------------------------------------------

def _path_for(sha: str) -> str:
    return os.path.join(_dir, sha[:32] + ".ptxc")


def _disk_load(sha: str, rep: str) -> ExecEntry | None:
    path = _path_for(sha)
    if not os.path.exists(path):
        return None
    t0 = time.perf_counter()
    try:
        with open(path, "rb") as f:
            blob = pickle.load(f)
        if not (isinstance(blob, dict) and blob.get("format") == FORMAT
                and blob.get("key") == rep):
            raise ValueError("format/key mismatch (version skew?)")
        compiled = _jc.deserialize_executable(
            blob["payload"], blob["in_tree"], blob["out_tree"],
            blob["device_ids"])
    except Exception as e:  # noqa: BLE001 — ANY bad artifact = fresh compile
        _stats["errors"] += 1
        _warn_once(f"ignoring {os.path.basename(path)} "
                   f"({type(e).__name__}: {e})")
        return None
    ms = (time.perf_counter() - t0) * 1e3
    saved = float(blob.get("compile_ms") or 0.0)
    _stats["disk_hits"] += 1
    _stats["compile_ms_saved"] += saved
    m = _monitor
    if m is not None:
        m.on_exec_cache_hit("disk", saved_ms=saved or None)
        m.on_exec_cache_deserialize_ms(ms)
    return ExecEntry(compiled, sha, "disk", saved or None)


def _disk_store(sha: str, rep: str, compiled, compile_ms: float,
                label: str | None) -> None:
    try:
        os.makedirs(_dir, exist_ok=True)
        t0 = time.perf_counter()
        payload, in_tree, out_tree, device_ids = \
            _jc.serialize_executable(compiled)
        # trial load before committing: a backend can serialize a payload
        # that only dies at deserialize (e.g. an XLA-cache-served
        # executable missing its object code) — never persist one
        _jc.deserialize_executable(payload, in_tree, out_tree, device_ids)
        blob = {"format": FORMAT, "key": rep, "label": label,
                "compile_ms": round(compile_ms, 3), "created": time.time(),
                "payload": payload, "in_tree": in_tree,
                "out_tree": out_tree, "device_ids": device_ids}
        path = _path_for(sha)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            pickle.dump(blob, f, protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, path)  # atomic: racing planner children are safe
        ms = (time.perf_counter() - t0) * 1e3
        _stats["serialized"] += 1
        m = _monitor
        if m is not None:
            m.on_exec_cache_serialize_ms(ms)
        _prune_disk()
    except Exception as e:  # noqa: BLE001 — serialization is an
        # optimization; a backend that can't serialize still trains
        _stats["errors"] += 1
        _warn_once(f"disk tier unavailable ({type(e).__name__}: {e})")


def _prune_disk() -> None:
    """Keep the newest ``PT_EXEC_CACHE_LIMIT`` (256) artifacts: source
    edits orphan every existing hash, and orphans are never re-read."""
    try:
        for ext in (".ptxc", ".meta.json"):
            paths = [os.path.join(_dir, f) for f in os.listdir(_dir)
                     if f.endswith(ext)]
            if len(paths) <= _MAX_DISK_ENTRIES:
                continue
            paths.sort(key=lambda p: os.stat(p).st_mtime)
            for p in paths[:len(paths) - _MAX_DISK_ENTRIES]:
                os.unlink(p)
    except OSError:
        pass  # a racing child pruned first, or the dir went away


@contextlib.contextmanager
def _fresh_compile():
    """Suppress XLA's own persistent compilation cache for a compile
    we're about to serialize: on this jax (0.4.37), an XLA-cache-served
    CpuExecutable re-serializes WITHOUT its jitted object code — the
    artifact then dies at load with "Symbols not found". Our disk tier
    supersedes XLA's cache for these executables anyway; a fresh compile
    is the price of a self-contained artifact.

    Toggling ``jax_enable_compilation_cache`` alone is NOT enough:
    ``compilation_cache.is_cache_used`` latches its verdict on the first
    compile of the process, so once any earlier compile initialized the
    cache the flag is ignored. ``reset_cache()`` drops that latch (and
    only in-process state — the disk cache files survive); a second
    reset in ``finally`` lets the next ordinary compile re-latch with
    the restored setting."""
    try:
        from jax._src import compilation_cache as _cc

        prev = bool(jax.config.jax_enable_compilation_cache)
    except (ImportError, AttributeError):  # internals moved: serialize
        yield                              # whatever we get
        return
    jax.config.update("jax_enable_compilation_cache", False)
    _cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        _cc.reset_cache()


def _mem_hit(sha: str) -> "ExecEntry | None":
    """Mem-tier lookup + LRU touch + hit accounting (None on miss)."""
    e = _mem.get(sha)
    if e is None:
        return None
    with contextlib.suppress(KeyError):  # racing eviction/clear
        _mem.move_to_end(sha)
    _stats["mem_hits"] += 1
    m = _monitor
    if m is not None:
        m.on_exec_cache_hit("mem")
    return e


def _mem_put(sha: str, entry: "ExecEntry") -> None:
    """Insert into the mem tier, evicting least-recently-used past the
    bound. Callers keep their own reference (TrainStep._cache / the
    Predictor), so eviction never kills a live executable."""
    _mem[sha] = entry
    _mem.move_to_end(sha)
    while len(_mem) > _MAX_MEM_ENTRIES:
        with contextlib.suppress(KeyError):
            _mem.popitem(last=False)


def get_or_compile(key, lower_fn, label: str | None = None) -> ExecEntry:
    """The one compile chokepoint.

    ``key``: the caller's fingerprint structure (None = uncacheable, go
    straight to a timed compile — what callers pass while the cache is
    disabled, so no key is ever built for nothing). ``lower_fn``: zero-arg
    callable returning a ``jax.stages.Lowered`` (trace+lower happens
    inside it, so a hit skips tracing too on the mem tier and everything
    but deserialization on the disk tier). Every executable handed out
    for the first time leaves its text with ``monitor/scopes`` (which
    layer issued each instruction: what a device trace is joined to).
    """
    au = _audit
    if key is not None and enabled():
        rep, sha = key_hash(key)
        e = _mem_hit(sha)
        if e is not None:
            if au is not None:
                au.on_hit(e, key, label)
            return e
        # the lock serializes the whole miss path: the _fresh_compile
        # toggle is process-global (two threads interleaving it would
        # hand one an XLA-cache-served executable that serializes
        # without object code), and the miss/hit accounting must stay
        # coherent — a thread that loses the race records ONE event (a
        # mem hit), never a miss without a compile
        with _compile_lock:
            e = _mem_hit(sha)  # a racing thread may have just compiled it
            if e is not None:
                if au is not None:
                    au.on_hit(e, key, label)
                return e
            e = _disk_load(sha, rep)
            if e is not None:
                _mem_put(sha, e)
                _scopes.record(label, e.compiled)
                if au is not None:
                    au.on_hit(e, key, label)
                return e
            _stats["misses"] += 1
            m = _monitor
            if m is not None:
                m.on_exec_cache_miss()
            t0 = time.perf_counter()
            with _fresh_compile():
                compiled = lower_fn().compile()
            ms = (time.perf_counter() - t0) * 1e3
            m = _monitor
            if m is not None:
                m.on_compile_ms(ms)
            entry = ExecEntry(compiled, sha, "compile", ms)
            _scopes.record(label, compiled)
            _mem_put(sha, entry)
            _disk_store(sha, rep, compiled, ms, label)
            if au is not None:
                au.on_compiled(entry, key, label)
            return entry
    t0 = time.perf_counter()
    compiled = lower_fn().compile()
    ms = (time.perf_counter() - t0) * 1e3
    m = _monitor
    if m is not None:
        m.on_compile_ms(ms)
    entry = ExecEntry(compiled, None, "compile", ms)
    _scopes.record(label, compiled)
    if au is not None:
        au.on_compiled(entry, key, label)
    return entry


_monitor_register(sys.modules[__name__])

# arm the program audit when requested: importing the auditor installs
# it into the _audit slot above (analysis/program_audit.py). Kept after
# _monitor_register so an armed process still satisfies the
# zero-overhead audit's module-registration order.
if os.environ.get("PT_PROGRAM_AUDIT", "0") not in ("", "0"):
    from ..analysis import program_audit as _program_audit  # noqa: F401
