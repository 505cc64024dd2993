"""Flash-attention block-size autotuner with a persisted cache.

The reference carries an Ansor-like kernel tuner
(`paddle/cinn/auto_schedule/auto_tuner.h`) and a GPU autotune cache
(`paddle/phi/kernels/autotune/cache.h`); this is that component at Pallas
scale: per-shape search over (block_q, block_k) for the flash kernels,
measured on the real chip with an amortized in-program loop (a host
fence per dispatch dwarfs a sub-millisecond kernel, so per-dispatch
timing is meaningless), persisted to ``flash_tune.json`` next to this
module with device/commit provenance. The rows in the tracked table date
from 2026-07-31 (pre-PR-1 tree, not reproduced since).

The cache ALSO re-derives the engagement heuristic: each entry stores the
kernel-vs-XLA-composite fwd+bwd ratio, so `flash_attention_kernel` engages
the Pallas kernel exactly where it measured faster, replacing
hand-edited thresholds.
"""
from __future__ import annotations

import json
import math
import os
import tempfile
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ...framework.device import on_tpu

_CACHE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "flash_tune.json")
_cache: Optional[Dict[str, Any]] = None


def _key(sq: int, sk: int, d: int, causal: bool,
         dropout: float = 0.0) -> str:
    base = f"s{sq}x{sk}_d{d}_{'c' if causal else 'f'}"
    if dropout > 0.0:
        base += f"_p{dropout:g}"
    return base


def load_cache() -> Dict[str, Any]:
    global _cache
    if _cache is None:
        try:
            with open(_CACHE_PATH) as f:
                _cache = json.load(f)
        except (OSError, ValueError):
            _cache = {"entries": {}}
    return _cache


def _atomic_write(path: str, data: Dict[str, Any]) -> None:
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                               prefix=".flash_tune_", suffix=".json")
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


def save_cache(cache: Dict[str, Any]) -> None:
    """Full-cache write — fcntl-locked + atomic tmp/rename
    (utils/measurements.py discipline; the old bare ``open(..., "w")``
    could tear under concurrent hwbench/autotune writers). Prefer
    :func:`update_cache` for read-modify-write."""
    global _cache
    _cache = cache
    from ...utils.measurements import _StoreLock

    with _StoreLock(_CACHE_PATH):
        _atomic_write(_CACHE_PATH, cache)


def update_cache(mutator) -> Dict[str, Any]:
    """Locked read-modify-write: reload from disk under the lock, apply
    ``mutator(cache)``, write atomically — concurrent tuners (hwbench's
    flashtune stage + a manual run) cannot drop each other's rows."""
    global _cache
    from ...utils.measurements import _StoreLock

    with _StoreLock(_CACHE_PATH):
        try:
            with open(_CACHE_PATH) as f:
                data = json.load(f)
            if not (isinstance(data, dict)
                    and isinstance(data.get("entries"), dict)):
                data = {"entries": {}}
        except (OSError, ValueError):
            data = {"entries": {}}
        mutator(data)
        _atomic_write(_CACHE_PATH, data)
    _cache = data
    return data


def _device_kind() -> Optional[str]:
    try:
        return getattr(jax.devices()[0], "device_kind", None)
    except Exception:  # noqa: BLE001 — no backend, no filtering
        return None


def _device_entries() -> Dict[str, Any]:
    """Cache entries measured on the RUNNING device generation only — a
    cache tuned on v5e must not drive decisions on v6e."""
    entries = load_cache().get("entries", {})
    kind = _device_kind()
    if kind is None:
        return entries
    return {k: e for k, e in entries.items()
            if e.get("device") in (None, kind)}


def lookup(sq: int, sk: int, d: int, causal: bool, *,
           exact: bool = False) -> Optional[Dict[str, Any]]:
    """Exact-shape cache entry, or (unless ``exact``) the nearest
    same-d/causal seq within one octave per dimension (block choices
    transfer well between close sequence lengths)."""
    entries = _device_entries()
    hit = entries.get(_key(sq, sk, d, causal))
    if hit is not None or exact:
        return hit
    best, best_dist = None, None
    for e in entries.values():
        if e["d"] != d or e["causal"] != causal:
            continue
        dq = abs(math.log2(max(e["sq"], 1) / max(sq, 1)))
        dk = abs(math.log2(max(e["sk"], 1) / max(sk, 1)))
        if dq > 1.0 or dk > 1.0:  # transfer at most one octave per dim
            continue
        if best_dist is None or dq + dk < best_dist:
            best, best_dist = e, dq + dk
    return best


def best_blocks(sq: int, sk: int, d: int, causal: bool
                ) -> Tuple[Optional[int], Optional[int]]:
    e = lookup(sq, sk, d, causal)
    if e is None:
        return None, None
    bq, bk = e["block_q"], e["block_k"]
    # a transferred entry must still tile the actual shape
    if sq % bq or sk % bk:
        return None, None
    return bq, bk


def kernel_beats_composite(sq: int, sk: int, d: int, causal: bool,
                           margin: float = 1.0,
                           dropout: float = 0.0) -> Optional[bool]:
    """Measured engagement decision; None when no measurement applies.

    Exact-shape hits only: the win/lose ratio flips across the measured
    seq crossover (round-4 DCE-free timing: composite wins at s=512,
    kernel from s=1024 — 3.4-6.1x, growing with seq), so transferring
    the verdict one octave would invert it exactly at the crossover.
    Block sizes transfer (see `best_blocks`); the binary verdict does not.
    ``margin > 1`` demands measured headroom — used when the caller adds
    unmeasured work on top of the measured configuration (in-kernel
    dropout adds hash+select VPU time the no-dropout rows don't carry).
    ``dropout``: a measured VARIANT row (tune_shape(dropout=...)) wins
    over the margin heuristic when one exists at this exact shape.
    """
    if dropout > 0.0:
        ev = _device_entries().get(_key(sq, sk, d, causal, dropout))
        if ev is not None and "ratio_fwd_bwd" in ev:
            return ev["ratio_fwd_bwd"] > 1.0
    e = lookup(sq, sk, d, causal, exact=True)
    if e is None or "ratio_fwd_bwd" not in e:
        return None
    return e["ratio_fwd_bwd"] > margin


def _candidates(seq: int):
    out = []
    for b in (128, 256, 512, 1024):
        if b <= seq and seq % b == 0:
            out.append(b)
    return out or [seq]


_sync_overhead: Dict[str, float] = {}


def _time_compiled(fn, args, iters=20, n_hint=None) -> float:
    """Amortized per-iteration seconds.

    Two things shape this method (both once produced plausible-looking
    0.01 ms "measurements" for s=4096 attention — 30x past chip peak):

    - the fence is a device->host transfer (`float(out[0, ...])`): the
      value cannot arrive before the loop that produces it has finished
      (see utils/timing.py).
    - the per-call dispatch + fence overhead dwarfs sub-ms kernels and
      jitters. So time TWO compiled loops (n and 4*n dependent
      applications) and divide the DIFFERENCE by 3*n: the constant
      overhead cancels, and n is sized so the difference carries ~600 ms
      of kernel time.

    The loop body feeds the output back as the next query — a true data
    dependence (`q + 0.0 * r.mean()` gets algebraically simplified away
    and the kernel DCE'd).
    """

    def make(n):
        @jax.jit
        def loop(*a):
            def body(_, q):
                r = fn(q, *a[1:])
                if r.shape == q.shape:
                    return r.astype(q.dtype)
                return q + r.astype(q.dtype).sum() * 1e-12

            return jax.lax.fori_loop(0, n, body, a[0])

        return loop

    def run(loop):
        t0 = time.perf_counter()
        out = loop(*args)
        float(out[(0,) * out.ndim])  # full sync (transfer-backed)
        return time.perf_counter() - t0

    if iters < 16 and not on_tpu():
        # smoke mode (interpret-mode CPU tests): one short loop, no
        # calibration — accuracy is irrelevant, wall-clock is not.
        # CPU-only: on a real backend small --iters still calibrates, so
        # a hardware tune can never persist uncalibrated numbers.
        loop = make(iters)
        run(loop)  # compile + warm
        return max(run(loop), 1e-9) / iters

    # constant dispatch + fence overhead: a property of the harness, not
    # of fn — measure once per backend and memoize
    overhead = _sync_overhead.get(jax.default_backend())
    if overhead is None:
        empty = make(0)
        run(empty)
        overhead = min(run(empty) for _ in range(2))
        _sync_overhead[jax.default_backend()] = overhead
    # calibrate: size n so the long-short difference carries ~600 ms of
    # kernel time — well above the measured ~±15 ms sync jitter.
    # Candidates of one shape/direction run within a small factor of each
    # other, so callers may share a calibration via n_hint (a mutable
    # dict) instead of paying the ~3 calibration runs per candidate.
    if n_hint and "n" in n_hint:
        n = n_hint["n"]
    else:
        cal_n = max(iters, 128)
        cal = make(cal_n)
        run(cal)  # compile + warm
        t_cal = min(run(cal) for _ in range(2))
        t_est = max((t_cal - overhead) / cal_n, 2e-7)
        n = int(min(max(0.6 / (3 * t_est), 8), 20000))
        if n_hint is not None:
            n_hint["n"] = n

    short, long_ = make(n), make(4 * n)
    run(short), run(long_)  # compile + warm both
    deltas = sorted(run(long_) - run(short) for _ in range(3))
    return max(deltas[1], 1e-9) / (3 * n)


def tune_shape(bh: int, sq: int, sk: int, d: int, causal: bool,
               dtype=jnp.bfloat16, iters: int = 20,
               verbose: bool = True) -> Dict[str, Any]:
    """Search (block_q, block_k) for one shape on the LIVE backend; also
    measure the XLA composite for the engagement ratio. Returns the cache
    entry (already persisted)."""
    from .flash_attention import _flash_bhsd

    scale = 1.0 / math.sqrt(d)
    q = jax.random.normal(jax.random.PRNGKey(0), (bh, sq, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (bh, sk, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (bh, sk, d), dtype)

    composite = _composite_sdpa(sq, sk, causal, scale)
    gradify = _gradify

    # the composite baseline may OOM at long-context shapes (it
    # materializes the [sq, sk] score matrix the flash kernel exists to
    # avoid) — tune the kernel anyway, just without an engagement ratio
    try:
        t_comp_fwd = _time_compiled(composite, (q, k, v), iters)
        t_comp_fb = _time_compiled(gradify(composite), (q, k, v), iters)
    except Exception as e:  # noqa: BLE001 — baseline OOM must not stop tuning
        if verbose:
            print(f"  composite baseline failed ({type(e).__name__}); "
                  f"tuning kernel without a ratio", flush=True)
        t_comp_fwd = t_comp_fb = None

    results = []
    hint_fwd, hint_fb = {}, {}  # one calibration per direction, shared
    for bq in _candidates(sq):
        for bk in _candidates(sk):
            def run(q, k, v, _bq=bq, _bk=bk):
                return _flash_bhsd(q, k, v, causal, scale, False, _bq, _bk)

            try:
                t_fwd = _time_compiled(run, (q, k, v), iters,
                                       n_hint=hint_fwd)
                t_fb = _time_compiled(gradify(run), (q, k, v), iters,
                                      n_hint=hint_fb)
            except Exception as e:  # noqa: BLE001 — a bad tiling skips
                if verbose:
                    print(f"  ({bq},{bk}): failed {type(e).__name__}",
                          flush=True)
                continue
            results.append((t_fb, t_fwd, bq, bk))
            if verbose:
                print(f"  ({bq},{bk}): fwd {t_fwd * 1e3:.2f}ms "
                      f"fwd+bwd {t_fb * 1e3:.2f}ms", flush=True)
    if not results:
        raise RuntimeError(f"no viable block sizes for {sq}x{sk} d{d}")
    results.sort()
    t_fb, t_fwd, bq, bk = results[0]
    dev = jax.devices()[0]
    entry = {
        "sq": sq, "sk": sk, "d": d, "causal": causal, "bh": bh,
        "block_q": bq, "block_k": bk,
        "t_fwd_ms": round(t_fwd * 1e3, 4),
        "t_fwd_bwd_ms": round(t_fb * 1e3, 4),
        "device": getattr(dev, "device_kind", str(dev)),
        "backend": jax.default_backend(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if t_comp_fwd is not None:
        entry.update({
            "t_xla_fwd_ms": round(t_comp_fwd * 1e3, 4),
            "t_xla_fwd_bwd_ms": round(t_comp_fb * 1e3, 4),
            "ratio_fwd": round(t_comp_fwd / t_fwd, 4),
            "ratio_fwd_bwd": round(t_comp_fb / t_fb, 4),
        })
    update_cache(lambda c: c.setdefault("entries", {}).update(
        {_key(sq, sk, d, causal): entry}))
    return entry


# the bench-relevant shapes: headline Llama (s1024 d128), BERT (s512
# d64), long-context legs
def _gradify(f):
    """fwd+bwd timing wrapper with every grad folded into the result —
    returning dq alone lets XLA DCE the dk/dv computation (measured:
    "bwd" adding only 0.2 ms on a 2.5x-fwd-FLOPs pass). Cross-length
    grads fold via a seq-reduced broadcast."""

    def g(q, k, v):
        dq, dk, dv = jax.grad(
            lambda *a: f(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)
        r = dq
        for dother in (dk, dv):
            if dother.shape == r.shape:
                r = r + dother
            else:
                r = r + dother.sum(axis=-2, keepdims=True) * 1e-6
        return r

    return g


def _composite_sdpa(sq, sk, causal, scale, dropout=0.0):
    """The XLA-composite attention baseline. With dropout, the bernoulli
    key is derived FROM the query data: a fixed key would be
    loop-invariant inside _time_compiled's fori_loop and XLA would
    hoist the mask generation out of the timed loop, biasing the ratio
    (the kernel regenerates its mask every iteration)."""

    def composite(q, k, v):
        s_ = (q.astype(jnp.float32) * scale) @ jnp.swapaxes(
            k.astype(jnp.float32), -1, -2)
        if causal:
            mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
            s_ = jnp.where(mask, s_, -1e30)
        p = jax.nn.softmax(s_, axis=-1)
        if dropout > 0.0:
            salt = jax.lax.bitcast_convert_type(
                q[(0,) * q.ndim].astype(jnp.float32), jnp.int32)
            key = jax.random.fold_in(jax.random.PRNGKey(5), salt)
            keep = jax.random.bernoulli(key, 1.0 - dropout, p.shape)
            p = jnp.where(keep, p / (1.0 - dropout), 0.0)
        return p @ v.astype(jnp.float32)

    return composite


def tune_variant_ratio(bh: int, sq: int, sk: int, d: int, causal: bool,
                       dropout: float, dtype=jnp.bfloat16,
                       iters: int = 20, verbose: bool = True
                       ) -> Dict[str, Any]:
    """Kernel-vs-composite fwd+bwd ratio for the in-kernel DROPOUT
    variant at this shape, run at the base entry's tuned blocks (no
    block re-search: only the engagement RATIO is variant-dependent).
    Persists a variant cache row consulted by
    `kernel_beats_composite(dropout=...)` — replacing the interim 1.2x
    demand-headroom margin with a measurement."""
    from .flash_attention import _flash_bhsd_drop

    scale = 1.0 / math.sqrt(d)
    q = jax.random.normal(jax.random.PRNGKey(0), (bh, sq, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (bh, sk, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (bh, sk, d), dtype)
    seed = jnp.asarray([7, 9], jnp.int32)
    bq, bk = best_blocks(sq, sk, d, causal)
    if bq is None and on_tpu():
        # a ratio at un-tuned default blocks would misstate the
        # kernel's best case; tune the base row first
        raise RuntimeError(
            f"no tuned base row for s{sq}x{sk} d{d} causal={causal}; "
            "run the standard tune before the variant")

    def kern(q, k, v):
        return _flash_bhsd_drop(q, k, v, seed, causal, scale, False,
                                bq, bk, 0, dropout)

    composite = _composite_sdpa(sq, sk, causal, scale, dropout)

    t_k = _time_compiled(_gradify(kern), (q, k, v), iters)
    try:
        t_c = _time_compiled(_gradify(composite), (q, k, v), iters)
    except Exception as e:  # noqa: BLE001 — composite OOM: no ratio
        if verbose:
            print(f"  variant composite failed ({type(e).__name__})",
                  flush=True)
        t_c = None
    entry: Dict[str, Any] = {
        "sq": sq, "sk": sk, "d": d, "causal": causal, "bh": bh,
        "dropout": dropout, "block_q": bq, "block_k": bk,
        "t_kernel_fwd_bwd_s": t_k,
        "device": _device_kind(),
        "backend": jax.default_backend(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if t_c is not None:
        entry["t_composite_fwd_bwd_s"] = t_c
        entry["ratio_fwd_bwd"] = t_c / max(t_k, 1e-12)
    if verbose:
        r = entry.get("ratio_fwd_bwd")
        print(f"  dropout={dropout} ratio_fwd_bwd="
              f"{r if r is None else round(r, 3)}", flush=True)
    update_cache(lambda c: c.setdefault("entries", {}).update(
        {_key(sq, sk, d, causal, dropout): entry}))
    return entry


# dropout-variant rows (BERT/ERNIE honest configs): ratio-only
# measurements at the base rows' tuned blocks
VARIANT_SHAPES = [
    (768, 512, 512, 64, False, 0.1),
    (48, 1024, 1024, 64, True, 0.1),
    (48, 1024, 1024, 128, True, 0.1),
]

STANDARD_SHAPES = [
    (48, 1024, 1024, 64, True),
    (48, 1024, 1024, 128, True),
    (32, 512, 512, 64, True),
    (24, 2048, 2048, 128, True),
    (12, 4096, 4096, 128, True),
    # long-context legs (composite may OOM-skip; kernel still tunes)
    (8, 8192, 8192, 128, True),
    (4, 16384, 16384, 128, True),
    # non-causal (encoder / BERT-shape) engagement rows
    (768, 512, 512, 64, False),
    (48, 1024, 1024, 64, False),
    (48, 1024, 1024, 128, False),
]


def tune_standard(iters: int = 20, verbose: bool = True):
    out = []
    for bh, sq, sk, d, causal in STANDARD_SHAPES:
        if verbose:
            print(f"tuning bh={bh} s={sq}x{sk} d={d} causal={causal}",
                  flush=True)
        out.append(tune_shape(bh, sq, sk, d, causal, iters=iters,
                              verbose=verbose))
    return out


# -- search-harness family (ops/pallas/search.py) -----------------------------

from . import search as _search  # noqa: E402 — no cycle: search imports
#                                  this module lazily, inside functions


class FlashFamily(_search.KernelFamily):
    """The original (block_q, block_k) flash search, expressed as a
    harness family. Rows persisted through the harness are mirrored
    into the legacy ``flash_tune.json`` (``on_persist``) so
    `flash_attention_kernel`'s `best_blocks`/`kernel_beats_composite`
    lookups see them — one engagement source, two writers."""

    name = "flash"
    grad = True
    parity_atol = 2e-5

    def shapes(self):
        return list(STANDARD_SHAPES)

    def smoke_shapes(self):
        return [(2, 128, 128, 8, True)]

    def key(self, shape):
        bh, sq, sk, d, causal = shape
        return _key(sq, sk, d, causal)

    def shape_info(self, shape):
        bh, sq, sk, d, causal = shape
        return {"bh": bh, "sq": sq, "sk": sk, "d": d, "causal": causal}

    def candidates(self, shape):
        bh, sq, sk, d, causal = shape
        return [{"block_q": bq, "block_k": bk}
                for bq in _candidates(sq) for bk in _candidates(sk)]

    def _inputs(self, shape, dtype):
        bh, sq, sk, d, causal = shape
        q = jax.random.normal(jax.random.PRNGKey(0), (bh, sq, d), dtype)
        k = jax.random.normal(jax.random.PRNGKey(1), (bh, sk, d), dtype)
        v = jax.random.normal(jax.random.PRNGKey(2), (bh, sk, d), dtype)
        return q, k, v

    def make_inputs(self, shape):
        return self._inputs(shape, jnp.bfloat16)

    def make_parity_inputs(self, shape):
        return self._inputs(shape, jnp.float32)

    def build(self, shape, config, interpret):
        from .flash_attention import _flash_bhsd

        bh, sq, sk, d, causal = shape
        scale = 1.0 / math.sqrt(d)

        def run(q, k, v):
            return _flash_bhsd(q, k, v, causal, scale, interpret,
                               config.get("block_q"),
                               config.get("block_k"))

        return run

    def build_composite(self, shape):
        bh, sq, sk, d, causal = shape
        return _composite_sdpa(sq, sk, causal, 1.0 / math.sqrt(d))

    def on_persist(self, shape, entry):
        """Mirror the harness row into the legacy cache in the exact
        schema `best_blocks`/`kernel_beats_composite` read."""
        bh, sq, sk, d, causal = shape
        legacy: Dict[str, Any] = {
            "sq": sq, "sk": sk, "d": d, "causal": causal, "bh": bh,
            "block_q": entry["config"]["block_q"],
            "block_k": entry["config"]["block_k"],
            "t_fwd_bwd_ms": entry["t_kernel_ms"],
            "device": entry.get("device"),
            "backend": entry.get("backend"),
            "timestamp": entry.get("timestamp"),
            "via": "kernel_search",
        }
        if "ratio" in entry:
            legacy["t_xla_fwd_bwd_ms"] = entry["t_composite_ms"]
            legacy["ratio_fwd_bwd"] = entry["ratio"]
        # interpret/CPU rows carry meaningless wall-clock: never mirror
        # them into the engagement cache (the smoke CLI runs on CPU)
        if entry.get("backend") == "cpu" or entry.get("interpret"):
            return
        update_cache(lambda c: c.setdefault("entries", {}).update(
            {_key(sq, sk, d, causal): legacy}))


_search.register_family(FlashFamily())
