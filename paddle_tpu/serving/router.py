"""Multi-replica serving router: prefix-affinity dispatch, compile-free
scale-out, replica-failure drain (docs/SERVING.md "Replica router").

One :class:`ServingEngine` is one replica behind FCFS; the
millions-of-users path needs N of them behind one front door. This
module is that front door — a **jax-free** :class:`RouterEngine`
exposing the same ``submit`` / ``step`` / ``run`` / ``pop_finished``
surface as the engine, dispatching over N replicas:

- **In-process replicas** (default): N engines sharing one model. The
  AOT exec cache (``jit/exec_cache.py``) keys compiled programs on
  generation config, param avals, pool geometry, lanes and mesh — all
  identical across identically-configured replicas — so replica 1
  compiles the three phase programs and replicas 2..N ride the warm
  cache: process-wide fresh XLA compiles stay at 3 no matter how many
  replicas serve (tests/test_serving_router.py proves it). This is
  GSPMD's one-program-many-instances economics one level up: the
  compiled artifact is the shared unit, so scale-out is a scheduling
  problem, not a compiler one.
- **Worker replicas** (``mode="worker"``): one subprocess per replica
  (:mod:`.router_worker`, a JSON-lines pipe protocol), each building
  its model from a ``module:callable`` factory spec — the deployment
  shape, where a warm ``PT_EXEC_CACHE`` directory makes every worker's
  start compile-free too. The router side stays jax-free either way.

**Dispatch is prefix-affinity-first**: the router hashes each prompt
with the same chained blake2b keys the block pool's prefix index uses
(``kv_cache.prefix_keys``) and keeps a shadow map of which replicas
were sent which chains. A new request routes to the live replica whose
recorded coverage of its opening is longest — that replica's prefix
cache already holds (or is about to hold) those published blocks, so
the prefill is cheap there and cold everywhere else. No coverage (or
affinity off via ``PT_SERVE_AFFINITY=0``): least-loaded wins — fewest
resident requests (occupied lanes + queue depth), ties to the lowest
replica index. Every rule is deterministic (this module is in
PTL005's determinism scope), so a seeded trace replays byte-identically.

**Replica failure is drained, not fatal**: a replica whose ``step()``
raises is marked dead; every request the router had routed to it —
queued AND in-flight — drains back into the router queue and
re-dispatches to survivors. Re-dispatch restarts from the prompt
(partial output is discarded): greedy decode is deterministic and
token-identical to per-request ``generate()``, so the survivor
reproduces the exact same tokens — the same argument that makes
recompute-on-preemption token-correct inside one engine. The router
registers as a blackbox state provider (``monitor/blackbox.py``,
label ``serving_router``), so the postmortem artifact names the dead
replica and snapshots every survivor's scheduler/pool/lane state.

Monitor contract: ``router/*`` counters under the None-slot
zero-overhead-off contract (``monitor.INSTRUMENTED_MODULES``).
Always-on plain-int ``RouterEngine.counters`` feed the serving bench
(``PT_SERVE_BENCH_REPLICAS``) independently of the monitor.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import subprocess
import sys

import numpy as np

from ..monitor import _register as _monitor_register
from ..monitor import blackbox as _blackbox
from ..monitor import live as _live_telemetry
from .kv_cache import prefix_keys

__all__ = ["RouterConfig", "RouterEngine"]

# telemetry slots (paddle_tpu.monitor None-slot contract): None unless
# PT_MONITOR wired them. `_live` (monitor/live.py) additionally drives
# the per-step worker telemetry pull that closes the fleet-aggregation
# gap: worker-mode replica counters/sketches ship over the pipe and
# merge router-side, so /metrics reads the same totals either mode.
_monitor = None
_live = None

_auto_id = itertools.count()


def _env_int(name, default):
    v = os.environ.get(name)
    return int(v) if v else default


class RouterConfig:
    """Router policy knobs. Env defaults (CLAUDE.md knob table):

    - ``replicas`` (``PT_SERVE_REPLICAS``, 2): engines behind the
      router.
    - ``affinity`` (``PT_SERVE_AFFINITY``, on): prefix-affinity
      dispatch; ``0`` routes least-loaded only (the A/B lever the
      serving bench's affinity proof and ``perf_guard
      --affinity-drop`` rest on).
    - ``mode`` (``PT_SERVE_ROUTER_MODE``, ``inproc``): ``inproc`` =
      N engines in this process sharing one model; ``worker`` = one
      :mod:`.router_worker` subprocess per replica.
    - ``worker_factory`` (``PT_SERVE_WORKER_FACTORY``): worker mode's
      model source, a ``module:callable`` spec — each worker imports
      ``module`` and calls ``callable()`` for its model.
    """

    def __init__(self, replicas=None, affinity=None, mode=None,
                 worker_factory=None):
        self.replicas = replicas if replicas is not None \
            else _env_int("PT_SERVE_REPLICAS", 2)
        if self.replicas < 1:
            raise ValueError(
                f"replicas must be >= 1, got {self.replicas}")
        if affinity is None:
            affinity = os.environ.get(
                "PT_SERVE_AFFINITY", "1") not in ("0", "off")
        self.affinity = bool(affinity)
        self.mode = mode or os.environ.get(
            "PT_SERVE_ROUTER_MODE", "inproc")
        if self.mode not in ("inproc", "worker"):
            raise ValueError(
                f"mode must be 'inproc' or 'worker', got {self.mode!r}")
        self.worker_factory = worker_factory \
            or os.environ.get("PT_SERVE_WORKER_FACTORY")
        if self.mode == "worker" and not self.worker_factory:
            raise ValueError(
                "worker mode needs a model factory: pass "
                "worker_factory='module:callable' or set "
                "PT_SERVE_WORKER_FACTORY")


class _RouteRecord:
    """The router's own account of one live request — everything a
    re-dispatch after a replica death needs (the dead replica's state
    is untrusted and, in worker mode, unreachable)."""

    __slots__ = ("request_id", "prompt", "max_new_tokens",
                 "eos_token_id", "replica", "seq", "redispatches")

    def __init__(self, request_id, prompt, max_new_tokens, eos_token_id,
                 seq):
        self.request_id = request_id
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_token_id = eos_token_id
        self.replica = None
        self.seq = seq
        self.redispatches = 0


class _InprocReplica:
    """One in-process :class:`ServingEngine` behind the handle protocol
    the router drives (submit / warmup / step / has_work / load /
    stats / debug_state / close)."""

    def __init__(self, index, model, config, drafter=None):
        # lazy: the router module itself must stay importable jax-free
        # (worker mode never pays the jax import on the router side)
        from .engine import ServingEngine

        self.index = index
        self._engine = ServingEngine(model, config, drafter=drafter)

    def submit(self, rec: _RouteRecord):
        return self._engine.submit(
            rec.prompt, max_new_tokens=rec.max_new_tokens,
            eos_token_id=rec.eos_token_id, request_id=rec.request_id)

    def warmup(self) -> None:
        self._engine.warmup()

    def step(self):
        worked = self._engine.step()
        return worked, self._engine.pop_finished()

    def has_work(self) -> bool:
        return self._engine.has_work()

    def load(self):
        sched = self._engine.scheduler
        return sched.lanes_occupied, len(sched.waiting)

    def stats(self) -> dict:
        return self._engine.stats()

    def telemetry(self):
        # in-process engines feed the process-local live collector
        # directly through their own `_live` slot — nothing to ship
        return None

    def debug_state(self) -> dict:
        return self._engine.scheduler.debug_state()

    def close(self) -> None:
        pass


class _WorkerReplica:
    """One :mod:`.router_worker` subprocess behind the same handle
    protocol: JSON-lines over stdin/stdout (replies ride a dedicated
    channel — the worker rebinds its own stdout to stderr so library
    chatter cannot corrupt the protocol). Load is modeled router-side
    from in-flight counts (submits minus finishes): exact enough for
    least-loaded, and it keeps dispatch decisions free of extra
    round-trips."""

    def __init__(self, index, factory, config_kwargs, max_lanes):
        self.index = index
        self._max_lanes = max_lanes
        self._inflight: dict = {}  # json rid key -> original rid
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ)
        env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
        # one exporter per fleet: the router process owns the metrics
        # port; workers collect (PT_LIVE_TELEMETRY) and ship their
        # telemetry over the pipe instead of binding their own server
        env.pop("PT_METRICS_PORT", None)
        if _live_telemetry.enabled():
            env["PT_LIVE_TELEMETRY"] = "1"
        self._proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.serving.router_worker"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True)
        self._call({"op": "init", "factory": factory,
                    "config": config_kwargs})

    def _call(self, msg: dict) -> dict:
        proc = self._proc
        if proc.poll() is not None:
            raise RuntimeError(
                f"router worker {self.index} exited "
                f"(rc={proc.returncode})")
        proc.stdin.write(json.dumps(msg) + "\n")
        proc.stdin.flush()
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"router worker {self.index} closed its pipe")
        reply = json.loads(line)
        if not reply.get("ok"):
            raise RuntimeError(
                f"router worker {self.index}: "
                f"{reply.get('error', 'unknown error')}")
        return reply

    def submit(self, rec: _RouteRecord):
        self._call({"op": "submit", "request_id": rec.request_id,
                    "prompt": [int(t) for t in rec.prompt],
                    "max_new_tokens": rec.max_new_tokens,
                    "eos_token_id": rec.eos_token_id})
        self._inflight[str(rec.request_id)] = rec.request_id
        return rec

    def warmup(self) -> None:
        self._call({"op": "warmup"})

    def step(self):
        reply = self._call({"op": "step"})
        fins = {}
        for key, toks in reply.get("finished", {}).items():
            rid = self._inflight.pop(key, key)
            fins[rid] = np.asarray(toks, np.int32)
        return bool(reply.get("worked")), fins

    def has_work(self) -> bool:
        return bool(self._inflight)

    def load(self):
        n = len(self._inflight)
        return min(n, self._max_lanes), max(0, n - self._max_lanes)

    def stats(self) -> dict:
        try:
            return self._call({"op": "stats"}).get("stats", {})
        except RuntimeError as exc:
            return {"worker_error": str(exc)}

    def telemetry(self):
        """The worker's cumulative monitor counters + live sketches
        (`live.export_local` shape) — cumulative, not deltas, so a
        missed pull self-heals and the router-side merge stays exact.
        None when the worker is unreachable (its last shipped payload
        stays merged)."""
        try:
            return self._call({"op": "telemetry"}).get("telemetry")
        except RuntimeError:
            return None

    def debug_state(self) -> dict:
        try:
            return self._call({"op": "debug_state"}).get("state", {})
        except RuntimeError as exc:
            return {"worker_error": str(exc)}

    def close(self) -> None:
        proc = self._proc
        if proc.poll() is None:
            try:
                proc.stdin.write(json.dumps({"op": "shutdown"}) + "\n")
                proc.stdin.flush()
                proc.wait(timeout=5)
            except Exception:
                proc.kill()
                proc.wait()


class RouterEngine:
    """Submit requests, call :meth:`step` (or :meth:`run`) — same
    driving contract as :class:`~paddle_tpu.serving.engine.ServingEngine`,
    over N replicas. See the module docstring for the dispatch and
    drain rules, docs/SERVING.md for the operational guide.

    ``config`` is the per-replica :class:`ServingConfig` (or a plain
    kwargs dict — worker mode ships it over the pipe without importing
    the jax-backed engine module router-side)."""

    def __init__(self, model=None, config=None, router_config=None,
                 drafter_factory=None):
        self.router_config = router_config or RouterConfig()
        rc = self.router_config
        self._config_kwargs = self._as_kwargs(config)
        self.block_size = self._config_kwargs.get(
            "block_size") or _env_int("PT_SERVE_BLOCK", 16)
        self.max_lanes = self._config_kwargs.get(
            "max_lanes") or _env_int("PT_SERVE_LANES", 8)
        if rc.mode == "inproc":
            if model is None:
                raise ValueError("inproc router mode needs a model")
            from .engine import ServingConfig

            cfg = config if isinstance(config, ServingConfig) \
                else ServingConfig(**self._config_kwargs)
            self._replicas = [
                _InprocReplica(
                    i, model, cfg,
                    drafter=drafter_factory() if drafter_factory
                    else None)
                for i in range(rc.replicas)]
        else:
            self._replicas = [
                _WorkerReplica(i, rc.worker_factory,
                               self._config_kwargs, self.max_lanes)
                for i in range(rc.replicas)]
        # shadow prefix index: chain key -> replicas that were routed a
        # request whose context publishes it, in dispatch order (a list,
        # never a set — dispatch is in PTL005's determinism scope)
        self._affinity: dict = {}
        self._records: dict = {}
        self._finished: dict = {}
        self._queue: collections.deque = collections.deque()
        self._dead: dict = {}  # replica index -> failure reason
        self._seq = itertools.count()
        # always-on plain-int accounting (the serving bench's source of
        # truth, like ServingEngine.counters)
        self.counters = {
            "dispatches": 0, "affinity_hits": 0, "affinity_misses": 0,
            "redispatches": 0, "dead_replicas": 0, "finished": 0,
        }
        self.dispatch_counts = [0] * rc.replicas
        _blackbox.register("serving_router", self._blackbox_state)
        # /healthz hook: the exporter reads per-replica dead/alive from
        # this weakly-held provider (monitor/live.py status registry)
        _live_telemetry.register_status("serving_router",
                                        self._health_state)

    @staticmethod
    def _as_kwargs(config) -> dict:
        if config is None:
            return {}
        if isinstance(config, dict):
            return dict(config)
        fields = ("max_lanes", "block_size", "num_blocks",
                  "prefill_chunk", "max_seq_len", "int8_weights",
                  "prefix_cache", "spec", "spec_k")
        return {f: getattr(config, f) for f in fields
                if getattr(config, f, None) is not None}

    # -- intake ---------------------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens=32, eos_token_id=None,
               request_id=None):
        """Queue one request and dispatch it to a replica immediately.
        Returns the replica's :class:`Request` handle (in-process mode)
        or the router's own record (worker mode)."""
        if hasattr(prompt_ids, "numpy"):  # framework Tensor, jax-free
            prompt_ids = prompt_ids.numpy()
        prompt = np.asarray(prompt_ids, dtype=np.int32).reshape(-1)
        rid = request_id if request_id is not None else next(_auto_id)
        if rid in self._records or rid in self._finished:
            raise ValueError(
                f"duplicate request_id {rid!r} (live or finished-but-"
                f"uncollected — pop_finished() first)")
        rec = _RouteRecord(rid, prompt, int(max_new_tokens),
                           eos_token_id, next(self._seq))
        self._records[rid] = rec
        return self._dispatch(rec)

    def warmup(self) -> None:
        """Warm every replica's compiled programs. In-process replicas
        share the exec cache's in-memory tier, so replica 1 pays the
        compiles and 2..N load warm — the compile-free scale-out
        contract."""
        for i, rep in enumerate(self._replicas):
            if i not in self._dead:
                rep.warmup()

    # -- dispatch -------------------------------------------------------------

    def _live(self) -> list:
        live = [i for i in range(len(self._replicas))
                if i not in self._dead]
        if not live:
            raise RuntimeError(
                f"all {len(self._replicas)} router replicas are dead: "
                f"{self._dead}")
        return live

    def _lookup_keys(self, prompt) -> list:
        # the same cap admission uses (kv_cache.prefix_keys): at least
        # one token always prefills, so only ctx-1 tokens are
        # acquirable — scoring past that would reward unsharable keys
        return prefix_keys(prompt, self.block_size,
                           limit_tokens=prompt.size - 1)

    def _choose(self, rec: _RouteRecord):
        """Pick a live replica for ``rec``: longest recorded prefix
        coverage first, then least-loaded, then lowest index — every
        comparison deterministic."""
        live = self._live()
        loads = {i: sum(self._replicas[i].load()) for i in live}
        if self.router_config.affinity and rec.prompt.size > 1:
            keys = self._lookup_keys(rec.prompt)
            cov = {}
            for i in live:
                n = 0
                for key in keys:
                    owners = self._affinity.get(key)
                    if owners is None or i not in owners:
                        break
                    n += 1
                cov[i] = n
            best = max(cov.values(), default=0)
            if best > 0:
                pick = min((i for i in live if cov[i] == best),
                           key=lambda i: (loads[i], i))
                return pick, True
        pick = min(live, key=lambda i: (loads[i], i))
        return pick, False

    def _dispatch(self, rec: _RouteRecord, redispatch=False):
        idx, hit = self._choose(rec)
        rec.replica = idx
        handle = self._replicas[idx].submit(rec)
        self.counters["dispatches"] += 1
        self.counters["affinity_hits" if hit else "affinity_misses"] += 1
        self.dispatch_counts[idx] += 1
        if redispatch:
            rec.redispatches += 1
            self.counters["redispatches"] += 1
        if self.router_config.affinity:
            # record the keys this replica's prefill will publish (all
            # full prompt blocks) so later same-opening requests chase it
            for key in prefix_keys(rec.prompt, self.block_size):
                owners = self._affinity.setdefault(key, [])
                if idx not in owners:
                    owners.append(idx)
        m = _monitor
        if m is not None:
            m.on_router_dispatch(idx, hit, redispatch=redispatch)
        return handle

    # -- the step loop --------------------------------------------------------

    def step(self) -> bool:
        """One router round: re-dispatch anything a dead replica
        drained back, then step every live replica that has work,
        collecting finished outputs. A replica raise marks it dead and
        drains its requests (see :meth:`_mark_dead`); the raise is
        absorbed — survivors keep serving. Returns whether any work was
        done."""
        worked = False
        while self._queue:
            self._dispatch(self._queue.popleft(), redispatch=True)
            worked = True
        for i, rep in enumerate(self._replicas):
            if i in self._dead or not rep.has_work():
                continue
            try:
                w, fins = rep.step()
            except Exception as exc:  # noqa: BLE001 — drain, don't die
                self._mark_dead(i, exc)
                worked = True
                continue
            worked = worked or w
            for rid, toks in fins.items():
                self._records.pop(rid, None)
                self._finished[rid] = np.asarray(toks)
                self.counters["finished"] += 1
            m = _monitor
            if m is not None:
                occ, queued = rep.load()
                m.on_router_lanes(i, occ, queued)
            lv = _live
            if lv is not None:
                # fleet aggregation: pull the worker's cumulative
                # telemetry after its step so this round's finishes are
                # already in the payload (in-process replicas return
                # None — they feed the local collector directly)
                tel = rep.telemetry()
                if tel is not None:
                    lv.set_remote(str(i), tel)
        return worked

    def run(self) -> dict:
        """Drain: step until every submitted request finished, then
        collect-and-retire (the engine's :meth:`run` contract)."""
        while self.has_work():
            self.step()
        return self.pop_finished()

    def pop_finished(self) -> dict:
        out = {rid: np.asarray(toks)
               for rid, toks in self._finished.items()}
        self._finished.clear()
        return out

    def has_work(self) -> bool:
        return bool(self._records)

    # -- failure drain --------------------------------------------------------

    def _mark_dead(self, idx: int, exc: BaseException) -> None:
        """Replica ``idx`` raised: mark it dead, abandon its engine
        state (pool and all — nothing it holds is trusted), and drain
        every request routed to it back into the router queue in
        original submit order. Re-dispatch restarts each from its
        prompt on a survivor; greedy determinism reproduces the exact
        tokens. The blackbox postmortem lands before serving resumes,
        naming the dead replica."""
        self._dead[idx] = f"{type(exc).__name__}: {exc}"
        self.counters["dead_replicas"] += 1
        drained = sorted(
            (rec for rec in self._records.values()
             if rec.replica == idx), key=lambda r: r.seq)
        for rec in drained:
            rec.replica = None
            self._queue.append(rec)
        m = _monitor
        if m is not None:
            m.on_router_dead(idx)
        try:
            self._replicas[idx].close()
        except Exception:  # noqa: BLE001 — a dead worker can't object
            pass
        _blackbox.maybe_dump(reason="router_replica_dead", error=exc)

    def close(self) -> None:
        """Shut every replica down (worker subprocesses exit)."""
        for i, rep in enumerate(self._replicas):
            if i not in self._dead:
                rep.close()

    # -- introspection --------------------------------------------------------

    @property
    def _params(self):
        """The first live in-process replica's decode params — the
        serving bench's HBM byte model reads sizes from the engine's
        OWN arrays (benchmarks/serving_bench.py), and every in-process
        replica shares one copy. Worker-mode replicas hold theirs in
        another process."""
        for i in self._live():
            rep = self._replicas[i]
            if isinstance(rep, _InprocReplica):
                return rep._engine._params
        raise AttributeError(
            "_params unavailable: worker-mode replicas hold params "
            "out-of-process")

    _ADDITIVE_STATS = (
        "admits", "finished", "preemptions", "prefill_chunks",
        "decode_steps", "verify_steps", "decoded_tokens",
        "spec_proposed_tokens", "spec_accepted_tokens",
        "spec_bonus_tokens", "prefix_hit_tokens", "prefix_miss_tokens",
        "kv_read_tokens", "kv_gathered_tokens", "kv_dense_read_tokens",
        "kv_kernel_rows", "operand_uploads", "operand_upload_bytes",
        "step_s", "admit_s",
        "prefill_s", "first_fetch_s", "grow_s", "draft_s", "pack_s",
        "dispatch_s", "fetch_s", "emit_s",
        "decode_rounds", "free_blocks", "allocatable_blocks",
        "shared_blocks", "cold_blocks", "indexed_blocks",
        "lanes_occupied", "waiting", "requests", "uncollected",
    )

    def stats(self) -> dict:
        """Aggregate engine stats summed across live replicas (the
        additive counters; geometry fields ride from the first live
        replica so bench code reads one dict either way), plus the
        router's own account."""
        live = [i for i in range(len(self._replicas))
                if i not in self._dead]
        out: dict = {}
        for n, i in enumerate(live):
            s = self._replicas[i].stats()
            if n == 0:
                out.update(s)
            else:
                for k in self._ADDITIVE_STATS:
                    if k in s:
                        out[k] = out.get(k, 0) + s[k]
        d = self.counters["dispatches"]
        out.update(
            replicas=len(self._replicas),
            live_replicas=len(live),
            dead_replicas=sorted(self._dead),
            affinity=self.router_config.affinity,
            router=dict(self.counters),
            affinity_hit_rate=(self.counters["affinity_hits"] / d
                               if d else 0.0),
            dispatches_per_replica=list(self.dispatch_counts),
            queued=len(self._queue),
        )
        return out

    def _health_state(self) -> dict:
        """/healthz provider: the light per-replica dead/alive ledger —
        plain ints and strings only, safe to read at scrape time (the
        heavyweight scheduler snapshots stay in `_blackbox_state`)."""
        return {
            "mode": self.router_config.mode,
            "queued": len(self._queue),
            "counters": dict(self.counters),
            "replicas": [
                {"replica": i, "dead": i in self._dead,
                 "reason": self._dead.get(i)}
                for i in range(len(self._replicas))],
        }

    def _blackbox_state(self) -> dict:
        """Blackbox provider (``monitor/blackbox.py``): router config +
        counters, the dead-replica ledger, the drain queue, every live
        request's routing record, and each surviving replica's
        scheduler/pool/lane snapshot. Read-only and exception-tolerant
        by the dump's contract."""
        per_replica = []
        for i, rep in enumerate(self._replicas):
            if i in self._dead:
                per_replica.append(
                    {"replica": i, "dead": True,
                     "reason": self._dead[i]})
            else:
                per_replica.append(
                    {"replica": i, "dead": False,
                     "scheduler": rep.debug_state()})
        return {
            "config": {
                "replicas": self.router_config.replicas,
                "affinity": self.router_config.affinity,
                "mode": self.router_config.mode,
                "block_size": self.block_size,
                "max_lanes": self.max_lanes,
            },
            "counters": dict(self.counters),
            "dispatches_per_replica": list(self.dispatch_counts),
            "dead": dict(self._dead),
            "queue": [rec.request_id for rec in self._queue],
            "records": [{
                "request_id": rec.request_id, "replica": rec.replica,
                "prompt_tokens": int(rec.prompt.size),
                "max_new_tokens": rec.max_new_tokens,
                "redispatches": rec.redispatches,
            } for rec in sorted(self._records.values(),
                                key=lambda r: r.seq)],
            "replicas": per_replica,
        }


_monitor_register(sys.modules[__name__])
