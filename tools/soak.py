#!/usr/bin/env python
"""Production-soak gate: an unattended fault-injected run must FINISH.

    python tools/soak.py --smoke                 # tier-1: CPU, ~1 min
    python tools/soak.py --steps 2000            # hardware soak row

The driver launches a training worker under the babysitting launcher
(``python -m paddle_tpu.distributed.launch --max_restart``) with the full
resilience stack armed — planned async checkpoints
(``hapi.fit(checkpoint_dir=)``), resume-from-latest-complete
(``resume_from=``), and NaN skip-and-continue (``nan_policy="skip"``) —
then injects the two faults that kill real long runs:

- ``PT_SOAK_CRASH_AT=<step>``: the worker ``os._exit``\\ s mid-run on its
  first life (async checkpoint writers die mid-write — torn checkpoints
  are part of the test); the launcher relaunches it
  (``PADDLE_RESTART_COUNT``) and it must resume from the last COMPLETE
  checkpoint, never a torn one.
- ``PT_SOAK_POISON_AT=<batch>``: one batch of NaNs; the numerics
  sentinel + skip policy must drop it and continue.
- ``PT_SOAK_HANG_AT=<batch>``: a sleep inside a host callback boundary
  (``PT_SOAK_HANG_S``, 2.5 s) freezes the step counter; the hang
  watchdog (``monitor/watchdog.py``, ``PT_HANG_MIN_S=1`` in the soak
  env) must trip mid-hang and write a blackbox artifact NAMING the hung
  step (``PT_HANG_BLACKBOX``) while policy ``warn`` lets the run go on.

The run's FINAL STATE is then gated — not just "no stack trace":

- loss-curve slope: mean(last quarter) < mean(first quarter) — the model
  learned through the crash and the poison;
- memory growth: live-census peak in the last third ≤ 10% over the first
  third (a leaking resume would show here);
- crash/skip proofs: ≥ 2 lives with a complete resume point when a crash
  was injected; ≥ 1 skipped batch when poison was;
- perf guard: the emitted line judged against the last-good record
  (``tools/perf_guard.py`` — including the ``--save-cost-growth``
  checkpoint-overhead gate via ``ckpt_save_ms_p50``).

Emits ONE JSON verdict line (the bench-line contract: ``metric`` =
``soak``) and exits 0 iff every gate passed. Hardware runs persist to
``PERF_MEASUREMENTS.json``. ``tools/hwbench.py`` carries a timeboxed soak
row; ``tests/test_resilience.py`` runs ``--smoke`` in tier-1.

``--router`` is the serving twin: an in-process replica-kill drain
scenario (docs/SERVING.md "Replica router") — a 3-replica
``RouterEngine`` serves a shared-prefix trace, one replica's ``step()``
starts raising mid-flight (``PT_SOAK_ROUTER_KILL`` picks the victim,
``PT_SOAK_ROUTER_KILL_AT`` the step), and the gate demands every
request finish on the survivors byte-identical to a no-failure
single-engine run, with the blackbox postmortem naming the dead
replica. Same one-JSON-verdict-line contract (``metric`` =
``soak_router``), exit 0 iff all checks hold.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SMOKE_STEPS = 48
SMOKE_BATCH = 8


# -- worker ------------------------------------------------------------------

def _worker(workdir: str) -> int:
    """One launcher-managed life of the soak training loop: hapi fit with
    the full resilience stack, fault injection from PT_SOAK_* env."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import monitor, resilience
    from paddle_tpu.framework.device import require_tpu

    # the worker is the process that holds the device: a soak that was
    # not started with --smoke (the parent then pins JAX_PLATFORMS=cpu)
    # needs the chip, and says so here — the parent never touches JAX
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        require_tpu("soak worker")

    restart = int(os.environ.get("PADDLE_RESTART_COUNT", "0") or 0)
    steps = int(os.environ.get("PT_SOAK_STEPS", str(SMOKE_STEPS)))
    batch = int(os.environ.get("PT_SOAK_BATCH", str(SMOKE_BATCH)))
    crash_at = int(os.environ.get("PT_SOAK_CRASH_AT", "-1"))
    poison_at = int(os.environ.get("PT_SOAK_POISON_AT", "-1"))
    hang_at = int(os.environ.get("PT_SOAK_HANG_AT", "-1"))
    hang_s = float(os.environ.get("PT_SOAK_HANG_S", "2.5"))
    ckpt_dir = os.path.join(workdir, "ckpt")

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(16, 64), nn.Tanh(), nn.Linear(64, 1))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())
    model = paddle.Model(net)
    model.prepare(opt, nn.MSELoss())

    rng = np.random.default_rng(0)
    xs = rng.standard_normal((steps * batch, 16)).astype("float32")
    w_true = rng.standard_normal((16, 1)).astype("float32")
    ys = xs @ w_true
    if poison_at >= 0:
        # one poisoned BATCH: the sentinel must trip, the policy must skip
        xs[poison_at * batch:(poison_at + 1) * batch] = np.nan
    ds = [(xs[i], ys[i]) for i in range(steps * batch)]

    from paddle_tpu.distributed import checkpoint as dckpt

    resumed = resilience.latest_complete(ckpt_dir)
    resumed_step = resumed[0] if resumed else None
    # torn-proof captured AT RESUME TIME: later GC removes torn dirs, so
    # a post-hoc scan by the driver could never catch a selector that
    # regressed into picking an incomplete checkpoint
    resumed_complete = (bool(dckpt.is_complete(resumed[1]))
                        if resumed else None)

    class CrashAt(paddle.callbacks.Callback):
        """Hard mid-run failure: os._exit skips every flush/join — the
        async checkpoint writer dies mid-write, exactly like a
        preemption. The blackbox postmortem is the ONE thing written
        first (os._exit skips atexit too, so this is its only chance) —
        the driver asserts the artifact exists and parses."""

        def __init__(self, at):
            self.at = at
            self.n = 0

        def on_train_batch_end(self, step, logs=None):
            self.n += 1
            if self.n == self.at:
                from paddle_tpu.monitor import blackbox

                blackbox.dump(reason="PT_SOAK_CRASH_AT",
                              error=f"injected crash at batch {self.n}")
                os._exit(23)

    class HangAt(paddle.callbacks.Callback):
        """Injected hang: a sleep inside a host callback boundary — the
        step counter stops, exactly like a wedged collective from the
        watchdog's viewpoint. PT_HANG_MIN_S is short in the soak env, so
        the hang watchdog (monitor/watchdog.py) must trip mid-sleep,
        write its blackbox artifact naming the hung step, and (policy
        ``warn``) let the run continue — the driver gates on the
        artifact."""

        def __init__(self, at, hold_s):
            self.at = at
            self.hold_s = hold_s
            self.n = 0

        def on_train_batch_end(self, step, logs=None):
            self.n += 1
            if self.n == self.at:
                time.sleep(self.hold_s)

    cbks = []
    if restart == 0 and crash_at >= 0:
        cbks.append(CrashAt(crash_at))
    if restart == 0 and hang_at >= 0:
        cbks.append(HangAt(hang_at, hang_s))

    t0 = time.perf_counter()
    model.fit(ds, batch_size=batch, epochs=1, shuffle=False, verbose=0,
              log_freq=5, checkpoint_dir=ckpt_dir, resume_from=ckpt_dir,
              nan_policy="skip", callbacks=cbks)
    wall = time.perf_counter() - t0

    counters = monitor.snapshot()["counters"]
    params = np.concatenate([
        np.asarray(p._data).ravel().astype(np.float64)
        for p in net.parameters()])
    summary = {
        "life": restart,
        "resumed_from": resumed_step,
        "resumed_from_complete": resumed_complete,
        "finished": True,
        "wall_s": round(wall, 3),
        "skipped_batches": counters.get("resilience/skipped_batches", 0),
        "saves": counters.get("resilience/saves", 0),
        "crash_resumes": counters.get("resilience/crash_resumes", 0),
        "hang_trips": counters.get("monitor/hang_trips", 0),
        "params_finite": bool(np.isfinite(params).all()),
        "params_sum": float(params.sum()),
    }
    with open(os.path.join(workdir, f"life_{restart}.json"), "w") as f:
        json.dump(summary, f)
    print("SOAK_WORKER_OK", restart, flush=True)
    return 0


# -- router drain leg --------------------------------------------------------

def _router_leg(args) -> int:
    """``--router``: the serving engine's crash-survival twin of the
    training soak — kill one of three router replicas mid-trace and
    gate on the drain contract (finish on survivors, byte-identical
    tokens, postmortem names the victim)."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    smoke = args.smoke
    if smoke:
        jax.config.update("jax_platforms", "cpu")
    else:
        from paddle_tpu.framework.device import require_tpu

        require_tpu("soak --router")

    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (
        RouterConfig, RouterEngine, ServingConfig, ServingEngine,
    )

    wd = args.out or tempfile.mkdtemp(prefix="pt_soak_router_")
    os.makedirs(wd, exist_ok=True)
    bb_path = os.path.join(wd, "router_blackbox.json")
    os.environ["PT_SERVE_BLACKBOX"] = bb_path
    victim = int(os.environ.get("PT_SOAK_ROUTER_KILL", "0"))
    kill_at = int(os.environ.get("PT_SOAK_ROUTER_KILL_AT", "2"))

    pt.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))
    model.eval()
    geom = ServingConfig(max_lanes=3, block_size=4, prefill_chunk=8,
                         max_seq_len=32)
    # shared-prefix trace: affinity funnels it onto ONE replica, so
    # killing that replica drains a full complement of in-flight work
    rng = np.random.RandomState(0)
    prefix = rng.randint(0, model.config.vocab_size, (8,)) \
        .astype(np.int32)
    work = []
    for _ in range(12):
        sfx = rng.randint(0, model.config.vocab_size,
                          (int(rng.randint(1, 6)),)).astype(np.int32)
        work.append((np.concatenate([prefix, sfx]),
                     int(rng.randint(4, 10))))

    print(f"soak --router: smoke={smoke} replicas=3 victim={victim} "
          f"kill_at={kill_at} workdir={wd}", flush=True)
    t0 = time.perf_counter()
    single = ServingEngine(model, geom)
    for i, (p, n) in enumerate(work):
        single.submit(p, max_new_tokens=n, request_id=f"r{i}")
    base = single.run()

    router = RouterEngine(model, geom,
                          RouterConfig(replicas=3, mode="inproc"))
    for i, (p, n) in enumerate(work):
        router.submit(p, max_new_tokens=n, request_id=f"r{i}")
    eng = router._replicas[victim]._engine
    real_step = eng.step
    calls = {"n": 0}

    def flaky_step():
        calls["n"] += 1
        if calls["n"] > kill_at:
            raise RuntimeError(
                f"soak-injected replica {victim} failure")
        return real_step()

    eng.step = flaky_step

    # live telemetry plane over the drain (docs/OBSERVABILITY.md): an
    # in-process /metrics endpoint on an ephemeral port, polled through
    # the kill — the healthz gate below requires the dead replica to be
    # visible in /healthz within the same driving step that killed it
    import urllib.request

    from paddle_tpu.monitor import exporter as _exporter

    port = _exporter.start(0)

    def _healthz():
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
            return json.loads(r.read().decode())

    healthz_ok = port is not None
    healthz_err = None if port else "exporter failed to start"
    killed_step = dead_reported_step = None
    steps_driven = 0
    last_health = None
    while router.has_work():
        router.step()
        steps_driven += 1
        if killed_step is None and calls["n"] > kill_at:
            killed_step = steps_driven
        if port:
            try:
                last_health = _healthz()
            except (OSError, ValueError) as e:
                healthz_ok, healthz_err = False, f"scrape failed: {e}"
                port = None
                continue
            if (last_health.get("dead_replicas")
                    and dead_reported_step is None):
                dead_reported_step = steps_driven
    outs = router.pop_finished()
    _exporter.stop()
    wall = time.perf_counter() - t0

    checks = []

    def check(name, ok, detail):
        checks.append({"name": name, "ok": bool(ok), "detail": detail})

    check("finished_all", set(outs) == set(base),
          f"{len(outs)}/{len(work)} requests finished after the kill")
    ident = all(np.array_equal(outs[k], base[k])
                for k in base if k in outs)
    check("token_identity", ident and set(outs) == set(base),
          "drained requests byte-identical to the no-failure run"
          if ident else "token mismatch after drain")
    c = router.counters
    check("drain", c["dead_replicas"] == 1 and c["redispatches"] > 0
          and victim in router._dead,
          f"dead_replicas={c['dead_replicas']} "
          f"redispatches={c['redispatches']} dead={router._dead}")
    bb_ok, bb_detail = False, f"missing: {bb_path}"
    try:
        with open(bb_path) as f:
            bb = json.load(f)
        srv = bb.get("state", {}).get("serving_router", {})
        bb_ok = (bb.get("reason") == "router_replica_dead"
                 and str(victim) in srv.get("dead", {}))
        bb_detail = (f"reason={bb.get('reason')} "
                     f"dead={srv.get('dead')}")
    except OSError:
        pass
    except ValueError as e:
        bb_detail = f"unparseable: {e}"
    check("blackbox", bb_ok, bb_detail)
    check("healthz", healthz_ok and killed_step is not None
          and dead_reported_step == killed_step,
          healthz_err or (f"dead replica visible in /healthz at step "
                          f"{dead_reported_step} (killed at step "
                          f"{killed_step}); degraded="
                          f"{(last_health or {}).get('degraded')}"))

    line = {
        "metric": "soak_router",
        "value": len(outs),
        "unit": "requests",
        "replicas": 3,
        "victim": victim,
        "kill_at": kill_at,
        "redispatched": c["redispatches"],
        "dispatches_per_replica": list(router.dispatch_counts),
        "killed_step": killed_step,
        "dead_reported_step": dead_reported_step,
        "wall_s": round(wall, 3),
        "checks": [{k: ch[k] for k in ("name", "ok")} for ch in checks],
    }
    if smoke:
        line["note"] = "cpu smoke; replica-kill drain proof"
    ok = all(ch["ok"] for ch in checks)
    line["ok"] = ok
    for ch in checks:
        mark = "ok  " if ch["ok"] else "FAIL"
        print(f"  [{mark}] {ch['name']:<16} {ch.get('detail', '')}",
              flush=True)
    print(json.dumps(line), flush=True)
    return 0 if ok else 3


# -- driver ------------------------------------------------------------------

def _read_jsonl(path):
    """(step_lines, run_ends) across ALL lives appended to the sink."""
    steps, ends = [], []
    try:
        with open(path) as f:
            for raw in f:
                raw = raw.strip()
                if not raw:
                    continue
                try:
                    line = json.loads(raw)
                except ValueError:
                    continue
                if not isinstance(line, dict):
                    continue
                if "step" in line:
                    steps.append(line)
                elif line.get("event") == "run_end":
                    ends.append(line)
    except OSError:
        pass
    return steps, ends


def _load_perf_guard():
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "perf_guard.py")
    spec = importlib.util.spec_from_file_location("perf_guard", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _scan_checkpoints(ckpt_dir):
    """(complete_steps, torn_steps) by manifest presence — pure stdlib
    (the worker's resume selector additionally size-verifies shards)."""
    complete, torn = [], []
    try:
        names = os.listdir(ckpt_dir)
    except OSError:
        return complete, torn
    for name in names:
        if not name.startswith("step-"):
            continue
        try:
            step = int(name.split("-", 1)[1])
        except ValueError:
            continue
        if os.path.exists(os.path.join(ckpt_dir, name, "MANIFEST.json")):
            complete.append(step)
        else:
            torn.append(step)
    return sorted(complete), sorted(torn)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Fault-injected soak run gated on loss slope, memory "
                    "growth, crash/NaN survival, and the perf guard.")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 sizing: CPU, ~50 steps, ~1 min")
    ap.add_argument("--steps", type=int, default=None,
                    help="total train steps (default: 48 smoke / 2000)")
    ap.add_argument("--out", default=None,
                    help="workdir (default: a fresh temp dir)")
    ap.add_argument("--router", action="store_true",
                    help="serving replica-kill drain leg: 3-replica "
                         "router, one injected step() failure, gated "
                         "on survivors finishing byte-identical")
    ap.add_argument("--worker", default=None, metavar="WORKDIR",
                    help=argparse.SUPPRESS)  # internal: launcher payload
    args = ap.parse_args(argv)
    if args.worker:
        return _worker(args.worker)
    if args.router:
        return _router_leg(args)

    # This parent starts (through the launcher) the children that hold
    # the chip, so it never initialises a JAX backend itself — no probe:
    # without --smoke the worker requires the TPU and fails the run.
    smoke = args.smoke
    steps = args.steps or (SMOKE_STEPS if smoke else 2000)
    batch = int(os.environ.get("PT_SOAK_BATCH", str(SMOKE_BATCH)))
    crash_at = int(os.environ.get("PT_SOAK_CRASH_AT",
                                  str(max(2, steps // 3))))
    poison_at = int(os.environ.get("PT_SOAK_POISON_AT",
                                   str(max(3, (2 * steps) // 3))))
    # hang BEFORE the crash: injected once, on the first life
    hang_at = int(os.environ.get("PT_SOAK_HANG_AT",
                                 str(max(1, steps // 6))))

    wd = args.out or tempfile.mkdtemp(prefix="pt_soak_")
    os.makedirs(wd, exist_ok=True)
    sink = os.path.join(wd, "steps.jsonl")
    env = dict(os.environ)
    env.update({
        "PT_SOAK_STEPS": str(steps),
        "PT_SOAK_BATCH": str(batch),
        "PT_SOAK_CRASH_AT": str(crash_at),
        "PT_SOAK_POISON_AT": str(poison_at),
        "PT_SOAK_HANG_AT": str(hang_at),
        "PT_MONITOR": "1",
        "PT_MONITOR_SINK": sink,
        "PT_MONITOR_MEM": "1",
        # crash postmortem lands in the workdir, not the repo cwd
        "PT_SERVE_BLACKBOX": os.path.join(wd, "serving_blackbox.json"),
        # hang watchdog: short deadline floor so the injected sleep
        # trips it; its artifact lands separately from the crash one
        "PT_HANG_MIN_S": env.get("PT_HANG_MIN_S") or "1",
        "PT_HANG_BLACKBOX": os.path.join(wd, "hang_blackbox.json"),
        # warm relaunch pays zero fresh XLA compiles (jit/exec_cache.py)
        "PT_EXEC_CACHE": env.get("PT_EXEC_CACHE")
        or os.path.join(wd, "exec_cache"),
        "PYTHONPATH": ROOT + os.pathsep + env.get("PYTHONPATH", ""),
    })
    env.pop("PADDLE_RESTART_COUNT", None)
    if smoke:
        env["JAX_PLATFORMS"] = "cpu"
        # a ~50-step smoke must exercise the planner AND still save often
        # enough to have a resume point near the crash: a tiny model's
        # save cost (~60 ms) vs its step time (~4 ms) would honestly plan
        # a sparser cadence than the smoke has steps
        env.setdefault("PT_CKPT_OVERHEAD_PCT", "40")
        env.setdefault("PT_CKPT_MAX_INTERVAL", "4")
    print(f"soak: smoke={smoke} steps={steps} crash_at={crash_at} "
          f"poison_at={poison_at} hang_at={hang_at} workdir={wd}",
          flush=True)

    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--max_restart", "2", "--log_dir", os.path.join(wd, "log"),
         os.path.abspath(__file__), "--worker", wd],
        cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=3600 if not smoke else 900)
    wall = time.perf_counter() - t0

    lives = []
    for name in sorted(os.listdir(wd)):
        if name.startswith("life_") and name.endswith(".json"):
            with open(os.path.join(wd, name)) as f:
                lives.append(json.load(f))
    step_lines, run_ends = _read_jsonl(sink)
    complete_ckpts, torn_ckpts = _scan_checkpoints(
        os.path.join(wd, "ckpt"))

    checks = []

    def check(name, ok, detail):
        checks.append({"name": name, "ok": bool(ok), "detail": detail})

    worker_logs = ""
    logdir = os.path.join(wd, "log")
    if os.path.isdir(logdir):
        for lg in sorted(os.listdir(logdir)):
            try:
                with open(os.path.join(logdir, lg)) as f:
                    worker_logs += f.read()[-2000:]
            except OSError:
                pass
    check("launcher", proc.returncode == 0,
          f"rc={proc.returncode}" + (
              f"; stderr: {proc.stderr[-500:]}; logs: {worker_logs[-800:]}"
              if proc.returncode != 0 else ""))
    final = lives[-1] if lives else {}
    # a crashed life never writes its summary (os._exit), so the life
    # count comes from the final life's restart index, not file count
    n_lives = (final.get("life", 0) + 1) if lives else 0
    check("finished", bool(final.get("finished"))
          and bool(final.get("params_finite")),
          f"{n_lives} live(s); final life finished="
          f"{final.get('finished')} params_finite="
          f"{final.get('params_finite')}")

    if crash_at >= 0:
        relaunched = [lv for lv in lives if lv.get("life", 0) > 0]
        res_from = [lv.get("resumed_from") for lv in relaunched]
        res_ok = (n_lives >= 2 and res_from
                  and all(s is not None for s in res_from))
        # the resume selector must have picked a COMPLETE checkpoint — a
        # torn one (crash mid-write) is never a resume point. Judged from
        # the worker's RESUME-TIME verification (post-run GC removes torn
        # dirs, so a driver-side scan would be vacuous)
        untorn = all(lv.get("resumed_from_complete") is True
                     for lv in relaunched)
        check("crash_resume", res_ok and untorn,
              f"lives={n_lives} resumed_from={res_from} "
              f"resume_point_complete={untorn} "
              f"complete={complete_ckpts[-3:]} torn={torn_ckpts}")
        # the injected crash must leave a parseable blackbox postmortem
        # (monitor/blackbox.py — written before os._exit, atomically)
        bb_path = env["PT_SERVE_BLACKBOX"]
        bb_ok, bb_detail = False, f"missing: {bb_path}"
        try:
            with open(bb_path) as f:
                bb = json.load(f)
            bb_ok = (isinstance(bb.get("spans"), list)
                     and isinstance(bb.get("state"), dict)
                     and bb.get("reason") == "PT_SOAK_CRASH_AT")
            bb_detail = (f"reason={bb.get('reason')} "
                         f"spans={len(bb.get('spans', []))} "
                         f"state_keys={sorted(bb.get('state', {}))}")
        except OSError:
            pass
        except ValueError as e:
            bb_detail = f"unparseable: {e}"
        check("blackbox", bb_ok, bb_detail)
    skipped = sum(lv.get("skipped_batches", 0) for lv in lives)
    if poison_at >= 0:
        check("nan_skip", skipped >= 1,
              f"{skipped} batch(es) skipped (poison at {poison_at})")
    if hang_at >= 0:
        # the injected hang must leave a parseable watchdog artifact
        # NAMING the hung step (the first life hangs after batch
        # `hang_at`, so step hang_at+1 is the one that never landed
        # within deadline)
        hb_path = env["PT_HANG_BLACKBOX"]
        hang_ok, hang_detail = False, f"missing: {hb_path}"
        try:
            with open(hb_path) as f:
                hb = json.load(f)
            trip = (hb.get("state", {}).get("training_watchdog", {})
                    or {}).get("last_trip") or {}
            hang_ok = (hb.get("reason") == "hang_watchdog"
                       and trip.get("hung_step") == hang_at + 1
                       and bool(trip.get("stacks")))
            hang_detail = (f"reason={hb.get('reason')} "
                           f"hung_step={trip.get('hung_step')} "
                           f"(expected {hang_at + 1}) "
                           f"stacks={len(trip.get('stacks') or {})} "
                           f"thread(s)")
        except OSError:
            pass
        except ValueError as e:
            hang_detail = f"unparseable: {e}"
        check("hang_watchdog", hang_ok, hang_detail)

    losses = [(s["step"], s["loss"]) for s in step_lines if "loss" in s]
    if len(losses) >= 8:
        vals = [v for _, v in losses]
        q = max(1, len(vals) // 4)
        first, last = vals[:q], vals[-q:]
        slope_ok = statistics.fmean(last) < statistics.fmean(first)
        check("loss_slope", slope_ok,
              f"mean(first {q})={statistics.fmean(first):.4f} -> "
              f"mean(last {q})={statistics.fmean(last):.4f} over "
              f"{len(vals)} logged losses")
    else:
        check("loss_slope", False,
              f"only {len(losses)} logged losses — not enough to judge")

    mem_series = [s["memory"].get("live_bytes", 0) for s in step_lines
                  if isinstance(s.get("memory"), dict)]
    peak_live = max(mem_series) if mem_series else None
    if len(mem_series) >= 9:
        third = len(mem_series) // 3
        early = max(mem_series[:third])
        late = max(mem_series[-third:])
        slack = 32 << 20  # small-model census noise floor
        mem_ok = late <= early * 1.10 + slack
        check("memory_growth", mem_ok,
              f"live-census peak first third {early / 2**20:.1f} MiB -> "
              f"last third {late / 2**20:.1f} MiB (max +10%)")

    ips = [s["ips"] for s in step_lines if s.get("ips")]
    value = round(statistics.median(ips), 3) if ips else 0.0
    final_end = run_ends[-1] if run_ends else {}
    save_h = (final_end.get("totals", {}).get("histograms", {})
              .get("resilience/save_ms")) or {}
    saves_total = sum(lv.get("saves", 0) for lv in lives)

    line = {
        "metric": "soak",
        "value": value,
        "unit": "samples/s",
        "steps": steps,
        "batch": batch,
        "lives": n_lives,
        "crash_at": crash_at,
        "poison_at": poison_at,
        "skipped_batches": skipped,
        "ckpt_saves": saves_total,
        "ckpt_complete": len(complete_ckpts),
        "ckpt_torn": len(torn_ckpts),
        "last_checkpoint_step": final_end.get("last_checkpoint_step"),
        "wall_s": round(wall, 3),
    }
    if save_h:
        line["ckpt_save_ms_p50"] = save_h.get("p50")
        line["ckpt_save_ms_max"] = save_h.get("max")
    gp = final_end.get("goodput") or {}
    if gp.get("goodput_frac") is not None:
        # the final life's wall-clock classification (run_end.goodput)
        line["goodput_frac"] = round(gp["goodput_frac"], 4)
    if hang_at >= 0:
        # from the artifact, not the life summaries: the hanging life is
        # the one the injected crash kills before it writes its summary
        try:
            with open(env["PT_HANG_BLACKBOX"]) as f:
                line["hang_trips"] = (json.load(f).get("state", {})
                                      .get("training_watchdog", {})
                                      or {}).get("trips", 0)
        except (OSError, ValueError):
            line["hang_trips"] = 0
    if losses:
        line["loss_first"] = losses[0][1]
        line["loss_last"] = losses[-1][1]
    if peak_live is not None:
        line["memory"] = {"peak_live_gib": round(peak_live / 2**30, 4)}
    if smoke:
        line["note"] = "cpu smoke; the hardware soak row needs the chip"

    guard = _load_perf_guard()
    baseline = guard.last_good(guard._default_store(), "soak",
                               fresh=line, match=guard.config_match(line))
    verdict = guard.evaluate(line, baseline,
                             hardware=None if not smoke else False)
    checks.extend(verdict["checks"])
    line["guard"] = verdict
    line["checks"] = [{k: c[k] for k in ("name", "ok")} for c in checks]
    ok = all(c["ok"] for c in checks)
    line["ok"] = ok

    if not smoke:
        try:
            from paddle_tpu.utils import measurements as meas

            extra = {k: line[k] for k in (
                "steps", "batch", "lives", "skipped_batches",
                "ckpt_saves", "ckpt_save_ms_p50", "goodput_frac",
                "wall_s") if k in line}
            meas.record("soak", value, "samples/s", extra=extra)
        except Exception as e:  # noqa: BLE001 — persist must not gate
            print(f"soak: measurement persist failed: {e}",
                  file=sys.stderr)

    for c in checks:
        mark = "ok  " if c["ok"] else "FAIL"
        print(f"  [{mark}] {c['name']:<16} {c.get('detail', '')}",
              flush=True)
    print(json.dumps(line), flush=True)
    return 0 if ok else 3


if __name__ == "__main__":
    sys.exit(main())
