"""Step-metrics JSONL sink.

One line per training step: step id, wall time, loss, ips, and the monitor
counter *diff* since the previous line — so a reader can see exactly which
step retraced, fenced the device, or moved collective bytes. Bracketed by a
``run_begin`` line (metadata) and a ``run_end`` line (cumulative totals,
including full histogram percentiles). Every line is independently
parseable JSON; ``tools/monitor_report.py`` renders a run summary from it,
optionally joined with a profiler chrome trace.
"""
from __future__ import annotations

import json
import os
import tempfile
import time


def _default_path() -> str:
    """``PT_MONITOR_SINK``, else a run-scoped path under the system
    tempdir — NEVER the working directory (a bare ``PT_MONITOR=1`` run
    used to litter a ``monitor_steps.jsonl`` wherever it was launched
    from). The pid scope keeps concurrent runs from interleaving one
    file; the ``run_end`` line reports the resolved ``sink`` so the
    artifact is findable without knowing this rule."""
    sink = os.environ.get("PT_MONITOR_SINK")
    if sink:
        return sink
    return os.path.join(tempfile.gettempdir(),
                        f"pt_monitor_steps.{os.getpid()}.jsonl")


class StepLogger:
    """Append-mode JSONL writer with monotonic step ids.

    Usage::

        with monitor.StepLogger("run.jsonl", meta={"source": "fit"}) as log:
            for batch in loader:
                loss = step(*batch)
                log.log_step(loss=float(loss.numpy()), num_samples=bs)

    Works with monitoring disabled too (lines simply carry no counter
    diffs), so explicit callers never crash on a missing ``PT_MONITOR=1``.
    """

    def __init__(self, path: str | None = None, meta: dict | None = None):
        from paddle_tpu import monitor as _mon
        from paddle_tpu.monitor import memory as _memory

        self._mon = _mon
        self._memory = _memory
        self.path = path or _default_path()
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._f = open(self.path, "a")
        self._step = 0
        self._ckpt_step = None
        self._t0 = self._t_last = time.perf_counter()
        self._prev = _mon.snapshot()
        self._write({
            "event": "run_begin",
            "ts": round(time.time(), 6),
            "pid": os.getpid(),
            "monitor_enabled": _mon.enabled(),
            "meta": meta or {},
        })

    def _write(self, obj: dict) -> None:
        self._f.write(json.dumps(obj) + "\n")
        self._f.flush()

    def log_step(self, loss=None, num_samples=None, **fields) -> dict:
        """Emit one step line; returns the dict that was written.

        ``dur_ms`` is host wall-time since the previous line — on async
        backends that is dispatch time unless the caller synced (which is
        exactly what a per-step `.numpy()` fetch of the loss does).
        """
        now = time.perf_counter()
        t_prev = self._t_last
        dur = now - t_prev
        self._t_last = now
        cur = self._mon.snapshot()
        delta = self._mon.diff(self._prev, cur)
        self._prev = cur
        self._step += 1
        # step marker span on its own lane: the window the --spans
        # attribution pass decomposes (no-op when the monitor is off)
        self._mon.record_span(f"step/{self._step}", "step", t_prev, now,
                              lane="steps")
        line = {"step": self._step, "ts": round(time.time(), 6),
                "dur_ms": round(dur * 1e3, 3)}
        if loss is not None:
            line["loss"] = float(loss)
        if num_samples:
            line["ips"] = round(num_samples / dur, 3) if dur > 0 else 0.0
        for k, v in fields.items():
            if v is not None:
                line[k] = v
        led = self._memory._ledger
        if led is not None:
            # step-boundary census: live bytes + running peak land on
            # every step line (and, via the memory/* gauges, in the
            # profiler's ph:"C" counter tracks)
            line["memory"] = led.step_census()
        line.update(delta)
        from . import goodput

        if goodput.active() is None:
            # the shared step-time EMA (monitor/step_ms_ema gauge; the
            # hang watchdog + ckpt cadence planner both read it). When
            # a goodput ledger is active the fit loop feeds it with
            # the true stepper wall-time instead.
            goodput.observe_step_ms(dur * 1e3)
        self._write(line)
        self._drain_breaches()
        return line

    def _drain_breaches(self) -> None:
        """SLO watchdog breaches queued since the last line land as
        structured ``{"event": "slo_breach"}`` lines — the live plane's
        durable record (monitor/live.py; zero-cost while live is off)."""
        from . import live

        if not live.enabled():
            return
        for breach in live.pop_breach_events():
            self._write({"event": "slo_breach", "step": self._step,
                         "ts": round(time.time(), 6), **breach})

    def note_checkpoint(self, step) -> None:
        """Record the last COMPLETE checkpoint's step: the ``run_end``
        line (clean or crashed) then says exactly what a relaunch will
        resume from — the postmortem's first question."""
        self._ckpt_step = int(step)

    def close(self, error=None, **fields) -> None:
        """Write the ``run_end`` totals line and close the file
        (idempotent). ``error`` marks a run that died mid-loop — the
        terminal line still lands, so a crashed run's JSONL is
        distinguishable from a truncated one."""
        if self._f is None:
            return
        line = {"event": "run_end", "ts": round(time.time(), 6),
                "steps": self._step,
                "wall_s": round(time.perf_counter() - self._t0, 3),
                "sink": self.path,
                "totals": self._mon.snapshot()}
        if self._ckpt_step is not None:
            line["last_checkpoint_step"] = self._ckpt_step
        led = self._memory._ledger
        if led is not None and "memory" not in fields:
            # run-level memory account: peak HBM + per-executable records
            line["memory"] = led.snapshot()
        if error is not None:
            line["error"] = str(error)[:500]
        for k, v in fields.items():
            if v is not None:
                line[k] = v
        from . import goodput

        gsnap = goodput.active_snapshot()
        if gsnap is not None:
            # where did the run's wall-clock go (exact telescoping;
            # monitor_report renders the verdict from this)
            line.setdefault("goodput", gsnap)
        from . import live

        if live.enabled():
            # undrained breaches still land, and the run_end carries
            # the live-window snapshot monitor_report's SLO section
            # renders (sketch quantiles + burn state)
            self._drain_breaches()
            line.setdefault("live", live.snapshot())
        self._write(line)
        self._f.close()
        self._f = None
        if error is not None:
            # a run that died mid-loop (NonFiniteError surfacing through
            # fit, an engine raise crossing the `with`) leaves the
            # blackbox postmortem next to its run_end line — gated the
            # same way as every crash site (monitor on or
            # PT_SERVE_BLACKBOX set), and never masking the error
            from . import blackbox

            blackbox.maybe_dump(reason="run_error", error=error)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        # an exception crossing the `with` still gets its run_end line,
        # tagged with the error that ended the run
        self.close(error=None if exc_type is None
                   else f"{exc_type.__name__}: {exc}")
        return False
