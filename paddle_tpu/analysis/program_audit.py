"""Compiled-program audit: the PR 10 tripwire, standing (tier 2).

GSPMD (PAPERS.md 2105.04663) makes the sharding truth of a program
readable from the compiled artifact alone — the collectives XLA's
partitioner inserted, the input/output alias table donation produced,
the host callbacks that snuck in. So the incident classes this repo has
actually shipped are auditable at the one compile chokepoint
(``jit/exec_cache.get_or_compile``) with zero hardware:

- **PA001 replicated_dp** — a train-step program on a dp>1 mesh with ZERO
  collectives crossing the dp axis: every device computes the same thing
  (exactly what PR 10's dropped ``with_sharding_constraint`` lowered to,
  caught then only because the autoshard sweep read zero collectives).
- **PA002 dropped_donation** — ``donate_argnums`` set but the compiled
  module's ``input_output_alias`` table is empty: HBM silently doubles
  (params + grads both live) and nobody OOMs until the next size bump.
- **PA003 host_callback** — host round-trips (``custom-call`` python
  callbacks, infeed/outfeed) inside a step program beyond the declared
  allowance: each one is a hidden host sync that stalls the device
  mid-step.
- **PA004 retrace_budget** — one compile site (label) accumulating more
  than ``PT_AUDIT_RETRACE_BUDGET`` (8) distinct executables: signature
  churn is paying an XLA compile per step somewhere.
- **PA005 missing_pp_handoff** — a train-step program on a pp>1 mesh
  with ZERO collective-permutes crossing the pp axis: the planned
  pipeline's stage handoff was silently dropped and every "stage"
  computes the whole model (the PA001 sibling for the pipeline axis —
  ISSUE 15; the ZeRO-style head/tail all-gathers over pp do not count,
  only the ppermute ring does).

Enablement: ``PT_PROGRAM_AUDIT=1`` (or :func:`enable`) installs this
module into ``exec_cache._audit`` — the same None-slot pattern as the
monitor, so the off state costs one ``is None`` check (this module is in
``monitor.INSTRUMENTED_MODULES``; the tier-1 audit test asserts
import-time inertness). Findings feed ``analysis/*`` monitor counters,
the bench line's ``program_audit`` sub-object (gated by
``tools/perf_guard.py --audit``), and are filed in the exec-cache meta
sidecar under the executable's own key, so a warm start re-reports
without re-parsing HLO. HLO parsing reuses ``autoshard/hlo_costs.py``
(post-SPMD collective extraction). Details: ``docs/STATIC_ANALYSIS.md``.
"""
from __future__ import annotations

import os
import re
import sys

from ..monitor import _register as _monitor_register


def _parse_collectives(hlo_text: str, degrees: dict) -> list:
    # lazy: pulling autoshard's package __init__ (planner, plan) at
    # import time would cycle through jit.exec_cache while it arms the
    # _audit slot mid-import; hlo_costs itself is stdlib-only
    from ..autoshard.hlo_costs import parse_collectives

    return parse_collectives(hlo_text, degrees)

__all__ = [
    "RULES", "enabled", "enable", "disable", "reset", "report",
    "audit_hlo", "audit_entry", "audit_train_step",
    "on_compiled", "on_hit", "RETRACE_BUDGET",
]

RULES = {
    "PA001": "replicated_dp",
    "PA002": "dropped_donation",
    "PA003": "host_callback",
    "PA004": "retrace_budget",
    "PA005": "missing_pp_handoff",
}

# distinct executables one compile site (label) may accumulate before
# the audit calls it signature churn
RETRACE_BUDGET = int(os.environ.get("PT_AUDIT_RETRACE_BUDGET", "8") or 8)

# telemetry slot (paddle_tpu.monitor None-slot contract)
_monitor = None

_enabled = False

# process-wide report state (read by bench.py / dryrun_multichip)
_audits = 0
_findings: list = []
_compiles_by_label: dict = {}

# a non-empty alias table has at least one `{output_index}: (...)` entry
# — `input_output_alias={ {}: (0, {}, may-alias) }`; keying on the inner
# `{` avoids matching unrelated parens later on the header line
_ALIAS_RE = re.compile(r"input_output_alias=\{\s*\{")
_CALLBACK_RE = re.compile(
    r'custom_call_target="[^"]*callback[^"]*"|'
    r"=\s*[^=]*\b(?:infeed|outfeed)\(")


def enabled() -> bool:
    return _enabled


def enable() -> None:
    """Arm the audit at the exec-cache compile chokepoint (same effect
    as starting the process with ``PT_PROGRAM_AUDIT=1``)."""
    global _enabled
    _enabled = True
    from ..jit import exec_cache

    exec_cache._audit = sys.modules[__name__]


def disable() -> None:
    global _enabled
    _enabled = False
    from ..jit import exec_cache

    exec_cache._audit = None


def reset() -> None:
    """Drop collected findings and retrace bookkeeping (test hook)."""
    global _audits
    _audits = 0
    _findings.clear()
    _compiles_by_label.clear()


def report() -> dict:
    """The process-wide audit account benches embed:
    ``{"audits", "findings"}`` (findings deduped on rule+label+detail,
    in first-seen order)."""
    seen, uniq = set(), []
    for f in _findings:
        k = (f.get("rule"), f.get("label"), f.get("detail"))
        if k not in seen:
            seen.add(k)
            uniq.append(f)
    return {"audits": _audits, "findings": uniq}


def _finding(rule: str, detail: str, label=None) -> dict:
    return {"rule": rule, "name": RULES[rule], "severity": "error",
            "detail": detail, "label": label}


# -- the pure HLO checks (unit-testable on captured fixtures) ----------------

def audit_hlo(hlo_text: str, *, degrees: dict | None = None,
              expect_dp: bool = False, expect_pp: bool = False,
              donate_expected: bool = False,
              allowed_host_calls: int = 0, label: str | None = None) -> list:
    """Findings for ONE compiled module's optimized-HLO text.

    ``degrees``: mesh axis degrees (``{"dp": 4, "mp": 2}``) for
    collective attribution; ``expect_dp``: the program SHOULD move bytes
    across dp (a train step on a dp>1 mesh); ``expect_pp``: the program
    SHOULD hand microbatches stage-to-stage (a train step on a pp>1
    mesh — zero cross-pp collective-permutes means the pipeline was
    compiled out); ``donate_expected``: the compile was requested with
    donated args; ``allowed_host_calls``: declared host round-trips
    (0 — the NaN sentinel is an in-program reduction, not a callback)."""
    out = []
    degrees = degrees or {}
    colls = (_parse_collectives(hlo_text, degrees)
             if (expect_dp or expect_pp) else [])
    if expect_dp:
        dp_colls = [c for c in colls
                    if "dp" in c["axis"].split("+")]
        if not dp_colls:
            out.append(_finding(
                "PA001",
                f"dp={degrees.get('dp')} mesh but the step program has "
                f"zero cross-dp collectives ({len(colls)} total) — data "
                "parallelism compiled to replicated compute (the PR 10 "
                "bug class: check sharding constraints survived the "
                "trace)", label))
    if expect_pp:
        pp_perms = [c for c in colls
                    if c["op"] == "collective-permute"
                    and "pp" in c["axis"].split("+")]
        if not pp_perms:
            out.append(_finding(
                "PA005",
                f"pp={degrees.get('pp')} mesh but the step program has "
                f"zero cross-pp collective-permutes ({len(colls)} "
                "collectives total) — the stage handoff was silently "
                "dropped; every stage is computing the whole model "
                "(stage the model through PipelineLayer / "
                "autoshard.stage_model)", label))
    if donate_expected and not _ALIAS_RE.search(hlo_text):
        out.append(_finding(
            "PA002",
            "donate_argnums set but the compiled module carries no "
            "input_output_alias entries — donation was dropped and "
            "peak HBM holds inputs AND outputs", label))
    host_calls = len(_CALLBACK_RE.findall(hlo_text))
    if host_calls > allowed_host_calls:
        out.append(_finding(
            "PA003",
            f"{host_calls} host round-trip(s) (python callbacks / "
            f"infeed / outfeed) in a step program (declared: "
            f"{allowed_host_calls}) — each is a hidden host sync",
            label))
    return out


# -- context derivation from an exec-cache key --------------------------------

def _degrees_from_key(key) -> dict | None:
    """Mesh axis degrees from a cache key's ``mesh`` entry
    (``exec_cache.mesh_spec()`` shape), else the live env."""
    if isinstance(key, dict):
        mesh = key.get("mesh")
        if (isinstance(mesh, (tuple, list)) and len(mesh) == 2
                and isinstance(mesh[0], (tuple, list))):
            return dict(zip(mesh[0], mesh[1]))
    try:
        from ..distributed import env as env_mod

        e = env_mod.get_env()
        if e is not None:
            return dict(zip(e.mesh.axis_names, e.mesh.devices.shape))
    except Exception:  # noqa: BLE001
        pass
    return None


def audit_entry(entry, key=None, label: str | None = None) -> list:
    """Audit one exec-cache entry with whatever context its key carries.

    ``expect_dp`` holds only for train-step programs on a dp>1 mesh: a
    training step that moves ZERO bytes over dp is the replicated-
    compute smell regardless of batch placement (replicated batch + no
    constraints = every device doing identical work). Forward-only
    programs legitimately ship without dp collectives, so they are not
    judged. The key is absent whenever the exec cache is disabled
    (callers pass ``key=None``), so train-step identity falls back to
    the compile-site label (``train_step/<Model>``) and mesh degrees to
    the live env — PA001 stands without ``PT_EXEC_CACHE``; only the
    donation check (PA002) needs the key's ``donate`` flag."""
    try:
        hlo = entry.compiled.as_text()
    except Exception:  # noqa: BLE001 — a backend whose executables carry
        return []      # no HLO (some deserialized artifacts) can't be audited
    degrees = _degrees_from_key(key) or {}
    kind = key.get("kind") if isinstance(key, dict) else None
    if kind is None and isinstance(label, str) \
            and label.startswith("train_step/"):
        kind = "train_step"
    expect_dp = (kind == "train_step"
                 and int(degrees.get("dp", 1) or 1) > 1)
    expect_pp = (kind == "train_step"
                 and int(degrees.get("pp", 1) or 1) > 1)
    donate_expected = (isinstance(key, dict) and bool(key.get("donate"))
                       and not key.get("nan_check"))
    return audit_hlo(hlo, degrees=degrees, expect_dp=expect_dp,
                     expect_pp=expect_pp,
                     donate_expected=donate_expected, label=label)


# -- exec_cache hook (invoked ONLY while the _audit slot is armed) -----------

def _file(findings: list, key, label) -> None:
    global _audits
    _audits += 1
    _findings.extend(findings)
    m = _monitor
    if m is not None:
        m.on_program_audit(len(findings),
                           [f["rule"] for f in findings])
    if findings:
        for f in findings:
            print(f"program_audit: {f['rule']} {f['name']} "
                  f"[{f.get('label')}]: {f['detail']}",
                  file=sys.stderr, flush=True)
    if key is not None:
        try:
            from ..jit import exec_cache

            meta = dict(exec_cache.meta_get(key) or {})
            # PA004 describes THIS PROCESS's signature churn, not the
            # artifact — persisting it would replay a one-off churn
            # verdict on every future warm start of this key
            meta["program_audit"] = {"findings": [
                f for f in findings if f.get("rule") != "PA004"]}
            exec_cache.meta_put(key, meta)
        except Exception:  # noqa: BLE001 — the sidecar is best-effort
            pass


def on_compiled(entry, key, label) -> None:
    """Fresh compile at the chokepoint: parse, judge, file. Never raises
    — an audit bug must not break compilation."""
    try:
        findings = audit_entry(entry, key, label)
        if label is not None:
            n = _compiles_by_label[label] = \
                _compiles_by_label.get(label, 0) + 1
            if n == RETRACE_BUDGET + 1:  # fire once, at the crossing
                findings.append(_finding(
                    "PA004",
                    f"compile site accumulated {n} distinct executables "
                    f"(budget {RETRACE_BUDGET}, PT_AUDIT_RETRACE_BUDGET)"
                    " — a signature is churning; every extra one is an "
                    "XLA compile on the hot path", label))
        _file(findings, key, label)
    except Exception as e:  # noqa: BLE001
        print(f"program_audit: audit failed ({type(e).__name__}: {e})",
              file=sys.stderr, flush=True)


def on_hit(entry, key, label) -> None:
    """Cache hit: re-report the sidecar's stored findings without
    re-parsing HLO; parse fresh only when the sidecar has no record
    (e.g. the artifact predates the audit)."""
    try:
        from ..jit import exec_cache

        meta = exec_cache.meta_get(key)
        stored = (meta or {}).get("program_audit")
        if isinstance(stored, dict) and isinstance(
                stored.get("findings"), list):
            _file(list(stored["findings"]), None, label)
            return
        _file(audit_entry(entry, key, label), key, label)
    except Exception as e:  # noqa: BLE001
        print(f"program_audit: hit re-report failed "
              f"({type(e).__name__}: {e})", file=sys.stderr, flush=True)


# -- explicit whole-step audit (dryrun_multichip's proof leg) ----------------

def audit_train_step(step, *batch) -> dict:
    """Full-context audit of a live ``TrainStep``: compiles (or reuses)
    its executable for ``batch`` and returns ``{"findings", "facts"}``
    — facts carry the positive assertions the multi-chip dry-run prints
    (dp collectives present, donation honored, zero host calls)."""
    from ..distributed import env as env_mod

    entry, _arrays, nan_check = step._get_compiled(batch)
    e = env_mod.get_env()
    degrees = (dict(zip(e.mesh.axis_names, e.mesh.devices.shape))
               if e is not None else {})
    donate_expected = bool(getattr(step, "_donate", False)) and not nan_check
    hlo = entry.compiled.as_text()
    expect_dp = int(degrees.get("dp", 1) or 1) > 1
    expect_pp = int(degrees.get("pp", 1) or 1) > 1
    findings = audit_hlo(hlo, degrees=degrees, expect_dp=expect_dp,
                         expect_pp=expect_pp,
                         donate_expected=donate_expected,
                         label=f"train_step/{type(step._model).__name__}")
    colls = _parse_collectives(hlo, degrees)
    facts = {
        "degrees": degrees,
        "collectives": len(colls),
        "dp_collectives": sum(1 for c in colls
                              if "dp" in c["axis"].split("+")),
        "pp_collectives": sum(1 for c in colls
                              if "pp" in c["axis"].split("+")),
        "pp_handoffs": sum(1 for c in colls
                           if c["op"] == "collective-permute"
                           and "pp" in c["axis"].split("+")),
        "donation_expected": donate_expected,
        "donation_honored": bool(_ALIAS_RE.search(hlo)),
        "host_calls": len(_CALLBACK_RE.findall(hlo)),
    }
    return {"findings": findings, "facts": facts}


_monitor_register(sys.modules[__name__])

if os.environ.get("PT_PROGRAM_AUDIT", "0") not in ("", "0"):
    enable()
