"""Shared CLI plumbing for the planner-family tools.

`tools/shard_plan.py` and `tools/memory_planner.py` sweep the same
probe over the same candidate space, so the probe-dimension arguments,
the smoke geometry, and the corrected-child re-exec (the virtual mesh
must exist BEFORE jax initializes a backend, so the sweep runs in a
child whose environment asks for it) live here once. Pure stdlib —
importable before any backend decision is made.
"""
from __future__ import annotations

import os
import subprocess
import sys

__all__ = ["add_probe_args", "apply_smoke", "reexec_virtual_child",
           "SMOKE_CONFIGS"]

# the tier-1 smoke sweep: tiny probe, four mesh candidates — one per
# parallelism family incl. a pp>1 pipeline (the smoke probe's 2 layers
# stage over pp=2)
SMOKE_CONFIGS = "dp8,dp4xmp2,dp2xmp4,dp4xpp2"


def add_probe_args(ap) -> None:
    """The probe-model dimension flags (defaults shared by both tools)."""
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--intermediate", type=int, default=0,
                    help="FFN width (default 3*hidden)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--moe-experts", type=int, default=0,
                    help="experts per MLP (0 = dense probe; >0 builds an "
                         "MoE probe so the sweep costs the expert "
                         "all-to-all)")


def apply_smoke(args) -> None:
    """Shrink to the smoke geometry in place (CI pipeline proof)."""
    args.hidden, args.layers, args.heads = 64, 2, 4
    args.seq, args.vocab, args.batches = 32, 512, "8"
    if not getattr(args, "configs", None):
        args.configs = SMOKE_CONFIGS


def reexec_virtual_child(tool_file: str, tool_name: str, argv,
                         devices: int, child_flag: str,
                         exec_cache: str | None = None,
                         force_cpu: bool = True,
                         timeout: int = 1800) -> int:
    """Re-exec ``tool_file`` in a corrected child environment and return
    its exit code. ``child_flag`` is the env marker the tool checks to
    detect it IS the child. ``force_cpu=False`` (a bench with a live
    TPU) keeps the real backend and device count."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env[child_flag] = "1"
    if exec_cache:
        env["PT_EXEC_CACHE"] = os.path.abspath(exec_cache)
    if force_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={devices}")
        env["XLA_FLAGS"] = " ".join(flags)
    code = ("import sys; sys.path.insert(0, %r); "
            "sys.path.insert(0, %r); "
            "import importlib.util; "
            "spec = importlib.util.spec_from_file_location(%r, %r); "
            "mod = importlib.util.module_from_spec(spec); "
            "spec.loader.exec_module(mod); "
            "sys.exit(mod.main(%r))"
            % (root, os.path.join(root, "tools"), tool_name,
               os.path.abspath(tool_file), list(argv)))
    try:
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              cwd=root, timeout=timeout)
    except subprocess.TimeoutExpired:
        # the documented setup-error exit code, not a traceback — a
        # timeboxed hwbench row must read a clean rc
        print(f"{tool_name}: child timed out after {timeout}s",
              file=sys.stderr, flush=True)
        return 2
    return proc.returncode
