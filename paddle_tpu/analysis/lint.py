"""pt-lint: AST rules for the traps this repo keeps re-finding.

Each rule is named for the incident that motivated it (full catalog with
history: ``docs/STATIC_ANALYSIS.md``):

- **PTL001** ``device_put`` in trace-reachable model/op code. On jax
  0.4.37 a ``jax.device_put`` inside a trace is a jaxpr NO-OP — PR 10
  found every in-model dp/mp hint silently dropped and dp compiled to
  fully replicated programs. Trace-reachable placement must branch on
  the tracer (``distributed/shard.py: constrain_or_put`` /
  ``shard_tensor``); an enclosing ``isinstance(..., Tracer)`` branch is
  recognized as that idiom and not flagged.
- **PTL002** ``block_until_ready`` under a timer. The repo has ONE
  fence for timed windows, ``utils/timing.device_sync`` (a host fetch:
  its latency lands in ``sync/fence_ms``, and it holds on any PJRT
  client, also one whose ``block_until_ready`` returns at enqueue — the
  incident the rule dates from). Any call is flagged; one inside a
  function that also reads a clock is an error. On the TPU v5e the two
  fences agree (chip_smoke.py prints the pair), so the rule is a
  convention there, not a correctness guard — ROADMAP C7.
- **PTL003** zero-overhead contract: a module that declares a monitor
  hook slot (``_monitor``/``_spans``/``_nancheck`` = None + a
  ``_register`` call) must guard every slot use with ``is not None``
  and join ``monitor.INSTRUMENTED_MODULES`` so the tier-1 audit test
  covers it.
- **PTL004** partial-axis ``sharding_constraint`` tuples in model code:
  naming 'mp' but not 'dp' forces XLA to gather the dp shards at every
  constraint boundary — a remat copy per layer now that traced
  constraints are honored (the PR 10 follow-up trap, CLAUDE.md).
- **PTL005** nondeterminism in planner/search/tune-table code paths:
  unseeded ``random``/``np.random`` calls, ``time.time()`` feeding
  logic, or set-iteration-ordered output would break the byte-identity
  contracts of ``shard_plan.json`` and ``kernel_tune.json``.

Escape hatch: ``# ptlint: disable=PTL001[,PTL002]`` on the offending
line (bare ``# ptlint: disable`` silences all rules for the line;
``# ptlint: skip-file`` anywhere in the first 10 lines skips the file).
Suppressions are deliberate and reviewable — the comment IS the audit
trail.

Pure stdlib (``ast`` + ``re``); no jax import, so the lint runs anywhere
the source lands.
"""
from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass

__all__ = [
    "Finding", "RULES", "lint_text", "lint_paths", "iter_py_files",
    "load_instrumented_modules", "TRACE_SCOPE", "DETERMINISM_SCOPE",
]

RULES = {
    "PTL001": "device_put in trace-reachable code (jaxpr no-op in a "
              "trace — route through shard.constrain_or_put)",
    "PTL002": "block_until_ready used for timing (the repo's one "
              "timed fence is utils/timing.device_sync)",
    "PTL003": "monitor hook-slot contract (unguarded slot use, or "
              "module missing from monitor.INSTRUMENTED_MODULES)",
    "PTL004": "partial-axis sharding_constraint in model code (name "
              "ALL live axes or XLA pays a remat copy per boundary)",
    "PTL005": "nondeterminism in planner/search/tune-table code "
              "(breaks shard_plan.json / tune-table byte-identity)",
}

# repo-relative path prefixes where code is reachable from a jax trace
# (model forwards, op builders, parallel layers) — the PTL001/PTL004
# scope. distributed/shard.py itself is deliberately OUT of scope: it is
# the one blessed home of the tracer-branch placement idiom.
TRACE_SCOPE = (
    "paddle_tpu/models/",
    "paddle_tpu/nn/",
    "paddle_tpu/ops/",
    "paddle_tpu/incubate/",
    "paddle_tpu/distributed/fleet/",
)

# code whose outputs carry a byte-identity contract (deterministic
# shard_plan.json, one locked tune table, replayable scheduler event
# logs — a nondeterministic drafter would break seeded serving-trace
# replays) — the PTL005 scope
DETERMINISM_SCOPE = (
    "paddle_tpu/autoshard/",
    "paddle_tpu/ops/pallas/",
    "paddle_tpu/serving/speculative",
    "paddle_tpu/serving/router",
    "tools/shard_plan.py",
    "tools/kernel_search.py",
    "tools/flash_autotune.py",
)

_SLOT_NAMES = ("_monitor", "_spans", "_nancheck", "_audit", "_live",
               "_goodput")

_DISABLE_RE = re.compile(r"#\s*ptlint:\s*disable(?:=([A-Z0-9, ]+))?")
_SKIP_FILE_RE = re.compile(r"#\s*ptlint:\s*skip-file")

# unseeded stdlib-random module functions (random.Random(seed) instances
# and np.random.default_rng(seed) are fine — they bind the seed)
_RANDOM_FNS = frozenset({
    "random", "randint", "randrange", "uniform", "choice", "choices",
    "shuffle", "sample", "normal", "randn", "rand", "permutation",
})
_CLOCK_NAMES = frozenset({"perf_counter", "monotonic", "time",
                          "perf_counter_ns", "monotonic_ns"})


@dataclass
class Finding:
    rule: str
    severity: str  # "error" | "warning"
    path: str
    line: int
    col: int
    message: str

    def to_dict(self) -> dict:
        return {"rule": self.rule, "severity": self.severity,
                "path": self.path, "line": self.line, "col": self.col,
                "message": self.message}

    def __str__(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule} [{self.severity}] {self.message}")


def _disabled_rules(text: str) -> dict:
    """line number -> set of disabled rule ids ({'*'} = all)."""
    out: dict = {}
    for i, raw in enumerate(text.splitlines(), start=1):
        m = _DISABLE_RE.search(raw)
        if not m:
            continue
        if m.group(1):
            out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
        else:
            out[i] = {"*"}
    return out


def _call_name(node: ast.Call) -> str | None:
    """Trailing name of the called function: ``jax.device_put`` and bare
    ``device_put`` both -> 'device_put'."""
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _dotted(node) -> str:
    """Best-effort dotted name of an expression ('np.random.randint')."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


class _Parents(ast.NodeVisitor):
    """One walk building child -> parent links + enclosing functions."""

    def __init__(self, tree):
        self.parent: dict = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                self.parent[child] = node

    def ancestors(self, node):
        while node in self.parent:
            node = self.parent[node]
            yield node

    def enclosing_functions(self, node) -> list:
        return [a for a in self.ancestors(node)
                if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda))]


def _mentions_tracer(fn_node) -> bool:
    """The enclosing function carries the blessed eager-vs-traced branch
    (``isinstance(x, jax.core.Tracer)``) — the shard.py idiom."""
    for n in ast.walk(fn_node):
        if isinstance(n, ast.Attribute) and n.attr == "Tracer":
            return True
        if isinstance(n, ast.Name) and n.id == "Tracer":
            return True
    return False


def _reads_clock(fn_node) -> bool:
    for n in ast.walk(fn_node):
        if isinstance(n, ast.Call):
            name = _call_name(n)
            if name in _CLOCK_NAMES:
                return True
    return False


def _trace_reachable(parents: _Parents, node) -> bool:
    """Heuristic for 'this call can execute under a trace': lexically
    inside a nested function/lambda (closures handed to jit/shard_map/
    custom_vjp/apply), or inside a Layer ``forward``/``__call__``."""
    fns = parents.enclosing_functions(node)
    if len(fns) >= 2:  # nested def / lambda-in-def
        return True
    return any(getattr(f, "name", "") in ("forward", "__call__")
               for f in fns)


def _compare_names(test, is_not: bool) -> set:
    """Names X for which ``test`` contains ``X is [not] None``."""
    out = set()
    for n in ast.walk(test):
        if (isinstance(n, ast.Compare)
                and isinstance(n.left, ast.Name)
                and any(isinstance(op, ast.IsNot if is_not else ast.Is)
                        for op in n.ops)
                and any(isinstance(c, ast.Constant) and c.value is None
                        for c in n.comparators)):
            out.add(n.left.id)
    return out


def _guarded_is_not_none(parents: _Parents, node, names: set) -> bool:
    """The node sits under an ``X is not None`` check for one of
    ``names`` — an ``if``/ternary body, the right side of an
    ``X is not None and ...`` bool-op, or past an
    ``if X is None: return ...`` early exit in the same function."""

    def covers(test) -> bool:
        return bool(_compare_names(test, is_not=True) & names)

    prev = node
    for anc in parents.ancestors(node):
        if isinstance(anc, ast.If) and prev not in anc.orelse \
                and covers(anc.test):
            return True
        if isinstance(anc, ast.IfExp) and prev is anc.body \
                and covers(anc.test):
            return True
        if isinstance(anc, ast.BoolOp) and isinstance(anc.op, ast.And):
            idx = anc.values.index(prev) if prev in anc.values else None
            if idx:
                if any(covers(v) for v in anc.values[:idx]):
                    return True
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # `if X is None: return ...` earlier in this function body
            for stmt in ast.walk(anc):
                if (isinstance(stmt, ast.If)
                        and stmt.body
                        and isinstance(stmt.body[-1],
                                       (ast.Return, ast.Raise, ast.Continue))
                        and (_compare_names(stmt.test, is_not=False)
                             & names)
                        and (stmt.body[-1].lineno
                             < getattr(node, "lineno", 0))):
                    return True
        prev = anc
    return False


def _slot_aliases(tree, parents: "_Parents") -> dict:
    """scope node (a FunctionDef, or None for module level) ->
    ``{alias: slot}`` for assignments like ``m = _monitor`` made
    directly in that scope. Scoped, not module-wide: a sibling
    function's ``m`` (a metric, a regex match) must not be mistaken
    for a hook-slot alias."""
    scoped: dict = {}
    for n in ast.walk(tree):
        if (isinstance(n, ast.Assign) and isinstance(n.value, ast.Name)
                and n.value.id in _SLOT_NAMES):
            fns = parents.enclosing_functions(n)
            scope = fns[0] if fns else None
            for t in n.targets:
                if isinstance(t, ast.Name) and t.id not in _SLOT_NAMES:
                    scoped.setdefault(scope, {})[t.id] = n.value.id
    return scoped


def _module_name(rel: str) -> str:
    return rel[:-3].replace("/", ".") if rel.endswith(".py") else rel


def _spec_literals(args) -> tuple | None:
    """Flatten literal spec args to their constant values; None when any
    element is dynamic (a computed spec can't be judged statically)."""
    out = []
    for a in args:
        if isinstance(a, ast.Constant):
            out.append(a.value)
        elif isinstance(a, (ast.Tuple, ast.List)):
            inner = _spec_literals(a.elts)
            if inner is None:
                return None
            out.extend(inner)
        elif isinstance(a, ast.Starred):
            return None
        else:
            return None
    return tuple(out)


def lint_text(rel: str, text: str,
              instrumented: tuple | None = None) -> list:
    """Lint one file's source. ``rel`` is the repo-relative path (scope
    rules key on it); ``instrumented`` is monitor.INSTRUMENTED_MODULES
    when known (None skips that sub-check)."""
    head = "\n".join(text.splitlines()[:10])
    if _SKIP_FILE_RE.search(head):
        return []
    try:
        tree = ast.parse(text)
    except SyntaxError as e:
        return [Finding("PTL000", "error", rel, e.lineno or 0, 0,
                        f"syntax error: {e.msg}")]
    parents = _Parents(tree)
    disabled = _disabled_rules(text)
    findings: list = []

    def emit(rule, severity, node, message):
        dis = disabled.get(getattr(node, "lineno", 0), ())
        if "*" in dis or rule in dis:
            return
        findings.append(Finding(rule, severity, rel, node.lineno,
                                node.col_offset, message))

    in_trace_scope = rel.startswith(TRACE_SCOPE)
    in_det_scope = rel.startswith(DETERMINISM_SCOPE)
    scoped_aliases = _slot_aliases(tree, parents)

    def aliases_at(node) -> dict:
        """{alias: slot} visible from ``node``: its enclosing functions'
        own assignments plus module level."""
        out = dict(scoped_aliases.get(None, {}))
        for fn in parents.enclosing_functions(node):
            out.update(scoped_aliases.get(fn, {}))
        return out

    # module-level slot declaration + registration (PTL003 applicability)
    declares_slot = any(
        isinstance(n, ast.Assign) and isinstance(n.value, ast.Constant)
        and n.value.value is None
        and any(isinstance(t, ast.Name) and t.id in _SLOT_NAMES
                for t in n.targets)
        for n in tree.body)
    registers = any(
        isinstance(n, ast.Call)
        and (_call_name(n) or "").endswith("_register")
        for n in ast.walk(tree))
    is_monitor_pkg = rel.startswith("paddle_tpu/monitor/")

    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            name = _call_name(node)

            # PTL001 — device_put under a trace
            if (name == "device_put" and in_trace_scope
                    and _trace_reachable(parents, node)):
                fns = parents.enclosing_functions(node)
                if not any(_mentions_tracer(f) for f in fns):
                    emit("PTL001", "error", node,
                         "device_put in trace-reachable code is a jaxpr "
                         "no-op (PR 10: dp compiled to fully replicated "
                         "programs) — use shard.constrain_or_put / "
                         "shard.sharding_constraint")

            # PTL002 — block_until_ready
            if name == "block_until_ready":
                fns = parents.enclosing_functions(node)
                timed = any(_reads_clock(f) for f in fns)
                emit("PTL002", "error" if timed else "warning", node,
                     "block_until_ready is not the repo's timed fence"
                     + (" — and this function reads a clock: end the "
                        "window with utils/timing.device_sync" if timed
                        else "; fence through utils/timing.device_sync "
                        "or a host transfer"))

            # PTL004 — partial-axis constraint tuples
            if name in ("sharding_constraint", "shard_tensor") \
                    and in_trace_scope:
                spec_args = list(node.args[1:]) if name == \
                    "sharding_constraint" else [
                        kw.value for kw in node.keywords
                        if kw.arg == "spec"]
                lits = _spec_literals(spec_args)
                if lits and any(isinstance(v, str) for v in lits) \
                        and "dp" not in lits:
                    named = sorted(v for v in lits if isinstance(v, str))
                    emit("PTL004", "error", node,
                         f"constraint names {named} but not 'dp' — XLA "
                         "gathers the dp shards at this boundary (a "
                         "remat copy per layer); name ALL live axes")

            # PTL005 — nondeterminism in deterministic scopes
            if in_det_scope:
                dotted = _dotted(node.func)
                if dotted == "time.time":
                    emit("PTL005", "error", node,
                         "time.time() in a byte-identity code path — "
                         "timestamps belong in provenance fields only; "
                         "use perf_counter for intervals")
                # jax.random is key-explicit (seeded by construction);
                # only the global-state stdlib/numpy RNGs are flagged
                if name in _RANDOM_FNS and dotted.startswith(
                        ("random.", "np.random.", "numpy.random.")):
                    emit("PTL005", "error", node,
                         f"unseeded global-RNG call ({dotted}) in a "
                         "byte-identity code path — use a seeded "
                         "Generator (np.random.default_rng(0)) or a "
                         "fixed PRNGKey")
                if name in ("list", "tuple") and node.args \
                        and isinstance(node.args[0], ast.Call) \
                        and _call_name(node.args[0]) == "set":
                    emit("PTL005", "error", node,
                         f"{name}(set(...)) is iteration-order-"
                         "dependent — wrap in sorted() before it feeds "
                         "output")

        # PTL005 — iterating a set directly
        if isinstance(node, ast.For) and in_det_scope:
            it = node.iter
            if isinstance(it, (ast.Set, ast.SetComp)) or (
                    isinstance(it, ast.Call)
                    and _call_name(it) == "set"):
                emit("PTL005", "error", node.iter,
                     "iterating a set feeds hash order into this code "
                     "path — iterate sorted(...) instead")

        # PTL003a — unguarded hook-slot use
        if (declares_slot and not is_monitor_pkg
                and isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and isinstance(node.ctx, ast.Load)
                and (node.value.id in _SLOT_NAMES
                     or node.value.id in aliases_at(node))):
            nm = node.value.id
            if not _guarded_is_not_none(parents, node, {nm}):
                emit("PTL003", "error", node,
                     f"hook-slot use {nm}.{node.attr} not guarded by "
                     f"'{nm} is not None' — the zero-overhead-off "
                     "contract (CLAUDE.md) requires hot paths to pay "
                     "one None check and nothing else")

    # PTL003b — registered slot module missing from the audit list
    if declares_slot and registers and not is_monitor_pkg \
            and instrumented is not None:
        mod = _module_name(rel)
        if mod.startswith("paddle_tpu.") and mod not in instrumented:
            findings.append(Finding(
                "PTL003", "error", rel, 1, 0,
                f"{mod} declares a monitor hook slot but is not in "
                "monitor.INSTRUMENTED_MODULES — the tier-1 "
                "zero-overhead audit cannot see it"))
    return findings


def load_instrumented_modules(root: str) -> tuple | None:
    """monitor.INSTRUMENTED_MODULES read STATICALLY from the source (no
    package import — the lint must run without jax)."""
    path = os.path.join(root, "paddle_tpu", "monitor", "__init__.py")
    try:
        tree = ast.parse(open(path).read())
    except (OSError, SyntaxError):
        return None
    for n in tree.body:
        if isinstance(n, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "INSTRUMENTED_MODULES"
                for t in n.targets):
            try:
                return tuple(ast.literal_eval(n.value))
            except ValueError:
                return None
    return None


def iter_py_files(paths) -> list:
    """Expand files/directories to .py files (sorted, __pycache__ and
    hidden dirs skipped)."""
    out = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = sorted(d for d in dirnames
                                 if d != "__pycache__"
                                 and not d.startswith("."))
            out.extend(os.path.join(dirpath, f)
                       for f in sorted(filenames) if f.endswith(".py"))
    return sorted(set(out))


def _find_root(path: str) -> str:
    """Nearest ancestor containing a ``paddle_tpu`` dir (repo root for
    scope-relative paths); falls back to the path's own directory."""
    d = os.path.abspath(path if os.path.isdir(path)
                        else os.path.dirname(path))
    while True:
        if os.path.isdir(os.path.join(d, "paddle_tpu")):
            return d
        nxt = os.path.dirname(d)
        if nxt == d:
            return os.path.abspath(path if os.path.isdir(path)
                                   else os.path.dirname(path))
        d = nxt


def lint_paths(paths, root: str | None = None) -> list:
    """Lint files/trees; repo-relative scoping + the INSTRUMENTED_MODULES
    cross-check are derived from ``root`` (auto-detected when None)."""
    files = iter_py_files(paths)
    if not files:
        return []
    root = os.path.abspath(root) if root else _find_root(files[0])
    instrumented = load_instrumented_modules(root)
    findings: list = []
    for f in files:
        rel = os.path.relpath(os.path.abspath(f), root).replace(os.sep, "/")
        try:
            text = open(f, encoding="utf-8").read()
        except (OSError, UnicodeDecodeError) as e:
            findings.append(Finding("PTL000", "error", rel, 0, 0,
                                    f"unreadable: {e}"))
            continue
        findings.extend(lint_text(rel, text, instrumented))
    return findings
