"""Program scopes: which layer issued each instruction of a compiled
program.

The step programs wear ``jax.named_scope`` names (``attn/rows``,
``moe/experts``, ``ssm/state_update``, ``norm``, ``head`` ...). A scope is
metadata: it changes no instruction of the compiled program, it rides in
each instruction's ``metadata={op_name="jit(f)/while/body/attn/rows/
dot_general"}``. This module is the ONE place that says which names are
scopes (``SCOPES``: first component -> its declared sub-scopes) and which
layer group a scope's first component falls to (``GROUPS``), and it keeps,
for every program that goes through ``jit/exec_cache.get_or_compile``, a
map ``instruction name -> [scope path, group, mixed, opcode, how]`` read
from the compiled module's text (:func:`parse`). A device trace names every op event by its whole
HLO instruction and every execution by its module, so instruction name
within module joins device time to scope: ``benchmarks/chip/chiplib/
devscopes.py`` does that for a traced run; an operator who keeps a trace
writes the map beside it with :func:`dump`.

The scope path is the ``op_name`` with everything that is not a declared
scope removed — ``jit(...)``, ``while/body``, ``closed_call``,
``checkpoint``, the primitive's own name, an einsum's spec — and the
transform wrappers peeled (``transpose(jvp(attn))`` is ``attn``), so a
scanned, a remat'd and a differentiated program read alike. A fusion
carries the metadata XLA gave it (its root's); ``mixed`` says the fused
computation's instructions fall into more than one GROUP.

JAX's persistent compile cache keys a program WITHOUT its metadata, so a
warm compile hands back the executable with the scope names of whichever
tree compiled it first: after renaming or adding a scope, clear the cache
directory. :func:`stale_programs` counts the recorded programs whose text
carries no declared scope at all.
"""
from __future__ import annotations

import functools
import json
import re

__all__ = ["GROUPS", "SCOPES", "PLUMBING", "UNSCOPED", "path_of", "group_of",
           "parse", "record", "compiled", "stale_programs", "dump"]

# first component of a scope path -> the layer group its device time
# falls to (PERF.md section 3 names the metric each group feeds)
GROUPS = {
    "attn": "attn", "mla": "attn",
    "mlp": "ffn", "moe": "ffn",
    "ssm": "state", "kda": "state", "sconv": "state",
    "norm": "norm",
    "embed": "head", "head": "head", "sample": "head", "spec": "head",
    "acc": "head",
}

# first component -> the sub-scopes declared under it. ``norm`` nests
# anywhere (``head/norm``, ``mla/q/norm``) and falls to its outermost
# scope's group.
SCOPES = {
    "attn": ("qkv", "rope", "kv_write", "rows", "window", "out"),
    "mla": ("q", "kv_write", "attend", "out"),
    "mlp": (),
    "moe": ("route", "dispatch", "experts", "combine", "shared"),
    "ssm": ("in_proj", "conv", "inputs", "state_update", "gate_norm",
            "out_proj"),
    "kda": ("proj", "conv", "gates", "state_update", "gate_norm",
            "out_proj"),
    "sconv": ("in_proj", "conv", "out_proj"),
    "norm": (), "embed": (), "head": (), "sample": (), "spec": (),
    "acc": (),
}

UNSCOPED = ""  # the path and the group of an instruction under no scope

_DECLARED = frozenset(GROUPS) | {s for sub in SCOPES.values() for s in sub}
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"(?<![=\w.\-])%([\w.\-]+)")
# the attributes that name other computations: a fusion's ``calls`` is
# fused into it; what a loop, a branch or a call names runs on its own; a
# reduce's or a sort's ``to_apply`` is neither
_CALLED = re.compile(r"\b(calls|to_apply|body|condition|true_computation|"
                     r"false_computation|branch_computations)="
                     r"(\{[^}]*\}|%?[\w.\-]+)")
_NAME = re.compile(r"[\w.\-]+")
_RUNS = frozenset({"body", "condition", "true_computation",
                   "false_computation", "branch_computations"})
_HEAD = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"^([a-z][\w\-]*)\(")

# what is no work of its own: left out of the "is it scoped" accounting
PLUMBING = frozenset({"parameter", "constant", "tuple", "get-tuple-element",
                      "bitcast"})


def _peel(component):
    """``transpose(jvp(mla/q))`` -> ``mla/q``; ``jit(f)`` and ``pjit(f)``
    name a function, not a scope."""
    while True:
        m = _WRAPPED.match(component)
        if not m:
            return component
        if m.group(1) in ("jit", "pjit"):
            return ""
        component = m.group(2)


def _split(op_name):
    """``op_name`` cut at the slashes outside any parentheses (a
    transform wraps a whole scope name, slashes and all)."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(op_name[start:i])
            start = i + 1
    out.append(op_name[start:])
    return out


@functools.lru_cache(maxsize=None)
def path_of(op_name: str) -> str:
    """The declared scopes of an ``op_name``, outermost first, joined by
    ``/``; ``UNSCOPED`` where there is none. The last component is the
    primitive and never a scope."""
    kept = []
    for wrapped in _split(op_name)[:-1]:
        for comp in _peel(wrapped).split("/"):
            if comp in _DECLARED and (kept or comp in GROUPS):
                kept.append(comp)
    return "/".join(kept)


def group_of(path: str) -> str:
    return GROUPS.get(path.split("/", 1)[0], UNSCOPED)


def _opcode(rest):
    """The opcode of an instruction's text after `` = ``: what follows
    its type, a tuple type's parentheses matched."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.partition(" ")[2]
    m = _OPCODE.match(rest)
    return m.group(1) if m else ""


def parse(text: str):
    """(module name, {instruction name: [scope path, group, mixed,
    opcode, how]}) of a compiled module's text (``compiled.as_text()``),
    for every instruction of every computation that runs as events of its
    own: the entry and every ``while`` / ``conditional`` / ``call`` body,
    not the fused computations (they only decide ``mixed``) nor the
    scalar computations a reduce or a scatter applies.

    ``how`` says where the scope came from. ``own``: its metadata.
    ``fused``: a fusion whose own metadata names no scope (its root is a
    loop's bookkeeping or XLA's) takes the scope most of the instructions
    it fused carry.
    ``none``: the program issued it (its ``op_name`` starts with the
    module's own ``jit(f)``) under no declared scope — a layer scan's
    counter and condition, or a line of a step program that still lacks
    its scope; it stays unscoped. What XLA inserted carries no metadata —
    a weight prefetch (``slice-start`` / ``slice-done`` / ``copy-done``),
    a layout copy, a broadcast — and takes its scope by dataflow,
    ``user``: the first instruction of its computation that uses its
    result and has a scope (followed through other such instructions),
    else ``operand``: the first of its operands that has one; ``""`` where
    none does."""
    module, entry, root = "", None, "jit("
    comps, runs, cur = {}, {}, None
    for line in text.splitlines():
        if cur is None:
            if line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
                if module.startswith("jit_"):
                    root = "jit(" + module[4:] + ")"
                continue
            m = _HEAD.match(line)
            if m and (m.group(1) or line.startswith("%")):
                comp = m.group(2)
                cur = comps.setdefault(comp, [])
                if m.group(1):
                    entry = comp
            continue
        if line.startswith("}"):
            cur = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        opcode = _opcode(rest)
        op = _OP_NAME.search(rest)
        called = None
        for attr, targets in _CALLED.findall(rest):
            for target in _NAME.findall(targets):
                if attr == "calls" and opcode == "fusion":
                    called = target
                elif attr in _RUNS or opcode in ("call", "async-start"):
                    runs.setdefault(comp, set()).add(target)
        # an op_name that starts with the program's own ``jit(f)`` is a
        # line of the program; what XLA made carries none (or a
        # parameter's name, a reducer's), and a library function traced
        # apart from its caller (``jit(searchsorted)/...`` inside the
        # grouped matmul) has lost the caller's names
        issued = bool(op) and op.group(1).startswith(root)
        cur.append((name, opcode, path_of(op.group(1)) if op else UNSCOPED,
                    called, rest, issued))

    def paths_in(comp, seen=()):
        """{scope path: instructions} of a fused computation."""
        out = {}
        for _, _, path, called, _, _ in comps.get(comp, ()):
            if path:
                out[path] = out.get(path, 0) + 1
            if called and called not in seen:
                for k, n in paths_in(called, seen + (comp,)).items():
                    out[k] = out.get(k, 0) + n
        return out

    # the computations whose instructions run as events of their own
    live, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in comps and comp not in live:
            live.add(comp)
            todo.extend(runs.get(comp, ()))
    table = {}
    for comp in live:
        instrs = comps[comp]
        rows = {}
        for name, opcode, path, called, _, issued in instrs:
            how = "own" if path else "none" if issued else ""
            within = paths_in(called) if called else {}
            if not path and within:  # a root XLA made: what it fused says
                path, how = max(within, key=within.get), "fused"
            group = group_of(path)
            mixed = len({group_of(k) for k in within} | {group}) > 1
            rows[name] = [path, group, mixed, opcode, how]
        _inherit(instrs, rows)
        table.update(rows)
    return module, table


def _inherit(instrs, rows):
    """Give the instructions of one computation that XLA inserted (``how``
    still ``""``) their first scoped user's scope (instructions are in
    schedule order, so one backward pass follows chains), else their
    first scoped operand's (one forward pass)."""
    if all(r[4] for r in rows.values()):
        return
    operands = {name: [o for o in _OPERAND.findall(rest.split(
        ", metadata=", 1)[0]) if o in rows and o != name]
        for name, _, _, _, rest, _ in instrs}
    first_user = {}
    for name, *_ in instrs:
        for o in operands[name]:
            first_user.setdefault(o, name)
    for name, *_ in reversed(instrs):
        row = rows[name]
        user = rows.get(first_user.get(name))
        if not row[4] and user and user[0]:
            row[0], row[1], row[4] = user[0], user[1], "user"
    for name, *_ in instrs:
        row = rows[name]
        if row[4]:
            continue
        for o in operands[name]:
            if rows[o][0]:
                row[0], row[1], row[4] = rows[o][0], rows[o][1], "operand"
                break


# module name -> {"label", "text" until first read, then "instructions"
# and "scoped"}: plain strings, kept for the life of the process. Neither
# ``exec_cache.clear()`` nor ``jax.clear_caches()`` touches it: a reader
# that runs after the programs were dropped still finds what they were.
_programs: dict = {}


def record(label, executable) -> None:
    """Keep one compiled program's text (``jit/exec_cache`` calls this
    for every executable it hands out); the text is read into a scope map
    when the registry is first asked for (:func:`compiled`), so a compile
    pays for ``as_text()`` and nothing else. A program compiled again
    under the same module name replaces its entry. An executable without
    a text (a backend that cannot print it) is skipped."""
    try:
        text = executable.as_text()
    except Exception:  # noqa: BLE001 — no text: nothing to join by
        return
    if text:
        module = text[:200].split(None, 2)[1].rstrip(",") \
            if text.startswith("HloModule ") else ""
        _programs[module] = {"label": label, "text": text}


def compiled() -> dict:
    """{module name: {"label": the compile site's label, "instructions":
    {instruction name: [scope path, group, mixed, opcode, how]},
    "scoped": whether any instruction carries a declared scope}}."""
    for prog in _programs.values():
        text = prog.pop("text", None)
        if text is not None:
            table = parse(text)[1]
            prog["instructions"] = table
            prog["scoped"] = any(r[4] == "own" for r in table.values())
    return _programs


def stale_programs() -> int:
    """Recorded ``serving/*`` programs whose text carries no declared
    scope at all: what a compile-cache hit on an executable built before
    the scopes existed looks like."""
    return sum(1 for p in compiled().values()
               if (p["label"] or "").startswith("serving/")
               and not p["scoped"])


def dump(path: str) -> None:
    """The whole registry as JSON, for an operator who keeps a trace."""
    with open(path, "w") as f:
        json.dump(compiled(), f)
