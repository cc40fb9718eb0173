"""dmllint — project-native async-hazard & cross-artifact drift linter.

Every robustness PR in this repo's history hand-fixed the same
recurring hazard classes in the asyncio control plane: fire-and-forget
tasks that wedge teardown (the PR-3 ``wait_for`` wedge), blanket
``except Exception: pass`` that eats real bugs, wire-message handlers
drifting from the ``MsgType`` enum, and hand-mirrored lists (pytest
markers, the observability docstring map)
silently desynchronizing. This module catches those classes
mechanically at test time — ``tests/test_dmllint.py`` enforces ZERO
un-baselined findings in tier-1 — instead of re-discovering them one
chaos soak at a time.

Run it::

    python -m dml_tpu.tools.dmllint [--json] [--root DIR] [--baseline F]
                                    [--rules R1,R2] [--paths GLOB,GLOB]
    python -m dml_tpu lint            # same, as a CLI verb

``--rules``/``--paths`` narrow what is REPORTED (iterate on one rule
or one file without the full-repo noise); the whole tree is always
scanned and stale-baseline reporting pauses while filtering. ``--json``
output carries a ``schema_version`` field.

Exit codes (CI contract): 0 = clean, 1 = un-baselined findings,
2 = internal error (unparseable source, malformed baseline).

Rule catalog
------------

Async-hazard rules (pure AST, per file, over ``dml_tpu/`` +
``tests/``):

- ``naked-task`` — ``asyncio.create_task(...)`` / ``ensure_future``
  as a bare expression statement: the handle is neither stored, reaped
  via ``cluster.util.reap_task``, nor awaited, so teardown can never
  cancel-and-join it and its exception is silently dropped (the exact
  class behind the PR-3 dispatch wedge).
- ``silent-except`` — a bare ``except:``, ``except Exception`` or
  ``except BaseException`` (alone or in a tuple) whose body is ONLY
  ``pass``: real bugs die invisibly. Narrow the type, or log what was
  swallowed; pass-only bodies on NARROW types are fine.
- ``blocking-async`` — a known blocking call (``time.sleep``, sync
  ``subprocess.run/call/check_call/check_output/Popen``,
  ``socket.create_connection/getaddrinfo/gethostbyname``,
  ``os.system``) lexically inside ``async def``: it stalls the whole
  event loop. Plain ``open()`` on small local files is deliberately
  NOT flagged (the store's atomic-write path uses it by design).
- ``unseeded-seam`` — module-global ``random.*`` (anything except the
  seeded ``random.Random``/``SystemRandom`` constructors, including
  ``from random import <fn>``) or wall-clock ``time.time()`` /
  ``time.time_ns()`` inside the determinism seams
  (``cluster/chaos.py``, ``ingress/loadgen.py``): same seed must mean
  identical schedule, and the injected-clock/seeded-rng discipline is
  what the chaos replay + loadgen trace guarantees rest on.

Cross-artifact drift rules (static introspection of the named
artifacts; each rule is skipped when its artifact files are absent,
so fixture trees exercise them selectively):

- ``drift-wire-handlers`` — ``cluster/wire.py``'s ``HANDLER_OWNERS``
  registry vs reality: every ``MsgType`` member must have exactly one
  declared owner; a class-owned type must actually be registered (via
  ``.register(MsgType.X, self._h_y)``) by that class and no other; a
  ``rid-fallback`` type must NOT be registered anywhere; an
  ``IntroducerService`` type must be referenced by the introducer's
  inline dispatch; every member must be referenced somewhere outside
  wire.py (dead protocol members accrete silently); handler callables
  must follow the ``_h_*`` naming contract; and no code may reference
  an undeclared ``MsgType.X``.
- ``drift-metrics-map`` — the machine-readable "Metric map" section
  of ``observability.py``'s module docstring vs every
  ``*.counter/gauge/histogram("name", ...)`` registration in
  ``dml_tpu/``: both directions must match exactly.
- ``drift-pytest-markers`` — markers used in ``tests/`` must be
  registered in ``pytest.ini``; the ``pytest.ini`` registry and the
  ``tests/conftest.py`` mirror must be identical sets; a registered
  marker no test uses is flagged (the mirror only stays honest while
  every entry is load-bearing).
- ``drift-span-names`` — every literal ``start_span("<name>", ...)``
  call site in the tree, and every ``loop_span("<name>", ...)`` /
  ``loop_record("<name>", ...)`` (serve-loop spans), must use a name
  declared in
  ``dml_tpu/tracing.py``'s ``SPAN_NAMES`` registry (the stage
  vocabulary the tail-attribution table reports); a registered name no
  call site emits is flagged, and a NON-literal span name in
  ``dml_tpu/`` (outside tracing.py itself) is flagged as unverifiable
  — stage names in the attribution table must not be able to drift
  from the instrumentation.
- ``drift-alert-names`` — every literal ``fire_alert("<name>", ...)``
  / ``resolve_alert("<name>", ...)`` call site in the tree must use a
  name declared in ``dml_tpu/signal.py``'s ``ALERT_NAMES`` registry
  (the closed alert vocabulary operators page on); a registered name
  no call site emits is flagged, and a NON-literal alert name in
  ``dml_tpu/`` (outside signal.py itself, whose manager/driver
  machinery passes names through variables by design) is flagged as
  unverifiable — the pager catalog must not be able to drift from the
  emission sites.

Flow-aware rules (implemented in the sibling ``dmlflow`` module — see
its docstring for the full semantics and recognized suppressions):

- ``race-yield-hazard`` — per ``async def`` in ``dml_tpu/``, a
  statement-ordered model of ``self.*`` / module-global mutable state:
  flags check-then-act sequences whose branch test and mutation of the
  same attribute straddle an ``await`` (the interleaving window), and
  acquire/release window markers whose release is not on the
  ``try/finally`` cancellation path. Recognized await-safe idioms —
  re-check-after-await, one ``async with <lock>`` across the whole
  window, snapshot-into-local — are not flagged.
- ``drift-wire-payloads`` — infers each ``MsgType``'s payload schema
  from every send site (dict literals and locally-built dicts) vs the
  keys its registered handler / reply-await sites read
  (``msg.data["k"]`` = required, ``.get("k")`` = optional), and
  cross-checks wire.py's docstring "Payload map (lint-enforced)"
  section in both directions: required-read-but-never-sent,
  conditionally-sent-but-required, sent-but-never-read, and any
  map/wire disagreement are findings.

Baseline
--------

``tools/dmllint_baseline.json`` grandfathers accepted findings. Each
entry is ``{"key": <finding key>, "justification": <non-empty why>}``;
an entry without a justification is a malformed baseline (exit 2). A
baselined finding is suppressed; a baseline entry matching NO current
finding is itself reported as ``baseline-stale`` so the file can only
shrink toward empty. Finding keys are scope-anchored
(``rule:path:qualname:ordinal``), not line-anchored, so unrelated
edits above a baselined site don't churn the file.
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import re
import sys
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

# rule ids (the catalog above is the human contract; this is the code's)
R_NAKED = "naked-task"
R_SILENT = "silent-except"
R_BLOCKING = "blocking-async"
R_UNSEEDED = "unseeded-seam"
R_WIRE = "drift-wire-handlers"
R_METRICS = "drift-metrics-map"
R_MARKERS = "drift-pytest-markers"
R_SPANS = "drift-span-names"
R_ALERTS = "drift-alert-names"
# flow-aware passes (implemented in the sibling dmlflow module)
R_RACE = "race-yield-hazard"
R_PAYLOAD = "drift-wire-payloads"
R_STALE = "baseline-stale"

ALL_RULES = (
    R_NAKED, R_SILENT, R_BLOCKING, R_UNSEEDED,
    R_WIRE, R_METRICS, R_MARKERS, R_SPANS, R_ALERTS,
    R_RACE, R_PAYLOAD, R_STALE,
)

#: --json output contract version: bumped when the shape of the JSON
#: document changes (2 = schema_version/rules fields + flow rules)
JSON_SCHEMA_VERSION = 2

#: blocking calls flagged inside ``async def`` (module attr, call name)
BLOCKING_CALLS: Set[Tuple[str, str]] = {
    ("time", "sleep"),
    ("subprocess", "run"), ("subprocess", "call"),
    ("subprocess", "check_call"), ("subprocess", "check_output"),
    ("subprocess", "Popen"),
    ("socket", "create_connection"), ("socket", "getaddrinfo"),
    ("socket", "gethostbyname"),
    ("os", "system"),
}

#: files where unseeded randomness / wall clocks break determinism
SEAM_FILES = ("dml_tpu/cluster/chaos.py", "dml_tpu/ingress/loadgen.py")

#: seeded constructors allowed through the seam rule
SEEDED_CTORS = {"Random", "SystemRandom"}

#: pytest's built-in marks — usable without registration
BUILTIN_MARKS = {
    "parametrize", "skip", "skipif", "xfail", "usefixtures",
    "filterwarnings",
}

DEFAULT_BASELINE = "dml_tpu/tools/dmllint_baseline.json"


class LintInternalError(Exception):
    """Analyzer could not run (unparseable input, malformed baseline).

    Maps to exit code 2 so CI can tell 'tree has findings' from
    'linter is broken'."""


@dataclass(frozen=True, order=True)
class Finding:
    path: str  # repo-relative posix path
    line: int
    rule: str
    msg: str
    key: str  # stable identity for the baseline (scope, not line)

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule}: {self.msg}"


def repo_root() -> str:
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )


def _rel(root: str, path: str) -> str:
    return os.path.relpath(path, root).replace(os.sep, "/")


def scan_paths(root: str) -> List[str]:
    """The lint surface: dml_tpu/ + tests/ (deterministic order;
    __pycache__ excluded)."""
    out: List[str] = []
    for sub in ("dml_tpu", "tests"):
        base = os.path.join(root, sub)
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.join(dirpath, fn))
    return out


def _parse(path: str, rel: str) -> ast.Module:
    try:
        with open(path, encoding="utf-8") as f:
            return ast.parse(f.read(), filename=rel)
    except SyntaxError as e:
        raise LintInternalError(f"cannot parse {rel}: {e}") from e


# ----------------------------------------------------------------------
# async-hazard rules (per-file AST)
# ----------------------------------------------------------------------


def _call_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _is_broad_handler(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True  # bare except
    elts = t.elts if isinstance(t, ast.Tuple) else [t]
    for e in elts:
        name = e.attr if isinstance(e, ast.Attribute) else getattr(e, "id", None)
        if name in ("Exception", "BaseException"):
            return True
    return False


class _HazardVisitor(ast.NodeVisitor):
    """One pass per file for all four async-hazard rules, tracking the
    enclosing scope qualname (finding keys anchor to scope+ordinal so
    baselines survive line drift)."""

    def __init__(self, rel: str) -> None:
        self.rel = rel
        self.scope: List[str] = []
        self.async_depth = 0
        self.seam = rel in SEAM_FILES
        self.raw: List[Tuple[str, str, int, str]] = []  # rule, scope, line, msg

    def _emit(self, rule: str, line: int, msg: str) -> None:
        self.raw.append((rule, ".".join(self.scope) or "<module>", line, msg))

    # -- scope / async-context tracking --------------------------------
    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.scope.append(node.name)
        self.generic_visit(node)
        self.scope.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        # a SYNC def nested in an async def runs outside the loop
        # thread (executor / to_thread) — blocking calls there are fine
        self.scope.append(node.name)
        saved, self.async_depth = self.async_depth, 0
        self.generic_visit(node)
        self.async_depth = saved
        self.scope.pop()

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self.scope.append(node.name)
        self.async_depth += 1
        self.generic_visit(node)
        self.async_depth -= 1
        self.scope.pop()

    # -- naked-task -----------------------------------------------------
    def visit_Expr(self, node: ast.Expr) -> None:
        v = node.value
        if isinstance(v, ast.Call):
            name = _call_name(v.func)
            if name in ("create_task", "ensure_future"):
                self._emit(
                    R_NAKED, node.lineno,
                    f"{name}(...) handle discarded — store it, reap it "
                    "via cluster.util.reap_task at teardown, or await "
                    "it (a dropped task can neither be cancelled nor "
                    "report its exception)",
                )
        self.generic_visit(node)

    # -- silent-except --------------------------------------------------
    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if _is_broad_handler(node) and all(
            isinstance(s, ast.Pass) for s in node.body
        ):
            what = "bare except" if node.type is None else "except Exception"
            self._emit(
                R_SILENT, node.lineno,
                f"{what} with a pass-only body swallows real bugs — "
                "narrow the exception type or log what was caught",
            )
        self.generic_visit(node)

    # -- blocking-async + unseeded-seam ---------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            mod, attr = f.value.id, f.attr
            if self.async_depth and (mod, attr) in BLOCKING_CALLS:
                self._emit(
                    R_BLOCKING, node.lineno,
                    f"blocking {mod}.{attr}(...) inside async def stalls "
                    "the event loop — await the async form or push it "
                    "through asyncio.to_thread",
                )
            if self.seam:
                if mod == "random" and attr not in SEEDED_CTORS:
                    self._emit(
                        R_UNSEEDED, node.lineno,
                        f"module-global random.{attr}(...) in a "
                        "determinism seam — use a seeded "
                        "random.Random(seed) instance (same seed must "
                        "mean identical schedule)",
                    )
                if mod == "time" and attr in ("time", "time_ns"):
                    self._emit(
                        R_UNSEEDED, node.lineno,
                        f"wall-clock time.{attr}() in a determinism "
                        "seam — use the injected clock / loop.time()",
                    )
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if self.seam and node.module == "random":
            bad = [a.name for a in node.names if a.name not in SEEDED_CTORS]
            if bad:
                self._emit(
                    R_UNSEEDED, node.lineno,
                    f"from random import {', '.join(bad)} in a "
                    "determinism seam enables unseeded module-global "
                    "randomness — import random.Random and seed it",
                )
        self.generic_visit(node)


def analyze_source(src: str, rel: str) -> List[Finding]:
    """Run the four async-hazard rules over one file's source."""
    try:
        tree = ast.parse(src, filename=rel)
    except SyntaxError as e:
        raise LintInternalError(f"cannot parse {rel}: {e}") from e
    return analyze_tree(tree, rel)


def analyze_tree(tree: ast.Module, rel: str) -> List[Finding]:
    v = _HazardVisitor(rel)
    v.visit(tree)
    # scope-anchored ordinals: n-th finding of this rule in this scope
    counts: Dict[Tuple[str, str], int] = {}
    out: List[Finding] = []
    for rule, scope, line, msg in v.raw:
        n = counts.get((rule, scope), 0)
        counts[(rule, scope)] = n + 1
        out.append(Finding(
            path=rel, line=line, rule=rule, msg=msg,
            key=f"{rule}:{rel}:{scope}:{n}",
        ))
    return out


# ----------------------------------------------------------------------
# drift-wire-handlers
# ----------------------------------------------------------------------


def extract_msgtype_members(wire_tree: ast.Module) -> Dict[str, int]:
    """MsgType member -> enum line, statically (no import)."""
    for node in wire_tree.body:
        if isinstance(node, ast.ClassDef) and node.name == "MsgType":
            out = {}
            for stmt in node.body:
                if isinstance(stmt, ast.Assign) and isinstance(
                    stmt.targets[0], ast.Name
                ):
                    out[stmt.targets[0].id] = stmt.lineno
            return out
    return {}


def extract_handler_owners(wire_tree: ast.Module) -> Dict[str, str]:
    """HANDLER_OWNERS dict literal -> {member name: owner string}."""
    for node in wire_tree.body:
        targets: List[ast.AST] = []
        if isinstance(node, ast.Assign):
            targets = list(node.targets)
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        if not any(
            isinstance(t, ast.Name) and t.id == "HANDLER_OWNERS"
            for t in targets
        ):
            continue
        val = node.value
        if isinstance(val, ast.Dict):
            out: Dict[str, str] = {}
            for k, v in zip(val.keys, val.values):
                if not (isinstance(k, ast.Attribute)
                        and isinstance(k.value, ast.Name)
                        and k.value.id == "MsgType"):
                    continue
                if isinstance(v, ast.Constant) and isinstance(v.value, str):
                    out[k.attr] = v.value
                elif isinstance(v, ast.Name) and v.id == "RID_FALLBACK":
                    out[k.attr] = "rid-fallback"
            return out
    return {}


def extract_registrations(
    tree: ast.Module, rel: str
) -> List[Tuple[str, str, str, int]]:
    """(member, enclosing class, handler name, line) for every
    ``<x>.register(MsgType.MEMBER, <handler>)`` call."""
    out: List[Tuple[str, str, str, int]] = []

    def walk(node: ast.AST, cls: str) -> None:
        for child in ast.iter_child_nodes(node):
            ncls = child.name if isinstance(child, ast.ClassDef) else cls
            if isinstance(child, ast.Call) and isinstance(
                child.func, ast.Attribute
            ) and child.func.attr == "register" and len(child.args) >= 2:
                a0 = child.args[0]
                if isinstance(a0, ast.Attribute) and isinstance(
                    a0.value, ast.Name
                ) and a0.value.id == "MsgType":
                    h = child.args[1]
                    hname = h.attr if isinstance(h, ast.Attribute) else (
                        h.id if isinstance(h, ast.Name) else "<expr>"
                    )
                    out.append((a0.attr, cls, hname, child.lineno))
            walk(child, ncls)

    walk(tree, "<module>")
    return out


def extract_msgtype_refs(tree: ast.Module) -> Dict[str, int]:
    """member name -> first reference line for ``MsgType.X`` attributes."""
    refs: Dict[str, int] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and isinstance(
            node.value, ast.Name
        ) and node.value.id == "MsgType":
            refs.setdefault(node.attr, node.lineno)
    return refs


def check_wire(
    members: Dict[str, int],
    owners: Dict[str, str],
    registrations: Dict[str, List[Tuple[str, str, str, int]]],
    refs_by_file: Dict[str, Dict[str, int]],
    wire_rel: str,
    introducer_rel: str,
) -> List[Finding]:
    """Pure drift check over statically-extracted wire data.

    ``registrations``: rel -> [(member, class, handler, line)].
    ``refs_by_file``: rel -> {member: line} (wire.py itself included;
    excluded from the dead-member check since HANDLER_OWNERS
    references every member by construction)."""
    fs: List[Finding] = []

    def f(path: str, line: int, subject: str, msg: str) -> None:
        fs.append(Finding(path=path, line=line, rule=R_WIRE, msg=msg,
                          key=f"{R_WIRE}:{subject}"))

    for m, line in members.items():
        if m not in owners:
            f(wire_rel, line, f"unowned:{m}",
              f"MsgType.{m} has no HANDLER_OWNERS entry — declare its "
              "owning service (or rid-fallback)")
    for m in owners:
        if m not in members:
            f(wire_rel, 1, f"ghost-owner:{m}",
              f"HANDLER_OWNERS claims MsgType.{m} which is not a "
              "declared enum member")

    regs_by_member: Dict[str, List[Tuple[str, str, str, int]]] = {}
    for rel, regs in registrations.items():
        for member, cls, handler, line in regs:
            regs_by_member.setdefault(member, []).append(
                (rel, cls, handler, line))
            if member not in members:
                f(rel, line, f"undeclared:{member}:{rel}",
                  f"handler registered for undeclared MsgType.{member}")
            if not (handler.startswith("_h_") or handler == "<expr>"):
                f(rel, line, f"handler-name:{member}:{handler}",
                  f"handler {handler!r} for MsgType.{member} breaks the "
                  "_h_* naming contract")

    intro_refs = refs_by_file.get(introducer_rel, {})
    for m, owner in owners.items():
        if m not in members:
            continue
        regs = regs_by_member.get(m, [])
        if owner == "rid-fallback":
            for rel, cls, handler, line in regs:
                f(rel, line, f"fallback-registered:{m}:{cls}",
                  f"MsgType.{m} is declared rid-fallback but {cls} "
                  f"registers {handler} for it — own it in "
                  "HANDLER_OWNERS or drop the registration")
        elif owner == "IntroducerService":
            if m not in intro_refs:
                f(wire_rel, members[m], f"intro-unhandled:{m}",
                  f"MsgType.{m} is declared IntroducerService-owned "
                  "but the introducer's dispatch never references it")
        else:
            classes = {cls for _, cls, _, _ in regs}
            if owner not in classes:
                f(wire_rel, members[m], f"unregistered:{m}",
                  f"MsgType.{m} is owned by {owner} but {owner} never "
                  "registers a handler for it")
            for rel, cls, handler, line in regs:
                if cls != owner:
                    f(rel, line, f"wrong-owner:{m}:{cls}",
                      f"MsgType.{m} is owned by {owner} but {cls} "
                      f"registers {handler} for it")

    for m, line in members.items():
        used = any(
            m in refs for rel, refs in refs_by_file.items() if rel != wire_rel
        )
        if not used:
            f(wire_rel, line, f"dead-member:{m}",
              f"MsgType.{m} is referenced nowhere outside wire.py — "
              "dead protocol surface (remove it; reserve the value in "
              "a comment)")
    return fs


def rule_wire(root: str, trees: Dict[str, ast.Module]) -> List[Finding]:
    wire_rel = "dml_tpu/cluster/wire.py"
    if wire_rel not in trees:
        return []
    wire_tree = trees[wire_rel]
    members = extract_msgtype_members(wire_tree)
    owners = extract_handler_owners(wire_tree)
    if not members:
        return []
    # registrations only from product code: tests wire ad-hoc fakes
    registrations = {
        rel: extract_registrations(t, rel)
        for rel, t in trees.items() if rel.startswith("dml_tpu/")
    }
    refs_by_file = {rel: extract_msgtype_refs(t) for rel, t in trees.items()}
    return check_wire(
        members, owners, registrations, refs_by_file,
        wire_rel, "dml_tpu/cluster/introducer.py",
    )


# ----------------------------------------------------------------------
# drift-metrics-map
# ----------------------------------------------------------------------

_METRIC_MAP_HEADER = "Metric map (lint-enforced)"
_METRIC_LINE_RE = re.compile(r"^ {4}([a-z][a-z0-9_]*)(?=\s|$)")


def parse_metric_map(docstring: str) -> Optional[Set[str]]:
    """The machine-readable metric list from observability.py's module
    docstring: lines indented 4 spaces, ``name  description``, in the
    section opened by the header line. None = no map section at all."""
    lines = docstring.splitlines()
    try:
        start = next(
            i for i, ln in enumerate(lines)
            if ln.strip() == _METRIC_MAP_HEADER
        )
    except StopIteration:
        return None
    names: Set[str] = set()
    in_list = False
    for ln in lines[start + 1:]:
        m = _METRIC_LINE_RE.match(ln)
        if m:
            in_list = True
            names.add(m.group(1))
        elif in_list and ln.strip() and not ln.startswith(" "):
            break  # next unindented section
    return names


def collect_metric_registrations(
    trees: Dict[str, ast.Module]
) -> Dict[str, Tuple[str, int]]:
    """metric name -> (rel, line) for every counter/gauge/histogram
    registration with a literal name, product code only."""
    out: Dict[str, Tuple[str, int]] = {}
    for rel in sorted(trees):
        if not rel.startswith("dml_tpu/"):
            continue
        for node in ast.walk(trees[rel]):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("counter", "gauge", "histogram")
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                name = node.args[0].value
                if re.fullmatch(r"[a-z][a-z0-9_]*", name):
                    out.setdefault(name, (rel, node.lineno))
    return out


def check_metrics(
    map_names: Optional[Set[str]],
    code_names: Dict[str, Tuple[str, int]],
    obs_rel: str,
) -> List[Finding]:
    fs: List[Finding] = []
    if map_names is None:
        fs.append(Finding(
            path=obs_rel, line=1, rule=R_METRICS,
            msg=f"module docstring has no '{_METRIC_MAP_HEADER}' "
                "section — the metric map is the operator's index and "
                "is lint-enforced",
            key=f"{R_METRICS}:no-map",
        ))
        return fs
    for name in sorted(map_names - set(code_names)):
        fs.append(Finding(
            path=obs_rel, line=1, rule=R_METRICS,
            msg=f"metric {name!r} is in the docstring map but no code "
                "registers it — stale map entry",
            key=f"{R_METRICS}:map-only:{name}",
        ))
    for name in sorted(set(code_names) - map_names):
        rel, line = code_names[name]
        fs.append(Finding(
            path=rel, line=line, rule=R_METRICS,
            msg=f"metric {name!r} is registered here but missing from "
                "observability.py's docstring metric map",
            key=f"{R_METRICS}:code-only:{name}",
        ))
    return fs


def rule_metrics(root: str, trees: Dict[str, ast.Module]) -> List[Finding]:
    obs_rel = "dml_tpu/observability.py"
    if obs_rel not in trees:
        return []
    doc = ast.get_docstring(trees[obs_rel]) or ""
    return check_metrics(
        parse_metric_map(doc), collect_metric_registrations(trees), obs_rel
    )


# ----------------------------------------------------------------------
# drift-span-names
# ----------------------------------------------------------------------

TRACING_REL = "dml_tpu/tracing.py"

#: the recorder's span-opening calls, each taking the span's name
#: first: a request span, a serve-loop span, a loop span recorded
#: after the fact
_SPAN_CALLS = ("start_span", "loop_span", "loop_record")


def _module_const_strs(tree: ast.Module, name: str) -> Optional[Dict[str, int]]:
    for node in tree.body:
        if isinstance(node, ast.Assign) and any(
            isinstance(t, ast.Name) and t.id == name for t in node.targets
        ):
            if isinstance(node.value, (ast.Tuple, ast.List)):
                return {
                    e.value: e.lineno
                    for e in node.value.elts
                    if isinstance(e, ast.Constant) and isinstance(e.value, str)
                }
    return None


def collect_span_call_sites(
    trees: Dict[str, ast.Module],
) -> Tuple[Dict[str, List[Tuple[str, int]]], List[Tuple[str, int]]]:
    """-> (span name -> [(path, line), ...] for every LITERAL
    ``start_span("<name>", ...)`` / ``loop_span("<name>", ...)`` /
    ``loop_record("<name>", ...)`` call, [(path, line), ...] of
    non-literal call sites). tracing.py itself is excluded — its
    generic machinery passes names through variables by design."""
    literal: Dict[str, List[Tuple[str, int]]] = {}
    dynamic: List[Tuple[str, int]] = []
    for rel, tree in sorted(trees.items()):
        if rel == TRACING_REL:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if _call_name(node.func) not in _SPAN_CALLS:
                continue
            name_arg: Optional[ast.AST] = (
                node.args[0] if node.args else None
            )
            if name_arg is None:
                for kw in node.keywords:
                    if kw.arg == "name":
                        name_arg = kw.value
            if isinstance(name_arg, ast.Constant) and isinstance(
                name_arg.value, str
            ):
                literal.setdefault(name_arg.value, []).append(
                    (rel, node.lineno)
                )
            else:
                dynamic.append((rel, node.lineno))
    return literal, dynamic


def collect_tracing_literals(tree: ast.Module) -> Set[str]:
    """Span names the tracer's OWN machinery emits, counting as used
    without a start_span call site. Deliberately narrow — only (a)
    module-level ``NAME = "str"`` aliases (``SPAN_ROOT``) and (b)
    string literals passed positionally to a ``Span(...)``
    construction (``note_exemplar``'s marker). Any broader net (e.g.
    every string constant in the module) would let incidental
    literals — the attribution code's stage sets, docstring fragments
    — permanently mask the registered-but-never-emitted check."""
    out: Set[str] = set()
    for node in tree.body:
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)):
            out.add(node.value.value)
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and _call_name(node.func) == "Span"):
            for arg in node.args:
                if isinstance(arg, ast.Constant) and isinstance(
                    arg.value, str
                ):
                    out.add(arg.value)
    return out


def check_span_names(
    registry: Optional[Dict[str, int]],
    literal: Dict[str, List[Tuple[str, int]]],
    dynamic: List[Tuple[str, int]],
    tracing_literals: Set[str],
    tracing_rel: str,
) -> List[Finding]:
    fs: List[Finding] = []

    def f(path: str, line: int, subject: str, msg: str) -> None:
        fs.append(Finding(path=path, line=line, rule=R_SPANS, msg=msg,
                          key=f"{R_SPANS}:{subject}"))

    if registry is None:
        f(tracing_rel, 1, "no-registry",
          "tracing.py has no module-level SPAN_NAMES tuple — the span "
          "vocabulary must be declared where the linter (and the "
          "attribution table) can see it")
        return fs
    for name, sites in sorted(literal.items()):
        if name not in registry:
            path, line = sites[0]
            f(path, line, f"unregistered:{name}",
              f"a span is opened as {name!r}, a name not declared in "
              "tracing.SPAN_NAMES — add it to the registry first, or "
              "the attribution table silently drops this stage")
    for name, line in sorted(registry.items()):
        if name not in literal and name not in tracing_literals:
            f(tracing_rel, line, f"unused:{name}",
              f"SPAN_NAMES entry {name!r} has no span-opening call site "
              "— a stage the table reports but nothing ever emits")
    for path, line in dynamic:
        if path.startswith("dml_tpu/"):
            f(path, line, f"dynamic:{path}:{line}",
              "a span opened with a non-literal name cannot be checked "
              "against SPAN_NAMES — pass the registry constant "
              "directly so the stage vocabulary stays closed")
    return fs


def rule_spans(root: str, trees: Dict[str, ast.Module]) -> List[Finding]:
    if TRACING_REL not in trees:
        return []
    tracing_tree = trees[TRACING_REL]
    literal, dynamic = collect_span_call_sites(trees)
    return check_span_names(
        _module_const_strs(tracing_tree, "SPAN_NAMES"),
        literal, dynamic,
        collect_tracing_literals(tracing_tree),
        TRACING_REL,
    )


# ----------------------------------------------------------------------
# drift-alert-names
# ----------------------------------------------------------------------

SIGNAL_REL = "dml_tpu/signal.py"

_ALERT_CALLS = ("fire_alert", "resolve_alert")


def collect_alert_call_sites(
    trees: Dict[str, ast.Module],
) -> Tuple[Dict[str, List[Tuple[str, int]]], List[Tuple[str, int]]]:
    """-> (alert name -> [(path, line), ...] for every LITERAL
    ``fire_alert("<name>", ...)`` / ``resolve_alert("<name>", ...)``
    call, [(path, line), ...] of non-literal call sites). Unlike the
    span rule, signal.py itself is NOT excluded from literal
    collection — its SignalPlane monitors are the primary emitters —
    but its dynamic sites (the ``_drive`` dispatcher, the manager
    pass-throughs) are the machinery's own and are filtered in
    ``check_alert_names``."""
    literal: Dict[str, List[Tuple[str, int]]] = {}
    dynamic: List[Tuple[str, int]] = []
    for rel, tree in sorted(trees.items()):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if _call_name(node.func) not in _ALERT_CALLS:
                continue
            name_arg: Optional[ast.AST] = (
                node.args[0] if node.args else None
            )
            if name_arg is None:
                for kw in node.keywords:
                    if kw.arg == "name":
                        name_arg = kw.value
            if isinstance(name_arg, ast.Constant) and isinstance(
                name_arg.value, str
            ):
                literal.setdefault(name_arg.value, []).append(
                    (rel, node.lineno)
                )
            else:
                dynamic.append((rel, node.lineno))
    return literal, dynamic


def check_alert_names(
    registry: Optional[Dict[str, int]],
    literal: Dict[str, List[Tuple[str, int]]],
    dynamic: List[Tuple[str, int]],
    signal_rel: str,
) -> List[Finding]:
    fs: List[Finding] = []

    def f(path: str, line: int, subject: str, msg: str) -> None:
        fs.append(Finding(path=path, line=line, rule=R_ALERTS, msg=msg,
                          key=f"{R_ALERTS}:{subject}"))

    if registry is None:
        f(signal_rel, 1, "no-registry",
          "signal.py has no module-level ALERT_NAMES tuple — the alert "
          "vocabulary must be declared where the linter (and the "
          "on-call runbook) can see it")
        return fs
    for name, sites in sorted(literal.items()):
        if name not in registry:
            path, line = sites[0]
            f(path, line, f"unregistered:{name}",
              f"fire_alert/resolve_alert({name!r}) uses an alert name "
              "not declared in signal.ALERT_NAMES — add it to the "
              "registry first, or the pager catalog silently gains an "
              "undocumented page")
    for name, line in sorted(registry.items()):
        if name not in literal:
            f(signal_rel, line, f"unused:{name}",
              f"ALERT_NAMES entry {name!r} has no fire_alert/"
              "resolve_alert call site — an alert the catalog promises "
              "but nothing ever emits")
    for path, line in dynamic:
        if path.startswith("dml_tpu/") and path != signal_rel:
            f(path, line, f"dynamic:{path}:{line}",
              "fire_alert/resolve_alert with a non-literal name cannot "
              "be checked against ALERT_NAMES — pass the registry "
              "constant directly so the alert vocabulary stays closed")
    return fs


def rule_alerts(root: str, trees: Dict[str, ast.Module]) -> List[Finding]:
    if SIGNAL_REL not in trees:
        return []
    literal, dynamic = collect_alert_call_sites(trees)
    return check_alert_names(
        _module_const_strs(trees[SIGNAL_REL], "ALERT_NAMES"),
        literal, dynamic, SIGNAL_REL,
    )


# ----------------------------------------------------------------------
# drift-pytest-markers
# ----------------------------------------------------------------------

_INI_MARKER_RE = re.compile(r"^(\s+)([A-Za-z_]\w*)\s*:")


def parse_ini_markers(text: str) -> Optional[Dict[str, int]]:
    """Marker names from pytest.ini's ``markers =`` block. Definition
    lines share the block's minimal indentation; deeper-indented lines
    are description continuations."""
    lines = text.splitlines()
    try:
        start = next(
            i for i, ln in enumerate(lines)
            if re.match(r"^markers\s*=", ln)
        )
    except StopIteration:
        return None
    out: Dict[str, int] = {}
    indent: Optional[int] = None
    for i in range(start + 1, len(lines)):
        ln = lines[i]
        if not ln.strip():
            continue
        if not ln[0].isspace():
            break  # next key or section
        m = _INI_MARKER_RE.match(ln)
        if m:
            if indent is None:
                indent = len(m.group(1))
            if len(m.group(1)) == indent:
                out[m.group(2)] = i + 1
    return out


def parse_conftest_markers(tree: ast.Module) -> Dict[str, int]:
    """Marker names from ``config.addinivalue_line("markers", "<name>:
    ...")`` calls in tests/conftest.py."""
    out: Dict[str, int] = {}
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "addinivalue_line"
                and len(node.args) >= 2
                and isinstance(node.args[0], ast.Constant)
                and node.args[0].value == "markers"
                and isinstance(node.args[1], ast.Constant)
                and isinstance(node.args[1].value, str)):
            name = node.args[1].value.split(":", 1)[0].strip()
            if name:
                out[name] = node.lineno
    return out


def collect_used_marks(
    trees: Dict[str, ast.Module]
) -> Dict[str, Tuple[str, int]]:
    """marker -> (rel, line) for every ``pytest.mark.<name>`` in tests/."""
    out: Dict[str, Tuple[str, int]] = {}
    for rel in sorted(trees):
        if not rel.startswith("tests/"):
            continue
        for node in ast.walk(trees[rel]):
            if (isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Attribute)
                    and node.value.attr == "mark"
                    and isinstance(node.value.value, ast.Name)
                    and node.value.value.id == "pytest"):
                out.setdefault(node.attr, (rel, node.lineno))
    return out


def check_markers(
    ini: Optional[Dict[str, int]],
    conftest: Dict[str, int],
    used: Dict[str, Tuple[str, int]],
    ini_rel: str,
    conftest_rel: str,
) -> List[Finding]:
    fs: List[Finding] = []

    def f(path: str, line: int, subject: str, msg: str) -> None:
        fs.append(Finding(path=path, line=line, rule=R_MARKERS, msg=msg,
                          key=f"{R_MARKERS}:{subject}"))

    if ini is None:
        f(ini_rel, 1, "no-registry",
          "pytest.ini has no `markers =` block — the marker registry "
          "is the canonical config and is lint-enforced")
        return fs
    custom_used = {
        m: loc for m, loc in used.items() if m not in BUILTIN_MARKS
    }
    for m, (rel, line) in sorted(custom_used.items()):
        if m not in ini:
            f(rel, line, f"unregistered:{m}",
              f"pytest marker {m!r} used here is not registered in "
              "pytest.ini — `-m` selections silently miss it and "
              "--strict-markers would fail")
    for m, line in sorted(ini.items()):
        if m not in conftest:
            f(ini_rel, line, f"ini-only:{m}",
              f"marker {m!r} is in pytest.ini but missing from the "
              "tests/conftest.py mirror (direct-module runs would "
              "warn)")
        if m not in custom_used:
            f(ini_rel, line, f"unused:{m}",
              f"registered marker {m!r} is used by no test — drop it "
              "or mark the coverage it was registered for")
    for m, line in sorted(conftest.items()):
        if m not in ini:
            f(conftest_rel, line, f"conftest-only:{m}",
              f"marker {m!r} is in the conftest mirror but not in "
              "pytest.ini (the canonical registry)")
    return fs


def rule_markers(root: str, trees: Dict[str, ast.Module]) -> List[Finding]:
    ini_path = os.path.join(root, "pytest.ini")
    conftest_rel = "tests/conftest.py"
    if not os.path.exists(ini_path) or conftest_rel not in trees:
        return []
    with open(ini_path, encoding="utf-8") as fh:
        ini_text = fh.read()
    return check_markers(
        parse_ini_markers(ini_text),
        parse_conftest_markers(trees[conftest_rel]),
        collect_used_marks(trees),
        "pytest.ini", conftest_rel,
    )


# ----------------------------------------------------------------------
# baseline
# ----------------------------------------------------------------------


def load_baseline(path: str) -> Dict[str, str]:
    """key -> justification. Malformed entries are an internal error
    (exit 2): a baseline that can't be trusted must not suppress."""
    if not os.path.exists(path):
        return {}
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        raise LintInternalError(f"baseline {path}: {e}") from e
    entries = doc.get("entries") if isinstance(doc, dict) else None
    if not isinstance(entries, list):
        raise LintInternalError(
            f"baseline {path}: expected {{'entries': [...]}}"
        )
    out: Dict[str, str] = {}
    for i, e in enumerate(entries):
        if not isinstance(e, dict) or not isinstance(e.get("key"), str):
            raise LintInternalError(
                f"baseline {path}: entry {i} has no string 'key'"
            )
        just = e.get("justification")
        if not isinstance(just, str) or not just.strip():
            raise LintInternalError(
                f"baseline {path}: entry {e['key']!r} has no "
                "justification — every grandfathered finding must say "
                "why it is accepted"
            )
        if e["key"] in out:
            raise LintInternalError(
                f"baseline {path}: duplicate key {e['key']!r}"
            )
        out[e["key"]] = just.strip()
    return out


def apply_baseline(
    findings: Sequence[Finding], baseline: Dict[str, str], baseline_rel: str
) -> Tuple[List[Finding], List[Finding]]:
    """-> (un-baselined findings + stale-entry findings, suppressed)."""
    keys = {f.key for f in findings}
    new = [f for f in findings if f.key not in baseline]
    suppressed = [f for f in findings if f.key in baseline]
    for k in sorted(baseline):
        if k not in keys:
            new.append(Finding(
                path=baseline_rel, line=1, rule=R_STALE,
                msg=f"baseline entry {k!r} matches no current finding — "
                    "the hazard is gone; delete the entry (the baseline "
                    "only ever shrinks)",
                key=f"{R_STALE}:{k}",
            ))
    return new, suppressed


# ----------------------------------------------------------------------
# driver
# ----------------------------------------------------------------------


@dataclass
class LintResult:
    findings: List[Finding]  # un-baselined (includes baseline-stale)
    suppressed: List[Finding]
    baseline_size: int

    @property
    def clean(self) -> bool:
        return not self.findings


def run_lint(
    root: Optional[str] = None,
    baseline_path: Optional[str] = None,
    rules: Optional[Sequence[str]] = None,
    paths: Optional[Sequence[str]] = None,
) -> LintResult:
    """Run the analyzer. ``rules``/``paths`` narrow what is REPORTED
    (for iterating on one rule or one file): the whole tree is always
    scanned — cross-artifact rules need the full view — and findings
    are filtered afterwards. While either filter is active,
    baseline-stale reporting is disabled (a partial view cannot judge
    staleness) and the baseline acts as suppression only."""
    from . import dmlflow  # sibling module; imported late (it imports us)

    root = os.path.abspath(root or repo_root())
    if baseline_path is None:
        baseline_path = os.path.join(root, DEFAULT_BASELINE)
    if rules:
        unknown = sorted(set(rules) - set(ALL_RULES))
        if unknown:
            raise LintInternalError(
                f"unknown rule(s) {', '.join(unknown)} — valid: "
                + ", ".join(ALL_RULES)
            )
    trees: Dict[str, ast.Module] = {}
    findings: List[Finding] = []
    for path in scan_paths(root):
        rel = _rel(root, path)
        trees[rel] = _parse(path, rel)  # raises LintInternalError
        findings.extend(analyze_tree(trees[rel], rel))
    for rule_fn in (rule_wire, rule_metrics, rule_markers,
                    rule_spans, rule_alerts,
                    dmlflow.rule_race, dmlflow.rule_payloads):
        findings.extend(rule_fn(root, trees))
    filtered = bool(rules) or bool(paths)
    if rules:
        findings = [f for f in findings if f.rule in set(rules)]
    if paths:
        import fnmatch

        findings = [
            f for f in findings
            if any(fnmatch.fnmatch(f.path, p) for p in paths)
        ]
    baseline = load_baseline(baseline_path)
    new, suppressed = apply_baseline(
        findings, baseline, _rel(root, baseline_path)
    )
    if filtered:
        new = [f for f in new if f.rule != R_STALE]
    # explicit sort key, not dataclass ordering: under `python -m
    # dml_tpu.tools.dmllint` this module is __main__ while dmlflow
    # imports the package copy, so findings from the two passes are
    # instances of two (identical) Finding classes
    sort_key = lambda f: (f.path, f.line, f.rule, f.msg, f.key)  # noqa: E731
    new.sort(key=sort_key)
    suppressed.sort(key=sort_key)
    return LintResult(
        findings=new, suppressed=suppressed, baseline_size=len(baseline)
    )


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="dmllint",
        description="project-native async-hazard & protocol-drift "
                    "linter (see module docstring for the rule catalog)",
    )
    p.add_argument("--root", default=None,
                   help="tree to lint (default: this repo)")
    p.add_argument("--baseline", default=None,
                   help=f"baseline JSON (default: {DEFAULT_BASELINE} "
                        "under the root)")
    p.add_argument("--json", action="store_true",
                   help="machine-readable output")
    p.add_argument("--rules", default=None, metavar="R1,R2",
                   help="only report these rules (comma-separated; "
                        "stale-baseline reporting is disabled while "
                        "filtering)")
    p.add_argument("--paths", default=None, metavar="GLOB[,GLOB]",
                   help="only report findings whose path matches one of "
                        "these globs (the whole tree is still scanned)")
    args = p.parse_args(argv)
    rules = [r.strip() for r in args.rules.split(",") if r.strip()] \
        if args.rules else None
    paths = [g.strip() for g in args.paths.split(",") if g.strip()] \
        if args.paths else None
    try:
        res = run_lint(args.root, args.baseline, rules=rules, paths=paths)
    except LintInternalError as e:
        if args.json:
            print(json.dumps({"internal_error": str(e),
                              "schema_version": JSON_SCHEMA_VERSION}))
        else:
            print(f"dmllint: internal error: {e}", file=sys.stderr)
        return 2
    if args.json:
        print(json.dumps({
            "schema_version": JSON_SCHEMA_VERSION,
            "clean": res.clean,
            "findings": [
                {"path": f.path, "line": f.line, "rule": f.rule,
                 "msg": f.msg, "key": f.key}
                for f in res.findings
            ],
            "suppressed": len(res.suppressed),
            "baseline_size": res.baseline_size,
            "rules": list(rules) if rules else list(ALL_RULES),
        }, indent=2))
    else:
        for f in res.findings:
            print(f.render())
        print(
            f"dmllint: {len(res.findings)} finding(s), "
            f"{len(res.suppressed)} baselined, "
            f"baseline size {res.baseline_size}"
        )
    return 1 if res.findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
