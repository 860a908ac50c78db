"""Fault-site registry lint: TPU601/602, pure AST.

The chaos explorer (distributed/fault_tolerance/chaos.py) can only
schedule faults at sites listed in the central ``FAULT_SITES``
registry, and a ``fault_point("store.gett")`` typo fails *silently* —
the injection hook just never fires and the test passes vacuously.
This pass closes both gaps statically:

* **TPU601** (error) — a literal fault-site reference
  (``fault_point(...)``, ``FaultEvent(...)``, ``plan.add(site,
  action)``, or a compact ``FaultPlan.parse``/``inject`` spec) names a
  site no registry pattern matches.  Register it or fix the typo.
* **TPU602** (warning) — a registry pattern that no scanned
  ``fault_point()`` call can ever satisfy: schedules will list the
  site but injection can never trigger.  Dead registry entries rot
  into false chaos coverage.

Dynamic sites are handled conservatively: an f-string or string
concatenation collapses its dynamic parts to ``*``, which matches only
a wildcard ``<...>`` registry segment (``f"fabric.host_down.h{i}"`` →
``fabric.host_down.h*`` → ``fabric.host_down.<host>``).  A site built
entirely at runtime (plain variable) is skipped — the lint only
judges what it can read.
"""
from __future__ import annotations

import ast
import os

from .diagnostics import Diagnostic, DiagnosticReport, record
from ..distributed.fault_tolerance.plan import (FAULT_SITES, FaultPlan,
                                                _ACTIONS, matching_sites)

__all__ = ["audit_fault_sites", "iter_source_files",
           "scan_fault_references"]

# repo-relative scan roots: every tree that references fault sites
_SCAN_DIRS = ("paddle_tpu", "scripts", "tests")


def _literal_site(node):
    """Best-effort literal for a site expression.  Constant strings come
    back verbatim; f-string / ``+``-concat dynamic parts collapse to
    ``*`` (matches only a wildcard registry segment); anything else is
    ``None`` — not judgeable, skipped."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        return "".join(v.value if isinstance(v, ast.Constant)
                       and isinstance(v.value, str) else "*"
                       for v in node.values)
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = _literal_site(node.left)
        right = _literal_site(node.right)
        if left is None and right is None:
            return None
        return (left if left is not None else "*") \
            + (right if right is not None else "*")
    return None


def _func_name(call):
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def scan_fault_references(path):
    """All judgeable fault-site references in one python file, as
    ``(site, lineno, kind)`` tuples.  ``kind`` is the call shape that
    produced the reference; only ``fault_point`` counts as
    *instrumentation* for TPU602 coverage — the other shapes are
    demand-side (schedules and plans)."""
    with open(path, "r", encoding="utf-8") as fh:
        src = fh.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError:
        return []
    refs = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _func_name(node)
        args = node.args
        if name in ("fault_point", "FaultEvent") and args:
            site = _literal_site(args[0])
            if site is not None and "." in site:
                refs.append((site, node.lineno, name))
        elif name == "add" and len(args) >= 2:
            # FaultPlan.add(site, action): claim the shape only when the
            # second arg is a literal action verb, so set.add / report
            # .add and friends never trip it.
            site = _literal_site(args[0])
            action = _literal_site(args[1])
            if site is not None and action in _ACTIONS and "." in site:
                refs.append((site, node.lineno, "plan.add"))
        elif name in ("parse", "inject") and args:
            spec = args[0]
            if (isinstance(spec, ast.Constant)
                    and isinstance(spec.value, str)
                    and ":" in spec.value):
                try:
                    plan = FaultPlan.parse(spec.value)
                except Exception:
                    continue  # not a fault spec (or a malformed one —
                    #           the call site's own test covers that)
                refs.extend((ev.site, node.lineno, name)
                            for ev in plan.events)
    return refs


def iter_source_files(root):
    """Every ``.py`` under the scan roots, deterministic order."""
    for d in _SCAN_DIRS:
        base = os.path.join(root, d)
        if not os.path.isdir(base):
            continue
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = sorted(x for x in dirnames
                                 if not x.startswith(".")
                                 and x != "__pycache__")
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def audit_fault_sites(root=None, *, report=None, emit=True):
    """TPU601/602 over the whole tree (module doc).  Pure AST — no
    imports of the scanned files, so a module with heavy import-time
    side effects lints the same as any other."""
    if root is None:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    report = report if report is not None else DiagnosticReport(
        label="fault sites")
    covered = set()
    for path in iter_source_files(root):
        rel = os.path.relpath(path, root)
        for site, lineno, kind in scan_fault_references(path):
            pats = matching_sites(site)
            if pats:
                if kind == "fault_point":
                    covered.update(pats)
                continue
            d = Diagnostic(
                "TPU601",
                f"{kind} references fault site {site!r} which no "
                "FAULT_SITES registry pattern matches — a chaos "
                "schedule can never reach it and a typo here fails "
                "silently",
                site=f"{rel}:{lineno}",
                hint="register the site in distributed/fault_tolerance/"
                     "plan.py FAULT_SITES (register_fault_site) or fix "
                     "the site string",
                data={"ref_site": site, "kind": kind, "path": rel,
                      "lineno": int(lineno)})
            if emit:
                record(d)
            report.add(d)
    for pat in sorted(FAULT_SITES):
        if pat in covered:
            continue
        d = Diagnostic(
            "TPU602",
            f"registered fault site {pat!r} has no fault_point() "
            "instrumentation anywhere in the tree — schedules list it "
            "but injection can never trigger",
            site=f"FAULT_SITES[{pat!r}]",
            hint="add a fault_point() at the code path the entry "
                 "describes, or drop the dead registry entry",
            data={"pattern": pat})
        if emit:
            record(d)
        report.add(d)
    return report
