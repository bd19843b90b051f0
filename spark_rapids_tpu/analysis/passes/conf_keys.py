"""conf-key — configuration-key drift and scope analysis.

Two checks against the single source of truth, ``config.py``'s typed
registry (the same registry ``docs_gen.py`` renders configs.md — and its
Scope column — from):

1. **Existence.** Every ``spark.rapids.tpu.*`` string literal anywhere in
   the engine (set_conf calls, conf.get fallbacks, error messages citing
   the key a user should flip) must name a registered key or a registered
   key *family* prefix. A typo'd key in a ``set_conf`` silently no-ops; a
   typo'd key in an error message sends the user to a switch that does
   not exist. Auto-derived per-rule kill switches
   (``spark.rapids.sql.exec.*`` / ``spark.rapids.sql.expression.*``) are
   exempt by namespace.
2. **Scope.** ``startup_only`` keys (backend, shims, mesh/multiproc
   topology) are frozen when the session is constructed; a
   ``<ENTRY>.get(conf)`` on one of them outside the session-init surface
   re-reads a value the engine already committed to — the running
   topology and the conf silently disagree after a live ``set_conf``
   (exactly the multiproc drift this pass's introduction fixed in
   exec/tpu.py and plan/physical.py).

This supersedes the docs-only existence check in test_config_docs.py:
that test keeps configs.md in sync; this pass covers every call site.
"""
from __future__ import annotations

import ast
import re
from typing import Iterable, List, Optional, Set, Tuple

from .. import Finding, LintPass, Project

_KEY_RE = re.compile(r"spark\.rapids\.tpu(?:\.[A-Za-z0-9_]+)+")

#: namespaces whose keys are minted dynamically per replacement rule
#: (plan/overrides.py) — existence is enforced by the rule registry itself
_DYNAMIC_NAMESPACES = (
    "spark.rapids.sql.exec.",
    "spark.rapids.sql.expression.",
)

#: files allowed to read startup_only entries: the session-construction
#: surface, the registry itself, docs generation, and the server
#: bootstrap (all run before or at session init)
ALLOWED_STARTUP_READERS = (
    "spark_rapids_tpu/session.py",
    "spark_rapids_tpu/config.py",
    "spark_rapids_tpu/docs_gen.py",
    "spark_rapids_tpu/serve/__main__.py",
)


def _registry():
    from ... import config as cfg

    keys = set(cfg.registry().keys())
    startup = cfg.startup_only_keys()  # shared with docs_gen's Scope column
    startup_attrs = {
        name: entry.key
        for name, entry in vars(cfg).items()
        if isinstance(entry, cfg.ConfEntry) and entry.key in startup
    }
    return keys, startup_attrs


class _Visitor(ast.NodeVisitor):
    def __init__(self, pass_: "ConfKeyPass", rel: str, keys: Set[str],
                 startup_attrs: dict):
        self.p = pass_
        self.rel = rel
        self.keys = keys
        self.startup_attrs = startup_attrs
        self.findings: List[Finding] = []
        self._prefixes = {k[: k.rindex(".")] for k in keys if "." in k}

    # ── literal existence ───────────────────────────────────────────────
    def _check_literal(self, node: ast.Constant) -> None:
        for token in _KEY_RE.findall(node.value):
            if token in self.keys:
                continue
            if any(token.startswith(ns) for ns in _DYNAMIC_NAMESPACES):
                continue
            # a family mention ("spark.rapids.tpu.faults", docstring
            # prose truncated at a wildcard) passes when it prefixes at
            # least one registered key
            if any(k.startswith(token + ".") for k in self.keys):
                continue
            self.findings.append(self.p.finding(
                self.rel, node.lineno,
                f"conf key {token!r} is not registered in config.py — a "
                "typo here either silently no-ops (set_conf) or points "
                "users at a switch that does not exist (messages/docs); "
                "register the key or fix the spelling",
            ))

    def visit_Constant(self, node: ast.Constant) -> None:
        if isinstance(node.value, str) and "spark.rapids.tpu." in node.value:
            self._check_literal(node)

    # ── startup_only scope ──────────────────────────────────────────────
    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        if (
            isinstance(fn, ast.Attribute)
            and fn.attr == "get"
            and self.rel not in ALLOWED_STARTUP_READERS
        ):
            entry_name = self._entry_name(fn.value)
            key = self.startup_attrs.get(entry_name) if entry_name else None
            if key is not None:
                self.findings.append(self.p.finding(
                    self.rel, node.lineno,
                    f"startup_only conf {key!r} re-read outside session "
                    "init — the session froze this value at construction "
                    "(topology, backend, shims); a live set_conf would "
                    "make this read disagree with the running state. "
                    "Read the frozen session/context field instead "
                    "(e.g. session.multiproc_topology())",
                ))
        self.generic_visit(node)

    @staticmethod
    def _entry_name(expr: ast.expr) -> Optional[str]:
        if isinstance(expr, ast.Attribute):
            return expr.attr          # cfg.MESH_ENABLED
        if isinstance(expr, ast.Name):
            return expr.id            # from config import MESH_ENABLED
        return None


class ConfKeyPass(LintPass):
    id = "conf-key"
    title = "conf-key existence + startup_only scope drift"

    def run(self, project: Project) -> Iterable[Finding]:
        keys, startup_attrs = _registry()
        for sf in project.files:
            if sf.rel == "spark_rapids_tpu/config.py" or sf.tree is None:
                continue
            v = _Visitor(self, sf.rel, keys, startup_attrs)
            v.visit(sf.tree)
            yield from v.findings


PASS = ConfKeyPass()
