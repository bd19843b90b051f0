"""graft-lint — the project-wide AST-based static analysis suite.

The reference enforces its invariants with dedicated tooling (the
api_validation module, per-shim build checks); this package is our
equivalent: a small multi-pass lint framework whose passes encode the
engine's *semantic* contracts — the ones a Python compiler cannot check
and three PRs' worth of concurrency bugs were hand-found violating:

* ``host-sync``   — no hidden device→host synchronization on the hot path
                    (the static complement of the PR-9 ledger's runtime
                    ``glue`` phase; docs/observability.md).
* ``lock-order``  — the lock-acquisition graph is acyclic and respects the
                    declared hierarchy (:mod:`.lock_order`), and nothing
                    blocks (sockets, sleeps, ``Future.result``, thread
                    joins, first-touch compiles) while holding a lock —
                    the exact shape of the PR-7 ``_COMPILE_LOCK`` deadlock.
* ``conf-key``    — every ``spark.rapids.tpu.*`` literal names a key in
                    ``config.py``'s registry, and ``startup_only`` keys are
                    never re-read on the per-query path.
* ``cancel-beat`` — batch-granular streaming loops carry a
                    ``CancelToken.check()``/watchdog beat so cancellation
                    and the PR-7 stall watchdog can see them.
* ``metrics``     — every emitted metric name is pre-registered in the
                    obs catalog (the PR-9 ``metrics_lint`` check, folded in
                    as a pass).

Run: ``python -m spark_rapids_tpu.analysis`` (or ``make lint``).

Findings are suppressed inline with ``# graft: ok(<pass>: <reason>)`` on
the finding's line or the line directly above, or recorded in the
checked-in baseline file (``analysis/BASELINE.lint``) with a mandatory
justification. The hot directories (``exec/``, ``serve/``, ``sched/``)
may never carry baseline entries — findings there are fixed or explicitly
suppressed at the site, so the baseline cannot quietly absorb new debt
where the performance and correctness contracts live.

See docs/static-analysis.md for the pass catalog, the suppression and
baseline policy, and how to add a pass.
"""
from __future__ import annotations

import ast
import hashlib
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: directories that may never carry baseline entries: every finding there
#: is fixed or suppressed at the site (ISSUE 10's no-new-debt contract)
PROTECTED_DIRS = (
    "spark_rapids_tpu/exec/",
    "spark_rapids_tpu/serve/",
    "spark_rapids_tpu/sched/",
)

#: default baseline location, next to the framework so it ships with it
BASELINE_NAME = "BASELINE.lint"

_SUPPRESS_RE = re.compile(
    r"#\s*graft:\s*ok\(\s*([A-Za-z0-9_-]+)\s*:\s*([^)]+?)\s*\)"
)
#: the guarded-by annotation grammar (passes/guarded_by.py): names the
#: lock protecting the attribute/global initialized on this line, as in
#: "self._plans = {}" followed by "graft: guarded_by(_lock)" in a
#: comment (spelled obliquely here: a literal example would annotate
#: the next assignment of THIS module)
_GUARDED_RE = re.compile(
    r"#\s*graft:\s*guarded_by\(\s*([A-Za-z_][A-Za-z0-9_.]*)\s*\)"
)
_GRAFT_MARKER_RE = re.compile(r"#\s*graft\s*:")


@dataclass(frozen=True)
class Finding:
    """One lint finding, anchored to a file:line.

    ``fingerprint`` identifies the finding across line-number drift: it
    hashes the pass, the path, and the *text* of the flagged line (plus an
    occurrence index for duplicate lines), so reformatting elsewhere in
    the file does not invalidate baseline entries.
    """

    pass_id: str
    path: str
    line: int
    message: str
    fingerprint: str = ""

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.pass_id}] {self.message}"


class SourceFile:
    """One parsed source file: text, lines, lazily-built AST, and the
    per-line suppression table."""

    def __init__(self, root: str, rel: str):
        self.root = root
        self.rel = rel.replace(os.sep, "/")
        with open(os.path.join(root, rel), encoding="utf-8") as fh:
            self.text = fh.read()
        self.lines = self.text.splitlines()
        self._tree: Optional[ast.AST] = None
        self._parse_error: Optional[SyntaxError] = None
        # line → [(pass_id, reason)]
        self.suppressions: Dict[int, List[Tuple[str, str]]] = {}
        # line → lock name (the guarded_by annotation grammar)
        self.guarded_by: Dict[int, str] = {}
        self.malformed_graft: List[int] = []
        i = 1
        n = len(self.lines)
        while i <= n:
            line = self.lines[i - 1]
            if not _GRAFT_MARKER_RE.search(line):
                i += 1
                continue
            guard = _GUARDED_RE.search(line)
            if guard is not None:
                self.guarded_by[i] = guard.group(1)
                i += 1
                continue
            hits = _SUPPRESS_RE.findall(line)
            if hits:
                self.suppressions.setdefault(i, []).extend(
                    (p, r.strip()) for p, r in hits
                )
                i += 1
                continue
            # multi-line form: a comment-only marker line whose reason
            # wraps onto following comment-only lines until the closing
            # paren — every block line carries the suppression, so the
            # line-below rule anchors on the block's last line
            block_end = self._scan_block(i)
            if block_end is not None:
                joined = " ".join(
                    self.lines[j - 1].lstrip().lstrip("#").strip()
                    for j in range(i, block_end + 1)
                )
                hits = _SUPPRESS_RE.findall("# " + joined)
                if hits:
                    for j in range(i, block_end + 1):
                        self.suppressions.setdefault(j, []).extend(
                            (p, r.strip()) for p, r in hits
                        )
                    i = block_end + 1
                    continue
            self.malformed_graft.append(i)
            i += 1

    def _scan_block(self, start: int, max_lines: int = 6) -> Optional[int]:
        """Last line of the comment block opening at ``start`` once the
        graft marker's parenthesis closes; None when the marker is not on
        a comment-only line or never closes within ``max_lines``."""
        first = self.lines[start - 1]
        if not first.lstrip().startswith("#"):
            return None
        depth = 0
        for j in range(start, min(start + max_lines, len(self.lines) + 1)):
            text = self.lines[j - 1]
            if not text.lstrip().startswith("#"):
                return None
            depth += text.count("(") - text.count(")")
            if j > start and not text.lstrip().lstrip("#").strip():
                return None  # blank comment breaks the block
            if depth <= 0 and (j > start or ")" in text):
                return j
        return None

    @property
    def tree(self) -> Optional[ast.AST]:
        if self._tree is None and self._parse_error is None:
            try:
                self._tree = ast.parse(self.text, filename=self.rel)
            except SyntaxError as e:  # surfaced as a framework finding
                self._parse_error = e
        return self._tree

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    def suppressed(self, pass_id: str, lineno: int) -> bool:
        """A finding on ``lineno`` is suppressed by a matching
        ``# graft: ok(<pass>: <reason>)`` on the same line or — for a
        comment standing on its own line — the line directly above."""
        for cand in (lineno, lineno - 1):
            for pid, _reason in self.suppressions.get(cand, ()):
                if pid != pass_id and pid != "all":
                    continue
                if cand == lineno:
                    return True
                # line above only counts when it is a pure comment line
                if self.line_text(cand).lstrip().startswith("#"):
                    return True
        return False


class Project:
    """The analysis unit: every source file of the package, parsed once
    and shared by all passes."""

    def __init__(self, root: str, files: Sequence[SourceFile]):
        self.root = root
        self.files = list(files)
        self._by_rel = {f.rel: f for f in self.files}

    @classmethod
    def load(cls, root: str) -> "Project":
        root = os.path.abspath(root)
        rels: List[str] = []
        pkg = os.path.join(root, "spark_rapids_tpu")
        for base, _dirs, names in os.walk(pkg):
            if "__pycache__" in base:
                continue
            for name in sorted(names):
                if name.endswith(".py"):
                    rels.append(
                        os.path.relpath(os.path.join(base, name), root)
                    )
        return cls(root, [SourceFile(root, r) for r in sorted(rels)])

    def file(self, rel: str) -> Optional[SourceFile]:
        return self._by_rel.get(rel.replace(os.sep, "/"))


class LintPass:
    """Base class: subclasses set ``id``/``title`` and yield Findings from
    ``run``. ``finding`` stamps the fingerprint-ready tuple (the framework
    fills occurrence indices afterwards, so duplicate lines stay stable)."""

    id = "base"
    title = "abstract pass"

    def run(self, project: Project) -> Iterable[Finding]:  # pragma: no cover
        raise NotImplementedError

    def finding(self, path: str, line: int, message: str) -> Finding:
        return Finding(self.id, path, line, message)


def _fingerprint(
    f: Finding, line_text: str, occurrence: int
) -> str:
    basis = "\0".join(
        (f.pass_id, f.path, " ".join(line_text.split()), str(occurrence))
    )
    return hashlib.sha1(basis.encode("utf-8")).hexdigest()[:12]


def _stamp_fingerprints(
    project: Project, findings: List[Finding]
) -> List[Finding]:
    seen: Dict[Tuple[str, str, str], int] = {}
    out: List[Finding] = []
    for f in findings:
        sf = project.file(f.path)
        text = sf.line_text(f.line) if sf is not None else ""
        key = (f.pass_id, f.path, " ".join(text.split()))
        occ = seen.get(key, 0)
        seen[key] = occ + 1
        out.append(
            Finding(f.pass_id, f.path, f.line, f.message,
                    _fingerprint(f, text, occ))
        )
    return out


# ── baseline ────────────────────────────────────────────────────────────────


@dataclass
class BaselineEntry:
    pass_id: str
    path: str
    fingerprint: str
    justification: str
    lineno: int = 0  # line in the baseline file (for error reporting)


@dataclass
class Baseline:
    path: str
    entries: List[BaselineEntry] = field(default_factory=list)
    errors: List[str] = field(default_factory=list)

    def index(self) -> Dict[Tuple[str, str, str], BaselineEntry]:
        return {
            (e.pass_id, e.path, e.fingerprint): e for e in self.entries
        }


def load_baseline(path: str) -> Baseline:
    bl = Baseline(path)
    if not os.path.exists(path):
        return bl
    with open(path, encoding="utf-8") as fh:
        for i, raw in enumerate(fh, start=1):
            line = raw.rstrip("\n")
            if not line.strip() or line.lstrip().startswith("#"):
                continue
            parts = [p.strip() for p in line.split("|")]
            if len(parts) != 4 or not all(parts[:3]):
                bl.errors.append(
                    f"{path}:{i}: malformed baseline row (want "
                    "'pass | path | fingerprint | justification')"
                )
                continue
            pass_id, rel, fp, just = parts
            if not just:
                bl.errors.append(
                    f"{path}:{i}: baseline entry {pass_id}:{rel} has no "
                    "justification — every baselined finding must say why "
                    "it is allowed to stand"
                )
                continue
            for prot in PROTECTED_DIRS:
                if rel.startswith(prot):
                    bl.errors.append(
                        f"{path}:{i}: baseline entry under protected "
                        f"directory {prot} — findings in exec/, serve/, "
                        "and sched/ must be fixed or suppressed at the "
                        "site, never baselined"
                    )
                    break
            else:
                bl.entries.append(
                    BaselineEntry(pass_id, rel, fp, just, i)
                )
    return bl


def write_baseline(
    path: str, findings: Sequence[Finding], old: Baseline,
    justify: str = ""
) -> Tuple[int, int]:
    """Regenerate the baseline from the currently-unsuppressed findings,
    keeping the justification of every surviving entry. New entries take
    ``justify``; with none given, regeneration refuses when new entries
    exist (the mandatory-justification policy)."""
    old_idx = old.index()
    rows: List[BaselineEntry] = []
    fresh = 0
    for f in findings:
        for prot in PROTECTED_DIRS:
            if f.path.startswith(prot):
                raise SystemExit(
                    f"refusing to baseline {f.render()} — {prot} findings "
                    "must be fixed or suppressed at the site"
                )
        kept = old_idx.get((f.pass_id, f.path, f.fingerprint))
        if kept is not None:
            rows.append(kept)
            continue
        if not justify:
            raise SystemExit(
                f"new baseline entry needs a justification: {f.render()}\n"
                "re-run with --justify '<why this finding may stand>'"
            )
        fresh += 1
        rows.append(
            BaselineEntry(f.pass_id, f.path, f.fingerprint, justify)
        )
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(
            "# graft-lint baseline — legacy findings explicitly allowed "
            "to stand.\n"
            "# Regenerate with `make lint-baseline JUSTIFY='<reason>'`; "
            "every row carries\n"
            "# a justification, entries under exec/, serve/, or sched/ "
            "are rejected, and\n"
            "# stale rows (finding gone) fail the lint so the file can "
            "only shrink honestly.\n"
            "# pass | path | fingerprint | justification\n"
        )
        for e in sorted(rows, key=lambda e: (e.path, e.pass_id, e.fingerprint)):
            fh.write(
                f"{e.pass_id} | {e.path} | {e.fingerprint} | "
                f"{e.justification}\n"
            )
    return len(rows), fresh


# ── driver ──────────────────────────────────────────────────────────────────


@dataclass
class LintResult:
    findings: List[Finding]          # unsuppressed, unbaselined — failures
    suppressed: List[Finding]
    baselined: List[Finding]
    framework: List[Finding]         # malformed suppressions, stale baseline
    all_findings: List[Finding] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.findings and not self.framework


def run_passes(
    project: Project,
    passes: Optional[Sequence[LintPass]] = None,
    baseline: Optional[Baseline] = None,
) -> LintResult:
    from .passes import all_passes

    active = list(passes) if passes is not None else all_passes()
    raw: List[Finding] = []
    for p in active:
        raw.extend(p.run(project))
    raw.sort(key=lambda f: (f.path, f.line, f.pass_id))
    stamped = _stamp_fingerprints(project, raw)

    framework: List[Finding] = []
    for sf in project.files:
        if sf.rel.startswith("spark_rapids_tpu/analysis/"):
            continue  # the lint's own docs spell out the marker grammar
        for ln in sf.malformed_graft:
            framework.append(
                Finding(
                    "graft", sf.rel, ln,
                    "malformed graft marker — the only recognized form is "
                    "'# graft: ok(<pass>: <reason>)'",
                )
            )
        if sf._parse_error is not None:  # parse the file to lint it at all
            framework.append(
                Finding(
                    "graft", sf.rel,
                    sf._parse_error.lineno or 1,
                    f"file does not parse: {sf._parse_error.msg}",
                )
            )

    bl = baseline if baseline is not None else Baseline("")
    for err in bl.errors:
        framework.append(Finding("baseline", bl.path, 0, err))
    bl_idx = bl.index()

    failures: List[Finding] = []
    suppressed: List[Finding] = []
    baselined: List[Finding] = []
    hit_entries = set()
    for f in stamped:
        sf = project.file(f.path)
        if sf is not None and sf.suppressed(f.pass_id, f.line):
            suppressed.append(f)
            continue
        entry = bl_idx.get((f.pass_id, f.path, f.fingerprint))
        if entry is not None:
            hit_entries.add(id(entry))
            baselined.append(f)
            continue
        failures.append(f)
    active_ids = {p.id for p in active}
    for e in bl.entries:
        # staleness is only decidable for passes that actually RAN this
        # invocation — a --passes subset must not declare the other
        # passes' entries dead
        if e.pass_id in active_ids and id(e) not in hit_entries:
            framework.append(
                Finding(
                    "baseline", bl.path, e.lineno,
                    f"stale baseline entry {e.pass_id} | {e.path} | "
                    f"{e.fingerprint} — the finding no longer exists; "
                    "remove the row (make lint-baseline) so the baseline "
                    "only ever shrinks honestly",
                )
            )
    return LintResult(failures, suppressed, baselined, framework, stamped)


def default_baseline_path(root: str) -> str:
    return os.path.join(
        os.path.abspath(root), "spark_rapids_tpu", "analysis", BASELINE_NAME
    )
