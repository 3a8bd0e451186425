"""The pfxlint engine: file collection, rule orchestration,
suppression, baseline.

The engine owns everything that is not a rule: walking the tree,
parsing sources once, building the call graph (``callgraph.py``),
handing a :class:`LintContext` to each rule module, then filtering the
raw findings through inline suppressions (``# pfxlint:
disable=RULE``) and the checked-in baseline
(``codestyle/pfxlint/baseline.txt``).

Baselines are fingerprint-based, NOT line-based: a fingerprint is
``path::CODE::key`` where ``key`` is a rule-chosen stable detail (a
counter name, a function qualname + hazard token, a docstring
message), so unrelated edits moving a finding by ten lines do not
churn the file. ``--write-baseline`` regenerates it; comment lines
are preserved conventionally by writing justifications above blocks
(regeneration keeps findings sorted so diffs stay reviewable).

Everything here is stdlib-only on purpose — the CI gate and the
pre-commit hook must run before (and without) the jax toolchain
installing.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from . import callgraph
from . import threadgraph

#: directories never scanned, wherever they appear
EXCLUDE_DIRS = {
    ".git", "__pycache__", ".github", ".claude", ".pytest_cache",
    "tests",            # the tier-1 suite lints itself via pytest
    "output", "profiler_log", "node_modules",
    # git-ignored copies of the tree a builder unpacks for chip runs
    "_archive", "_parent", "chiprun_out", ".xla_cache",
}

#: docs scanned by the contract rules
DOCS_GLOB_DIR = "docs"

_SUPPRESS_RE = re.compile(
    r"#\s*pfxlint:\s*disable(?P<scope>-file)?="
    r"(?P<codes>[A-Za-z0-9_,\s]+)")


@dataclasses.dataclass
class Finding:
    """One rule violation, with a line-independent fingerprint."""

    path: str
    line: int
    code: str
    message: str
    key: str = ""          # stable detail; message used when empty

    def fingerprint(self) -> str:
        return f"{self.path}::{self.code}::{self.key or self.message}"

    def __str__(self):
        return f"{self.path}:{self.line}: {self.code} {self.message}"


@dataclasses.dataclass
class SourceFile:
    """One parsed Python file."""

    path: str              # repo-relative, forward slashes
    text: str
    tree: ast.Module
    lines: List[str]
    #: line -> codes disabled on that line ("*" disables all)
    suppressions: Dict[int, Set[str]] = \
        dataclasses.field(default_factory=dict)
    #: codes disabled for the whole file
    file_suppressions: Set[str] = dataclasses.field(default_factory=set)


@dataclasses.dataclass
class DocFile:
    """One documentation file the contract rules read."""

    path: str
    text: str
    lines: List[str]


class LintContext:
    """Everything a rule may look at; built once per run."""

    def __init__(self, py_files: List[SourceFile],
                 docs: List[DocFile], root: str):
        self.py_files = py_files
        self.docs = docs
        self.root = root
        self.callgraph = callgraph.build(
            {f.path: f.tree for f in py_files})
        self.threadgraph = threadgraph.build(self.callgraph)

    def file(self, path: str) -> Optional[SourceFile]:
        for f in self.py_files:
            if f.path == path:
                return f
        return None

    @classmethod
    def from_sources(cls, sources: Dict[str, str],
                     docs: Optional[Dict[str, str]] = None,
                     root: str = "<memory>") -> "LintContext":
        """Build a context from in-memory sources (the test path).

        Args:
            sources (dict): repo-relative path -> Python source text.
            docs (dict): repo-relative path -> markdown text.
            root (str): reported root, cosmetic only.

        Returns:
            LintContext over exactly the given files.

        Raises:
            SyntaxError: when a source does not parse.
        """
        py = [_parse_source(p, t) for p, t in sorted(sources.items())]
        dd = [DocFile(p, t, t.splitlines())
              for p, t in sorted((docs or {}).items())]
        return cls(py, dd, root)


def _parse_source(path: str, text: str) -> SourceFile:
    tree = ast.parse(text, filename=path)
    sf = SourceFile(path, text, tree, text.splitlines())
    for i, line in enumerate(sf.lines, 1):
        m = _SUPPRESS_RE.search(line)
        if not m:
            continue
        codes = {c.strip() for c in m.group("codes").split(",")
                 if c.strip()}
        if m.group("scope"):
            sf.file_suppressions |= codes
        else:
            sf.suppressions.setdefault(i, set()).update(codes)
    return sf


def collect_files(root: str, paths: Optional[Sequence[str]] = None
                  ) -> Tuple[List[SourceFile], List[DocFile]]:
    """Walk the tree (or explicit paths) into parsed sources + docs.

    Args:
        root (str): repository root all paths are made relative to.
        paths (list): optional explicit files/dirs; default full tree.

    Returns:
        ``(py_files, docs)`` with stable, sorted ordering.

    Raises:
        SyntaxError: when a Python source fails to parse — a broken
            file must fail the gate loudly, not fall out of coverage.
    """
    root = os.path.abspath(root)
    py: List[SourceFile] = []
    seen: Set[str] = set()

    def add_py(abspath: str):
        rel = os.path.relpath(abspath, root).replace(os.sep, "/")
        if rel in seen:
            return
        seen.add(rel)
        with open(abspath, "r", encoding="utf-8") as f:
            py.append(_parse_source(rel, f.read()))

    targets = [os.path.join(root, p) for p in paths] if paths \
        else [root]
    for target in targets:
        if os.path.isfile(target):
            if target.endswith(".py"):
                add_py(target)
            continue
        for dirpath, dirnames, filenames in os.walk(target):
            dirnames[:] = sorted(d for d in dirnames
                                 if d not in EXCLUDE_DIRS)
            for name in sorted(filenames):
                if name.endswith(".py"):
                    add_py(os.path.join(dirpath, name))

    docs: List[DocFile] = []
    docs_dir = os.path.join(root, DOCS_GLOB_DIR)
    if os.path.isdir(docs_dir):
        for name in sorted(os.listdir(docs_dir)):
            if name.endswith(".md"):
                p = os.path.join(docs_dir, name)
                with open(p, "r", encoding="utf-8") as f:
                    text = f.read()
                docs.append(DocFile(f"docs/{name}", text,
                                    text.splitlines()))
    return py, docs


# -- baseline ----------------------------------------------------------

def load_baseline(path: str) -> List[str]:
    """Baseline fingerprints, in file order (comments/blanks skipped)."""
    if not os.path.exists(path):
        return []
    out = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line and not line.startswith("#"):
                out.append(line)
    return out


def write_baseline(path: str, findings: Sequence[Finding],
                   header: str = "") -> None:
    """Serialize findings as a fresh baseline file.

    Args:
        path (str): destination file.
        findings (list): findings to carry; sorted for diff stability.
        header (str): optional comment block for the top of the file.
    """
    lines = [
        "# pfxlint baseline — findings carried, not fixed.",
        "# One fingerprint per line: path::CODE::key. Lines starting",
        "# with '#' are justification comments. Regenerate with:",
        "#   python -m codestyle.pfxlint --write-baseline",
    ]
    if header:
        lines += ["#", *("# " + h for h in header.splitlines())]
    lines += sorted({f.fingerprint() for f in findings})
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")


# -- orchestration -----------------------------------------------------

@dataclasses.dataclass
class LintResult:
    """Outcome of one lint run, pre-split for reporting."""

    findings: List[Finding]            # actionable (rc 1 when any)
    suppressed: List[Finding]          # killed by inline comments
    baselined: List[Finding]           # carried by the baseline file
    unused_baseline: List[str]         # stale fingerprints

    @property
    def exit_code(self) -> int:
        return 1 if self.findings else 0

    def suppression_counts(self) -> Dict[str, int]:
        """Inline suppressions per rule code — the creep metric
        ``--stats`` prints so a quietly growing pile of disables is
        visible in CI logs."""
        out: Dict[str, int] = {}
        for f in self.suppressed:
            out[f.code] = out.get(f.code, 0) + 1
        return out


def _all_rules():
    from .rules import ALL_RULES
    return ALL_RULES


def run_rules(ctx: LintContext,
              select: Optional[Set[str]] = None,
              ignore: Optional[Set[str]] = None) -> List[Finding]:
    """Raw findings from every (selected) rule module, sorted."""
    findings: List[Finding] = []
    for rule in _all_rules():
        if select and not (set(rule.CODES) & select):
            continue
        findings.extend(rule.check(ctx))
    if select:
        findings = [f for f in findings if f.code in select]
    if ignore:
        findings = [f for f in findings if f.code not in ignore]
    findings.sort(key=lambda f: (f.path, f.line, f.code, f.message))
    return findings


def apply_suppressions(ctx: LintContext, findings: Sequence[Finding]
                       ) -> Tuple[List[Finding], List[Finding]]:
    """Split findings into (kept, suppressed) via inline comments."""
    kept, suppressed = [], []
    by_path = {f.path: f for f in ctx.py_files}
    for f in findings:
        sf = by_path.get(f.path)
        codes = set()
        if sf is not None:
            codes |= sf.file_suppressions
            codes |= sf.suppressions.get(f.line, set())
        if f.code in codes or "all" in codes:
            suppressed.append(f)
        else:
            kept.append(f)
    return kept, suppressed


def run_lint(root: str,
             paths: Optional[Sequence[str]] = None,
             select: Optional[Set[str]] = None,
             ignore: Optional[Set[str]] = None,
             baseline_path: Optional[str] = None,
             use_baseline: bool = True) -> LintResult:
    """Full pipeline over a directory tree.

    Args:
        root (str): repository root.
        paths (list): optional explicit sub-paths (full tree default).
        select (set): restrict to these rule codes.
        ignore (set): drop these rule codes.
        baseline_path (str): baseline file; default
            ``codestyle/pfxlint/baseline.txt`` under ``root``.
        use_baseline (bool): set False to see every finding.

    Returns:
        LintResult with actionable / suppressed / baselined splits.
    """
    py, docs = collect_files(root, paths)
    ctx = LintContext(py, docs, root)
    raw = run_rules(ctx, select=select, ignore=ignore)
    kept, suppressed = apply_suppressions(ctx, raw)
    baselined: List[Finding] = []
    unused: List[str] = []
    if use_baseline:
        if baseline_path is None:
            baseline_path = os.path.join(
                root, "codestyle", "pfxlint", "baseline.txt")
        entries = set(load_baseline(baseline_path))
        hit: Set[str] = set()
        still: List[Finding] = []
        for f in kept:
            fp = f.fingerprint()
            if fp in entries:
                baselined.append(f)
                hit.add(fp)
            else:
                still.append(f)
        kept = still
        unused = sorted(entries - hit)
    return LintResult(kept, suppressed, baselined, unused)
