"""PFX203/PFX204 — every ``PFX_*`` environment knob is documented.

Knobs are the repo's operational API: a driver, an SRE, or the
next session discovers ``PFX_WATCHDOG_ACTION`` only if a doc
says it exists. The contract is bidirectional:

- **PFX203** — a ``PFX_*`` name appears as a string literal in code
  (an ``os.environ`` read, a launcher write, a validator set) but in
  no ``docs/*.md``. Anchored at the first code site.
- **PFX204** — a doc mentions a ``PFX_*`` name no code references:
  stale docs. Anchored at the docs line.

Code side: any string constant that IS a knob name (full match) in
any scanned file — reads through loops like
``for var in ("PFX_CACHE_HOME", ...): os.environ.get(var)`` count,
docstrings never match (a docstring is one big string). Docs side:
exact tokens only — ``PFX_WATCHDOG_*`` style globs are prose
shorthand and satisfy NEITHER direction, so each knob needs its own
documented line (deleting one line always trips PFX203).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Tuple

from ..engine import Finding

CODES = ("PFX203", "PFX204")

_KNOB_RE = re.compile(r"^PFX_[A-Z0-9]+(?:_[A-Z0-9]+)*$")
_DOC_KNOB_RE = re.compile(r"PFX_[A-Z0-9_]+\*?")


def _code_knobs(ctx) -> Dict[str, Tuple[str, int]]:
    out: Dict[str, Tuple[str, int]] = {}
    for sf in ctx.py_files:
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str) and \
                    _KNOB_RE.match(node.value):
                out.setdefault(node.value,
                               (sf.path, node.lineno))
    return out


def _doc_knobs(ctx) -> Dict[str, Tuple[str, int]]:
    out: Dict[str, Tuple[str, int]] = {}
    for doc in ctx.docs:
        for lineno, line in enumerate(doc.lines, 1):
            for tok in _DOC_KNOB_RE.findall(line):
                if tok.endswith("*") or tok.endswith("_"):
                    continue   # glob/prefix shorthand: prose only
                out.setdefault(tok, (doc.path, lineno))
    return out


def check(ctx) -> List[Finding]:
    """Cross-check code knob literals against docs mentions."""
    code = _code_knobs(ctx)
    docs = _doc_knobs(ctx)
    findings: List[Finding] = []
    for knob, (path, line) in sorted(code.items()):
        if knob not in docs:
            findings.append(Finding(
                path, line, "PFX203",
                f"env knob `{knob}` is referenced here but documented "
                f"in no docs/*.md — add it to the knob table "
                f"(docs/observability.md) or docs/quick_start.md",
                key=knob))
    for knob, (path, line) in sorted(docs.items()):
        if knob not in code:
            findings.append(Finding(
                path, line, "PFX204",
                f"docs mention env knob `{knob}` but no code "
                f"references it — stale doc or spelling drift",
                key=knob))
    return findings
