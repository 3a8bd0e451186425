"""PFX201/PFX202 — dispatch-counter names vs the docs matrices.

The repo's observability contract (PR 3 onward): every trace-time
dispatch counter, gauge or timer registered from ``paddlefleetx_tpu/``
appears — by exact name — in a docs matrix (`docs/attention_dispatch
.md`, `docs/moe.md`, `docs/inference.md`, `docs/tensor_parallel.md`,
`docs/observability.md`), and every name the docs promise exists in
code. Review kept this honest for five PRs; this rule makes it
mechanical in both directions:

- **PFX201** — a series name ``inc``'d / ``set_gauge``'d /
  ``timer``'d / ``add_time``'d / ``observe``'d in code — or a SPAN
  name opened via ``start_trace`` / ``start_span`` / ``span_point`` /
  ``complete_span`` / ``annotate`` — but absent from every docs file. Anchored at
  the first code site.
- **PFX202** — a docs-promised name (in a namespace code actually
  uses) with no code site: stale docs. Anchored at the docs line.

Name extraction understands the in-tree idioms: plain string
constants, the two-way ``IfExp`` dispatch
(``"a/x" if flag else "a/y"``), and prefix concatenation
(``inc("moe/config/" + mode)`` — recorded as a ``moe/config/*``
wildcard satisfied by any documented name under the prefix). Docs
names use the matrices' ``ns/{a,b,c}`` brace shorthand (expanded) —
glob rows like ``serving/*`` are prose cross-references and count for
NEITHER direction, so deleting a concrete docs row always trips
PFX201 regardless of a surviving glob mention. ``timer(X)`` also
registers the implicit ``X/calls`` series; those are docs-optional
but resolve a documented ``X/calls`` row.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Tuple

from ..engine import Finding

CODES = ("PFX201", "PFX202")

#: code files whose registrations feed the contract
_CODE_PREFIX = "paddlefleetx_tpu/"
#: the registry/tracer implementations themselves register nothing
_EXEMPT_FILES = {"paddlefleetx_tpu/observability/metrics.py",
                 "paddlefleetx_tpu/observability/spans.py"}

#: histogram observe() joined in PR 10 — same exact-name contract
_REGISTER_ATTRS = {"inc", "set_gauge", "add_time", "timer", "observe"}
#: span-name call sites (observability/spans.py) hold the same
#: docs contract: every span/trace/point name is a docs matrix row;
#: `_phase` is the serving loop's phase-transition wrapper (its name
#: argument is positional arg 1, so span attrs scan EVERY positional
#: arg, not just the first); `annotate` is the host-phase primitive
#: (observability/trace.py), whose slash-path names are rows of the
#: "Host phases" table
_SPAN_ATTRS = {"start_trace", "start_span", "span_point",
               "complete_span", "_phase", "annotate"}
_NAME_RE = re.compile(r"^[a-z0-9_]+(/[a-z0-9_]+)+$")
_PREFIX_RE = re.compile(r"^[a-z0-9_]+(/[a-z0-9_]+)*/$")
_BACKTICK_RE = re.compile(r"`([^`]+)`")
_DOC_TOKEN_RE = re.compile(r"^[a-z0-9_]+(/[a-z0-9_{},*]+)+$")


def _expand_braces(token: str) -> List[str]:
    """``a/{x,y}/b`` -> ``["a/x/b", "a/y/b"]`` (recursive)."""
    m = re.search(r"\{([^{}]*)\}", token)
    if not m:
        return [token]
    out: List[str] = []
    for alt in m.group(1).split(","):
        out.extend(_expand_braces(
            token[:m.start()] + alt + token[m.end():]))
    return out


def _code_registrations(ctx) -> Tuple[
        Dict[str, Tuple[str, int]], Dict[str, Tuple[str, int]],
        Dict[str, Tuple[str, int]]]:
    """Scan the package for series registrations.

    Returns:
        ``(exact, prefixes, synthetic)`` dicts of name -> first
        ``(path, line)`` site; ``synthetic`` holds the implicit
        ``<timer>/calls`` names (docs-optional).
    """
    exact: Dict[str, Tuple[str, int]] = {}
    prefixes: Dict[str, Tuple[str, int]] = {}
    synthetic: Dict[str, Tuple[str, int]] = {}

    def record(table, name, sf, node):
        table.setdefault(name, (sf.path, node.lineno))

    for sf in ctx.py_files:
        if not sf.path.startswith(_CODE_PREFIX) or \
                sf.path in _EXEMPT_FILES:
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            func = node.func
            attr = func.attr if isinstance(func, ast.Attribute) \
                else (func.id if isinstance(func, ast.Name) else None)
            if attr not in _REGISTER_ATTRS and \
                    attr not in _SPAN_ATTRS:
                continue
            scan = node.args if attr in _SPAN_ATTRS \
                else node.args[:1]
            for arg in scan:
                for c in ast.walk(arg):
                    if not (isinstance(c, ast.Constant)
                            and isinstance(c.value, str)):
                        continue
                    if _NAME_RE.match(c.value):
                        record(exact, c.value, sf, node)
                        if attr == "timer":
                            record(synthetic, c.value + "/calls",
                                   sf, node)
                    elif _PREFIX_RE.match(c.value) \
                            and "/" in c.value[:-1]:
                        record(prefixes, c.value, sf, node)
    return exact, prefixes, synthetic


def _doc_names(ctx) -> Dict[str, Tuple[str, int]]:
    """Exact (brace-expanded, non-glob) series names promised by the
    docs, name -> first ``(path, line)``."""
    out: Dict[str, Tuple[str, int]] = {}
    for doc in ctx.docs:
        for lineno, line in enumerate(doc.lines, 1):
            for tok in _BACKTICK_RE.findall(line):
                if not _DOC_TOKEN_RE.match(tok):
                    continue
                if "*" in tok:
                    continue   # glob: prose cross-reference only
                for name in _expand_braces(tok):
                    if _NAME_RE.match(name):
                        out.setdefault(name, (doc.path, lineno))
    return out


def check(ctx) -> List[Finding]:
    """Cross-check code registrations against the docs matrices."""
    exact, prefixes, synthetic = _code_registrations(ctx)
    documented = _doc_names(ctx)
    findings: List[Finding] = []

    # PFX201: code name with no docs row
    for name, (path, line) in sorted(exact.items()):
        if name not in documented:
            findings.append(Finding(
                path, line, "PFX201",
                f"telemetry series `{name}` is registered here but "
                f"appears in no docs matrix (docs/*.md) — add a row "
                f"or rename to a documented series",
                key=name))
    for prefix, (path, line) in sorted(prefixes.items()):
        if not any(d.startswith(prefix) for d in documented):
            findings.append(Finding(
                path, line, "PFX201",
                f"telemetry prefix `{prefix}*` is registered here "
                f"but no documented series falls under it",
                key=prefix + "*"))

    # PFX202: docs row with no code site, within code's namespaces
    namespaces = {n.split("/", 1)[0] for n in exact} | \
        {p.split("/", 1)[0] for p in prefixes}
    known = set(exact) | set(synthetic)
    for name, (path, line) in sorted(documented.items()):
        if name.split("/", 1)[0] not in namespaces:
            continue
        if name in known:
            continue
        if any(name.startswith(p) for p in prefixes):
            continue
        findings.append(Finding(
            path, line, "PFX202",
            f"docs promise telemetry series `{name}` but no code in "
            f"paddlefleetx_tpu/ registers it — stale row or spelling "
            f"drift",
            key=name))
    return findings
