"""PFX104 — use-after-donation of a jit argument buffer.

``jax.jit(f, donate_argnums=(0,))`` tells XLA it may reuse the
argument's device buffer for the outputs. Reading that Python
reference AFTER the call touches a deleted buffer and raises (or, on
some backends, silently reads garbage). The safe idiom rebinds the
donated reference from the call's own result::

    state, metrics = self._train_step(state, batch)   # fine
    loss = self._train_step(state, batch)             # state donated
    print(state.step)                                 # PFX104

Detection: every ``jax.jit(fn, donate_argnums=...)`` /
``donate_argnames=...`` wrapping is recorded against wherever the
wrapped callable is stored (``self._train_step``, a module global, a
local) or against the decorated function itself. At each call site
the donated positions map to the argument expressions; a donated
``name`` / ``self.attr`` argument read later in the SAME function
body — with no rebind in between — is flagged. A rebind on the
statement that makes the call (tuple targets included) counts as at
the call line. A donor imported by name from another in-tree module
(``from ..generation import decode_step``) is followed to its
definition.

Known-unsound: reads that lexically precede the call but execute
after it on a loop back-edge are missed (the analysis is
line-ordered); donated buffers escaping through other aliases are
missed. Both are documented in docs/static_analysis.md.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from ..callgraph import _dotted_from
from ..engine import Finding
from . import own_nodes

CODES = ("PFX104",)

_JIT_NAMES = {"jax.jit", "jax.pjit", "jax.experimental.pjit.pjit"}


def _expr_token(expr: ast.AST) -> Optional[str]:
    """A stable token for a donatable reference: bare name or a
    ``self.attr`` chain."""
    d = _dotted_from(expr)
    if d is None:
        return None
    parts = d.split(".")
    if len(parts) == 1 or parts[0] in ("self", "cls"):
        return d
    return None


def _donations_from_call(call: ast.Call) -> Tuple[Set[int], Set[str]]:
    """(donated positions, donated names) from jit kwargs."""
    nums: Set[int] = set()
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and \
                        isinstance(c.value, int):
                    nums.add(c.value)
        elif kw.arg == "donate_argnames":
            for c in ast.walk(kw.value):
                if isinstance(c, ast.Constant) and \
                        isinstance(c.value, str):
                    names.add(c.value)
    return nums, names


def _jit_donation(ctx, fn, value: ast.AST
                  ) -> Optional[Tuple[Set[int], Set[str],
                                      Optional[str]]]:
    """``jax.jit(inner, donate_*=...)`` -> (nums, names, inner
    qualname or None)."""
    if not isinstance(value, ast.Call):
        return None
    dotted = _dotted_from(value.func)
    if dotted is None:
        return None
    mod = ctx.callgraph.modules.get(fn.modname) if fn else None
    gdot = ctx.callgraph.resolve_dotted(mod, dotted) if mod else dotted
    if gdot not in _JIT_NAMES:
        return None
    nums, names = _donations_from_call(value)
    if not nums and not names:
        return None
    inner = None
    if value.args:
        hit = ctx.callgraph._resolve_fn_arg(mod, fn, value.args[0])
        if hit is not None:
            inner = hit.qualname
    return nums, names, inner


def _positions_for(ctx, inner_qual: Optional[str], nums: Set[int],
                   names: Set[str]) -> Tuple[Set[int], Set[str]]:
    """Fold donate_argnames into positions via the wrapped function's
    param list when it resolved."""
    if not names or inner_qual is None:
        return nums, names
    info = ctx.callgraph.functions.get(inner_qual)
    if info is None:
        return nums, names
    params = [p for p in info.params if p not in ("self", "cls")]
    out = set(nums)
    left = set(names)
    for n in list(left):
        if n in params:
            out.add(params.index(n))
            left.discard(n)
    return out, left


def _collect_donors(ctx) -> Dict[Tuple[str, str],
                                 Tuple[Set[int], Set[str]]]:
    """(function qualname, callee token) -> donated (positions,
    keyword names). The token is how call sites name the donor:
    ``self._train_step``, a bare local name, or a module global."""
    donors: Dict[Tuple[str, str], Tuple[Set[int], Set[str]]] = {}
    cg = ctx.callgraph
    for fq, fn in cg.functions.items():
        for node in own_nodes(fn.node):
            if not isinstance(node, ast.Assign):
                continue
            got = _jit_donation(ctx, fn, node.value)
            if got is None:
                continue
            nums, names, inner = got
            nums, names = _positions_for(ctx, inner, nums, names)
            for tgt in node.targets:
                tok = _expr_token(tgt)
                if tok is None:
                    continue
                if tok.startswith("self.") or tok.startswith("cls."):
                    # methods of the same class call it as self.X
                    scope = fn.class_name or ""
                    donors[(f"{fn.modname}|{scope}", tok)] = \
                        (nums, names)
                else:
                    donors[(fq, tok)] = (nums, names)
                    donors[(f"{fn.modname}|", tok)] = (nums, names)
    # decorated form: @partial(jax.jit, donate_argnums=...) etc. is
    # rooted by callgraph already; here handle the direct decorator
    for fq, fn in cg.functions.items():
        for deco in getattr(fn.node, "decorator_list", []):
            if isinstance(deco, ast.Call):
                got = _jit_donation(ctx, fn, deco)
                if got is None:
                    # @partial(jax.jit, donate_argnums=...)
                    got = _partial_jit_donation(ctx, fn, deco)
                if got is None:
                    continue
                nums, names, _ = got
                params = [p for p in fn.params
                          if p not in ("self", "cls")]
                pos = set(nums)
                for n in names:
                    if n in params:
                        pos.add(params.index(n))
                donors[(f"{fn.modname}|", fn.node.name)] = (pos, names)
                if fn.class_name:
                    donors[(f"{fn.modname}|{fn.class_name}",
                            f"self.{fn.node.name}")] = (pos, names)
    return donors


def _partial_jit_donation(ctx, fn, deco: ast.Call):
    """``@functools.partial(jax.jit, donate_argnums=...)``."""
    dotted = _dotted_from(deco.func)
    mod = ctx.callgraph.modules.get(fn.modname)
    if dotted is None or mod is None:
        return None
    if ctx.callgraph.resolve_dotted(mod, dotted) not in (
            "functools.partial", "partial"):
        return None
    if not deco.args:
        return None
    inner_dot = _dotted_from(deco.args[0])
    if inner_dot is None or \
            ctx.callgraph.resolve_dotted(mod, inner_dot) not in \
            _JIT_NAMES:
        return None
    nums, names = _donations_from_call(deco)
    if not nums and not names:
        return None
    return nums, names, None


def _rebind_lines(fn, token: str) -> List[int]:
    """Lines where ``token`` is (re)assigned inside the function."""
    out = []
    for node in own_nodes(fn.node):
        tgts = []
        if isinstance(node, ast.Assign):
            tgts = node.targets
        elif isinstance(node, (ast.AugAssign, ast.AnnAssign,
                               ast.NamedExpr)):
            tgts = [node.target]
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            tgts = [node.target]
        for t in tgts:
            for part in ast.walk(t):
                if _expr_token(part) == token:
                    out.append(node.lineno)
    return out


def _read_lines(fn, token: str) -> List[int]:
    """Lines where ``token`` is READ inside the function."""
    out = []
    for node in own_nodes(fn.node):
        if isinstance(node, (ast.Name, ast.Attribute)) and \
                isinstance(getattr(node, "ctx", None), ast.Load) and \
                _expr_token(node) == token:
            out.append(node.lineno)
    return out


def check(ctx) -> List[Finding]:
    """PFX104 at every call site of a donating jit wrapper.

    Args:
        ctx: the lint context (call graph already built).

    Returns:
        One finding per donated argument still read after the call.
    """
    donors = _collect_donors(ctx)
    if not donors:
        return []
    findings: List[Finding] = []
    cg = ctx.callgraph
    for fq, fn in cg.functions.items():
        scope_keys = [fq, f"{fn.modname}|",
                      f"{fn.modname}|{fn.class_name or ''}"]
        for node in own_nodes(fn.node):
            if not isinstance(node, ast.Call):
                continue
            tok = _expr_token(node.func)
            if tok is None:
                continue
            spec = None
            for sk in scope_keys:
                spec = donors.get((sk, tok))
                if spec is not None:
                    break
            if spec is None:
                # a donor imported from another in-tree module
                # (``from ..generation import decode_step``)
                mod = cg.modules.get(fn.modname)
                gmod, _, gname = (cg.resolve_dotted(mod, tok)
                                  if mod else tok).rpartition(".")
                spec = donors.get((f"{gmod}|", gname))
            if spec is None:
                continue
            nums, kwnames = spec
            donated_exprs: List[ast.AST] = []
            for i, arg in enumerate(node.args):
                if i in nums:
                    donated_exprs.append(arg)
            for kw in node.keywords:
                if kw.arg and kw.arg in kwnames:
                    donated_exprs.append(kw.value)
            for arg in donated_exprs:
                atok = _expr_token(arg)
                if atok is None:
                    continue
                call_line = node.lineno
                end_line = node.end_lineno or call_line
                rebinds = sorted(
                    ln for ln in _rebind_lines(fn, atok)
                    if ln >= call_line)
                next_rebind = rebinds[0] if rebinds else None
                for rl in _read_lines(fn, atok):
                    if rl <= end_line:
                        continue
                    if next_rebind is not None and rl > next_rebind:
                        continue
                    if next_rebind is not None and \
                            next_rebind <= call_line and \
                            next_rebind <= end_line:
                        break   # rebound by the call statement itself
                    findings.append(Finding(
                        path=fn.path, line=rl, code="PFX104",
                        message=(
                            f"`{atok}` was donated to `{tok}` at "
                            f"line {call_line} (donate_argnums) — "
                            f"its device buffer may already be "
                            f"reused; rebind it from the call's "
                            f"result before reading it"),
                        key=f"{fq}:{atok}->{tok}"))
                    break   # one finding per donated arg per call
    return findings
