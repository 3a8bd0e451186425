"""Module-level call graph with jit-reachability marking.

The traced-context hazard rules (PFX101-PFX103, ``docs/
static_analysis.md``) need to know which functions can execute under a
JAX trace. This module builds that set statically, in two passes over
the scanned tree's ASTs:

1. **Index** every module: its import aliases (``import jax.numpy as
   jnp``, ``from ..observability import metrics``, relative levels
   resolved against the module's package), every function/method
   definition (nested functions get ``outer.<locals>.inner``
   qualnames), every class with its base list, and every call site
   inside each function with enough syntax kept around to resolve it
   later (dotted path, ``self.`` receiver, bare name).

2. **Resolve and propagate**: call targets are resolved through the
   alias table to either an external dotted name (``jax.jit``) or an
   in-tree function. Functions become *roots* when they are

   - decorated with / passed to a tracing wrapper — ``jax.jit``,
     ``pjit``, ``shard_map``, ``pl.pallas_call`` (the boundary set the
     repo admits SPMD programs through) plus the propagating tracers
     ``vmap`` / ``grad`` / ``value_and_grad`` / ``checkpoint`` /
     ``remat`` / ``lax.{scan,while_loop,fori_loop,cond,switch,map,
     associative_scan}`` — including through ``functools.partial``
     (whose bound argument names are recorded as STATIC params);
   - the ``__call__`` / ``setup`` / ``@nn.compact`` methods of a
     ``flax.linen.Module`` subclass (flax modules in this repo only
     ever run under ``Module.apply`` inside a jitted step);
   - arguments of a ``*.defvjp(fwd, bwd)`` call (custom-VJP halves
     run under the autodiff trace).

   Reachability then spreads breadth-first along resolved call edges
   (bare names in scope, ``self.method`` through in-tree MRO, imported
   names, ``module.attr``), and into functions *defined inside* a
   reachable function (conservative: a nested def is usually a scan
   body or branch closure handed to an unresolvable higher-order
   callee).

For functions rooted DIRECTLY in a tracing wrapper the parameter list
is trustworthy: every param not claimed by ``static_argnames`` /
``static_argnums`` / a ``partial`` binding IS a tracer at run time, so
rules may treat bare comparisons on those names as sound findings, not
heuristics (``FunctionInfo.tracer_params``). For functions reached
only transitively, only parameters with array-ish annotations
(``jax.Array``, ``jnp.ndarray``, ...) are nominated — unannotated
params of helpers are very often static config threaded through, and a
lint that cries wolf gets disabled.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

#: wrappers whose function-valued arguments execute under a trace.
#: Keys are fully-qualified names after alias resolution; ``jit`` and
#: ``pjit`` additionally carry static-arg semantics.
TRACING_WRAPPERS = {
    "jax.jit", "jax.pjit",
    "jax.experimental.pjit.pjit",
    "jax.experimental.shard_map.shard_map",
    "jax.sharding.shard_map",
    "jax.shard_map",
    "jax.experimental.pallas.pallas_call",
    "jax.vmap", "jax.pmap",
    "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat", "jax.ad_checkpoint.checkpoint",
    "jax.custom_vjp", "jax.custom_jvp",
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan",
    "flax.linen.scan", "flax.linen.remat", "flax.linen.jit",
}

#: wrappers with jit-style ``static_argnames`` / ``static_argnums``
_JIT_LIKE = {"jax.jit", "jax.pjit", "jax.experimental.pjit.pjit"}

#: annotations that nominate a parameter as array/tracer-typed
_ARRAY_ANNOTATIONS = {
    "jax.Array", "jax.numpy.ndarray", "jnp.ndarray", "np.ndarray",
    "numpy.ndarray", "Array", "ArrayLike", "jax.typing.ArrayLike",
    "chex.Array",
}

_FLAX_MODULE = {"flax.linen.Module", "flax.linen.nn.Module"}


@dataclasses.dataclass
class CallRef:
    """One call site inside a function, pre-resolution."""

    node: ast.Call
    dotted: Optional[str]       # "a.b.c" when func is a Name/Attribute chain
    is_self: bool               # receiver is ``self`` / ``cls``


@dataclasses.dataclass
class FunctionInfo:
    """One function/method definition and everything rules ask of it."""

    qualname: str               # "pkg.mod:Class.method" / "pkg.mod:f"
    modname: str
    path: str
    node: ast.AST               # FunctionDef / AsyncFunctionDef / Lambda
    class_name: Optional[str]   # enclosing class qualpart, if a method
    params: List[str] = dataclasses.field(default_factory=list)
    annotations: Dict[str, Optional[ast.AST]] = \
        dataclasses.field(default_factory=dict)
    calls: List[CallRef] = dataclasses.field(default_factory=list)
    static_params: Set[str] = dataclasses.field(default_factory=set)
    direct_traced: bool = False     # rooted straight in a wrapper
    traced_via: Optional[str] = None    # human-readable root reason
    jit_reachable: bool = False
    parent: Optional[str] = None    # enclosing function qualname

    @property
    def tracer_params(self) -> Set[str]:
        """Parameter names rules may treat as tracer-typed.

        Sound for direct roots (non-static params of a jitted
        function ARE tracers); annotation-gated for transitive
        reachability (see module docstring).
        """
        skip = {"self", "cls"} | self.static_params
        if self.direct_traced:
            return {p for p in self.params if p not in skip}
        out = set()
        for p in self.params:
            if p in skip:
                continue
            ann = self.annotations.get(p)
            if ann is not None and _mentions_array(ann):
                out.add(p)
        return out


def _mentions_array(ann: ast.AST) -> bool:
    """Whether an annotation AST mentions an array-ish type (walks
    through ``Optional[...]`` / unions / string annotations)."""
    for node in ast.walk(ann):
        name = _dotted_from(node)
        if name and (name in _ARRAY_ANNOTATIONS
                     or name.split(".")[-1] in ("Array", "ndarray")):
            return True
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            if any(tok in node.value for tok in ("Array", "ndarray")):
                return True
    return False


def _dotted_from(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class ModuleIndex(ast.NodeVisitor):
    """Pass 1: per-module symbol/import/function/class tables."""

    def __init__(self, modname: str, path: str, tree: ast.Module):
        self.modname = modname
        self.path = path
        self.tree = tree
        self.aliases: Dict[str, str] = {}   # local name -> dotted target
        self.functions: Dict[str, FunctionInfo] = {}   # qual -> info
        self.classes: Dict[str, List[str]] = {}   # class qual -> base dots
        self._scope: List[str] = []
        self._class: List[str] = []
        self.visit(tree)

    # -- imports -------------------------------------------------------
    def visit_Import(self, node: ast.Import):
        for a in node.names:
            self.aliases[a.asname or a.name.split(".")[0]] = (
                a.name if a.asname else a.name.split(".")[0])

    def visit_ImportFrom(self, node: ast.ImportFrom):
        """Record aliases, resolving relative imports against
        ``self.modname`` so ``from ..observability import metrics``
        lands on its absolute dotted target."""
        if node.level:
            pkg = self.modname.split(".")
            # ``from . import x`` inside pkg.mod: level 1 strips the
            # module leaf; each extra level strips one package
            pkg = pkg[: len(pkg) - node.level]
            base = ".".join(pkg + ([node.module] if node.module else []))
        else:
            base = node.module or ""
        for a in node.names:
            if a.name == "*":
                continue
            target = f"{base}.{a.name}" if base else a.name
            self.aliases[a.asname or a.name] = target

    # -- defs ----------------------------------------------------------
    def _qual(self, name: str) -> str:
        return ".".join(self._scope + [name]) if self._scope else name

    def visit_ClassDef(self, node: ast.ClassDef):
        qual = self._qual(node.name)
        self.classes[qual] = [d for d in
                              (_dotted_from(b) for b in node.bases) if d]
        self._scope.append(node.name)
        self._class.append(qual)
        for stmt in node.body:
            self.visit(stmt)
        self._class.pop()
        self._scope.pop()

    def _visit_fn(self, node):
        qual = self._qual(node.name)
        info = FunctionInfo(
            qualname=f"{self.modname}:{qual}",
            modname=self.modname, path=self.path, node=node,
            class_name=self._class[-1] if self._class else None,
            parent=(f"{self.modname}:{'.'.join(self._scope)}"
                    if self._scope and not self._class else None))
        a = node.args
        for arg in (list(a.posonlyargs) + list(a.args)
                    + list(a.kwonlyargs)):
            info.params.append(arg.arg)
            info.annotations[arg.arg] = arg.annotation
        self.functions[qual] = info
        self._scope.append(node.name + ".<locals>")
        # collect calls lexically inside THIS function, not nested defs
        for stmt in node.body:
            self._collect_calls(stmt, info)
        for stmt in node.body:
            self.visit(stmt)
        self._scope.pop()

    def visit_FunctionDef(self, node):        # noqa: D102 (visitor)
        self._visit_fn(node)

    def visit_AsyncFunctionDef(self, node):   # noqa: D102 (visitor)
        self._visit_fn(node)

    def _collect_calls(self, stmt: ast.AST, info: FunctionInfo):
        for node in ast.walk(stmt):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)) and node is not stmt:
                continue   # nested defs walked separately (note: walk
                # still descends — filtered at use via lineno ownership;
                # call OWNERSHIP only matters for edges, which are
                # conservative, so double-attribution is harmless)
            if isinstance(node, ast.Call):
                dotted = _dotted_from(node.func)
                is_self = bool(dotted) and \
                    dotted.split(".")[0] in ("self", "cls")
                info.calls.append(CallRef(node, dotted, is_self))


class CallGraph:
    """The resolved, reachability-marked graph over scanned modules."""

    def __init__(self, modules: Dict[str, ModuleIndex]):
        self.modules = modules
        #: qualname ("mod:qual") -> FunctionInfo
        self.functions: Dict[str, FunctionInfo] = {}
        for m in modules.values():
            for qual, info in m.functions.items():
                self.functions[info.qualname] = info
        self._flax_classes = self._find_flax_classes()
        self._mark_roots()
        self._propagate()

    # -- resolution helpers -------------------------------------------
    def resolve_dotted(self, mod: ModuleIndex, dotted: str) -> str:
        """Resolve a local dotted name to a global one via the module's
        alias table (``fa.flash_decode`` ->
        ``paddlefleetx_tpu.ops.pallas.flash_attention.flash_decode``).
        """
        head, _, rest = dotted.partition(".")
        target = mod.aliases.get(head)
        if target is None:
            return dotted
        return f"{target}.{rest}" if rest else target

    def _function_for_global(self, gdot: str) -> Optional[FunctionInfo]:
        """Global dotted name -> in-tree FunctionInfo, if any."""
        # exact module:attr split, longest module prefix first
        parts = gdot.split(".")
        for cut in range(len(parts) - 1, 0, -1):
            modname = ".".join(parts[:cut])
            m = self.modules.get(modname)
            if m is not None:
                qual = ".".join(parts[cut:])
                info = m.functions.get(qual)
                if info is not None:
                    return info
                # classname -> its __call__ won't be a call target here
                return None
        return None

    def _find_flax_classes(self) -> Set[str]:
        """Fixpoint of in-tree ``flax.linen.Module`` subclasses, as
        ``modname:ClassQual`` keys."""
        flax: Set[str] = set()
        changed = True
        while changed:
            changed = False
            for m in self.modules.values():
                for cqual, bases in m.classes.items():
                    key = f"{m.modname}:{cqual}"
                    if key in flax:
                        continue
                    for b in bases:
                        gdot = self.resolve_dotted(m, b)
                        if gdot in _FLAX_MODULE or \
                                self._class_key(m, gdot) in flax:
                            flax.add(key)
                            changed = True
                            break
        return flax

    def _class_key(self, mod: ModuleIndex, gdot: str) -> Optional[str]:
        """Global dotted name -> in-tree ``modname:ClassQual`` key."""
        parts = gdot.split(".")
        for cut in range(len(parts), 0, -1):
            modname = ".".join(parts[:cut])
            m = self.modules.get(modname)
            if m is not None:
                qual = ".".join(parts[cut:])
                if qual in m.classes:
                    return f"{modname}:{qual}"
                return None
        # bare name in the same module
        if gdot in mod.classes:
            return f"{mod.modname}:{gdot}"
        return None

    # -- root marking --------------------------------------------------
    def _mark_root(self, info: FunctionInfo, reason: str,
                   static: Set[str] = frozenset()):
        info.direct_traced = True
        info.static_params |= set(static)
        if not info.traced_via:
            info.traced_via = reason

    def _unwrap_partial(self, mod: ModuleIndex, node: ast.AST
                        ) -> Tuple[Optional[ast.AST], Set[str]]:
        """``partial(f, a, k=v)`` -> (f-node, static names bound)."""
        if not isinstance(node, ast.Call):
            return node, set()
        dotted = _dotted_from(node.func)
        if dotted is None:
            return node, set()
        gdot = self.resolve_dotted(mod, dotted)
        if gdot not in ("functools.partial", "partial"):
            return node, set()
        if not node.args:
            return None, set()
        inner = node.args[0]
        static = {kw.arg for kw in node.keywords if kw.arg}
        # positional partial bindings claim leading params — resolved
        # by the caller once the target's param list is known
        n_pos = len(node.args) - 1
        static.add(f"<pos:{n_pos}>")
        return inner, static

    def _static_from_jit_kwargs(self, call: ast.Call,
                                target: FunctionInfo) -> Set[str]:
        """``static_argnames`` / ``static_argnums`` keyword payloads."""
        static: Set[str] = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and \
                            isinstance(c.value, str):
                        static.add(c.value)
            elif kw.arg == "static_argnums":
                for c in ast.walk(kw.value):
                    if isinstance(c, ast.Constant) and \
                            isinstance(c.value, int):
                        params = [p for p in target.params
                                  if p not in ("self", "cls")]
                        if 0 <= c.value < len(params):
                            static.add(params[c.value])
        return static

    def _resolve_fn_arg(self, mod: ModuleIndex,
                        owner: Optional[FunctionInfo],
                        node: ast.AST) -> Optional[FunctionInfo]:
        """An argument expression -> the FunctionInfo it names."""
        dotted = _dotted_from(node)
        if dotted is None:
            return None
        head = dotted.split(".")[0]
        if head in ("self", "cls") and owner and owner.class_name:
            meth = dotted.split(".", 1)[1] if "." in dotted else None
            if meth:
                return self._method_on(mod, owner.class_name, meth)
            return None
        # bare name: sibling nested function of the owner first
        if "." not in dotted and owner is not None:
            base = owner.qualname.split(":", 1)[1]
            sib = f"{base}.<locals>.{dotted}"
            hit = mod.functions.get(sib)
            if hit is not None:
                return hit
        gdot = self.resolve_dotted(mod, dotted)
        hit = self._function_for_global(gdot)
        if hit is not None:
            return hit
        # bare (or Class.method) name defined in this same module
        return mod.functions.get(dotted)

    def _method_on(self, mod: ModuleIndex, class_qual: str,
                   meth: str) -> Optional[FunctionInfo]:
        """Look up a method through the in-tree single-module MRO."""
        seen = set()
        stack = [(mod, class_qual)]
        while stack:
            m, cq = stack.pop()
            if (m.modname, cq) in seen:
                continue
            seen.add((m.modname, cq))
            info = m.functions.get(f"{cq}.{meth}")
            if info is not None:
                return info
            for b in m.classes.get(cq, []):
                key = self._class_key(m, self.resolve_dotted(m, b))
                if key:
                    bmod, bqual = key.split(":", 1)
                    stack.append((self.modules[bmod], bqual))
        return None

    def _apply_partial_positional(self, info: FunctionInfo,
                                  static: Set[str]):
        """Translate ``<pos:N>`` partial markers into leading param
        names."""
        markers = {s for s in static if s.startswith("<pos:")}
        names = static - markers
        n = sum(int(s[5:-1]) for s in markers)
        params = [p for p in info.params if p not in ("self", "cls")]
        names |= set(params[:n])
        return names

    def _mark_roots(self):
        for mod in self.modules.values():
            # decorators
            for qual, info in mod.functions.items():
                for deco in getattr(info.node, "decorator_list", []):
                    target, static = self._unwrap_partial(mod, deco)
                    if target is None:
                        continue
                    dotted = _dotted_from(
                        target.func if isinstance(target, ast.Call)
                        else target)
                    if dotted is None:
                        continue
                    gdot = self.resolve_dotted(mod, dotted)
                    if gdot in TRACING_WRAPPERS:
                        if isinstance(target, ast.Call) and \
                                gdot in _JIT_LIKE:
                            static |= self._static_from_jit_kwargs(
                                target, info)
                        if isinstance(deco, ast.Call) and \
                                gdot in _JIT_LIKE:
                            static |= self._static_from_jit_kwargs(
                                deco, info)
                        static = self._apply_partial_positional(
                            info, static)
                        self._mark_root(
                            info, f"decorated @{gdot}", static)
                    elif gdot in ("flax.linen.compact", "nn.compact"):
                        self._mark_root(info, "flax @nn.compact")
                # flax module methods
                if info.class_name and \
                        f"{mod.modname}:{info.class_name}" in \
                        self._flax_classes and \
                        info.node.name in ("__call__", "setup"):
                    self._mark_root(
                        info,
                        f"flax Module method {info.class_name}."
                        f"{info.node.name}")
            # call-site wrapping: jax.jit(fn, ...), shard_map(fn, ...),
            # pl.pallas_call(kernel, ...), lax.scan(body, ...),
            # f.defvjp(fwd, bwd) — anywhere in the module
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                dotted = _dotted_from(node.func)
                if dotted is None:
                    continue
                if dotted.endswith(".defvjp") or \
                        dotted.endswith(".defjvp"):
                    owner = self._owner_of(mod, node)
                    for arg in node.args:
                        hit = self._resolve_fn_arg(mod, owner, arg)
                        if hit is not None:
                            self._mark_root(hit, "custom-VJP half")
                    continue
                gdot = self.resolve_dotted(mod, dotted)
                if gdot not in TRACING_WRAPPERS:
                    continue
                owner = self._owner_of(mod, node)
                for arg in node.args:
                    target, static = self._unwrap_partial(mod, arg)
                    if target is None:
                        continue
                    hit = self._resolve_fn_arg(mod, owner, target)
                    if hit is None:
                        continue
                    if gdot in _JIT_LIKE:
                        static |= self._static_from_jit_kwargs(node, hit)
                    static = self._apply_partial_positional(hit, static)
                    self._mark_root(
                        hit, f"passed to {gdot}", static)

    def _owner_of(self, mod: ModuleIndex,
                  call: ast.Call) -> Optional[FunctionInfo]:
        """The innermost function whose span contains the call."""
        best = None
        for info in mod.functions.values():
            node = info.node
            if node.lineno <= call.lineno <= \
                    (node.end_lineno or node.lineno):
                if best is None or node.lineno > best.node.lineno:
                    best = info
        return best

    # -- propagation ---------------------------------------------------
    def _propagate(self):
        queue = [f for f in self.functions.values() if f.direct_traced]
        for f in queue:
            f.jit_reachable = True
        while queue:
            fn = queue.pop()
            mod = self.modules[fn.modname]
            targets: List[FunctionInfo] = []
            for ref in fn.calls:
                if ref.dotted is None:
                    continue
                hit = self._resolve_fn_arg(mod, fn, ref.dotted and
                                           ref.node.func)
                if hit is not None:
                    targets.append(hit)
            # nested defs of a traced function are conservatively
            # traced too (scan bodies, cond branches)
            base = fn.qualname.split(":", 1)[1] + ".<locals>."
            for qual, info in mod.functions.items():
                if info.qualname.split(":", 1)[1].startswith(base) and \
                        "." not in info.qualname.split(":", 1)[1][
                            len(base):]:
                    targets.append(info)
            for t in targets:
                if not t.jit_reachable:
                    t.jit_reachable = True
                    if not t.traced_via:
                        t.traced_via = f"called from {fn.qualname}"
                    queue.append(t)

    # -- public lookups ------------------------------------------------
    def reachable_functions(self) -> List[FunctionInfo]:
        return [f for f in self.functions.values() if f.jit_reachable]

    def module(self, modname: str) -> Optional[ModuleIndex]:
        return self.modules.get(modname)


def modname_for(relpath: str) -> str:
    """Repo-relative path -> dotted module name (``chip_smoke.py`` ->
    ``chip_smoke``; package ``__init__.py`` -> the package)."""
    p = relpath[:-3] if relpath.endswith(".py") else relpath
    parts = [seg for seg in p.replace("\\", "/").split("/") if seg]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def build(files: Dict[str, ast.Module]) -> CallGraph:
    """Build the graph from ``{relpath: parsed AST}``."""
    modules = {}
    for relpath, tree in files.items():
        modname = modname_for(relpath)
        modules[modname] = ModuleIndex(modname, relpath, tree)
    return CallGraph(modules)
