"""Per-module call graph with jit- and kernel-reachability.

``replint`` rules need to know, for every function in a module, whether it
can run *inside a trace* -- under ``jax.jit``, as a ``lax.while_loop`` /
``lax.scan`` / ``lax.cond`` body, or as a Pallas kernel.  A host-sync that is
harmless in driver code silently de-optimizes (or raises) on the hot path,
so the trace-safety rules only fire on reachable functions.

The graph is deliberately *per module* (one file at a time): cross-module
calls are not resolved.  Functions that are traced entry points for *other*
modules (e.g. ``repro.models.lm.prefill``, jitted by the serving engine) are
annotated at the definition site with a ``# replint: traced`` comment on the
``def`` line or the line above, which makes them roots here.

Root discovery:

* decorators: ``@jax.jit``, ``@jit``, ``@partial(jax.jit, ...)``,
  ``@jax.checkpoint``, ``@jax.vmap`` ... (``TRACE_WRAPPERS``);
* call sites: ``jax.jit(f)``, ``jax.vmap(f)``, ``lax.while_loop(cond, body,
  ...)``, ``lax.scan(f, ...)``, ``lax.cond(p, t, f, ...)``,
  ``lax.fori_loop(lo, hi, body, ...)``, ``lax.switch(i, [f, g])``,
  ``lax.map(f, ...)`` -- positional function operands become roots;
* ``pl.pallas_call(kernel, ...)`` -- ``kernel`` becomes a *kernel* root
  (kernel-reachable implies jit-reachable);
* ``# replint: traced`` markers.

Propagation: inside a reachable function, every reference (call or bare
name) that resolves to a module-level function, an enclosing function's
nested def, a ``self.``/``cls.`` method of the enclosing class, a local
alias (``g = f`` or ``g = functools.partial(f, ...)``), or a lambda literal
marks that function reachable too.  Nested defs of a reachable function are
reachable (they execute in-trace).
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field

#: wrappers whose (first) functional argument runs traced
TRACE_WRAPPERS = {
    "jax.jit", "jax.vmap", "jax.pmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat", "jax.eval_shape", "jax.linearize",
    "jax.vjp", "jax.jvp",
    "jax.shard_map",
}

#: control-flow primitives: which positional args are traced bodies
TRACE_BODY_ARGS = {
    "jax.lax.while_loop": (0, 1),
    "jax.lax.scan": (0,),
    "jax.lax.cond": (1, 2),
    "jax.lax.fori_loop": (2,),
    "jax.lax.map": (0,),
    "jax.lax.associative_scan": (0,),
    "jax.lax.custom_root": (0, 1, 2),
}

#: lax.switch(index, branches, *operands): every element of ``branches``
TRACE_BRANCHLIST_ARGS = {"jax.lax.switch": 1}

PALLAS_CALL = ("jax.experimental.pallas.pallas_call",)

PARTIAL = {"functools.partial", "partial"}

FuncNode = ast.FunctionDef | ast.AsyncFunctionDef | ast.Lambda


@dataclass
class FunctionInfo:
    node: FuncNode
    name: str
    qualname: str
    parent: "FunctionInfo | None" = None   # enclosing function, if nested
    class_name: str | None = None          # owning class, if a method
    jit_reachable: bool = False
    kernel_reachable: bool = False
    is_root: bool = False                  # explicitly rooted (not inherited)


@dataclass
class ModuleGraph:
    functions: dict[int, FunctionInfo] = field(default_factory=dict)
    module_funcs: dict[str, FunctionInfo] = field(default_factory=dict)
    classes: dict[str, dict[str, FunctionInfo]] = field(default_factory=dict)
    #: (outer_call, inner pallas_call Call, kernel FunctionInfo|None,
    #:  enclosing FunctionInfo|None).  ``outer_call`` is the
    #: ``pl.pallas_call(...)(*operands)`` application when present.
    pallas_sites: list[tuple] = field(default_factory=list)

    def info(self, node: FuncNode) -> FunctionInfo | None:
        return self.functions.get(id(node))

    def jit_reachable_functions(self) -> list[FunctionInfo]:
        return [f for f in self.functions.values() if f.jit_reachable]

    def kernel_functions(self) -> list[FunctionInfo]:
        return [f for f in self.functions.values() if f.kernel_reachable]


def dotted_name(node: ast.expr, imports: dict[str, str]) -> str | None:
    """Canonical dotted name of an expression, resolving import aliases.

    ``np.asarray`` -> ``numpy.asarray`` under ``import numpy as np``;
    ``pl.ds`` -> ``jax.experimental.pallas.ds``.  Returns None for anything
    that is not a plain dotted chain.
    """
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    root = imports.get(node.id, node.id)
    parts.append(root)
    return ".".join(reversed(parts))


def build_imports(tree: ast.Module) -> dict[str, str]:
    """Local name -> canonical dotted module/object path."""
    table: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                table[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                table[a.asname or a.name] = f"{node.module}.{a.name}"
    # normalize the common jax shorthands so rules can match one spelling
    for local, target in list(table.items()):
        if target == "jax.numpy":
            table[local] = "jax.numpy"
    return table


class _Collector(ast.NodeVisitor):
    """First pass: record every function/lambda with its scope context."""

    def __init__(self, graph: ModuleGraph):
        self.graph = graph
        self.func_stack: list[FunctionInfo] = []
        self.class_stack: list[str] = []

    def _add(self, node: FuncNode, name: str) -> FunctionInfo:
        parent = self.func_stack[-1] if self.func_stack else None
        cls = self.class_stack[-1] if self.class_stack and parent is None else (
            self.class_stack[-1] if self.class_stack else None)
        qual = ".".join(
            ([parent.qualname] if parent else [])
            + ([cls] if cls and not parent else []) + [name])
        info = FunctionInfo(node=node, name=name, qualname=qual,
                            parent=parent, class_name=cls)
        self.graph.functions[id(node)] = info
        if parent is None and not self.class_stack:
            self.graph.module_funcs[name] = info
        if self.class_stack and parent is None:
            self.graph.classes.setdefault(self.class_stack[-1], {})[name] = info
        return info

    def visit_ClassDef(self, node: ast.ClassDef):
        self.class_stack.append(node.name)
        self.generic_visit(node)
        self.class_stack.pop()

    def _visit_func(self, node, name):
        info = self._add(node, name)
        self.func_stack.append(info)
        self.generic_visit(node)
        self.func_stack.pop()

    def visit_FunctionDef(self, node):
        self._visit_func(node, node.name)

    def visit_AsyncFunctionDef(self, node):
        self._visit_func(node, node.name)

    def visit_Lambda(self, node):
        self._visit_func(node, "<lambda>")


def _scope_chain(info: FunctionInfo | None) -> list[FunctionInfo]:
    out = []
    while info is not None:
        out.append(info)
        info = info.parent
    return out


class _Resolver:
    """Resolve a reference expression to a FunctionInfo, if possible."""

    def __init__(self, graph: ModuleGraph, imports: dict[str, str],
                 aliases: dict[int, dict[str, FunctionInfo]]):
        self.graph = graph
        self.imports = imports
        self.aliases = aliases  # per-function-id local name -> FunctionInfo

    def resolve(self, expr: ast.expr,
                scope: FunctionInfo | None) -> FunctionInfo | None:
        if isinstance(expr, ast.Lambda):
            return self.graph.info(expr)
        if isinstance(expr, ast.Call):
            fn = dotted_name(expr.func, self.imports)
            if fn in PARTIAL and expr.args:
                return self.resolve(expr.args[0], scope)
            if fn in TRACE_WRAPPERS and expr.args:
                return self.resolve(expr.args[0], scope)
            return None
        if isinstance(expr, ast.Name):
            for s in _scope_chain(scope):
                local = self.aliases.get(id(s.node), {})
                if expr.id in local:
                    return local[expr.id]
                # nested defs of an enclosing function
                for stmt in ast.walk(s.node):
                    if (isinstance(stmt, (ast.FunctionDef,
                                          ast.AsyncFunctionDef))
                            and stmt.name == expr.id):
                        info = self.graph.info(stmt)
                        if info is not None and info.parent is s:
                            return info
            return self.graph.module_funcs.get(expr.id)
        if isinstance(expr, ast.Attribute):
            # self.method / cls.method within the enclosing class
            if (isinstance(expr.value, ast.Name)
                    and expr.value.id in ("self", "cls")):
                for s in _scope_chain(scope):
                    if s.class_name:
                        meth = self.graph.classes.get(s.class_name, {})
                        if expr.attr in meth:
                            return meth[expr.attr]
        return None


def _collect_aliases(graph: ModuleGraph, imports: dict[str, str]
                     ) -> dict[int, dict[str, FunctionInfo]]:
    """``g = f`` and ``g = functools.partial(f, ...)`` bindings per scope."""
    aliases: dict[int, dict[str, FunctionInfo]] = {}
    resolver = _Resolver(graph, imports, aliases)

    def scan(body_owner: FuncNode | ast.Module, scope: FunctionInfo | None):
        for node in ast.walk(body_owner):
            if not isinstance(node, ast.Assign):
                continue
            if len(node.targets) != 1 or not isinstance(node.targets[0],
                                                        ast.Name):
                continue
            target = resolver.resolve(node.value, scope)
            if target is not None:
                key = id(scope.node) if scope else 0
                aliases.setdefault(key, {})[node.targets[0].id] = target

    # two passes so an alias of an alias still resolves
    for _ in range(2):
        for info in graph.functions.values():
            scan(info.node, info)
    return aliases


def build_graph(tree: ast.Module, imports: dict[str, str],
                traced_lines: frozenset[int] = frozenset()) -> ModuleGraph:
    graph = ModuleGraph()
    _Collector(graph).visit(tree)
    aliases = _collect_aliases(graph, imports)
    resolver = _Resolver(graph, imports, aliases)

    # -- map every node to its enclosing function -------------------------------
    enclosing: dict[int, FunctionInfo | None] = {}

    def mark_scope(owner, scope):
        for child in ast.iter_child_nodes(owner):
            enclosing[id(child)] = scope
            child_scope = graph.info(child) if isinstance(
                child, (ast.FunctionDef, ast.AsyncFunctionDef,
                        ast.Lambda)) else scope
            mark_scope(child, child_scope)

    mark_scope(tree, None)

    roots: list[FunctionInfo] = []
    kernel_roots: list[FunctionInfo] = []

    # -- decorator + marker roots ------------------------------------------------
    for info in graph.functions.values():
        node = info.node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if (node.lineno in traced_lines
                    or (node.lineno - 1) in traced_lines):
                roots.append(info)
            for dec in node.decorator_list:
                name = dotted_name(dec, imports)
                if name in TRACE_WRAPPERS or name == "jit":
                    roots.append(info)
                elif isinstance(dec, ast.Call):
                    cname = dotted_name(dec.func, imports)
                    if cname in TRACE_WRAPPERS or cname == "jit":
                        roots.append(info)
                    elif cname in PARTIAL and dec.args:
                        inner = dotted_name(dec.args[0], imports)
                        if inner in TRACE_WRAPPERS or inner == "jit":
                            roots.append(info)

    # -- call-site roots ----------------------------------------------------------
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = dotted_name(node.func, imports)
        scope = enclosing.get(id(node))
        if fn in TRACE_WRAPPERS and node.args:
            target = resolver.resolve(node.args[0], scope)
            if target is not None:
                roots.append(target)
        elif fn in TRACE_BODY_ARGS:
            for i in TRACE_BODY_ARGS[fn]:
                if i < len(node.args):
                    target = resolver.resolve(node.args[i], scope)
                    if target is not None:
                        roots.append(target)
        elif fn in TRACE_BRANCHLIST_ARGS:
            i = TRACE_BRANCHLIST_ARGS[fn]
            if i < len(node.args) and isinstance(node.args[i],
                                                 (ast.List, ast.Tuple)):
                for el in node.args[i].elts:
                    target = resolver.resolve(el, scope)
                    if target is not None:
                        roots.append(target)
        elif fn is not None and (fn in PALLAS_CALL
                                 or fn.endswith("pallas.pallas_call")
                                 or fn == "pallas_call"):
            kernel = (resolver.resolve(node.args[0], scope)
                      if node.args else None)
            if kernel is not None:
                kernel_roots.append(kernel)
            graph.pallas_sites.append((None, node, kernel, scope))

    # attach the outer application call (pl.pallas_call(...)(operands))
    inner_ids = {id(site[1]) for site in graph.pallas_sites}
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and id(node.func) in inner_ids:
            for i, site in enumerate(graph.pallas_sites):
                if id(site[1]) == id(node.func):
                    graph.pallas_sites[i] = (node, site[1], site[2], site[3])

    # -- propagate ----------------------------------------------------------------
    def propagate(info: FunctionInfo, *, kernel: bool):
        stack = [info]
        while stack:
            cur = stack.pop()
            attr = "kernel_reachable" if kernel else "jit_reachable"
            if getattr(cur, attr):
                continue
            setattr(cur, attr, True)
            if kernel:
                cur.jit_reachable = True
            for node in ast.walk(cur.node):
                nxt = None
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)) and node is not cur.node:
                    nxt = graph.info(node)
                    if nxt is not None and nxt.parent is not cur:
                        nxt = None          # handled by its own parent
                elif isinstance(node, (ast.Name, ast.Attribute)):
                    nxt = resolver.resolve(node, cur)
                if nxt is not None and not getattr(nxt, attr):
                    stack.append(nxt)

    for info in roots:
        info.is_root = True
        propagate(info, kernel=False)
    for info in kernel_roots:
        info.is_root = True
        propagate(info, kernel=True)
    return graph


__all__ = ["FunctionInfo", "ModuleGraph", "build_graph", "build_imports",
           "dotted_name", "TRACE_WRAPPERS", "TRACE_BODY_ARGS"]
