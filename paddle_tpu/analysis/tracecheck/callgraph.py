"""Traced-reachability call graph over a package (pure AST, no imports).

Two questions drive every rule:

1. **Which functions run under a jax trace?**  Roots are functions
   handed to (or decorated with) a *trace wrapper* — ``jax.jit``,
   ``pl.pallas_call``, ``jax.checkpoint``/``remat``, ``shard_map``,
   ``jax.vmap``/``grad``/``value_and_grad``, ``jax.custom_vjp``/``jvp``,
   ``lax`` control flow, and the repo's own wrappers (``apply_op``,
   ``jit_fn``/``to_static``) — plus every function in configured
   *traced modules* (the op/kernel libraries whose documented contract
   is "callable under jit").  Reachability closes over statically
   resolvable calls: locals in scope, module-level defs, ``from x
   import f`` edges inside the package, ``mod.f`` through an in-package
   module alias, and ``self.m`` within a class.

2. **Which callables donate buffers?**  ``jax.jit(f, donate_argnums=
   (..,))`` results are *donors*; donor-ness propagates through local /
   ``self.`` assignment, ``functools.partial``, function return values,
   and the decode-program-cache admission idiom ``cache.get(key,
   builder)`` (the compiled step a builder returns).  Rule TRC003
   consumes the resulting map of call-site -> donated positions.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# trace wrappers: name -> positions of the traced callable argument(s)
# (None = every positional argument may be a traced callable)
_JAX_WRAPPERS: Dict[str, Tuple[int, ...]] = {
    "jit": (0,), "pallas_call": (0,), "checkpoint": (0,), "remat": (0,),
    "shard_map": (0,), "vmap": (0,), "pmap": (0,), "grad": (0,),
    "value_and_grad": (0,), "custom_vjp": (0,), "custom_jvp": (0,),
    "named_call": (0,),
    # lax control flow — bodies are traced (matched only under a `lax`
    # root, see _LAX_ONLY: `jax.tree.map` / builtin map must not hit)
    "scan": (0,), "while_loop": (0, 1), "fori_loop": (2,),
    "cond": (1, 2), "switch": (1, 2, 3, 4, 5, 6, 7, 8),
    "associative_scan": (0,), "map": (0,),
}
_LAX_ONLY = {"scan", "while_loop", "fori_loop", "cond", "switch",
             "associative_scan", "map"}
# repo wrappers
_REPO_WRAPPERS: Dict[str, Tuple[int, ...]] = {
    "apply_op": (1,),          # apply_op(name, fn, *args)
    "jit_fn": (0,),
    "to_static": (0,),
}


@dataclass
class FunctionInfo:
    qualname: str                       # module-relative ('Cls.m', 'f.g')
    node: ast.AST                       # FunctionDef / Lambda
    module: "ModuleInfo"
    parent: Optional["FunctionInfo"]    # lexically enclosing function
    cls: Optional[str]                  # enclosing class name, if a method
    lineno: int = 0
    traced: bool = False
    trace_root: bool = False
    hotpath: bool = False
    calls: List[ast.Call] = field(default_factory=list)
    # donor analysis results filled by DonorPass
    returns_donor: Optional[Tuple[int, ...]] = None
    # rules._body_walk's memo: the six suites walk every body many times
    body_nodes: Optional[Tuple[ast.AST, ...]] = field(
        default=None, repr=False, compare=False)

    @property
    def name(self) -> str:
        return self.qualname.rsplit(".", 1)[-1]


@dataclass
class ModuleInfo:
    relpath: str                        # posix, relative to package parent
    tree: ast.Module
    source_lines: List[str]
    functions: Dict[str, FunctionInfo] = field(default_factory=dict)
    # import alias tables
    module_aliases: Dict[str, str] = field(default_factory=dict)   # name->modpath
    imported_names: Dict[str, Tuple[str, str]] = field(default_factory=dict)
    # ^ local name -> (module path, original name) for `from X import Y`
    lambda_seq: int = 0

    def line(self, n: int) -> str:
        if 1 <= n <= len(self.source_lines):
            return self.source_lines[n - 1].strip()
        return ""


def _dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def callee_name(call: ast.Call) -> Optional[str]:
    return _dotted(call.func)


def wrapper_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """If ``call`` invokes a trace wrapper, the positional indices whose
    arguments are traced callables; else None.  Matches on the terminal
    attribute name so every alias spelling (``jax.jit``, ``jit``,
    ``pl.pallas_call``, ``jax.experimental.shard_map.shard_map``,
    ``functools.partial(jax.jit, ...)`` as decorator) resolves."""
    name = callee_name(call)
    if name is None:
        return None
    tail = name.rsplit(".", 1)[-1]
    if tail == "partial" and call.args:
        inner = _dotted(call.args[0])
        if inner is not None:
            itail = inner.rsplit(".", 1)[-1]
            if itail in _JAX_WRAPPERS or itail in _REPO_WRAPPERS:
                # partial(jax.jit, f?) — shift positions by the bound args
                base = _JAX_WRAPPERS.get(itail, _REPO_WRAPPERS.get(itail))
                return tuple(p - (len(call.args) - 1) for p in base
                             if p - (len(call.args) - 1) >= 0) or (0,)
        return None
    if tail in _LAX_ONLY:
        parts = name.split(".")
        return _JAX_WRAPPERS[tail] if "lax" in parts[:-1] else None
    if tail in _JAX_WRAPPERS:
        return _JAX_WRAPPERS[tail]
    if tail in _REPO_WRAPPERS:
        return _REPO_WRAPPERS[tail]
    return None


def is_wrapper_decorator(dec: ast.expr) -> bool:
    """Decorator forms that put the function body under trace:
    ``@jax.jit``, ``@jit_fn``, ``@jax.custom_vjp``,
    ``@functools.partial(jax.jit, static_argnums=..)``, ``@checkpoint``.
    """
    if isinstance(dec, ast.Call):
        name = callee_name(dec)
        if name is None:
            return False
        tail = name.rsplit(".", 1)[-1]
        if tail == "partial" and dec.args:
            inner = _dotted(dec.args[0])
            if inner is not None and \
                    inner.rsplit(".", 1)[-1] in _JAX_WRAPPERS:
                return True
        return tail in _JAX_WRAPPERS or tail in _REPO_WRAPPERS
    name = _dotted(dec)
    if name is None:
        return False
    tail = name.rsplit(".", 1)[-1]
    return tail in _JAX_WRAPPERS or tail in _REPO_WRAPPERS


# -------------------------------------------------------------- indexing
class _Indexer(ast.NodeVisitor):
    """One pass per module: functions (incl. nested + lambdas), imports,
    per-function call lists.  Nested defs do NOT contribute their body
    statements to the parent's rule scan — each FunctionInfo is analyzed
    against its own traced flag."""

    def __init__(self, mod: ModuleInfo, package: str):
        self.mod = mod
        self.package = package
        self.stack: List[FunctionInfo] = []
        self.cls_stack: List[str] = []

    # imports ------------------------------------------------------------
    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            self.mod.module_aliases[a.asname or a.name.split(".")[0]] = \
                a.name
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        base = self._resolve_from(node)
        for a in node.names:
            local = a.asname or a.name
            # `from X import Y`: Y may be a submodule or a symbol; record
            # both interpretations, resolution tries symbol first
            self.mod.imported_names[local] = (base, a.name)
            self.mod.module_aliases.setdefault(local, f"{base}.{a.name}")
        self.generic_visit(node)

    def _resolve_from(self, node: ast.ImportFrom) -> str:
        if node.level == 0:
            return node.module or ""
        # relative: anchor at this module's package path
        parts = self.mod.relpath[:-3].split("/")          # strip .py
        if parts[-1] == "__init__":
            parts = parts[:-1]
        else:
            parts = parts[:-1]
        # one level = current package; each extra level pops one
        for _ in range(node.level - 1):
            if parts:
                parts = parts[:-1]
        base = ".".join(parts)
        if node.module:
            base = f"{base}.{node.module}" if base else node.module
        return base

    # classes / functions ------------------------------------------------
    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self.cls_stack.append(node.name)
        for child in node.body:
            self.visit(child)
        self.cls_stack.pop()

    def _enter_function(self, node, name: str) -> FunctionInfo:
        parent = self.stack[-1] if self.stack else None
        prefix = parent.qualname + "." if parent else (
            ".".join(self.cls_stack) + "." if self.cls_stack else "")
        info = FunctionInfo(
            qualname=prefix + name, node=node, module=self.mod,
            parent=parent, cls=self.cls_stack[-1] if self.cls_stack else None,
            lineno=getattr(node, "lineno", 0))
        self.mod.functions[info.qualname] = info
        return info

    def _walk_function(self, info: FunctionInfo, body) -> None:
        self.stack.append(info)
        for child in body:
            self.visit(child)
        self.stack.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        info = self._enter_function(node, node.name)
        for dec in node.decorator_list:
            self.visit(dec)
            if is_wrapper_decorator(dec):
                info.trace_root = True
        self._walk_function(info, node.body)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node: ast.Lambda) -> None:
        self.mod.lambda_seq += 1
        info = self._enter_function(
            node, f"<lambda:{node.lineno}:{self.mod.lambda_seq}>")
        self._walk_function(info, [ast.Expr(value=node.body)])

    # calls --------------------------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        if self.stack:
            self.stack[-1].calls.append(node)
        else:
            self.mod.functions.setdefault(
                "", FunctionInfo("", self.mod.tree, self.mod, None, None)
            ).calls.append(node)
        self.generic_visit(node)


# ------------------------------------------------------------ call graph
class CallGraph:
    def __init__(self, modules: Dict[str, ModuleInfo], package: str):
        self.modules = modules
        self.package = package
        # (modpath, funcname) -> [FunctionInfo] for module-level defs
        self.by_module_name: Dict[Tuple[str, str], List[FunctionInfo]] = {}
        # class method index: (modpath, clsname, methname) -> FunctionInfo
        self.methods: Dict[Tuple[str, str, str], FunctionInfo] = {}
        for mp, mod in modules.items():
            for qn, fi in mod.functions.items():
                if not qn:
                    continue
                parts = qn.split(".")
                if len(parts) == 1:
                    self.by_module_name.setdefault((mp, parts[0]), []) \
                        .append(fi)
                elif fi.cls is not None and len(parts) == 2:
                    self.methods[(mp, fi.cls, parts[1])] = fi
                    # methods are also name-resolvable within the module
                    self.by_module_name.setdefault((mp, parts[-1]), []) \
                        .append(fi)

    def modpath_of(self, mod: ModuleInfo) -> str:
        p = mod.relpath[:-3]
        if p.endswith("/__init__"):
            p = p[: -len("/__init__")]
        return p.replace("/", ".")

    # resolution ---------------------------------------------------------
    def resolve_call(self, fi: FunctionInfo, call: ast.Call
                     ) -> List[FunctionInfo]:
        name = callee_name(call)
        if name is None:
            return []
        mod = fi.module
        mp = self.modpath_of(mod)
        parts = name.split(".")

        # self.m(...): method on the enclosing class
        if parts[0] in ("self", "cls") and len(parts) == 2 and fi.cls:
            hit = self.methods.get((mp, fi.cls, parts[1]))
            return [hit] if hit else []

        if len(parts) == 1:
            n = parts[0]
            # nested function in an enclosing scope
            scope = fi
            while scope is not None:
                hit = mod.functions.get(
                    (scope.qualname + "." if scope.qualname else "") + n)
                if hit is not None:
                    return [hit]
                scope = scope.parent
            # module-level def (incl. methods indexed by bare name only
            # when unambiguous is too risky — restrict to plain defs)
            hits = [f for f in self.by_module_name.get((mp, n), [])
                    if f.cls is None]
            if hits:
                return hits
            # from X import n
            imp = mod.imported_names.get(n)
            if imp is not None:
                return self._resolve_imported(imp[0], imp[1])
            return []

        # mod_alias.func(...)
        alias, rest = parts[0], parts[1:]
        target_mod = mod.module_aliases.get(alias)
        if target_mod is None:
            imp = mod.imported_names.get(alias)
            if imp is not None:
                target_mod = f"{imp[0]}.{imp[1]}" if imp[0] else imp[1]
        if target_mod is None or not target_mod.startswith(self.package):
            return []
        if len(rest) == 1:
            return self._resolve_imported(target_mod, rest[0])
        return []

    def _resolve_imported(self, modpath: str, name: str
                          ) -> List[FunctionInfo]:
        if not modpath or not modpath.startswith(self.package):
            return []
        # exact module file
        hits = [f for f in self.by_module_name.get((modpath, name), [])
                if f.cls is None]
        if hits:
            return hits
        # re-export through a package __init__: search submodules
        prefix = modpath + "."
        out: List[FunctionInfo] = []
        for (mp, n), fis in self.by_module_name.items():
            if n == name and mp.startswith(prefix):
                out.extend(f for f in fis if f.cls is None)
        return out

    # reachability -------------------------------------------------------
    def propagate_traced(self) -> None:
        work: List[FunctionInfo] = []
        for mod in self.modules.values():
            for fi in mod.functions.values():
                if fi.trace_root and not fi.traced:
                    fi.traced = True
                    work.append(fi)
        while work:
            fi = work.pop()
            for call in fi.calls:
                for callee in self.resolve_call(fi, call):
                    if not callee.traced:
                        callee.traced = True
                        work.append(callee)


def index_module(relpath: str, source: str, package: str) -> ModuleInfo:
    tree = ast.parse(source)
    mod = ModuleInfo(relpath=relpath, tree=tree,
                     source_lines=source.splitlines())
    _Indexer(mod, package).visit(tree)
    return mod


def mark_roots_from_wrapper_calls(mod: ModuleInfo) -> None:
    """Functions *passed to* trace wrappers anywhere in the module become
    roots: ``jax.jit(run)``, ``pl.pallas_call(kernel, ...)``,
    ``lax.scan(body, ..)``, ``apply_op("x", fn, ..)``, lambdas inline."""
    lambda_by_pos = {
        (f.node.lineno, f.node.col_offset): f
        for f in mod.functions.values()
        if isinstance(f.node, ast.Lambda)}

    def local_named(fi_scope: Optional[FunctionInfo], n: str):
        scope = fi_scope
        while scope is not None:
            hit = mod.functions.get(scope.qualname + "." + n)
            if hit is not None:
                return hit
            scope = scope.parent
        return mod.functions.get(n)

    for owner in list(mod.functions.values()):
        for call in owner.calls:
            pos = wrapper_positions(call)
            if pos is None:
                continue
            for p in pos:
                if p >= len(call.args):
                    continue
                arg = call.args[p]
                if isinstance(arg, ast.Lambda):
                    hit = lambda_by_pos.get((arg.lineno, arg.col_offset))
                    if hit:
                        hit.trace_root = True
                elif isinstance(arg, ast.Name):
                    hit = local_named(owner if owner.qualname else None,
                                      arg.id)
                    if hit is not None:
                        hit.trace_root = True
                elif isinstance(arg, ast.Call):
                    # jax.jit(functools.partial(f, ...)) — unwrap partial
                    n = callee_name(arg)
                    if n and n.rsplit(".", 1)[-1] == "partial" and arg.args:
                        inner = arg.args[0]
                        if isinstance(inner, ast.Name):
                            hit = local_named(
                                owner if owner.qualname else None,
                                inner.id)
                            if hit is not None:
                                hit.trace_root = True
