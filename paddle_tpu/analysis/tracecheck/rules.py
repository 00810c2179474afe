"""The TRC rule checkers.

Each rule is a function ``(FunctionInfo, CallGraph) -> List[Finding]``
run over ONE function body (nested defs are their own FunctionInfo, so
visitors never descend into an inner ``def``/``lambda`` — the inner
function is judged against its own traced flag).
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Sequence

from .callgraph import CallGraph, FunctionInfo, _dotted, callee_name
from .findings import Finding

# ownership-handoff naming convention TRC003 recognizes: a donated
# argument produced by ``*.take_*()`` / ``*.donate_*()`` has been
# detached from live state by its owner before dispatch
_HANDOFF_PREFIXES = ("take_", "donate_", "detach_")

_SYNC_METHODS = {"item", "block_until_ready", "numpy", "tolist"}
_NUMPY_SYNCS = {"asarray", "array"}
_CLOCK_CALLS = {"time", "perf_counter", "monotonic", "process_time",
                "time_ns", "perf_counter_ns", "now", "utcnow", "today"}


def _body_walk(fi: FunctionInfo) -> Iterator[ast.AST]:
    """Walk this function's body without entering nested functions
    (walked once a function, the order kept on ``fi``)."""
    if fi.body_nodes is None:
        fi.body_nodes = tuple(_walk_body(fi))
    return iter(fi.body_nodes)


def _walk_body(fi: FunctionInfo) -> Iterator[ast.AST]:
    if isinstance(fi.node, ast.Lambda):
        roots: Sequence[ast.AST] = [fi.node.body]
    elif isinstance(fi.node, ast.Module):
        roots = []                                  # module scope: skip
    else:
        roots = fi.node.body
    stack = list(roots)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _finding(fi: FunctionInfo, node: ast.AST, rule: str, msg: str
             ) -> Finding:
    line = getattr(node, "lineno", fi.lineno)
    return Finding(rule=rule, path=fi.module.relpath, line=line,
                   func=fi.qualname, message=msg,
                   source=fi.module.line(line))


def _is_numpy_alias(fi: FunctionInfo, name: str) -> bool:
    target = fi.module.module_aliases.get(name)
    return target == "numpy" or (target or "").startswith("numpy.")


def _param_names(fi: FunctionInfo) -> set:
    node = fi.node
    if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
        return set()
    a = node.args
    names = [p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)]
    for extra in (a.vararg, a.kwarg):
        if extra is not None:
            names.append(extra.arg)
    return set(names)


def _arg_mentions_param(fi: FunctionInfo, call: ast.Call) -> bool:
    params = _param_names(fi)
    if not params:
        return False
    for arg in list(call.args) + [kw.value for kw in call.keywords]:
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Name) and sub.id in params:
                return True
    return False


def _is_flags_module(fi: FunctionInfo, name: str) -> bool:
    """Does local name ``name`` refer to the package flag registry?"""
    target = fi.module.module_aliases.get(name, "")
    if target.endswith(".flags") or target == "flags":
        return True
    imp = fi.module.imported_names.get(name)
    return bool(imp and (imp[1] == "flags" or imp[0].endswith("flags")))


# ------------------------------------------------------------------ TRC001
def trc001_flag_read_under_trace(fi: FunctionInfo, graph: CallGraph
                                 ) -> List[Finding]:
    """Flags get_flag/get_flags in trace-reachable code.  Deliberately
    NOT flagged: ``flags.snapshot(...)`` — the snapshot call IS the
    repo's trace-boundary marker (r06 idiom).  A snapshot taken while
    tracing still resolves once per trace, but it is one batched,
    thread-safe resolve whose ``as_tuple()`` rides the decode-program-
    cache flag key, so a later set_flags invalidates the compiled
    program instead of silently serving the stale value; per-call
    get_flag reads have neither property."""
    if not fi.traced:
        return []
    out: List[Finding] = []
    for node in _body_walk(fi):
        if not isinstance(node, ast.Call):
            continue
        name = callee_name(node)
        if name is None:
            continue
        parts = name.split(".")
        tail = parts[-1]
        if tail not in ("get_flag", "get_flags"):
            continue
        ok = False
        if len(parts) == 1:
            imp = fi.module.imported_names.get(tail)
            ok = bool(imp and imp[0].endswith("flags"))
        elif len(parts) == 2:
            ok = _is_flags_module(fi, parts[0])
        if ok:
            out.append(_finding(
                fi, node, "TRC001",
                f"registry read {name}(...) in trace-reachable code — the "
                "value is baked in at trace time and bypasses the "
                "program-cache flag key; resolve a flags.snapshot() at "
                "the trace boundary and thread it through"))
    return out


# ------------------------------------------------------------------ TRC002
def trc002_host_sync(fi: FunctionInfo, graph: CallGraph) -> List[Finding]:
    if not (fi.traced or fi.hotpath):
        return []
    ctx = ("traced function" if fi.traced
           else "declared hot path (tracecheck: hotpath)")
    out: List[Finding] = []
    for node in _body_walk(fi):
        if not isinstance(node, ast.Call):
            continue
        # x.item() / x.block_until_ready() / x.numpy() / x.tolist()
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in _SYNC_METHODS and not node.args:
            out.append(_finding(
                fi, node, "TRC002",
                f".{node.func.attr}() host sync in {ctx} — stalls the "
                "dispatch pipeline (and fails on traced values); keep "
                "values on device or pull them at an explicit sync point"))
            continue
        name = callee_name(node)
        if name is None:
            continue
        parts = name.split(".")
        tail = parts[-1]
        if tail == "device_get" and len(parts) >= 2:
            out.append(_finding(
                fi, node, "TRC002",
                f"jax.device_get in {ctx} — host transfer on the hot "
                "path; move it behind the metrics/sync boundary"))
        elif len(parts) == 2 and tail in _NUMPY_SYNCS and \
                _is_numpy_alias(fi, parts[0]) and \
                (fi.hotpath or _arg_mentions_param(fi, node)):
            # in traced code, np.asarray of LOCAL host data is ordinary
            # trace-time constant building; only values flowing in
            # through the traced signature can be tracers
            out.append(_finding(
                fi, node, "TRC002",
                f"{name}(...) in {ctx} — forces a device->host copy "
                "(and fails on traced values); use jnp, or sync "
                "explicitly where staleness is acceptable"))
        elif len(parts) == 1 and tail == "float" and fi.hotpath and \
                node.args and not isinstance(node.args[0], ast.Constant):
            # hotpath-only: in traced code float()/int() usually digest
            # STATIC python args (axes, shapes) — the tracer-concretizing
            # cases there are covered by TRC006 / the runtime error
            out.append(_finding(
                fi, node, "TRC002",
                f"{tail}(...) in {ctx} — blocks on the device value; "
                "pull metrics on the metrics_every/sync() cadence "
                "instead"))
    return out


# ------------------------------------------------------------------ TRC003
def _attr_chain(node: ast.AST) -> Optional[str]:
    return _dotted(node)


def _mentions_self_state(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in ("self", "cls"):
            return True
    return False


def _is_handoff_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    name = callee_name(node)
    if not name:
        return False
    tail = name.rsplit(".", 1)[-1]
    return tail.startswith(_HANDOFF_PREFIXES)


def trc003_donated_use(fi: FunctionInfo, graph: CallGraph,
                       donors) -> List[Finding]:
    """``donors``: resolver ``(fi, call) -> Optional[Tuple[int, ...]]``
    giving donated positional indices for a call site.  Applies to host
    code too — donation hazards live OUTSIDE the traced function.

    The reuse scan is block-structured: "after the call" means the rest
    of the call's own block plus the continuations of its enclosing
    blocks — never a sibling ``elif``/``else`` branch (those are
    mutually exclusive with the donating dispatch)."""
    out: List[Finding] = []
    if isinstance(fi.node, (ast.Module, ast.Lambda)):
        return out

    def check_call(call: ast.Call, successors: List[ast.stmt],
                   own_stmt: ast.stmt) -> None:
        pos = donors(fi, call)
        if not pos:
            return
        for p in pos:
            if p >= len(call.args):
                continue
            arg = call.args[p]
            if isinstance(arg, ast.Starred):
                continue
            chain = _attr_chain(arg)
            if chain is not None:
                f = _check_chain_reuse(fi, successors, own_stmt, chain)
                if f is not None:
                    out.append(f)
            elif _is_handoff_call(arg):
                continue            # explicit ownership transfer
            elif _mentions_self_state(arg):
                line = arg.lineno
                out.append(Finding(
                    rule="TRC003", path=fi.module.relpath, line=line,
                    func=fi.qualname, source=fi.module.line(line),
                    message="donated argument is a live view of "
                            "object state — after dispatch the "
                            "donated buffers are invalid but the "
                            "object still references them (stale on "
                            "error paths); detach ownership first "
                            "via a take_*/donate_* helper"))

    def scan_block(stmts: List[ast.stmt],
                   continuation: List[ast.stmt]) -> None:
        for i, stmt in enumerate(stmts):
            successors = stmts[i + 1:] + continuation
            for call in _header_calls(stmt):
                check_call(call, successors, stmt)
            for sub in _sub_blocks(stmt):
                scan_block(sub, successors)

    scan_block(list(fi.node.body), [])
    return out


def _sub_blocks(stmt: ast.stmt) -> List[List[ast.stmt]]:
    if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return []
    blocks = []
    for field_name in ("body", "orelse", "finalbody"):
        sub = getattr(stmt, field_name, None)
        if isinstance(sub, list) and sub and isinstance(sub[0], ast.stmt):
            blocks.append(sub)
    for h in getattr(stmt, "handlers", []) or []:
        blocks.append(h.body)
    return blocks


def _header_calls(stmt: ast.stmt) -> List[ast.Call]:
    """Calls evaluated by this statement itself — its expressions, not
    its nested blocks (those are scanned with their own successor
    lists)."""
    if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return []                   # a nested def's calls run later
    nested = {id(s) for block in _sub_blocks(stmt) for s in block}
    out: List[ast.Call] = []
    stack: List[ast.AST] = [stmt]
    while stack:
        node = stack.pop()
        if id(node) in nested or isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        if isinstance(node, ast.Call):
            out.append(node)
        stack.extend(ast.iter_child_nodes(node))
    return out


def _flatten_statements(body: List[ast.stmt]) -> List[ast.stmt]:
    """Statement list in source order, descending into compound bodies
    (but not nested function defs)."""
    out: List[ast.stmt] = []
    for stmt in body:
        out.append(stmt)
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for field_name in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, field_name, None)
            if isinstance(sub, list):
                out.extend(_flatten_statements(
                    [s for s in sub if isinstance(s, ast.stmt)]))
        for h in getattr(stmt, "handlers", []) or []:
            out.extend(_flatten_statements(h.body))
    return out


def _assigned_chains(stmt: ast.stmt) -> List[str]:
    targets: List[ast.expr] = []
    if isinstance(stmt, ast.Assign):
        targets = list(stmt.targets)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        targets = [stmt.target]
    elif isinstance(stmt, ast.For):
        targets = [stmt.target]
    out: List[str] = []
    for t in targets:
        for el in ([t] if not isinstance(t, (ast.Tuple, ast.List))
                   else list(t.elts)):
            c = _attr_chain(el)
            if c is not None:
                out.append(c)
    return out


def _check_chain_reuse(fi: FunctionInfo, successors: List[ast.stmt],
                       call_stmt: ast.stmt, chain: str
                       ) -> Optional[Finding]:
    """A Name/attribute chain passed at a donated position: flag the
    first Load of that chain after the donating statement, unless the
    chain is rebound first (including by the donating statement itself —
    the sanctioned ``x = step(x, ...)`` shape)."""
    if _assigned_in(call_stmt, chain):
        return None
    for stmt in successors:
        hit = _loads_chain(stmt, chain)
        if hit is not None:
            line = getattr(hit, "lineno", stmt.lineno)
            return Finding(
                rule="TRC003", path=fi.module.relpath, line=line,
                func=fi.qualname, source=fi.module.line(line),
                message=f"'{chain}' was donated to a jit(donate_argnums) "
                        "call and is read again before being rebound — "
                        "the buffer no longer exists after dispatch")
        if _assigned_in(stmt, chain):
            return None
    return None


def _assigned_in(stmt: ast.stmt, chain: str) -> bool:
    return any(c == chain for c in _assigned_chains(stmt))


def _loads_chain(stmt: ast.stmt, chain: str) -> Optional[ast.AST]:
    assigned = set(_assigned_chains(stmt))
    for node in ast.walk(stmt):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        c = _attr_chain(node)
        if c == chain and c not in assigned and \
                isinstance(getattr(node, "ctx", ast.Load()), ast.Load):
            return node
    return None


# ------------------------------------------------------------------ TRC004
def trc004_unstable_jit(fi: FunctionInfo, graph: CallGraph
                        ) -> List[Finding]:
    """Host-side rule: jit admissions that defeat jax's per-callable
    cache — jit inside a loop, jit of a lambda, jit immediately
    invoked."""
    out: List[Finding] = []
    if isinstance(fi.node, (ast.Module, ast.Lambda)):
        return out

    def is_jit_call(node: ast.Call) -> bool:
        name = callee_name(node)
        if name is None:
            return False
        tail = name.rsplit(".", 1)[-1]
        return tail in ("jit", "jit_fn")

    # walk with loop-depth tracking
    def walk(node: ast.AST, in_loop: bool) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            child_in_loop = in_loop or isinstance(
                child, (ast.For, ast.While, ast.AsyncFor))
            if isinstance(child, ast.Call):
                if is_jit_call(child):
                    if in_loop:
                        out.append(_finding(
                            fi, child, "TRC004",
                            "jax.jit(...) inside a loop — every "
                            "iteration admits a fresh callable and "
                            "retraces; hoist the jit or key it through "
                            "the decode program cache"))
                    elif child.args and isinstance(child.args[0],
                                                   ast.Lambda):
                        out.append(_finding(
                            fi, child, "TRC004",
                            "jax.jit of a lambda built per call — jit "
                            "caches per callable object, so each fresh "
                            "closure recompiles; define the function "
                            "once or cache the jitted result"))
                elif isinstance(child.func, ast.Call) and \
                        is_jit_call(child.func):
                    out.append(_finding(
                        fi, child, "TRC004",
                        "jax.jit(f)(...) immediately invoked — the "
                        "compiled program is discarded and rebuilt on "
                        "every call; bind the jitted callable once"))
            walk(child, child_in_loop)

    walk(fi.node, False)
    return out


# ------------------------------------------------------------------ TRC005
def trc005_impure_time_rng(fi: FunctionInfo, graph: CallGraph
                           ) -> List[Finding]:
    if not fi.traced:
        return []
    out: List[Finding] = []
    for node in _body_walk(fi):
        if not isinstance(node, ast.Call):
            continue
        name = callee_name(node)
        if name is None:
            continue
        parts = name.split(".")
        if len(parts) < 2:
            continue
        root, tail = parts[0], parts[-1]
        root_target = fi.module.module_aliases.get(root, "")
        if root_target in ("time", "datetime") and tail in _CLOCK_CALLS:
            out.append(_finding(
                fi, node, "TRC005",
                f"{name}() under trace — evaluated once at trace time "
                "and baked into the compiled program; pass times in as "
                "arguments"))
        elif root_target == "random" or \
                (name.startswith("random.") and root_target == "random"):
            out.append(_finding(
                fi, node, "TRC005",
                f"stdlib {name}() under trace — one sample frozen at "
                "trace time; use jax.random with a traced key"))
        elif len(parts) >= 3 and parts[1] == "random" and \
                _is_numpy_alias(fi, root):
            out.append(_finding(
                fi, node, "TRC005",
                f"{name}() under trace — numpy RNG runs at trace time "
                "only (same values every call); use jax.random with a "
                "traced key"))
    return out


# ------------------------------------------------------------------ TRC006
def _test_has_tracer_guard(test: ast.expr) -> bool:
    for node in ast.walk(test):
        if isinstance(node, ast.Call):
            name = callee_name(node)
            if name and name.rsplit(".", 1)[-1] == "isinstance":
                return True
    return False


# trace-STATIC jnp predicates: dtype/shape/rank queries return concrete
# python values even on tracers — branching on them is fine.
# lax.axis_size is a static mesh-shape query (NOT axis_index, which
# returns a tracer).
_STATIC_JNP = {"shape", "ndim", "size", "result_type", "dtype",
               "iscomplexobj", "isrealobj", "issubdtype", "isdtype",
               "axis_size"}
# value-producing reductions commonly branched on: x.any(), x.sum() > 0
_VALUE_METHODS = {"any", "all", "sum", "max", "min", "mean", "prod"}
# concretizers: int(x)/float(x)/bool(x) yield host values (or raise at
# trace time) — their results are NOT tracers, so they clear taint
_CONCRETIZERS = {"int", "float", "bool"}


def _is_identity_test(test: ast.expr) -> bool:
    return isinstance(test, ast.Compare) and all(
        isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops)


def _tensorish(fi: FunctionInfo, node: ast.expr,
               tainted: set) -> Optional[str]:
    """Does this expression compute on a jnp/lax value or a locally
    jnp-tainted name in a way that forces concretization when branched
    on?  Returns a short description or None.

    Deliberately NOT tensorish: ``x.ndim``/``x.shape`` style attribute
    reads (static under trace), ``x is None`` identity tests, dict/pytree
    container method calls like ``state.get(k)``, and anything passed
    through int()/float()/bool() (already concrete)."""
    if _is_identity_test(node):
        return None
    # parent map so `x.anything` (attribute read on a tainted name) can
    # be told apart from `x`, `x[0]`, `x + 1` (all concretizing)
    parent: dict = {}
    stack: List[ast.AST] = [node]
    order: List[ast.AST] = []
    while stack:
        sub = stack.pop()
        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            continue
        order.append(sub)
        for child in ast.iter_child_nodes(sub):
            parent[id(child)] = sub
            stack.append(child)
    skip_subtrees: set = set()
    for sub in order:
        if isinstance(sub, ast.Call):
            if _under_skipped(sub, parent, skip_subtrees):
                continue
            name = callee_name(sub)
            if name:
                tail = name.rsplit(".", 1)[-1]
                if tail in _STATIC_JNP or tail in _CONCRETIZERS:
                    skip_subtrees.add(id(sub))
                    continue
                root = name.split(".")[0]
                target = fi.module.module_aliases.get(root, "")
                if target in ("jax.numpy", "jax.lax") or \
                        target.startswith("jax.numpy.") or \
                        name.startswith(("jnp.", "lax.", "jax.numpy.",
                                         "jax.lax.")):
                    return name
            if isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr in _VALUE_METHODS:
                base = _dotted(sub.func.value)
                if base is not None and base.split(".")[0] in tainted:
                    return f"{base}.{sub.func.attr}()"
    for sub in order:
        if not (isinstance(sub, ast.Name) and sub.id in tainted):
            continue
        if _under_skipped(sub, parent, skip_subtrees):
            continue
        p = parent.get(id(sub))
        if isinstance(p, ast.Attribute):
            continue                # x.ndim / state.get(...) — static
        return sub.id
    return None


def _under_skipped(node: ast.AST, parent: dict, skipped: set) -> bool:
    cur = node
    while cur is not None:
        if id(cur) in skipped:
            return True
        cur = parent.get(id(cur))
    return False


def _is_observability_name(fi: FunctionInfo, name: str) -> bool:
    """Is local name ``name`` imported from the observability package
    (``from .. import observability as obs`` / ``from ..observability
    import span``)?"""
    target = fi.module.module_aliases.get(name, "")
    if target.endswith("observability") or ".observability." in target:
        return True
    imp = fi.module.imported_names.get(name)
    return bool(imp and "observability" in imp[0])


def _module_imports_observability(fi: FunctionInfo) -> bool:
    for target in fi.module.module_aliases.values():
        if target.endswith("observability") or ".observability." in target:
            return True
    for modname, _orig in fi.module.imported_names.values():
        if "observability" in modname:
            return True
    return False


# instrument/tracer write methods distinctive enough to flag by name —
# but only in modules that import the observability package, so e.g. a
# quantization observer's ``.observe()`` never false-positives
_TELEMETRY_METHODS = {"inc", "dec", "observe", "span", "event", "phase"}

# the sanctioned hot-path aggregation idiom (like take_* for TRC003):
# batching a step's gauge/counter writes into one enabled-guarded
# ``_observe_*`` helper is the annotation — the name is the pragma
_OBSERVE_PREFIX = "_observe_"


def _telemetry_writes(fi: FunctionInfo) -> List:
    """Direct telemetry write call sites in this function's body:
    ``[(node, dotted_name), ...]``."""
    obs_imported = _module_imports_observability(fi)
    out = []
    for node in _body_walk(fi):
        if not isinstance(node, ast.Call):
            continue
        name = callee_name(node)
        if name is None:
            continue
        parts = name.split(".")
        if len(parts) == 1:
            if _is_observability_name(fi, parts[0]):
                out.append((node, name))
        elif _is_observability_name(fi, parts[0]):
            out.append((node, name))
        elif obs_imported and parts[-1] in _TELEMETRY_METHODS:
            out.append((node, name))
    return out


def trc007_telemetry_under_trace(fi: FunctionInfo, graph: CallGraph
                                 ) -> List[Finding]:
    """Telemetry is host-side only. In TRACE-REACHABLE code a registry/
    tracer write either fails on tracers or fires once at trace time and
    silently freezes — record at the dispatch boundary instead. In
    declared ``# tracecheck: hotpath`` code a telemetry write is legal
    but costs the path it observes, so it must carry an explicit
    ``# tracecheck: disable=TRC007`` pragma with a reason; the scan
    also reaches ONE call level into same-module helpers (batching a
    step's writes into an enabled-guarded ``_observe_*`` helper is the
    sanctioned idiom and exempt by name)."""
    out: List[Finding] = []
    if fi.traced:
        for node, name in _telemetry_writes(fi):
            out.append(_finding(
                fi, node, "TRC007",
                f"telemetry write {name}(...) in trace-reachable code — "
                "the metrics registry and span tracer are host-side "
                "only (a write here fires once at trace time and "
                "freezes, or fails on a tracer); record at the dispatch "
                "boundary instead"))
        return out
    if not fi.hotpath:
        return []
    for node, name in _telemetry_writes(fi):
        out.append(_finding(
            fi, node, "TRC007",
            f"telemetry write {name}(...) on a declared hot path — "
            "acknowledge the per-step host cost with an inline "
            "`# tracecheck: disable=TRC007` pragma and a reason"))
    # one-level helper reach: a hot path routing writes through a plain
    # same-module helper doesn't escape the annotation contract
    for node in _body_walk(fi):
        if not isinstance(node, ast.Call):
            continue
        cname = callee_name(node)
        if cname is None or \
                cname.rsplit(".", 1)[-1].startswith(_OBSERVE_PREFIX):
            continue
        for callee in graph.resolve_call(fi, node):
            if callee.module is not fi.module or callee.hotpath \
                    or callee.traced:
                continue        # other modules / directly-scanned defs
            helper = callee.qualname.rsplit(".", 1)[-1]
            if helper.startswith(_OBSERVE_PREFIX):
                continue
            for wnode, wname in _telemetry_writes(callee):
                out.append(_finding(
                    callee, wnode, "TRC007",
                    f"telemetry write {wname}(...) reached one call from "
                    f"hot path '{fi.qualname}' — pragma it with a "
                    "reason, or batch it into an `_observe_*` helper"))
    return out


def trc006_tensor_control_flow(fi: FunctionInfo, graph: CallGraph
                               ) -> List[Finding]:
    if not fi.traced or isinstance(fi.node, (ast.Module, ast.Lambda)):
        return []
    # one linear pass: taint local names assigned from jnp expressions
    tainted: set = set()
    out: List[Finding] = []
    for stmt in _flatten_statements(list(fi.node.body)):
        if isinstance(stmt, ast.Assign):
            desc = _tensorish(fi, stmt.value, tainted)
            for c in _assigned_chains(stmt):
                if "." not in c:
                    (tainted.add(c) if desc else tainted.discard(c))
        if isinstance(stmt, (ast.If, ast.While)):
            if _test_has_tracer_guard(stmt.test):
                continue            # isinstance(x, Tracer)-guarded branch
            desc = _tensorish(fi, stmt.test, tainted)
            if desc is not None:
                kind = "while" if isinstance(stmt, ast.While) else "if"
                out.append(Finding(
                    rule="TRC006", path=fi.module.relpath,
                    line=stmt.lineno, func=fi.qualname,
                    source=fi.module.line(stmt.lineno),
                    message=f"Python `{kind}` on tensor-valued "
                            f"expression ({desc}) in traced code — "
                            "concretizes a tracer; use jnp.where/"
                            "lax.cond, or guard the eager branch with "
                            "isinstance(x, jax.core.Tracer)"))
    return out
