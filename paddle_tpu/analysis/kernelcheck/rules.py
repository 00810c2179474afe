"""The KRN rule checkers.

Each rule is ``(FunctionInfo, KernelContext) -> List[Finding]`` over ONE
function (nested defs are their own FunctionInfo), mirroring the
tracecheck/meshcheck/faultcheck suites.  The rules encode the TPU
kernel discipline the r05–r17 Pallas arc relies on but has only ever
exercised in CPU interpret mode — tile alignment, the 16 MB VMEM
bound, grid/index-map hygiene, Mosaic-compilable kernel bodies,
f32 accumulation, and the ref-twin parity convention.

Shape dimensions are only judged when the static evaluator can prove
their value (module constants, literal locals, ``tile()`` calls) —
an unresolvable dimension is never a finding.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from ..tracecheck import rules as R
from ..tracecheck.callgraph import FunctionInfo, callee_name
from ..tracecheck.findings import Finding
from ..tile_geometry import (DOUBLE_BUFFER, DTYPE_BYTES,
                             FUSED_DECODE_SCRATCH,
                             FUSED_DECODE_SINGLE_SCRATCH, LANES,
                             VMEM_LIMIT_BYTES, sublane_multiple)
from .geometry import (KernelContext, PallasSite, ScratchInfo, SpecInfo,
                       _module_consts, _scalar_assigns, eval_dim,
                       kernel_closure, map_arity, resolve_index_map_def)

KERNEL_RULES: Dict[str, str] = {
    "KRN001": "BlockSpec/scratch shape off the TPU tile grid — the "
              "minor-most (lane) dimension must be a multiple of 128 "
              "and the second-minor (sublane) dimension a multiple of "
              "the dtype's packing (8/f32, 16/bf16, 32/int8); "
              "misaligned blocks force Mosaic relayouts or fail to "
              "lower at all on hardware (interpret mode hides this)",
    "KRN002": "static VMEM budget — the site's block operands (double-"
              "buffered by Mosaic) plus persistent scratch must fit the "
              "16 MB per-core bound, and the fused-decode kernels' "
              "scratch lists must match the shared geometry templates "
              "(tile_geometry.py) the memwatch planner prices from — "
              "drift either way and planner and kernel disagree",
    "KRN003": "grid/index-map discipline — every index_map's arity must "
              "equal grid rank + num_scalar_prefetch, grid extents "
              "derived by floor division need a ceil-div or an explicit "
              "divisibility guard (a ragged tail silently drops "
              "otherwise), and index maps must return BLOCK indices, "
              "not element offsets (no multiplying by the block size)",
    "KRN004": "kernel-body purity — a Pallas kernel body must lower "
              "through Mosaic: no host/numpy/FLAGS/callback/clock "
              "calls, no Python while loops or data-dependent Python "
              "iteration (use lax.fori_loop / pl.when), no jnp ops "
              "known to have no Mosaic lowering (sort/unique/nonzero/"
              "quantile family); interpret mode happily runs all of "
              "these and hides the failure until a real TPU",
    "KRN005": "accumulation discipline — reduction carries must live in "
              "f32 scratch (not bf16/f16), dots must pin "
              "preferred_element_type (bf16/int8 inputs otherwise "
              "accumulate in low precision on the MXU), and scratch "
              "carried across grid steps needs a step-0 init under "
              "pl.when (stale VMEM from the previous grid cell "
              "otherwise leaks into the first accumulation)",
    "KRN006": "ref-twin census — every public pallas entry point needs "
              "a pure-jnp twin (<stem>_ref/_xla/_dense) as the parity "
              "oracle; a kernel without a ref twin cannot be validated "
              "in CPU CI and regressions surface only on hardware",
}

# KRN002 normalization: spellings the kernels use for dims the shared
# templates name symbolically (tile_geometry.fused_decode_env keys)
_SPELLINGS: Dict[str, str] = {
    "_LANES": "LANES",
    "nh": "heads",
    "nkv": "kv_heads",
    "nh + 2 * nkv": "qkv_heads",
}

# fused-decode entry -> the scratch template its site must match
_SCRATCH_TEMPLATES: Dict[str, Tuple[Tuple[str, ...], ...]] = {
    "fused_block_decode_pallas": FUSED_DECODE_SINGLE_SCRATCH,
    "fused_multi_block_decode_pallas": FUSED_DECODE_SCRATCH,
}

# jnp ops with no Mosaic lowering (value-dependent shapes / gather-
# heavy): fine under interpret, dead on hardware
_MOSAIC_UNSUPPORTED = {
    "sort", "argsort", "unique", "nonzero", "searchsorted", "bincount",
    "median", "quantile", "percentile",
}

_HOST_CALL_TAILS = {"print", "breakpoint", "input", "get_flag",
                    "snapshot", "pure_callback", "io_callback",
                    "host_callback"}
_HOST_MODULES = {"time", "random", "datetime", "os", "sys", "logging"}
_LOOP_ITER_TAILS = {"range", "enumerate", "zip", "reversed"}
_INIT_VALUE_TAILS = {"zeros", "zeros_like", "full", "full_like"}
_LOW_PRECISION = {"bfloat16", "bf16", "float16", "f16"}
_DOT_TAILS = {"dot_general", "dot", "matmul"}


def _finding(fi: FunctionInfo, node, rule: str, msg: str) -> Finding:
    line = getattr(node, "lineno", fi.lineno) or fi.lineno
    return Finding(rule=rule, path=fi.module.relpath, line=line,
                   func=fi.qualname, message=msg,
                   source=fi.module.line(line))


def _env(ctx: KernelContext, fi: FunctionInfo
         ) -> Tuple[Dict[str, int], Dict[str, List[ast.expr]]]:
    mp = fi.module.relpath
    consts = ctx.mod_consts.get(mp)
    if consts is None:
        consts = _module_consts(fi.module)
        ctx.mod_consts[mp] = consts
    return consts, _scalar_assigns(fi) if not isinstance(
        fi.node, (ast.Module, ast.Lambda)) else {}


def _sites_of(ctx: KernelContext, fi: FunctionInfo) -> List[PallasSite]:
    return [s for s in ctx.sites.get(fi.module.relpath, ())
            if s.fi is fi]


def _kernel_sites(ctx: KernelContext, fi: FunctionInfo
                  ) -> List[PallasSite]:
    """Sites whose KERNEL is this function (the gate for KRN004/005)."""
    return [s for s in ctx.sites.get(fi.module.relpath, ())
            if s.kernel is fi]


# ------------------------------------------------------------------ KRN001
def _check_shape(fi: FunctionInfo, shape: Sequence[ast.expr],
                 lineno: int, what: str, dtype: str,
                 consts: Dict[str, int],
                 assigns: Dict[str, List[ast.expr]]) -> List[Finding]:
    out: List[Finding] = []
    if not shape:
        return out
    anchor = shape[-1] if hasattr(shape[-1], "lineno") else None
    lane = eval_dim(shape[-1], consts, assigns)
    if lane is not None and lane % LANES != 0:
        out.append(_finding(
            fi, anchor or fi.node, "KRN001",
            f"{what} shape has minor-most dim {lane}, not a multiple "
            f"of the {LANES}-lane tile — Mosaic pads every such block "
            "to a full lane tile (or refuses the layout); make the "
            "last dim a multiple of 128, fold narrow columns into a "
            "wider block, or pragma a deliberate scalar/stat column "
            "with a reason"))
    if len(shape) >= 2:
        need = sublane_multiple(dtype) or 8   # 8 = min for any dtype
        sub = eval_dim(shape[-2], consts, assigns)
        if sub is not None and sub > 1 and sub % need != 0:
            dt = dtype or "any dtype"
            out.append(_finding(
                fi, anchor or fi.node, "KRN001",
                f"{what} shape has second-minor dim {sub}, not a "
                f"multiple of the sublane packing {need} for {dt} — "
                "the block straddles partial (sublane, lane) tiles; "
                "pad the dim (the -(-n // 8) * 8 idiom) or retile"))
    return out


def krn001_tile_alignment(fi: FunctionInfo, ctx: KernelContext
                          ) -> List[Finding]:
    mp = fi.module.relpath
    key = (mp, fi.qualname)
    specs = ctx.census_specs.get(key, ())
    scratch = ctx.census_scratch.get(key, ())
    if not specs and not scratch:
        return []
    consts, assigns = _env(ctx, fi)
    out: List[Finding] = []
    for s in specs:
        if s.shape is not None:
            out += _check_shape(fi, s.shape, s.lineno, "BlockSpec block",
                                "", consts, assigns)
    for sc in scratch:
        if sc.space == "SMEM" or sc.shape is None:
            continue                      # SMEM is scalar memory: untiled
        out += _check_shape(fi, sc.shape, sc.lineno,
                            f"VMEM scratch ({sc.dtype or 'unknown'})",
                            sc.dtype, consts, assigns)
    return out


# ------------------------------------------------------------------ KRN002
def _shape_bytes(shape: Optional[Sequence[ast.expr]], per_elem: int,
                 consts, assigns) -> Tuple[int, bool]:
    """(bytes, resolved) — resolved False means the shape made no claim
    and contributes 0 (an under-count, so any overrun is still real)."""
    if shape is None:
        return 0, False
    n = 1
    for d in shape:
        v = eval_dim(d, consts, assigns)
        if v is None:
            return 0, False
        n *= max(v, 0)
    return n * per_elem, True


def _norm_dim(expr: ast.expr) -> str:
    s = ast.unparse(expr)
    return _SPELLINGS.get(s, s)


def krn002_vmem_budget(fi: FunctionInfo, ctx: KernelContext
                       ) -> List[Finding]:
    sites = _sites_of(ctx, fi)
    if not sites:
        return []
    consts, assigns = _env(ctx, fi)
    out: List[Finding] = []
    for site in sites:
        # (a) literal pricing: streamed blocks double-buffered at 4 B
        # (the widest storage — an unresolvable block contributes 0, so
        # the sum is a LOWER bound and any overrun is real)
        total = 0
        unresolved = 0
        for spec in (site.in_specs or []) + (site.out_specs or []):
            b, ok = _shape_bytes(spec.shape, 4, consts, assigns)
            total += DOUBLE_BUFFER * b
            unresolved += 0 if ok else 1
        for sc in site.scratch or []:
            per = DTYPE_BYTES.get(sc.dtype, 4)
            b, ok = _shape_bytes(sc.shape, per, consts, assigns)
            total += b
            unresolved += 0 if ok else 1
        if total > VMEM_LIMIT_BYTES:
            mb = total / (1 << 20)
            extra = (f", {unresolved} shapes unresolved and uncounted"
                     if unresolved else "")
            out.append(_finding(
                fi, site.call, "KRN002",
                f"pallas_call working set is statically >= {mb:.1f} MB "
                f"(double-buffered blocks at 4 B/elem + scratch{extra})"
                f" — over the {VMEM_LIMIT_BYTES >> 20} MB per-core "
                "VMEM bound; shrink block tiles or split the kernel"))
        # (b) fused-decode scratch geometry must match the shared
        # template the memwatch planner prices from
        tmpl = _SCRATCH_TEMPLATES.get(fi.qualname)
        if tmpl is not None and site.scratch is not None:
            got = sorted(
                tuple(_norm_dim(d) for d in sc.shape)
                for sc in site.scratch if sc.shape is not None)
            want = sorted(tuple(t) for t in tmpl)
            if got != want:
                missing = [w for w in want if w not in got]
                extra = [g for g in got if g not in want]
                out.append(_finding(
                    fi, site.call, "KRN002",
                    f"scratch geometry of {fi.qualname} drifted from "
                    "the shared template "
                    "(tile_geometry.FUSED_DECODE_*SCRATCH) that "
                    "memwatch's plan_fused_layers prices VMEM from — "
                    f"template-only: {missing or '[]'}, kernel-only: "
                    f"{extra or '[]'}; update BOTH the kernel and the "
                    "template (and the planner test) together"))
    return out


# ------------------------------------------------------------------ KRN003
def _floordivs(expr: ast.expr) -> List[Tuple[ast.BinOp, List[ast.AST]]]:
    """(floordiv node, ancestor chain) pairs inside a grid entry."""
    out: List[Tuple[ast.BinOp, List[ast.AST]]] = []

    def walk(node: ast.AST, anc: List[ast.AST]) -> None:
        if isinstance(node, ast.BinOp) and \
                isinstance(node.op, ast.FloorDiv):
            out.append((node, list(anc)))
        for child in ast.iter_child_nodes(node):
            walk(child, anc + [node])

    walk(expr, [])
    return out


def _is_ceil_div(fd: ast.BinOp, ancestors: List[ast.AST]) -> bool:
    # -(-a // b)
    if isinstance(fd.left, ast.UnaryOp) and \
            isinstance(fd.left.op, ast.USub) and any(
                isinstance(a, ast.UnaryOp) and isinstance(a.op, ast.USub)
                for a in ancestors):
        return True
    # (a + b - 1) // b style: compound additive numerator
    if isinstance(fd.left, ast.BinOp) and \
            isinstance(fd.left.op, (ast.Add, ast.Sub)):
        return True
    return False


def _has_divisibility_guard(fi: FunctionInfo, divisor: ast.expr) -> bool:
    want = ast.dump(divisor)
    for node in ast.walk(fi.node):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mod) \
                and ast.dump(node.right) == want:
            return True
    return False


def _map_returns(node: ast.AST) -> List[ast.expr]:
    if isinstance(node, ast.Lambda):
        return [node.body]
    out: List[ast.expr] = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Return) and sub.value is not None:
            out.append(sub.value)
    return out


def krn003_grid_discipline(fi: FunctionInfo, ctx: KernelContext
                           ) -> List[Finding]:
    sites = _sites_of(ctx, fi)
    if not sites:
        return []
    consts, assigns = _env(ctx, fi)
    out: List[Finding] = []
    for site in sites:
        if site.grid is None:
            continue
        # non-ceil floor division in a grid extent
        for entry in site.grid:
            for fd, anc in _floordivs(entry):
                if _is_ceil_div(fd, anc):
                    continue
                if _has_divisibility_guard(fi, fd.right):
                    continue
                out.append(_finding(
                    fi, fd, "KRN003",
                    "grid extent derived by floor division "
                    f"`{ast.unparse(fd)}` with no ceil-div and no "
                    "divisibility guard in scope — a ragged final tile "
                    "is silently dropped; use pl.cdiv(a, b) (masking "
                    "the tail in-kernel) or guard `a % b == 0`"))
        expected = len(site.grid) + site.num_scalar_prefetch
        for spec in (site.in_specs or []) + (site.out_specs or []):
            if spec.index_map is None:
                continue
            arity = map_arity(fi, spec.index_map, assigns)
            if arity is not None and arity != expected:
                out.append(_finding(
                    fi, spec.index_map, "KRN003",
                    f"index_map takes {arity} args but the site's grid "
                    f"rank + num_scalar_prefetch is {expected} "
                    f"(grid rank {len(site.grid)}, prefetch "
                    f"{site.num_scalar_prefetch}) — Pallas passes one "
                    "arg per grid axis plus one ref per prefetch "
                    "operand; the map silently mis-indexes"))
            # element-offset returns: multiplying by the own block dim
            mapdef = resolve_index_map_def(fi, spec.index_map, assigns)
            if mapdef is None or spec.shape is None:
                continue
            dim_names: Set[str] = set()
            dim_vals: Set[int] = set()
            for d in spec.shape:
                if isinstance(d, ast.Name):
                    dim_names.add(d.id)
                v = eval_dim(d, consts, assigns)
                if v is not None and v > 1:
                    dim_vals.add(v)
            for ret in _map_returns(mapdef):
                elems = ret.elts if isinstance(ret, ast.Tuple) \
                    else [ret]
                for el in elems:
                    for sub in ast.walk(el):
                        if not (isinstance(sub, ast.BinOp) and
                                isinstance(sub.op, ast.Mult)):
                            continue
                        for op in (sub.left, sub.right):
                            hit = (isinstance(op, ast.Name) and
                                   op.id in dim_names) or \
                                  (isinstance(op, ast.Constant) and
                                   op.value in dim_vals)
                            if hit:
                                out.append(_finding(
                                    fi, spec.index_map, "KRN003",
                                    "index_map return multiplies by "
                                    "the spec's own block dimension "
                                    f"(`{ast.unparse(sub)}`) — index "
                                    "maps return BLOCK indices and "
                                    "Pallas scales by the block shape "
                                    "itself; this double-scales the "
                                    "offset"))
                                break
    return out


# ------------------------------------------------------------------ KRN004
def _is_jnp_rooted(fi: FunctionInfo, name: str) -> bool:
    root = name.split(".")[0]
    target = fi.module.module_aliases.get(root, "")
    return target in ("jax.numpy",) or name.startswith(
        ("jnp.", "jax.numpy."))


def _purity_findings(member: FunctionInfo) -> List[Finding]:
    out: List[Finding] = []
    for node in R._body_walk(member):
        if isinstance(node, ast.While):
            out.append(_finding(
                member, node, "KRN004",
                "Python `while` inside a kernel body — Mosaic has no "
                "lowering for data-dependent Python control flow; use "
                "jax.lax.while_loop/fori_loop (or restructure over the "
                "grid)"))
        elif isinstance(node, ast.For):
            it = node.iter
            ok = isinstance(it, (ast.List, ast.Tuple, ast.Constant))
            if isinstance(it, ast.Call):
                tail = (callee_name(it) or "").rsplit(".", 1)[-1]
                ok = tail in _LOOP_ITER_TAILS
            if not ok:
                out.append(_finding(
                    member, node, "KRN004",
                    "Python `for` over a non-static iterable inside a "
                    "kernel body — only range/enumerate/zip over "
                    "Python ints unroll at trace time; iterating a "
                    "traced value needs lax.fori_loop"))
        elif isinstance(node, ast.Call):
            name = callee_name(node)
            if name is None:
                continue
            parts = name.split(".")
            tail = parts[-1]
            root_target = member.module.module_aliases.get(parts[0], "")
            if R._is_numpy_alias(member, parts[0]):
                out.append(_finding(
                    member, node, "KRN004",
                    f"host numpy call {name}(...) inside a kernel "
                    "body — np.* executes at trace time on host "
                    "values; a traced ref here either crashes or "
                    "silently bakes a constant; use jnp"))
            elif root_target.split(".")[0] in _HOST_MODULES or \
                    parts[0] in _HOST_MODULES:
                out.append(_finding(
                    member, node, "KRN004",
                    f"host-module call {name}(...) inside a kernel "
                    "body — clocks/RNG/IO do not exist on the TPU "
                    "core; hoist it out of the kernel"))
            elif tail in _HOST_CALL_TAILS or name.startswith("FLAGS"):
                out.append(_finding(
                    member, node, "KRN004",
                    f"impure call {name}(...) inside a kernel body — "
                    "flags reads, callbacks and debugging hooks have "
                    "no Mosaic lowering; resolve the value at trace "
                    "time and close over it"))
            elif _is_jnp_rooted(member, name) and \
                    tail in _MOSAIC_UNSUPPORTED:
                out.append(_finding(
                    member, node, "KRN004",
                    f"jnp.{tail}(...) has no Mosaic lowering "
                    "(value-dependent shape / unsupported gather) — "
                    "interpret mode runs it, hardware rejects it; "
                    "restructure with masks/top_k-style primitives"))
    return out


def krn004_kernel_purity(fi: FunctionInfo, ctx: KernelContext
                         ) -> List[Finding]:
    if not _kernel_sites(ctx, fi):
        return []
    out: List[Finding] = []
    for member in kernel_closure(ctx.graph, fi):
        out += _purity_findings(member)
    return out


# ------------------------------------------------------------------ KRN005
def _scratch_params(kernel: FunctionInfo, n_scratch: int) -> List[str]:
    node = kernel.node
    if not isinstance(node, ast.FunctionDef) or node.args.vararg:
        return []
    pos = [a.arg for a in node.args.posonlyargs + node.args.args]
    return [p for p in pos[-n_scratch:] if p.endswith("_ref")] \
        if n_scratch and len(pos) >= n_scratch else []


def _stores_to(name: str, node: ast.AST,
               self_ref_only: bool) -> List[ast.AST]:
    out: List[ast.AST] = []
    for sub in ast.walk(node):
        tgt = None
        if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
            tgt = sub.targets[0]
        elif isinstance(sub, ast.AugAssign):
            tgt = sub.target
        if not (isinstance(tgt, ast.Subscript) and
                isinstance(tgt.value, ast.Name) and
                tgt.value.id == name):
            continue
        if self_ref_only:
            carries = isinstance(sub, ast.AugAssign) or any(
                isinstance(v, ast.Name) and v.id == name
                for v in ast.walk(sub.value))
            if not carries:
                continue
        out.append(sub)
    return out


def _when_decorated(member: FunctionInfo) -> bool:
    node = member.node
    if not isinstance(node, ast.FunctionDef):
        return False
    for dec in node.decorator_list:
        if isinstance(dec, ast.Call) and \
                (callee_name(dec) or "").rsplit(".", 1)[-1] == "when":
            return True
    return False


def krn005_accumulation(fi: FunctionInfo, ctx: KernelContext
                        ) -> List[Finding]:
    out: List[Finding] = []
    # (a) low-precision scratch + carry-init, gated on sites OWNED here
    for site in _sites_of(ctx, fi):
        for sc in site.scratch or []:
            if sc.dtype in _LOW_PRECISION:
                out.append(_finding(
                    fi, site.call, "KRN005",
                    f"{sc.space} scratch declared {sc.dtype} — "
                    "reduction carries accumulate per grid step and "
                    "low-precision carries drift (bf16 has 8 mantissa "
                    "bits); declare scratch f32 and cast on the final "
                    "store"))
        kernel = site.kernel
        if kernel is None or site.scratch is None:
            continue
        closure = kernel_closure(ctx.graph, kernel)
        for pname in _scratch_params(kernel, len(site.scratch)):
            carries = [s for m in closure
                       for s in _stores_to(pname, m.node, True)]
            if not carries:
                continue
            inited = any(
                _when_decorated(m) and _stores_to(pname, m.node, False)
                for m in closure if m is not kernel)
            if not inited:
                out.append(_finding(
                    fi, site.call, "KRN005",
                    f"scratch ref `{pname}` of kernel "
                    f"{kernel.qualname} is carried across grid steps "
                    f"(self-referential store, line "
                    f"{carries[0].lineno}) but never initialized "
                    "under a @pl.when(step == 0) guard — VMEM scratch "
                    "persists across grid cells, so the first "
                    "accumulation reads stale data from the previous "
                    "cell"))
    # (b) unpinned dots, gated on being a kernel of some site
    if _kernel_sites(ctx, fi):
        for member in kernel_closure(ctx.graph, fi):
            for node in R._body_walk(member):
                if isinstance(node, ast.BinOp) and \
                        isinstance(node.op, ast.MatMult):
                    out.append(_finding(
                        member, node, "KRN005",
                        "`@` matmul inside a kernel body cannot pin "
                        "preferred_element_type — on bf16/int8 inputs "
                        "the MXU accumulates at input precision; use "
                        "jax.lax.dot_general(..., "
                        "preferred_element_type=jnp.float32)"))
                elif isinstance(node, ast.Call):
                    tail = (callee_name(node) or "").rsplit(".", 1)[-1]
                    if tail in _DOT_TAILS and not any(
                            kw.arg == "preferred_element_type"
                            for kw in node.keywords):
                        out.append(_finding(
                            member, node, "KRN005",
                            f"{tail}(...) inside a kernel body without "
                            "preferred_element_type — bf16/int8 "
                            "operands accumulate at input precision "
                            "on the MXU; pin "
                            "preferred_element_type=jnp.float32"))
    return out


# ------------------------------------------------------------------ KRN006
def krn006_ref_twin(fi: FunctionInfo, ctx: KernelContext
                    ) -> List[Finding]:
    entries = ctx.uncovered_entries.get(fi.module.relpath)
    if not entries or fi not in entries:
        return []
    return [_finding(
        fi, fi.node, "KRN006",
        f"public pallas entry point {fi.qualname}() has no pure-jnp "
        "twin — the repo's parity convention names it "
        f"{fi.qualname.rsplit('_pallas', 1)[0]}_ref (or _xla/_dense) "
        "so CPU CI can diff kernel output against a reference; "
        "without one, kernel regressions surface only on hardware")]
