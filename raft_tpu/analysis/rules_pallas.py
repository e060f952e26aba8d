"""R4 Pallas-budget and R6 interpret-coverage — the kernel-discipline
rules. Both walk every ``pallas_call`` in the tree, so ops guarding and
linting share one traversal.

R4 enforces what ``resolve_scan_engine`` assumes when it promises a
kernel will compile:

- every ``pallas_call`` must set ``compiler_params`` as
  ``pltpu.CompilerParams`` with an explicit ``vmem_limit_bytes`` — an unbounded kernel is sized by Mosaic's
  default and dies on the first big shape;
- when every BlockSpec / scratch shape folds to constants, the summed
  VMEM footprint (double-buffered blocks + scratch) must fit the
  declared limit and the 128 MB physical ceiling — dynamically-sized
  kernels are expected to self-limit the way ``ivf_scan`` does, and
  are covered by the explicit-limit check instead;
- a grid dimension computed as ``a // b`` must point at a round-up
  binding (``-(-x // b) * b`` or ``pl.cdiv``) — a plain floor division
  silently drops the ragged tail of the last block.

R6 is the old ``tests/test_ops_guard.py`` walk behind the registry:
every kernel module under ``raft_tpu/ops/`` must expose a public entry
with an ``interpret`` parameter, and some test must call each entry
with ``interpret=True`` — CPU CI must always cover kernel numerics
even though Mosaic only compiles on real TPUs.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from raft_tpu.analysis import astutil
from raft_tpu.analysis.core import Finding, Project, rule

VMEM_PHYSICAL_BYTES = 128 << 20  # v4+ physical VMEM per core

_DTYPE_BYTES = {
    "float32": 4, "f32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "bf16": 2, "float16": 2, "int16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "bool": 1,
    "float64": 8, "int64": 8,
}


def _dtype_bytes(expr: Optional[ast.AST]) -> int:
    leaf = (astutil.dotted(expr) or "").split(".")[-1] if expr else ""
    return _DTYPE_BYTES.get(leaf, 4)


def _enclosing_function(tree: ast.AST, call: ast.Call):
    best = None
    for fn in astutil.collect_functions(tree):
        if fn.lineno <= call.lineno and (
                best is None or fn.lineno > best.lineno):
            # containment by line span (ast gives end_lineno on 3.8+)
            if getattr(fn, "end_lineno", 1 << 30) >= call.lineno:
                best = fn
    return best


def _is_roundup_of(binding: ast.AST, divisor: ast.AST,
                   env: Optional[astutil.Env] = None,
                   depth: int = 1) -> bool:
    """Match the repo's pad idioms against the grid divisor ``b``:
    ``-(-x // b) * b``, ``x + (-x) % b`` (via a pad variable), or
    ``pl.cdiv(x, b)``. Resolves names one level through ``env`` so a
    ``pad_q = (-q) % B; qp = q + pad_q`` chain is recognized."""
    want = ast.dump(divisor)

    def same(node):
        return ast.dump(node) == want

    for n in ast.walk(binding):
        if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mult):
            for inner, mul in ((n.left, n.right), (n.right, n.left)):
                if not same(mul):
                    continue
                for m in ast.walk(inner):
                    if isinstance(m, ast.BinOp) \
                            and isinstance(m.op, ast.FloorDiv) \
                            and same(m.right):
                        return True
        if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mod) \
                and same(n.right):
            return True
        if isinstance(n, ast.Call):
            nm = (astutil.call_name(n) or "").split(".")[-1]
            if nm == "cdiv" and len(n.args) == 2 and same(n.args[1]):
                return True
        if isinstance(n, ast.Name) and env is not None and depth > 0 \
                and n.id not in env.multi:
            sub = env.bindings.get(n.id)
            if sub is not None and sub is not binding \
                    and _is_roundup_of(sub, divisor, env, depth - 1):
                return True
    return False


def _pallas_calls(tree: ast.AST) -> List[ast.Call]:
    return [n for n in ast.walk(tree)
            if isinstance(n, ast.Call)
            and (astutil.call_name(n) or "").split(".")[-1]
            == "pallas_call"]


def _kw(call: ast.Call, name: str) -> Optional[ast.AST]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _collect_specs(call: ast.Call, fn) -> Tuple[List[ast.Call],
                                                List[ast.Call]]:
    """(BlockSpec calls, VMEM scratch calls) reachable from this
    pallas_call — through grid_spec=/in_specs=/out_specs= kwargs,
    following one level of local-name indirection."""
    roots: List[ast.AST] = []
    for name in ("grid_spec", "in_specs", "out_specs", "scratch_shapes"):
        v = _kw(call, name)
        if v is not None:
            roots.append(v)
    env_bindings = {}
    if fn is not None:
        for stmt in astutil.walk_in_order(fn.body):
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                env_bindings[stmt.targets[0].id] = stmt.value
    resolved: List[ast.AST] = []
    for r in roots:
        if isinstance(r, ast.Name) and r.id in env_bindings:
            resolved.append(env_bindings[r.id])
        else:
            resolved.append(r)
    blockspecs, scratch = [], []
    for r in resolved:
        for n in ast.walk(r):
            if isinstance(n, ast.Call):
                leaf = (astutil.call_name(n) or "").split(".")[-1]
                if leaf == "BlockSpec":
                    blockspecs.append(n)
                elif leaf in ("VMEM", "SMEM"):
                    scratch.append(n)
    return blockspecs, scratch


@rule("R4", "pallas-budget")
def check_pallas_budget(project: Project) -> Iterable[Finding]:
    """pallas_call compiler-params discipline, static VMEM footprint
    vs the declared limit, and grid round-up evidence."""
    out: List[Finding] = []
    for f in project.lib():
        if f.tree is None:
            continue
        for call in _pallas_calls(f.tree):
            fn = _enclosing_function(f.tree, call)
            env = astutil.Env(fn) if fn is not None else None

            cp = _kw(call, "compiler_params")
            vmem_limit = None
            if cp is None:
                out.append(Finding(
                    "R4", f.rel, call.lineno,
                    "pallas_call without compiler_params — pass "
                    "pltpu.CompilerParams(vmem_limit_bytes=...) so "
                    "the kernel states its VMEM budget"))
            else:
                cp_name = (astutil.call_name(cp) or "") if isinstance(
                    cp, ast.Call) else ""
                leaf = cp_name.split(".")[-1]
                if leaf != "CompilerParams":
                    out.append(Finding(
                        "R4", f.rel, cp.lineno,
                        "compiler_params is not built via "
                        "pltpu.CompilerParams"))
                if isinstance(cp, ast.Call):
                    vl = _kw(cp, "vmem_limit_bytes")
                    if vl is None:
                        out.append(Finding(
                            "R4", f.rel, cp.lineno,
                            "compiler_params without vmem_limit_bytes "
                            "— declare the budget resolve_scan_engine "
                            "checks against"))
                    else:
                        vmem_limit = astutil.const_fold(vl, env)

            # static VMEM estimate — exact when every shape folds;
            # when a dim doesn't const-fold, fall back to a symbolic
            # upper bound (min(n, CAP) is bounded by CAP even when n
            # is runtime) so bounded-dynamic kernels stay inside the
            # rule's reach instead of silently escaping it
            blockspecs, scratch = _collect_specs(call, fn)
            total = 0
            all_static = bool(blockspecs or scratch)
            bounded = False
            for bs in blockspecs:
                shape = bs.args[0] if bs.args else _kw(bs, "block_shape")
                dims = astutil.fold_shape(shape, env) if shape is not None \
                    else None
                if dims is None and shape is not None:
                    dims = astutil.shape_upper_bound(shape, env)
                    if dims is not None:
                        bounded = True
                if dims is None:
                    all_static = False
                    break
                n = 1
                for d in dims:
                    n *= max(int(d), 1)
                total += 2 * n * 4  # double-buffered, f32-conservative
            if all_static:
                for sc in scratch:
                    shape = sc.args[0] if sc.args else None
                    dims = astutil.fold_shape(shape, env)
                    if dims is None and shape is not None:
                        dims = astutil.shape_upper_bound(shape, env)
                        if dims is not None:
                            bounded = True
                    if dims is None:
                        all_static = False
                        break
                    n = 1
                    for d in dims:
                        n *= max(int(d), 1)
                    total += n * _dtype_bytes(
                        sc.args[1] if len(sc.args) > 1 else None)
            if all_static:
                budget = min(vmem_limit or VMEM_PHYSICAL_BYTES,
                             VMEM_PHYSICAL_BYTES)
                if total > budget:
                    kind = ("VMEM upper bound" if bounded
                            else "static VMEM footprint")
                    out.append(Finding(
                        "R4", f.rel, call.lineno,
                        f"{kind} ~{total >> 20} MiB "
                        "(double-buffered blocks + scratch) exceeds "
                        f"the {int(budget) >> 20} MiB budget — shrink "
                        "the BlockSpecs or raise vmem_limit_bytes"))

            # grid round-up evidence
            grid = _kw(call, "grid")
            if grid is None:
                gs = _kw(call, "grid_spec")
                if isinstance(gs, ast.Name) and env is not None:
                    gs = env.bindings.get(gs.id)
                if isinstance(gs, ast.Call):
                    grid = _kw(gs, "grid")
            if isinstance(grid, (ast.Tuple, ast.List)) and env is not None:
                for el in grid.elts:
                    expr = el
                    if isinstance(expr, ast.Name) \
                            and expr.id not in env.multi:
                        expr = env.bindings.get(expr.id, expr)
                    if isinstance(expr, ast.BinOp) and isinstance(
                            expr.op, ast.FloorDiv) and isinstance(
                            expr.left, ast.Name):
                        binding = env.bindings.get(expr.left.id)
                        if expr.left.id in env.multi or binding is None:
                            continue
                        if not _is_roundup_of(binding, expr.right, env):
                            out.append(Finding(
                                "R4", f.rel, el.lineno,
                                f"grid dimension "
                                f"'{expr.left.id} // ...' but "
                                f"'{expr.left.id}' is not padded up to "
                                "the divisor — a ragged tail would be "
                                "silently dropped; pad with "
                                "-(-x // b) * b or pl.cdiv"))
    return out


# ---------------------------------------------------------------------------
# R6 — interpret-mode coverage (the ops guard, shared traversal)
# ---------------------------------------------------------------------------


def public_kernel_entries(project: Project) -> Dict[str, List[str]]:
    """Per ops module: public module-level functions exposing an
    ``interpret`` knob — the kernel-entry convention of the package."""
    out: Dict[str, List[str]] = {}
    for f in project.lib():
        if not f.rel.startswith("raft_tpu/ops/") or f.tree is None:
            continue
        if not _pallas_calls(f.tree):
            continue
        entries = []
        for node in f.tree.body:
            if not isinstance(node, ast.FunctionDef) \
                    or node.name.startswith("_"):
                continue
            names = {a.arg for a in node.args.args
                     + node.args.kwonlyargs}
            if "interpret" in names:
                entries.append(node.name)
        out[f.rel] = entries
    return out


def interpret_covered_names(project: Project) -> Set[str]:
    """Names some test calls with a literal ``interpret=True`` — a
    docstring mention cannot satisfy the guard, only a call site."""
    covered: Set[str] = set()
    for f in project.tests():
        if f.tree is None:
            continue
        for node in ast.walk(f.tree):
            if not isinstance(node, ast.Call):
                continue
            nm = (astutil.call_name(node) or "").split(".")[-1]
            if not nm:
                continue
            for kw in node.keywords:
                if kw.arg == "interpret" \
                        and isinstance(kw.value, ast.Constant) \
                        and kw.value.value is True:
                    covered.add(nm)
    return covered


@rule("R6", "interpret-coverage", scope="program")
def check_interpret_coverage(project: Project) -> Iterable[Finding]:
    """Every pallas_call module under raft_tpu/ops/ exposes public
    entries with an ``interpret`` knob, and every entry has an
    interpret=True call site in some test."""
    out: List[Finding] = []
    covered = interpret_covered_names(project)
    for rel, entries in sorted(public_kernel_entries(project).items()):
        if not entries:
            out.append(Finding(
                "R6", rel, 1,
                "module contains pallas_call but exposes no public "
                "entry with an `interpret` parameter — CPU CI cannot "
                "cover the kernel"))
            continue
        for name in entries:
            if name not in covered:
                out.append(Finding(
                    "R6", rel, 1,
                    f"kernel entry '{name}' has no interpret=True call "
                    "in any test — add an interpret-mode parity test"))
    return out
