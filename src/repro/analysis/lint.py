"""Repo-invariant AST lint (no dependency beyond the stdlib ``ast``).

Encodes the invariants this codebase keeps re-breaking in review, as
mechanical checks:

``poly-no-math``
    No ``math.*`` calls in the scalar/array-polymorphic Eq. 1-7 path
    (``core/cost.py``, ``core/collectives.py``, ``core/validate.py``,
    ``core/numerics.py`` and their array callers): ``math.ceil`` on a
    NumPy array raises (or silently scalarizes) and breaks the batched
    engine's SoA pass.  Scalar-only helpers (e.g. the factor-table
    builders in ``collectives.py``) are allowlisted by function name.

``poly-array-branch``
    No array-truthiness branches in the same files: ``if dv <= 0:`` on an
    array raises "truth value is ambiguous".  Lines audited to be
    scalar-only carry a ``# scalar-ok`` pragma; comparisons against
    strings/None, ``is``/``in`` tests, and guards on ``.size``/``.ndim``/
    ``len()``/``isinstance()``/``is_array()`` are recognized as scalar.
    Builtin ``max``/``min`` over 2+ positional args are flagged too
    (use ``numerics.vmax``/``vmin``).

``kernel-no-host``
    No float64 references, host NumPy (``np.*``), ``.item()``/
    ``.tolist()``/``device_get`` round-trips inside Pallas kernel bodies
    (functions passed to ``pl.pallas_call``): each is either a tracing
    error or a silent performance cliff on TPU.

``core-no-sqlite``
    No raw ``sqlite3`` access in ``core/`` outside ``planstore.py``'s
    retry/degradation wrapper.

``vmem-budget``
    Static VMEM working-set estimation: block shapes and scratch shapes
    are extracted from each kernel's ``pallas_call`` declaration by AST
    and evaluated against every VMEM-feasible candidate the autotuner can
    emit for the paper shapes; (working set x 2 for double buffering)
    must fit the scoped VMEM limit every kernel requests from Mosaic
    (``kernels/autotune.py::VMEM_BUDGET``).  An un-evaluatable
    declaration is itself a finding — the extraction must not silently
    rot.

Adding a rule: write a ``check_<name>(ctx) -> Iterable[LintFinding]``
function, register it in ``RULES``, and document it here and in
ARCHITECTURE.md ("Static contracts").
"""
from __future__ import annotations

import ast
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

__all__ = ["LintFinding", "lint_repo", "lint_source", "RULES",
           "vmem_findings"]

_PKG_ROOT = Path(__file__).resolve().parents[1]   # src/repro

PRAGMA = "scalar-ok"

# Files on the scalar/array-polymorphic Eq. 1-7 path.
POLY_FILES = (
    "core/cost.py",
    "core/collectives.py",
    "core/validate.py",
    "core/numerics.py",
    "core/batcheval.py",
    "core/mapping.py",
)

# Scalar-only helpers inside poly files where math.* is legitimate.
MATH_ALLOWED_FUNCS: Dict[str, Set[str]] = {
    "core/collectives.py": {"_step_distances", "_scalar_factors",
                            "_factor_table", "_mesh_avg_distance",
                            "overlapped_collective_seconds"},
}

# Functions that are documented scalar-only paths (validated entry points,
# table builders): array-truthiness rules do not apply inside them.
SCALAR_ONLY_FUNCS: Dict[str, Set[str]] = {
    "core/collectives.py": {"_step_distances", "_scalar_factors",
                            "_factor_table", "_mesh_avg_distance",
                            "overlapped_collective_seconds"},
    "core/validate.py": {"validate_headroom_levels", "validate_tree"},
}

KERNEL_DIR = "kernels"
KERNEL_EXEMPT = {"kernels/autotune.py"}  # host-side planner, no kernel body

CORE_SQLITE_OWNER = "core/planstore.py"


@dataclass(frozen=True)
class LintFinding:
    rule: str
    path: str        # package-relative, e.g. "core/cost.py"
    line: int
    col: int
    message: str

    def describe(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.rule}] {self.message}"

    def to_dict(self) -> Dict:
        return {"rule": self.rule, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message}


@dataclass
class _Ctx:
    path: str                  # package-relative posix path
    tree: ast.AST
    lines: List[str]

    def pragma(self, node: ast.AST) -> bool:
        ln = getattr(node, "lineno", 0)
        if 1 <= ln <= len(self.lines):
            return PRAGMA in self.lines[ln - 1]
        return False


def _enclosing_funcs(tree: ast.AST) -> Dict[ast.AST, str]:
    """Map every node to the name of its innermost enclosing function."""
    owner: Dict[ast.AST, str] = {}

    def walk(node: ast.AST, fn: str) -> None:
        for child in ast.iter_child_nodes(node):
            name = fn
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                name = child.name
            owner[child] = name
            walk(child, name)

    owner[tree] = ""
    walk(tree, "")
    return owner


# --------------------------------------------------------- rule: poly math


def check_poly_math(ctx: _Ctx) -> Iterable[LintFinding]:
    if ctx.path not in POLY_FILES:
        return []
    allowed = MATH_ALLOWED_FUNCS.get(ctx.path, set())
    owner = _enclosing_funcs(ctx.tree)
    out = []
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "math"):
            if owner.get(node, "") in allowed or ctx.pragma(node):
                continue
            out.append(LintFinding(
                "poly-no-math", ctx.path, node.lineno, node.col_offset,
                f"math.{node.attr} in the scalar/array-polymorphic path "
                f"(use numerics.* / numpy ufuncs, or allowlist the "
                f"scalar-only helper)"))
    return out


# ------------------------------------------------- rule: poly array branch


_SCALAR_ATTRS = {"size", "ndim", "shape"}
_SCALAR_CALLS = {"len", "int", "float", "bool", "isinstance", "is_array",
                 "hasattr", "getattr", "callable"}


def _is_scalar_expr(node: ast.expr) -> bool:
    """Conservatively true when an expression is guaranteed non-array.

    Numeric constants are deliberately NOT scalar evidence: ``dv <= 0``
    with an array ``dv`` is the canonical array-truthiness bug, so a
    numeric literal on one side says nothing about the other side.
    """
    if isinstance(node, ast.Constant):
        return isinstance(node.value, (str, bytes, bool)) or node.value is None
    if isinstance(node, ast.UnaryOp):
        return _is_scalar_expr(node.operand)
    if isinstance(node, ast.Attribute):
        return node.attr in _SCALAR_ATTRS
    if isinstance(node, ast.Call):
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id in _SCALAR_CALLS:
            return True
        if isinstance(fn, ast.Attribute) and fn.attr in ("all", "any"):
            return True   # np.all(...) / arr.all() reduce to a scalar bool
    if isinstance(node, ast.BinOp):
        return _is_scalar_expr(node.left) and _is_scalar_expr(node.right)
    return False


def _compare_is_scalar(node: ast.Compare) -> bool:
    if all(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
           for op in node.ops):
        return True
    operands = [node.left, *node.comparators]
    if any(isinstance(o, ast.Constant) and isinstance(o.value, (str, bytes))
           for o in operands):
        return True   # string equality (schedule names etc.)
    if any(isinstance(o, ast.Tuple) and not o.elts for o in operands):
        return True   # sentinel compare against the empty tuple
    return any(_is_scalar_expr(o) for o in operands)


def _condition_findings(ctx: _Ctx, cond: ast.expr, owner: Dict[ast.AST, str],
                        scalar_funcs: Set[str]) -> Iterable[LintFinding]:
    stack = [cond]
    while stack:
        node = stack.pop()
        if isinstance(node, ast.BoolOp):
            stack.extend(node.values)
            continue
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
            stack.append(node.operand)
            continue
        if isinstance(node, ast.Compare):
            if _compare_is_scalar(node):
                continue
            if owner.get(node, "") in scalar_funcs or ctx.pragma(node):
                continue
            yield LintFinding(
                "poly-array-branch", ctx.path, node.lineno, node.col_offset,
                "comparison used as a branch condition in the "
                "array-polymorphic path — ambiguous for arrays (use "
                "numerics.vwhere / np.where, or mark the audited scalar "
                "site with '# scalar-ok')")


def check_poly_branches(ctx: _Ctx) -> Iterable[LintFinding]:
    if ctx.path not in POLY_FILES:
        return []
    scalar_funcs = SCALAR_ONLY_FUNCS.get(ctx.path, set())
    owner = _enclosing_funcs(ctx.tree)
    out: List[LintFinding] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.If, ast.While)):
            out.extend(_condition_findings(ctx, node.test, owner,
                                           scalar_funcs))
        elif isinstance(node, ast.IfExp):
            out.extend(_condition_findings(ctx, node.test, owner,
                                           scalar_funcs))
        elif isinstance(node, ast.Call):
            fn = node.func
            if (isinstance(fn, ast.Name) and fn.id in ("max", "min")
                    and len(node.args) >= 2
                    and not any(isinstance(a, ast.Starred)
                                for a in node.args)):
                if owner.get(node, "") in scalar_funcs or ctx.pragma(node):
                    continue
                out.append(LintFinding(
                    "poly-array-branch", ctx.path, node.lineno,
                    node.col_offset,
                    f"builtin {fn.id}() over multiple args in the "
                    f"array-polymorphic path (use numerics.vmax/vmin, or "
                    f"'# scalar-ok')"))
    return out


# ----------------------------------------------------- rule: kernel bodies


def _kernel_function_names(tree: ast.AST) -> Set[str]:
    """Names of functions handed to pl.pallas_call (directly or through
    functools.partial), plus the ``*_kernel`` naming convention."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name.endswith("_kernel") or node.name == "_kernel":
                names.add(node.name)
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr == "pallas_call":
                for arg in node.args[:1]:
                    if isinstance(arg, ast.Name):
                        names.add(arg.id)
                    elif isinstance(arg, ast.Call):
                        for sub in arg.args[:1]:
                            if isinstance(sub, ast.Name):
                                names.add(sub.id)
    return names


def check_kernel_host(ctx: _Ctx) -> Iterable[LintFinding]:
    if not ctx.path.startswith(KERNEL_DIR + "/") or ctx.path in KERNEL_EXEMPT:
        return []
    kernel_names = _kernel_function_names(ctx.tree)
    out: List[LintFinding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name not in kernel_names:
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute):
                if sub.attr == "float64" or sub.attr == "f64":
                    out.append(LintFinding(
                        "kernel-no-host", ctx.path, sub.lineno,
                        sub.col_offset,
                        f"float64 reference inside kernel body "
                        f"'{node.name}' (TPU kernels are f32/bf16)"))
                elif (isinstance(sub.value, ast.Name)
                        and sub.value.id in ("np", "numpy")):
                    out.append(LintFinding(
                        "kernel-no-host", ctx.path, sub.lineno,
                        sub.col_offset,
                        f"host numpy ({sub.value.id}.{sub.attr}) inside "
                        f"kernel body '{node.name}' (use jnp/jax.lax)"))
                elif sub.attr in ("item", "tolist", "device_get"):
                    out.append(LintFinding(
                        "kernel-no-host", ctx.path, sub.lineno,
                        sub.col_offset,
                        f".{sub.attr} host round-trip inside kernel body "
                        f"'{node.name}'"))
            elif (isinstance(sub, ast.Constant) and sub.value == "float64"):
                out.append(LintFinding(
                    "kernel-no-host", ctx.path, sub.lineno, sub.col_offset,
                    f"'float64' dtype string inside kernel body "
                    f"'{node.name}'"))
    return out


# ------------------------------------------------------ rule: core sqlite


def check_core_sqlite(ctx: _Ctx) -> Iterable[LintFinding]:
    if not ctx.path.startswith("core/") or ctx.path == CORE_SQLITE_OWNER:
        return []
    out = []
    for node in ast.walk(ctx.tree):
        bad = None
        if isinstance(node, ast.Import):
            if any(a.name.split(".")[0] == "sqlite3" for a in node.names):
                bad = "import sqlite3"
        elif isinstance(node, ast.ImportFrom):
            if (node.module or "").split(".")[0] == "sqlite3":
                bad = "from sqlite3 import"
        if bad:
            out.append(LintFinding(
                "core-no-sqlite", ctx.path, node.lineno, node.col_offset,
                f"{bad} outside planstore.py — all SQLite access goes "
                f"through core/planstore.py's retry/degradation wrapper"))
    return out


# ------------------------------------------------------- rule: vmem budget


class _ShapeEval(ast.NodeVisitor):
    """Safe arithmetic evaluator for block-shape expressions."""

    def __init__(self, env: Dict[str, int]):
        self.env = env

    def eval(self, node: ast.expr) -> int:
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return node.value
        if isinstance(node, ast.Name):
            if node.id in self.env:
                return int(self.env[node.id])
            raise KeyError(node.id)
        if isinstance(node, ast.BinOp):
            left, right = self.eval(node.left), self.eval(node.right)
            if isinstance(node.op, ast.Add):
                return left + right
            if isinstance(node.op, ast.Sub):
                return left - right
            if isinstance(node.op, ast.Mult):
                return left * right
            if isinstance(node.op, ast.FloorDiv):
                return left // right
            if isinstance(node.op, ast.Mod):
                return left % right
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return -self.eval(node.operand)
        raise ValueError(ast.dump(node))


_DTYPE_ATTR_BYTES = {"float32": 4, "f32": 4, "bfloat16": 2, "bf16": 2,
                     "float16": 2, "int32": 4, "uint32": 4, "int8": 1}


def _pallas_decl(tree: ast.AST) -> Optional[Dict]:
    """Extract (in_specs shapes, out_specs shape, scratch (shape, bytes))
    expression lists from the first pallas_call in a module."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "pallas_call"):
            continue
        decl = {"in": [], "out": [], "scratch": [], "line": node.lineno}

        def block_shape(call: ast.expr):
            if (isinstance(call, ast.Call) and call.args
                    and isinstance(call.args[0], ast.Tuple)):
                return call.args[0].elts
            return None

        for kw in node.keywords:
            if kw.arg == "in_specs" and isinstance(kw.value, (ast.List,
                                                              ast.Tuple)):
                for el in kw.value.elts:
                    shp = block_shape(el)
                    if shp is not None:
                        decl["in"].append(shp)
            elif kw.arg == "out_specs":
                els = (kw.value.elts
                       if isinstance(kw.value, (ast.List, ast.Tuple))
                       else [kw.value])
                for el in els:
                    shp = block_shape(el)
                    if shp is not None:
                        decl["out"].append(shp)
            elif kw.arg == "scratch_shapes" and isinstance(
                    kw.value, (ast.List, ast.Tuple)):
                for el in kw.value.elts:
                    if isinstance(el, ast.Call) and el.args:
                        shp = (el.args[0].elts
                               if isinstance(el.args[0], ast.Tuple) else None)
                        nbytes = 4
                        if len(el.args) > 1 and isinstance(el.args[1],
                                                           ast.Attribute):
                            nbytes = _DTYPE_ATTR_BYTES.get(
                                el.args[1].attr, 4)
                        if shp is not None:
                            decl["scratch"].append((shp, nbytes))
        return decl
    return None


def _kernel_vmem_cases() -> Dict[str, Tuple[List[Dict[str, int]], str]]:
    """Per kernel file: the candidate-variable environments the autotuner
    can emit for the paper shapes (the feasible sets its VMEM filters
    produce), plus a label for reports."""
    from repro.kernels.allgather_gemm import BUDGET_SHAPES
    from repro.kernels.autotune import (PAPER_KERNEL_SHAPES,
                                        _attention_pairs, _gemm_pairs,
                                        _ssd_chunk_cands)
    gemm_envs, attn_envs, ssd_envs = [], [], []
    for m, n, k in PAPER_KERNEL_SHAPES["gemm_epilogue_blocks"]:
        for bm, bk in _gemm_pairs(m, n, k):
            gemm_envs.append({"block_m": bm, "block_k": bk, "N": n})
    for sq, skv, d in PAPER_KERNEL_SHAPES["attention_blocks"]:
        for bq, bk in _attention_pairs(sq, skv, d):
            attn_envs.append({"block_q": bq, "block_k": bk, "D": d})
    for s, p, n in PAPER_KERNEL_SHAPES["ssd_chunk_len"]:
        for c in _ssd_chunk_cands(s, p, n):
            ssd_envs.append({"chunk": c, "P": p, "N": n})
    # the streamed all-gather-GEMM declares its double buffers explicitly
    # (a ``buffers`` axis on the scratch shapes), so the envs cross both
    # buffer counts; the rule's global x2 stays as conservative headroom
    agg_envs = [{"buffers": b, "M": m, "kc": k // c, "N": n}
                for m, k, n, c in BUDGET_SHAPES for b in (1, 2)]
    return {
        "kernels/gemm_softmax.py": (gemm_envs, "gemm paper shapes"),
        "kernels/gemm_layernorm.py": (gemm_envs, "gemm paper shapes"),
        "kernels/flash_attention.py": (attn_envs, "attention paper shapes"),
        "kernels/ssd.py": (ssd_envs, "ssd paper shapes"),
        "kernels/allgather_gemm.py": (agg_envs,
                                      "all-gather-GEMM stream shapes"),
    }


def vmem_findings(root: Optional[Path] = None) -> List[LintFinding]:
    """Static VMEM working-set check of every kernel's pallas_call
    declaration against the scoped VMEM limit the kernels request
    (``autotune.VMEM_BUDGET``), across all autotuner-feasible candidate
    blocks for the paper shapes."""
    from repro.kernels.autotune import VMEM_BUDGET
    root = root or _PKG_ROOT
    capacity = VMEM_BUDGET
    block_bytes = 2  # kernels take/emit bf16 blocks; scratch dtype is read
    out: List[LintFinding] = []
    for rel, (envs, label) in _kernel_vmem_cases().items():
        path = root / rel
        if not path.exists():
            continue
        tree = ast.parse(path.read_text())
        decl = _pallas_decl(tree)
        if decl is None:
            out.append(LintFinding("vmem-budget", rel, 1, 0,
                                   "no pallas_call declaration found "
                                   "(extraction rot — update the lint)"))
            continue
        worst = (0, None)
        for env in envs:
            ev = _ShapeEval(env)
            try:
                total = 0
                for shp in decl["in"] + decl["out"]:
                    n = 1
                    for e in shp:
                        n *= ev.eval(e)
                    total += n * block_bytes
                for shp, nbytes in decl["scratch"]:
                    n = 1
                    for e in shp:
                        n *= ev.eval(e)
                    total += n * nbytes
            except (KeyError, ValueError) as exc:
                out.append(LintFinding(
                    "vmem-budget", rel, decl["line"], 0,
                    f"could not statically evaluate a block shape with "
                    f"candidate env {env} ({exc!r}) — update "
                    f"_kernel_vmem_cases"))
                break
            if total > worst[0]:
                worst = (total, env)
        else:
            working = worst[0] * 2  # double buffering
            if working > capacity:
                out.append(LintFinding(
                    "vmem-budget", rel, decl["line"], 0,
                    f"declared working set {worst[0]} B x2 (double "
                    f"buffer) exceeds the kernels' scoped VMEM limit "
                    f"{capacity} B for "
                    f"candidate {worst[1]} ({label})"))
    return out


# ------------------------------------------------------------------ driver


RULES = {
    "poly-no-math": check_poly_math,
    "poly-array-branch": check_poly_branches,
    "kernel-no-host": check_kernel_host,
    "core-no-sqlite": check_core_sqlite,
}


def lint_source(source: str, path: str) -> List[LintFinding]:
    """Lint one in-memory module under a package-relative ``path`` (the
    path selects which rules apply) — the unit-test entry point."""
    ctx = _Ctx(path=path, tree=ast.parse(source),
               lines=source.splitlines())
    out: List[LintFinding] = []
    for check in RULES.values():
        out.extend(check(ctx))
    return out


def lint_repo(root: Optional[Path] = None,
              with_vmem: bool = True) -> List[LintFinding]:
    """Run every rule over the package tree (``src/repro``)."""
    root = root or _PKG_ROOT
    out: List[LintFinding] = []
    for path in sorted(root.rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        try:
            source = path.read_text()
            out.extend(lint_source(source, rel))
        except SyntaxError as exc:
            out.append(LintFinding("parse-error", rel,
                                   exc.lineno or 1, 0, str(exc)))
    if with_vmem:
        out.extend(vmem_findings(root))
    return sorted(out, key=lambda f: (f.path, f.line, f.rule))
