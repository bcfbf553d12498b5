"""dtype-discipline: dequant affine arithmetic is f32; bf16 only at the dot.

The PR-4 rule (EXPERIMENTS.md §Perf H1, kernels/dequant_matmul.py): the
dequantization affine ``q * scale + zero`` must be computed in float32 —
bf16's 8-bit mantissa rounds the reconstruction grid — and bfloat16 may
appear only as the *operand dtype of the MXU dot* (cast after the affine).

This is an AST pass over ``kernels/``, ``models/layers.py`` and
``models/kv.py`` (the KV cache layouts).  It finds
affine-dequant expressions (an ``Add`` whose left operand is a ``Mult``)
and resolves the compute dtype of each factor through a per-function
symbol table:

* ``x.astype(jnp.float32)``            -> f32 (compliant)
* ``x.astype(jnp.bfloat16)``           -> bf16 (violation)
* ``x.astype(dt)`` with ``dt = y.dtype`` or a ``dtype=jnp.bfloat16``
  parameter default                    -> dynamic/bf16 (violation: the
  affine inherits whatever the activation carries)

Factors whose dtype cannot be resolved are *not* flagged (no guessing);
the violations this checker does report are therefore high-confidence.
Intentional bf16 affines — ``layers.deq`` and friends define the bf16
quantization *grid* that the bit-identity contract pins — live in the
baseline with per-entry justifications.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Optional

from .base import Finding, iter_py_files, rel

TARGET_GLOBS = ["src/repro/kernels/*.py", "src/repro/models/layers.py",
                "src/repro/models/kv.py"]

F32, BF16, DYN = "float32", "bfloat16", "dynamic"
_DTYPE_ATTRS = {"float32": F32, "bfloat16": BF16, "float16": BF16}


def _dtype_of_node(node: ast.AST, env: Dict[str, str]) -> Optional[str]:
    """Resolve a dtype-valued expression: jnp.float32, a Name, x.dtype."""
    if isinstance(node, ast.Attribute):
        if node.attr in _DTYPE_ATTRS:
            return _DTYPE_ATTRS[node.attr]
        if node.attr == "dtype":
            return DYN
    if isinstance(node, ast.Name):
        return env.get(node.id)
    return None


def _value_dtype(node: ast.AST, env: Dict[str, str]) -> Optional[str]:
    """Compute dtype of a value expression, best effort (None = unknown)."""
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
            and node.func.attr == "astype" and node.args:
        return _dtype_of_node(node.args[0], env)
    if isinstance(node, ast.Name):
        return env.get(node.id)
    if isinstance(node, ast.BinOp):
        # dtype of an arithmetic expr: any bf16/dyn factor taints it
        for side in (node.left, node.right):
            d = _value_dtype(side, env)
            if d in (BF16, DYN):
                return d
        l, r = _value_dtype(node.left, env), _value_dtype(node.right, env)
        if F32 in (l, r):
            return F32
    return None


class _FnChecker(ast.NodeVisitor):
    def __init__(self, file: str, fn: ast.FunctionDef):
        self.file = file
        self.fn = fn
        self.env: Dict[str, str] = {}
        self.findings: List[Finding] = []
        # parameter defaults: def f(..., dtype=jnp.bfloat16) taints `dtype`
        args = fn.args
        defaults = list(args.defaults) + list(args.kw_defaults or [])
        names = [a.arg for a in args.args][len(args.args)
                                           - len(args.defaults):] \
            + [a.arg for a in args.kwonlyargs]
        for name, d in zip(names, defaults):
            if d is None:
                continue
            dt = _dtype_of_node(d, {})
            if dt:
                self.env[name] = dt

    def visit_Assign(self, node: ast.Assign) -> None:
        if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
            dt = _value_dtype(node.value, self.env)
            if dt is None and isinstance(node.value, ast.Attribute) \
                    and node.value.attr == "dtype":
                dt = DYN
            if dt:
                self.env[node.targets[0].id] = dt
        self.generic_visit(node)

    def visit_BinOp(self, node: ast.BinOp) -> None:
        # affine dequant shape: (a * b) + c
        if isinstance(node.op, ast.Add) and \
                isinstance(node.left, ast.BinOp) and \
                isinstance(node.left.op, ast.Mult):
            factors = [node.left.left, node.left.right, node.right]
            bad = []
            for f in factors:
                d = _value_dtype(f, self.env)
                if d in (BF16, DYN):
                    bad.append(d)
            if bad:
                kind = BF16 if BF16 in bad else DYN
                self.findings.append(Finding(
                    file=self.file, line=node.lineno,
                    rule="dtype-discipline",
                    message=f"dequant affine computed in {kind} dtype; "
                            f"PR-4 rule: affine in f32, bf16 only as the "
                            f"dot operand", symbol=self.fn.name))
        self.generic_visit(node)


def check_source(src: str, file: str) -> List[Finding]:
    tree = ast.parse(src)
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fc = _FnChecker(file, node)
            for stmt in node.body:
                fc.visit(stmt)
            findings.extend(fc.findings)
    return findings


def check(root: Path) -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_py_files(root, TARGET_GLOBS):
        findings.extend(check_source(path.read_text(), rel(path, root)))
    return findings
