"""Dtype-discipline lint for pricing paths.

PRs 1–5 established (and the parity tests depend on) a hard rule on
every path that prices a design — simulator, router, categories,
designer, FMMD/mixing/SCA: **all priced quantities are float64, all
index arrays are int64**. A single float32 literal perturbs makespans
enough to break bitwise reference parity; an int32 index array
overflows silently at the 5000-agent scale ROADMAP item 5 targets
(5000² dense link ids exceed int32).

Scanned: ``net/`` plus the pricing modules of ``core/`` (the learning
half — gossip/dpsgd/compression — legitimately trades in float32
wire formats and is out of scope).

``narrow-float-dtype``  np/jnp float32/float16/half/single references
``narrow-int-dtype``    np/jnp int32/int16/int8/uint* references
``narrow-dtype-string`` "float32"/"single"/"int32"/"f4"/"i4"… string
                        dtype literals in array constructors/casts,
                        including the method spellings
                        ``.astype("float32")`` / ``.view("float32")``
                        (``.astype(np.float32)`` is caught by the
                        attribute rules at the dtype reference)
``implicit-jnp-dtype``  dtype-less ``jnp.zeros``/``ones``/``empty``/
                        ``full``/``arange`` — numpy defaults to
                        float64 but jax defaults to float32 (and
                        int32 for ``arange``) unless x64 is on, so an
                        implicit jnp dtype silently narrows whenever
                        it is traced outside the pricing launch's
                        x64 scope
"""

from __future__ import annotations

import ast
from pathlib import Path

from repro.analysis.common import (
    Finding,
    ScopedVisitor,
    dotted_name,
    iter_python_files,
    parse_file,
    rel,
)

CHECKER = "dtypes"

# Pricing paths: the whole network stack plus core's design/pricing
# modules. core/gossip.py, core/dpsgd.py and runtime/compression.py
# are the *learning* half (float32 wire formats are intentional there).
SCAN_DIRS = [
    "src/repro/net",
    "src/repro/core/designer.py",
    "src/repro/core/fmmd.py",
    "src/repro/core/mixing.py",
    "src/repro/core/sca.py",
    "src/repro/core/topology_baselines.py",
    "src/repro/core/weight_opt.py",
]

_NARROW_FLOAT = {"float32", "float16", "half", "single", "longdouble"}
_NARROW_INT = {
    "int32", "int16", "int8", "uint8", "uint16", "uint32", "uint64",
    "short", "intc",
}
_NARROW_STRINGS = {
    "float32", "float16", "half", "single", "f4", "f2", "<f4", "<f2",
    "int32", "int16", "int8", "i4", "i2", "i1",
    "<i4", "<i2", "uint8", "uint16", "uint32", "u4",
}
# ``view``/``astype`` are *method* spellings of a cast — narrowing via
# ``x.view("float32")`` is the same violation as ``np.float32(x)``.
_ARRAY_BUILDERS = {
    "array", "asarray", "zeros", "ones", "empty", "full", "arange",
    "astype", "view", "dtype", "frombuffer", "fromiter",
}
# jnp builders whose *implicit* dtype is jax's (float32/int32 without
# x64) rather than numpy's float64 — these must spell dtype= on
# pricing paths. Maps builder -> number of positional args after which
# a positional dtype appears (arange's positionals are all numeric, so
# only a dtype= keyword counts there).
_JNP_DEFAULT_BUILDERS = {
    "zeros": 1, "ones": 1, "empty": 1, "full": 2, "arange": None,
}


def _numeric_module(chain: str) -> bool:
    head = chain.split(".", 1)[0]
    return head in ("np", "numpy", "jnp", "jax")


class _Visitor(ScopedVisitor):
    def __init__(self, path: str) -> None:
        super().__init__()
        self.path = path
        self.findings: list[Finding] = []

    def _emit(self, node: ast.AST, code: str, message: str) -> None:
        self.findings.append(Finding(
            checker=CHECKER, path=self.path,
            line=getattr(node, "lineno", 0), scope=self.scope,
            code=code, message=message,
        ))

    def visit_Attribute(self, node: ast.Attribute) -> None:
        chain = dotted_name(node)
        if chain and _numeric_module(chain):
            leaf = chain.rsplit(".", 1)[-1]
            if leaf in _NARROW_FLOAT:
                self._emit(
                    node, "narrow-float-dtype",
                    f"{chain} on a pricing path — every priced quantity "
                    "is float64 (bitwise reference parity depends on it)",
                )
            elif leaf in _NARROW_INT:
                self._emit(
                    node, "narrow-int-dtype",
                    f"{chain} on a pricing path — index arrays are "
                    "int64 (int32 dense link ids overflow at the "
                    "5000-agent scale)",
                )
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        func = node.func
        leaf = None
        if isinstance(func, ast.Attribute):
            leaf = func.attr
        elif isinstance(func, ast.Name):
            leaf = func.id
        if leaf in _ARRAY_BUILDERS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if (
                    isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                    and arg.value in _NARROW_STRINGS
                ):
                    self._emit(
                        arg, "narrow-dtype-string",
                        f"dtype string {arg.value!r} on a pricing path — "
                        "use np.float64 / np.int64 explicitly",
                    )
        chain = dotted_name(func) if isinstance(func, ast.Attribute) \
            else None
        if chain and chain.split(".", 1)[0] in ("jnp", "jax"):
            builder = chain.rsplit(".", 1)[-1]
            dtype_pos = _JNP_DEFAULT_BUILDERS.get(builder)
            if builder in _JNP_DEFAULT_BUILDERS:
                has_kw = any(kw.arg == "dtype" for kw in node.keywords)
                has_pos = (
                    dtype_pos is not None and len(node.args) > dtype_pos
                )
                if not has_kw and not has_pos:
                    self._emit(
                        node, "implicit-jnp-dtype",
                        f"{chain}(...) without dtype= on a pricing path "
                        "— jax defaults to float32/int32 when x64 is "
                        "off; spell dtype=jnp.float64 / jnp.int64 so "
                        "narrowing cannot depend on the x64 flag",
                    )
        self.generic_visit(node)


def check(root: Path) -> list[Finding]:
    findings: list[Finding] = []
    for path in iter_python_files(root, SCAN_DIRS):
        tree = parse_file(path)
        if tree is None:
            continue
        visitor = _Visitor(rel(path, root))
        visitor.visit(tree)
        findings.extend(visitor.findings)
    return findings
