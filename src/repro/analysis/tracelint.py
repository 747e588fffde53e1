"""Trace lint — jaxpr-level proof of the pricing path's contracts.

The JAX rollout engine's load-bearing properties — "one XLA launch per
pricing call", float64 on every priced quantity, no silent retraces
across the benchmark grid — were docstring claims checked indirectly
by runtime parity tests. This checker makes them lint invariants by
*tracing* every registered entry point (``tracelint_targets.py``, a
per-tree registry of ``TraceTarget``\\ s with concrete small-instance
argument builders) and walking the resulting ``ClosedJaxpr``:

IR-level sub-checks (need jax; degrade to a named skip without it):

``narrow-float-in-trace``   a primitive on the pricing path produces a
                            float16/bfloat16/float32/complex64 value —
                            silent promotion the AST ``dtypes`` checker
                            structurally cannot see (e.g. introduced
                            inside a ``lax.scan`` carry).
``narrow-float-literal``    a literal or captured constant enters the
                            trace at a narrow float dtype.
``host-callback``           a ``pure_callback``/``io_callback``/
                            ``debug_callback`` primitive anywhere in
                            the trace — a host round-trip inside the
                            "one launch".
``multiple-launches``       the entry does not lower to exactly one
                            top-level jit computation (e.g. the kernel
                            was split into two jitted calls, or traced
                            un-jitted).
``eqn-budget-exceeded``     the recursive equation count outgrew the
                            per-target budget in
                            ``tracelint_manifest.txt`` — the tripwire
                            for "someone added a host round-trip or an
                            accidental unrolling".
``missing-eqn-budget``      a registered target has no manifest entry.
``stale-eqn-budget-entry``  a manifest entry names no registered
                            target.
``malformed-eqn-budget``    a manifest line that does not parse.
``trace-error``             a registered case failed to build or
                            trace (the registry itself is broken).
``targets-import-error``    the registry module failed to load.

AST sub-pass (always runs, jax or not) over the retrace-critical
modules (``RETRACE_SCAN_DIRS``): starting from jit-decorated functions
(and ``jax.jit(...)`` aliases), the transitive module-local call
closure is *device scope* — code that runs under trace. Within it:

``traced-python-branch``    ``if``/``while``/ternary/``assert`` whose
                            test reads a traced value — concretizes
                            the tracer (TracerBoolConversionError at
                            best, shape-dependent retraces at worst).
                            Static reads (``.shape``/``.ndim``/
                            ``.size``/``.dtype``/``.itemsize``,
                            ``len()``/``isinstance()``) are exempt.
``closure-captured-array``  a module-level numpy array read inside a
                            device scope — baked into the compiled
                            program as a constant; rebinding it never
                            retraces, so results silently go stale.
``unhashable-static-arg``   a call site passes a list/dict/set display
                            or an ``np.array(...)`` expression in a
                            ``static_argnums``/``static_argnames``
                            position — unhashable statics raise, and
                            array-valued statics retrace per call.

A trace-counting harness (``count_compilations``) backs the
"exactly one compilation per shape signature" assertion in
``tests/test_tracelint.py``, and ``collect_metrics`` statically
computes the water-filling round's carry/operand/round-pair bytes from
the jaxpr — the Pallas-readiness numbers ROADMAP open item 1 tracks
through ``benchmarks/analysis_bench.py`` + ``trend.py``.
"""

from __future__ import annotations

import ast
import dataclasses
import importlib.util
from pathlib import Path
from typing import Any, Callable, Iterator, Sequence

from repro.analysis.common import (
    Finding,
    dotted_name,
    iter_python_files,
    parse_file,
    rel,
    repo_root,
)

CHECKER = "tracelint"

TARGETS_REL_PATH = "src/repro/analysis/tracelint_targets.py"
MANIFEST_REL_PATH = "src/repro/analysis/tracelint_manifest.txt"
MANIFEST_FILENAME = "tracelint_manifest.txt"

# The retrace-critical surface: the device engine itself plus the
# pricing loop that drives it. core/dpsgd.py and core/weight_opt.py
# jit learning-side math with host-scalar closures by design and are
# covered by their own parity tests, not this pass.
RETRACE_SCAN_DIRS = [
    "src/repro/net",
    "src/repro/core/priced_training.py",
]

# Reading these off a traced array is static (shape metadata, not the
# tracer's value) — branching on them is how bucketed programs are
# *supposed* to specialize.
_STATIC_ATTRS = {
    "shape", "ndim", "size", "dtype", "itemsize", "weak_type", "sharding",
}
_STATIC_WRAPPERS = {"len", "isinstance", "type", "hasattr", "range"}

_NARROW_FLOAT_DTYPES = {"float16", "bfloat16", "float32", "complex64"}
_CALLBACK_PRIMITIVES = {"pure_callback", "io_callback", "debug_callback"}
_CALL_PRIMITIVES = {"pjit", "jit", "xla_call", "closed_call", "core_call"}

# Notes the CLI prints after a run — a named skip is visible, a silent
# one is a hole in the gate. Reset on every check().
LAST_SKIP_NOTES: list[str] = []


# ---------------------------------------------------------------------------
# Target registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TraceCase:
    """One concrete shape point of a target: ``make()`` returns the
    ``(fn, args)`` pair to hand ``jax.make_jaxpr`` — ``fn`` must be the
    jit-wrapped entry exactly as the host path launches it."""

    label: str
    make: Callable[[], tuple[Callable, tuple]]


@dataclasses.dataclass(frozen=True)
class TraceTarget:
    """A registered JAX entry point.

    ``name`` keys the eqn-budget manifest; ``path``/``scope`` anchor
    findings (and waiver keys) at the entry the target certifies.
    """

    name: str
    path: str
    scope: str
    cases: tuple[TraceCase, ...]


_TARGETS_CACHE: dict[Path, Any] = {}


def _load_targets(root: Path) -> tuple[tuple[TraceTarget, ...], list[Finding]]:
    path = (root / TARGETS_REL_PATH).resolve()
    if not path.is_file():
        return (), []
    mod = _TARGETS_CACHE.get(path)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            f"_tracelint_targets_{len(_TARGETS_CACHE)}", path
        )
        mod = importlib.util.module_from_spec(spec)
        try:
            spec.loader.exec_module(mod)
        except Exception as exc:  # registry code is arbitrary
            return (), [Finding(
                checker=CHECKER, path=TARGETS_REL_PATH, line=1,
                scope="<module>", code="targets-import-error",
                message=(
                    f"target registry failed to import: {exc!r} — the "
                    "jaxpr pass has nothing to certify until it loads"
                ),
            )]
        _TARGETS_CACHE[path] = mod
    targets = getattr(mod, "TARGETS", None)
    if not targets:
        return (), [Finding(
            checker=CHECKER, path=TARGETS_REL_PATH, line=1,
            scope="<module>", code="targets-import-error",
            message=(
                "target registry defines no TARGETS tuple — register "
                "every JAX entry point (see TraceTarget)"
            ),
        )]
    return tuple(targets), []


# ---------------------------------------------------------------------------
# Eqn-budget manifest
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BudgetEntry:
    name: str
    max_eqns: int
    line: int


def load_manifest(path: Path) -> tuple[dict[str, BudgetEntry], list[Finding]]:
    """``<target-name> <max-eqns>`` per line; ``#`` comments."""
    budgets: dict[str, BudgetEntry] = {}
    findings: list[Finding] = []
    if not path.is_file():
        return budgets, findings
    for lineno, raw in enumerate(path.read_text().splitlines(), 1):
        line = raw.split("#", 1)[0].strip()
        if not line:
            continue
        fields = line.split()
        if len(fields) != 2 or not fields[1].isdigit() \
                or fields[0] in budgets:
            why = "duplicate target" if len(fields) == 2 \
                and fields[0] in budgets else "expected '<target> <max-eqns>'"
            findings.append(Finding(
                checker=CHECKER, path=path.name, line=lineno,
                scope="<module>", code="malformed-eqn-budget",
                message=f"cannot use manifest line {raw!r}: {why}",
            ))
            continue
        budgets[fields[0]] = BudgetEntry(fields[0], int(fields[1]), lineno)
    return budgets, findings


# ---------------------------------------------------------------------------
# Jaxpr walking
# ---------------------------------------------------------------------------


def _as_jaxprs(value: Any) -> Iterator[Any]:
    """Sub-jaxprs inside an eqn param value, duck-typed so no jax
    import is needed here: ClosedJaxpr carries ``.jaxpr``/``.consts``,
    a raw Jaxpr carries ``.eqns``/``.invars``, branch params are
    tuples of either."""
    if hasattr(value, "jaxpr") and hasattr(value, "consts"):
        yield value.jaxpr
    elif hasattr(value, "eqns") and hasattr(value, "invars"):
        yield value
    elif isinstance(value, (list, tuple)):
        for item in value:
            yield from _as_jaxprs(item)


def iter_jaxprs(jaxpr: Any, depth: int = 0) -> Iterator[tuple[Any, int]]:
    """(jaxpr, nesting depth) for the jaxpr and every sub-jaxpr hiding
    in its equations' params (scan/while/cond/pjit bodies)."""
    yield jaxpr, depth
    for eqn in jaxpr.eqns:
        for sub in _as_jaxprs_of_eqn(eqn):
            yield from iter_jaxprs(sub, depth + 1)


def _as_jaxprs_of_eqn(eqn: Any) -> Iterator[Any]:
    for value in eqn.params.values():
        yield from _as_jaxprs(value)


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    for sub, _depth in iter_jaxprs(jaxpr):
        yield from sub.eqns


def count_eqns(jaxpr: Any) -> int:
    return sum(1 for _ in iter_eqns(jaxpr))


def _aval_bytes(aval: Any) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for extent in shape:
        n *= int(extent)
    return n * int(getattr(dtype, "itemsize", 0) or 0)


def _dtype_name(var: Any) -> str | None:
    aval = getattr(var, "aval", None)
    dtype = getattr(aval, "dtype", None)
    return None if dtype is None else str(dtype)


def _is_literal(var: Any) -> bool:
    return hasattr(var, "val")


# ---------------------------------------------------------------------------
# Per-target IR checks
# ---------------------------------------------------------------------------


class _Issues:
    """Deduplicated per-target findings: one finding per code, with an
    occurrence count — a narrow dtype inside a scan body would
    otherwise flood one finding per unrolled primitive."""

    def __init__(self, target: TraceTarget) -> None:
        self.target = target
        self._first: dict[str, str] = {}
        self._count: dict[str, int] = {}

    def add(self, code: str, message: str) -> None:
        self._first.setdefault(code, message)
        self._count[code] = self._count.get(code, 0) + 1

    def findings(self) -> list[Finding]:
        out = []
        for code, message in self._first.items():
            n = self._count[code]
            if n > 1:
                message = f"{message} (+{n - 1} more site(s))"
            out.append(Finding(
                checker=CHECKER, path=self.target.path, line=1,
                scope=self.target.scope, code=code, message=message,
            ))
        return out


def _check_launch(issues: _Issues, label: str, closed: Any) -> None:
    top = list(closed.jaxpr.eqns)
    prims = [str(eqn.primitive) for eqn in top]
    if len(top) != 1 or prims[0] not in _CALL_PRIMITIVES:
        issues.add(
            "multiple-launches",
            f"case {label!r} lowers to {len(top)} top-level equation(s) "
            f"{prims[:6]!r} — the registered entry must be exactly one "
            "jit-wrapped computation (one XLA launch per pricing call); "
            "re-fuse the split or jit the composite",
        )


def _check_callbacks(issues: _Issues, label: str, closed: Any) -> None:
    for eqn in iter_eqns(closed.jaxpr):
        name = str(eqn.primitive)
        if name in _CALLBACK_PRIMITIVES or "callback" in name:
            issues.add(
                "host-callback",
                f"case {label!r} traces a {name} primitive — a host "
                "round-trip inside the one-launch kernel; compute on "
                "device or hoist the host work out of the jitted scope",
            )


def _check_dtypes(issues: _Issues, label: str, closed: Any) -> None:
    for const in getattr(closed, "consts", ()):
        dtype = str(getattr(const, "dtype", ""))
        if dtype in _NARROW_FLOAT_DTYPES:
            issues.add(
                "narrow-float-literal",
                f"case {label!r} captures a {dtype} constant — every "
                "priced quantity is float64 (bitwise parity with the "
                "numpy oracle depends on it)",
            )
    for eqn in iter_eqns(closed.jaxpr):
        for var in eqn.invars:
            if _is_literal(var):
                dtype = _dtype_name(var)
                if dtype in _NARROW_FLOAT_DTYPES:
                    issues.add(
                        "narrow-float-literal",
                        f"case {label!r}: a {dtype} literal feeds "
                        f"{eqn.primitive} — spell float64 (or let the "
                        "x64-weak default promote)",
                    )
        for var in eqn.outvars:
            dtype = _dtype_name(var)
            if dtype in _NARROW_FLOAT_DTYPES:
                issues.add(
                    "narrow-float-in-trace",
                    f"case {label!r}: {eqn.primitive} produces {dtype} "
                    "on the pricing path — silent narrowing inside the "
                    "trace; every priced quantity is float64",
                )


def trace_case(jax_mod: Any, fn: Callable, args: tuple) -> Any:
    """Closed jaxpr of ``fn(*args)``, traced the way the pricing launch
    traces it: inside ``jax.enable_x64(True)``, the float64 scope
    ``jax_engine.run_rollouts`` opens around ``_run_batch``."""
    with jax_mod.enable_x64(True):
        return jax_mod.make_jaxpr(fn)(*args)


def _trace_target(
    target: TraceTarget,
    budgets: dict[str, BudgetEntry],
    jax_mod: Any,
) -> list[Finding]:
    issues = _Issues(target)
    max_eqns = 0
    for case in target.cases:
        try:
            fn, args = case.make()
            closed = trace_case(jax_mod, fn, args)
        except Exception as exc:
            issues.add(
                "trace-error",
                f"case {case.label!r} failed to build/trace: {exc!r} — "
                "the registry must stay runnable on every lint host",
            )
            continue
        _check_launch(issues, case.label, closed)
        _check_callbacks(issues, case.label, closed)
        _check_dtypes(issues, case.label, closed)
        max_eqns = max(max_eqns, count_eqns(closed.jaxpr))
    findings = issues.findings()
    entry = budgets.get(target.name)
    if entry is None:
        findings.append(Finding(
            checker=CHECKER, path=MANIFEST_FILENAME, line=1,
            scope=target.name, code="missing-eqn-budget",
            message=(
                f"target {target.name!r} has no entry in "
                f"{MANIFEST_FILENAME} — record its equation budget "
                f"(measured {max_eqns} eqn(s); leave ~30% headroom for "
                "jax-version drift)"
            ),
        ))
    elif max_eqns > entry.max_eqns:
        findings.append(Finding(
            checker=CHECKER, path=MANIFEST_FILENAME, line=entry.line,
            scope=target.name, code="eqn-budget-exceeded",
            message=(
                f"target {target.name!r} traces to {max_eqns} eqn(s), "
                f"budget is {entry.max_eqns} — the kernel grew; either "
                "a host round-trip/unrolling crept in (fix it) or the "
                "growth is intentional (raise the budget in review)"
            ),
        ))
    return findings


# ---------------------------------------------------------------------------
# AST retrace pass
# ---------------------------------------------------------------------------


def _jit_decoration(node: ast.AST) -> tuple[bool, set[str], set[int]]:
    """(is jax.jit, static_argnames, static_argnums) of a decorator or
    wrapper expression: ``jax.jit`` / ``jit`` / ``jax.jit(...)`` /
    ``(functools.)partial(jax.jit, ...)``."""
    chain = dotted_name(node)
    if chain in ("jax.jit", "jit"):
        return True, set(), set()
    if isinstance(node, ast.Call):
        fchain = dotted_name(node.func)
        inner_jit = False
        if fchain in ("jax.jit", "jit"):
            inner_jit = True
        elif fchain in ("functools.partial", "partial") and node.args:
            if dotted_name(node.args[0]) in ("jax.jit", "jit"):
                inner_jit = True
        if inner_jit:
            names: set[str] = set()
            nums: set[int] = set()
            for kw in node.keywords:
                if kw.arg == "static_argnames":
                    names |= _str_constants(kw.value)
                elif kw.arg == "static_argnums":
                    nums |= _int_constants(kw.value)
            return True, names, nums
    return False, set(), set()


def _str_constants(node: ast.AST) -> set[str]:
    out = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            out.add(sub.value)
    return out


def _int_constants(node: ast.AST) -> set[int]:
    out = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, int) \
                and not isinstance(sub.value, bool):
            out.add(sub.value)
    return out


def _param_names(node: ast.AST) -> list[str]:
    a = node.args
    params = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        params.append(a.vararg.arg)
    if a.kwarg:
        params.append(a.kwarg.arg)
    return params


def _mentions_traced(node: ast.AST, traced: set[str]) -> bool:
    """Does the expression read a traced value non-statically?"""
    if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
        return False
    if isinstance(node, ast.Call):
        fname = node.func.id if isinstance(node.func, ast.Name) else None
        if fname in _STATIC_WRAPPERS:
            return False
    if isinstance(node, ast.Name):
        return node.id in traced
    return any(
        _mentions_traced(child, traced)
        for child in ast.iter_child_nodes(node)
    )


def _is_unhashable_expr(node: ast.AST,
                        module_arrays: dict[str, int]) -> bool:
    if isinstance(node, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                         ast.DictComp, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        chain = dotted_name(node.func) or ""
        head = chain.split(".", 1)[0]
        leaf = chain.rsplit(".", 1)[-1]
        if head in ("np", "numpy", "jnp") and leaf in (
            "array", "asarray", "zeros", "ones", "empty", "full", "arange",
        ):
            return True
    if isinstance(node, ast.Name) and node.id in module_arrays:
        return True
    return False


class _ModuleRetraceScan:
    """One scanned module: device-scope closure + the three findings."""

    def __init__(self, tree: ast.Module, path: str) -> None:
        self.tree = tree
        self.path = path
        self.findings: list[Finding] = []
        self.module_funcs: dict[str, ast.FunctionDef] = {}
        self.module_arrays: dict[str, int] = {}  # name -> lineno
        # callable name -> (static names, static nums): jit-decorated
        # defs plus ``alias = jax.jit(fn, ...)`` wrapper aliases (call
        # sites go through these names).
        self.jitted: dict[str, tuple[set[str], set[int]]] = {}
        # def names that run under trace (decorated defs AND the
        # ``fn`` inside wrapper assigns) — the device-scope seeds.
        self.device_seeds: dict[str, tuple[set[str], set[int]]] = {}
        self._collect_module_level()

    def _collect_module_level(self) -> None:
        for node in self.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.module_funcs[node.name] = node
                for deco in node.decorator_list:
                    is_jit, names, nums = _jit_decoration(deco)
                    if is_jit:
                        self.jitted[node.name] = (names, nums)
                        self.device_seeds[node.name] = (names, nums)
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                name = node.targets[0].id
                value = node.value
                chain = dotted_name(getattr(value, "func", value)) or ""
                if isinstance(value, ast.Call) \
                        and chain.split(".", 1)[0] in ("np", "numpy"):
                    self.module_arrays[name] = node.lineno
                is_jit, names, nums = _jit_decoration(value)
                if is_jit and isinstance(value, ast.Call) and value.args:
                    # name = jax.jit(fn, static_arg...=...) wrapper:
                    # call sites use the alias; ``fn`` runs under trace.
                    self.jitted[name] = (names, nums)
                    wrapped = value.args[0]
                    if isinstance(wrapped, ast.Name):
                        self.device_seeds[wrapped.id] = (names, nums)

    def _emit(self, node: ast.AST, scope: str, code: str,
              message: str) -> None:
        self.findings.append(Finding(
            checker=CHECKER, path=self.path,
            line=getattr(node, "lineno", 0), scope=scope,
            code=code, message=message,
        ))

    def run(self) -> list[Finding]:
        seeds: list[tuple[ast.FunctionDef, set[str]]] = []
        for name, (static_names, static_nums) in self.device_seeds.items():
            fndef = self.module_funcs.get(name)
            if fndef is None:
                continue
            params = _param_names(fndef)
            traced = {
                p for i, p in enumerate(params)
                if p not in static_names and i not in static_nums
            }
            seeds.append((fndef, traced))
        visited: set[str] = {fndef.name for fndef, _ in seeds}
        queue = list(seeds)
        while queue:
            fndef, traced = queue.pop()
            called = self._scan_device_scope(fndef, traced, fndef.name)
            for name in called:
                if name in visited:
                    continue
                callee = self.module_funcs.get(name)
                if callee is None:
                    continue
                visited.add(name)
                queue.append((callee, set(_param_names(callee))))
        self._scan_static_call_sites()
        return self.findings

    def _scan_device_scope(self, fndef: ast.AST, traced: set[str],
                           scope: str) -> set[str]:
        """Findings inside one device-scope function; returns the
        module-local function names it calls (closure expansion).
        Nested defs are device scope too (they trace with the parent),
        with their own params joining the traced set."""
        called: set[str] = set()

        def walk(node: ast.AST, traced: set[str], scope: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    inner = traced | set(_param_names(child))
                    walk(child, inner, f"{scope}.{child.name}")
                    continue
                if isinstance(child, (ast.If, ast.While)):
                    self._check_branch(child.test, child, traced, scope)
                elif isinstance(child, ast.IfExp):
                    self._check_branch(child.test, child, traced, scope)
                elif isinstance(child, ast.Assert):
                    self._check_branch(child.test, child, traced, scope)
                if isinstance(child, ast.Call) \
                        and isinstance(child.func, ast.Name) \
                        and child.func.id in self.module_funcs:
                    called.add(child.func.id)
                if isinstance(child, ast.Name) \
                        and isinstance(child.ctx, ast.Load) \
                        and child.id in self.module_arrays:
                    self._emit(
                        child, scope, "closure-captured-array",
                        f"device scope reads module-level numpy array "
                        f"{child.id!r} (defined at line "
                        f"{self.module_arrays[child.id]}) — it is baked "
                        "into the compiled program as a constant; pass "
                        "it as an argument so rebinding cannot silently "
                        "serve stale results",
                    )
                walk(child, traced, scope)

        walk(fndef, traced, scope)
        return called

    def _check_branch(self, test: ast.AST, node: ast.AST,
                      traced: set[str], scope: str) -> None:
        if _mentions_traced(test, traced):
            kind = type(node).__name__.lower()
            self._emit(
                node, scope, "traced-python-branch",
                f"Python {kind} branches on a traced value — this "
                "concretizes the tracer (error or per-value retrace); "
                "use lax.cond/jnp.where, or read only static "
                "shape/dtype attributes in the test",
            )

    def _scan_static_call_sites(self) -> None:
        if not any(names or nums for names, nums in self.jitted.values()):
            return
        scopes: list[str] = []

        class V(ast.NodeVisitor):
            def visit_FunctionDef(v, node):  # noqa: N805
                scopes.append(node.name)
                v.generic_visit(node)
                scopes.pop()

            visit_AsyncFunctionDef = visit_FunctionDef
            visit_ClassDef = visit_FunctionDef

            def visit_Call(v, node):  # noqa: N805
                if isinstance(node.func, ast.Name) \
                        and node.func.id in self.jitted:
                    names, nums = self.jitted[node.func.id]
                    scope = ".".join(scopes) or "<module>"
                    for i, arg in enumerate(node.args):
                        if i in nums and _is_unhashable_expr(
                                arg, self.module_arrays):
                            self._emit_static(node, scope, i)
                    for kw in node.keywords:
                        if kw.arg in names and _is_unhashable_expr(
                                kw.value, self.module_arrays):
                            self._emit_static(node, scope, kw.arg)
                v.generic_visit(node)

        V().visit(self.tree)

    def _emit_static(self, node: ast.Call, scope: str,
                     which: int | str) -> None:
        self._emit(
            node, scope, "unhashable-static-arg",
            f"static argument {which!r} of {node.func.id} receives an "
            "unhashable/array-valued expression — static args key the "
            "jit cache by hash; pass a hashable scalar/tuple or make "
            "the argument traced",
        )


def _retrace_ast_pass(root: Path) -> list[Finding]:
    findings: list[Finding] = []
    for path in iter_python_files(root, RETRACE_SCAN_DIRS):
        tree = parse_file(path)
        if tree is None:
            continue
        findings.extend(_ModuleRetraceScan(tree, rel(path, root)).run())
    return findings


# ---------------------------------------------------------------------------
# Harness + metrics (tests and benchmarks; not part of check())
# ---------------------------------------------------------------------------


def count_compilations(fn: Callable, arg_sets: Sequence[tuple]) -> int:
    """Compilations a *fresh* jit of ``fn`` performs over ``arg_sets``.
    ``fn`` may already be jitted (its ``__wrapped__`` is unwrapped),
    and the unwrapped function is re-wrapped through a new closure:
    jit's compilation cache is keyed by function identity, so reusing
    the original object would inherit — and count — every compilation
    prior callers already paid. The retrace contract: the result
    equals the number of distinct shape signatures in ``arg_sets``."""
    import jax

    inner = getattr(fn, "__wrapped__", fn)

    def fresh(*args):
        return inner(*args)

    jitted = jax.jit(fresh)
    for args in arg_sets:
        jitted(*args)
    return int(jitted._cache_size())


def _deepest_while(jaxpr: Any) -> Any | None:
    best, best_depth = None, -1
    for sub, depth in iter_jaxprs(jaxpr):
        for eqn in sub.eqns:
            if str(eqn.primitive) == "while" and depth >= best_depth:
                best, best_depth = eqn, depth
    return best


def waterfill_metrics(closed: Any) -> dict[str, int]:
    """Pallas-readiness numbers for the water-filling round, read off
    the jaxpr statically: the innermost ``while`` is the water-fill
    loop (its body is the 2x-unrolled round pair).

    ``waterfill_carry_bytes``     carried state crossing each round
                                  pair (what a fused kernel keeps
                                  resident in registers/VMEM);
    ``waterfill_operand_bytes``   loop-invariant operands (tables,
                                  capacities) re-read every round;
    ``waterfill_roundpair_bytes`` total IR-level operand+result bytes
                                  of the round-pair body — the
                                  HLO-boundary traffic the Pallas
                                  kernel (ROADMAP item 1) removes.
    """
    eqn = _deepest_while(closed.jaxpr)
    if eqn is None:
        return {}
    body = eqn.params["body_jaxpr"].jaxpr
    nconsts = int(eqn.params.get("body_nconsts", 0))
    consts, carry = body.invars[:nconsts], body.invars[nconsts:]
    moved = 0
    for body_eqn in body.eqns:
        for var in body_eqn.invars:
            if not _is_literal(var):
                moved += _aval_bytes(getattr(var, "aval", None))
        for var in body_eqn.outvars:
            moved += _aval_bytes(getattr(var, "aval", None))
    return {
        "waterfill_carry_bytes": sum(
            _aval_bytes(v.aval) for v in carry
        ),
        "waterfill_operand_bytes": sum(
            _aval_bytes(v.aval) for v in consts
        ),
        "waterfill_roundpair_bytes": moved,
    }


def collect_metrics(root: Path | None = None) -> dict[str, int]:
    """Per-target eqn counts plus water-fill bytes, at each target's
    *first* (canonical) case shapes — the numbers
    ``benchmarks/analysis_bench.py`` emits for the nightly trend."""
    import jax

    root = (root or repo_root()).resolve()
    targets, findings = _load_targets(root)
    if findings:
        raise RuntimeError(findings[0].message)
    metrics: dict[str, int] = {}
    for target in targets:
        fn, args = target.cases[0].make()
        closed = trace_case(jax, fn, args)
        key = "eqns_" + target.name.replace("-", "_")
        metrics[key] = count_eqns(closed.jaxpr)
        if target.name == "rollout-batch":
            metrics.update(waterfill_metrics(closed))
    return metrics


# ---------------------------------------------------------------------------
# Checker entry
# ---------------------------------------------------------------------------


def _try_import_jax() -> Any | None:
    try:
        import jax
    except Exception:
        return None
    return jax


def check(root: Path) -> list[Finding]:
    LAST_SKIP_NOTES.clear()
    findings = _retrace_ast_pass(root)
    jax_mod = _try_import_jax()
    if jax_mod is None:
        LAST_SKIP_NOTES.append(
            "tracelint: jax is not importable here — the jaxpr pass "
            "(dtype/launch/eqn-budget certification) was SKIPPED; the "
            "AST retrace pass still ran. Run on a host with jax before "
            "trusting the one-launch/f64 claims."
        )
        return findings
    targets, target_findings = _load_targets(root)
    findings.extend(target_findings)
    if not targets and not (root / MANIFEST_REL_PATH).is_file():
        # Tree registers no JAX entry points (and budgets none) —
        # nothing for the jaxpr pass to certify.
        return findings
    budgets, manifest_findings = load_manifest(root / MANIFEST_REL_PATH)
    findings.extend(manifest_findings)
    traced_names: set[str] = set()
    for target in targets:
        findings.extend(_trace_target(target, budgets, jax_mod))
        traced_names.add(target.name)
    for name, entry in budgets.items():
        if name not in traced_names:
            findings.append(Finding(
                checker=CHECKER, path=MANIFEST_FILENAME, line=entry.line,
                scope=name, code="stale-eqn-budget-entry",
                message=(
                    f"manifest budgets unknown target {name!r} — the "
                    "target was renamed or deleted; update the entry "
                    "(and make sure the launch certification moved "
                    "with the code)"
                ),
            ))
    return findings
