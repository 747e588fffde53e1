"""Trace-lint (`repro.analysis.tracelint`) unit tests.

Four layers:

* **one-launch / IR sub-checks** on tiny traced functions — a
  `pure_callback` or a two-jit split must fail lint (the statically
  asserted half of the "one XLA launch per pricing call" claim);
* **eqn-budget manifest** mechanics (missing/exceeded/malformed);
* the **retrace contract** — the trace-counting harness proves the
  registered grid compiles exactly once per shape signature, and the
  AST pass's exemptions (static shape reads) stay green;
* **Pallas-readiness metrics** — carry/operand/round-pair bytes read
  statically off the water-fill loop's jaxpr, as emitted by
  `benchmarks/analysis_bench.py`.
"""

import textwrap
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.net import jax_engine  # noqa: E402
from repro.analysis import tracelint, tracelint_targets  # noqa: E402
from repro.analysis.tracelint import (  # noqa: E402
    BudgetEntry,
    TraceCase,
    TraceTarget,
    _Issues,
    _check_callbacks,
    _check_dtypes,
    _check_launch,
    _trace_target,
    count_compilations,
    trace_case,
    count_eqns,
    load_manifest,
    waterfill_metrics,
)

REPO = Path(__file__).resolve().parents[1]


def _issues_for(fn, args):
    """(issues, closed) after running every IR sub-check on fn(*args)."""
    target = TraceTarget(
        name="t", path="src/x.py", scope="s",
        cases=(TraceCase("c", lambda: (fn, args)),),
    )
    issues = _Issues(target)
    closed = trace_case(jax, fn, args)
    _check_launch(issues, "c", closed)
    _check_callbacks(issues, "c", closed)
    _check_dtypes(issues, "c", closed)
    return issues, closed


def _codes(findings):
    return {f.code for f in findings}


ARGS = (np.arange(4, dtype=np.float64),)


# ---------------------------------------------------------------------------
# One-launch / IR sub-checks
# ---------------------------------------------------------------------------


def test_single_jit_f64_entry_is_clean():
    @jax.jit
    def entry(x):
        return x * 2.0 + 1.0

    issues, closed = _issues_for(entry, ARGS)
    assert issues.findings() == []
    assert count_eqns(closed.jaxpr) >= 2


def test_two_jit_split_fails_one_launch():
    """Splitting the kernel into two jitted calls is exactly the
    regression the one-launch assertion exists to catch."""
    @jax.jit
    def half1(x):
        return x * 2.0

    @jax.jit
    def half2(x):
        return x + 1.0

    issues, _ = _issues_for(lambda x: half2(half1(x)), ARGS)
    assert _codes(issues.findings()) == {"multiple-launches"}


def test_unjitted_entry_fails_one_launch():
    issues, _ = _issues_for(lambda x: x * 2.0 + 1.0, ARGS)
    assert _codes(issues.findings()) == {"multiple-launches"}


def test_pure_callback_fails_lint():
    @jax.jit
    def entry(x):
        y = jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )
        return y + 1.0

    issues, _ = _issues_for(entry, ARGS)
    assert "host-callback" in _codes(issues.findings())


def test_f32_promotion_fails_lint():
    import jax.numpy as jnp

    @jax.jit
    def entry(x):
        return x.astype(jnp.float32) * jnp.float32(3.0)

    got = _codes(_issues_for(entry, ARGS)[0].findings())
    assert "narrow-float-in-trace" in got


# ---------------------------------------------------------------------------
# Eqn-budget manifest
# ---------------------------------------------------------------------------


def _jit_double():
    @jax.jit
    def double(x):
        return x * 2.0

    return TraceTarget(
        name="double", path="src/x.py", scope="double",
        cases=(TraceCase("c", lambda: (double, ARGS)),),
    )


def test_missing_budget_entry_is_a_finding():
    findings = _trace_target(_jit_double(), {}, jax)
    assert _codes(findings) == {"missing-eqn-budget"}


def test_exceeded_budget_is_a_finding():
    budgets = {"double": BudgetEntry("double", 0, 1)}
    findings = _trace_target(_jit_double(), budgets, jax)
    assert _codes(findings) == {"eqn-budget-exceeded"}


def test_generous_budget_is_clean():
    budgets = {"double": BudgetEntry("double", 100, 1)}
    assert _trace_target(_jit_double(), budgets, jax) == []


def test_malformed_and_duplicate_manifest_lines(tmp_path):
    path = tmp_path / "tracelint_manifest.txt"
    path.write_text(
        "# comment\n"
        "good 100\n"
        "bad-no-count\n"
        "bad not-a-number\n"
        "good 200\n"  # duplicate
    )
    budgets, findings = load_manifest(path)
    assert list(budgets) == ["good"]
    assert budgets["good"].max_eqns == 100
    assert [f.code for f in findings] == ["malformed-eqn-budget"] * 3


# ---------------------------------------------------------------------------
# Retrace contract (harness + AST exemptions)
# ---------------------------------------------------------------------------


def test_one_compilation_per_shape_signature():
    """The retrace contract over the registered grid: compilations ==
    distinct shape signatures, never more. Identical args are a pure
    cache hit; a different seed may change the sampled segment-grid
    length (a *legitimate* new signature), and a new rollout width
    always does."""
    arg_sets = [
        tracelint_targets.rollout_batch_args(4),
        tracelint_targets.rollout_batch_args(4),  # cache hit
        tracelint_targets.rollout_batch_args(4, seed=1),
        tracelint_targets.rollout_batch_args(8),
    ]
    signatures = {
        tuple((a.shape, str(a.dtype)) for a in args)
        for args in arg_sets
    }
    assert len(signatures) >= 2  # the grid genuinely varies
    with jax.enable_x64(True):  # the launch's own precision scope
        compiled = count_compilations(jax_engine._run_batch, arg_sets)
    assert compiled == len(signatures)


def _ast_findings(tmp_path, source):
    net = tmp_path / "src" / "repro" / "net"
    net.mkdir(parents=True)
    (net / "mod.py").write_text(textwrap.dedent(source))
    return tracelint.check(tmp_path)


def test_ast_pass_exempts_static_shape_reads(tmp_path):
    """Branching on shape/dtype metadata is how bucketed programs
    specialize — the `_waterfill` cdtype selection pattern must stay
    green; branching on the tracer's value must not."""
    findings = _ast_findings(tmp_path, """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def kernel(edge_table, x):
            cdtype = jnp.int16 if edge_table.shape[1] < 2**15 \\
                else jnp.int32
            if len(x) > 3:
                pass
            if x.ndim > 1:
                pass
            return x.astype(cdtype)
    """)
    assert findings == []


def test_ast_pass_flags_traced_branch_in_call_closure(tmp_path):
    """Device scope is the transitive module-local call closure of the
    jitted entry, not just its body."""
    findings = _ast_findings(tmp_path, """
        import jax

        def _helper(y):
            if y > 0:
                return y
            return -y

        @jax.jit
        def entry(x):
            return _helper(x)
    """)
    assert [(f.scope, f.code) for f in findings] == [
        ("_helper", "traced-python-branch")
    ]


def test_ast_pass_flags_wrapper_alias_and_static_call_site(tmp_path):
    findings = _ast_findings(tmp_path, """
        import jax

        def _impl(x, mode):
            while x > 0:
                x = x - 1
            return x

        scale = jax.jit(_impl, static_argnames=("mode",))

        def run(x):
            return scale(x, mode=[1, 2])
    """)
    assert _codes(findings) == {
        "traced-python-branch", "unhashable-static-arg"
    }


def test_jax_absent_degrades_to_named_skip(tmp_path, monkeypatch):
    """Without jax the AST pass still runs and the jaxpr pass is a
    *named* skip (visible note), never a silent pass."""
    monkeypatch.setattr(tracelint, "_try_import_jax", lambda: None)
    findings = _ast_findings(tmp_path, """
        import jax

        @jax.jit
        def entry(x):
            if x > 0:
                return x
            return -x
    """)
    assert _codes(findings) == {"traced-python-branch"}
    assert tracelint.LAST_SKIP_NOTES
    assert "SKIPPED" in tracelint.LAST_SKIP_NOTES[0]


# ---------------------------------------------------------------------------
# Pallas-readiness metrics
# ---------------------------------------------------------------------------


def test_waterfill_metrics_from_registered_case():
    fn, args = tracelint_targets.TARGETS[0].cases[0].make()
    closed = trace_case(jax, fn, args)
    metrics = waterfill_metrics(closed)
    assert set(metrics) == {
        "waterfill_carry_bytes",
        "waterfill_operand_bytes",
        "waterfill_roundpair_bytes",
    }
    assert all(v > 0 for v in metrics.values())
    # the round pair touches at least the carried state once
    assert metrics["waterfill_roundpair_bytes"] > \
        metrics["waterfill_carry_bytes"]


def test_waterfill_metrics_empty_without_loop():
    closed = trace_case(jax, lambda x: x * 2.0, ARGS)
    assert waterfill_metrics(closed) == {}


def test_collect_metrics_covers_every_target():
    metrics = tracelint.collect_metrics(REPO)
    assert set(metrics) >= {
        "eqns_rollout_batch",
        "eqns_phased_scan",
        "eqns_stochastic_price",
        "waterfill_carry_bytes",
        "waterfill_operand_bytes",
        "waterfill_roundpair_bytes",
    }
    assert all(
        isinstance(v, int) and v > 0 for v in metrics.values()
    )


def test_registry_budgets_have_headroom():
    """Every registered target is budgeted, and measured counts sit
    under budget with real headroom (>=10%) so routine jax drift does
    not page the gate."""
    budgets, malformed = load_manifest(
        REPO / tracelint.MANIFEST_REL_PATH
    )
    assert malformed == []
    metrics = tracelint.collect_metrics(REPO)
    for target in tracelint_targets.TARGETS:
        entry = budgets[target.name]
        eqns = metrics["eqns_" + target.name.replace("-", "_")]
        assert eqns <= entry.max_eqns * 0.9, target.name
