"""JAX rollout engine: parity with the numpy batched engine, vmap
bitwise-determinism, the padded device-CSR contract, the launch-scoped
float64, and the designer/service plumbing that selects ``engine="jax"``."""

import dataclasses

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax
from repro.analysis.contracts import ContractViolation
from repro.net import (
    CapacityPhase,
    ChurnEvent,
    CrossTraffic,
    MarkovLinkModel,
    Scenario,
    StochasticScenario,
    StragglerEvent,
    build_overlay,
    compile_incidence,
    compute_categories,
    demands_from_links,
    line_underlay,
    random_geometric_underlay,
    route_congestion_aware,
    route_direct,
    simulate,
    simulate_phased,
)
from repro.net.jax_engine import (
    DeviceIncidence,
    _rollout_batch_reference,
    device_incidence,
    rollout_batch_results,
    run_rollouts,
    simulate_jax,
    simulate_rollout_batch,
)
from repro.net.routing import PhasedRoutingSolution
from repro.net.simulator import _phase_capacity_array
from repro.net.stochastic import densify_realizations


def _random_instance(seed: int, m: int, relay: bool = False):
    u = random_geometric_underlay(12, radius=0.5, seed=seed)
    ov = build_overlay(u, list(u.graph.nodes)[:m])
    cats = compute_categories(ov)
    rng = np.random.default_rng(seed)
    links = [
        (i, j) for i in range(m) for j in range(i + 1, m)
        if rng.random() < 0.6
    ] or [(0, 1)]
    demands = demands_from_links(links, 1e6, m)
    if relay:
        sol = route_congestion_aware(demands, cats, 1e6, m, rounds=2)
    else:
        sol = route_direct(demands, cats, 1e6)
    return sol, ov


def _line_instance(kappa=1e6, capacity=125_000.0):
    u = line_underlay(2, capacity=capacity)
    ov = build_overlay(u, [0, 1])
    cats = compute_categories(ov)
    demands = demands_from_links([(0, 1)], kappa, 2)
    return route_direct(demands, cats, kappa), ov


def _two_state(edges, stay_good=0.5, stay_bad=0.75, drop=0.1):
    return MarkovLinkModel(
        edges=edges, scales=(1.0, drop),
        transition=(
            (stay_good, 1.0 - stay_good),
            (1.0 - stay_bad, stay_bad),
        ),
    )


def _stochastic_for(ov, tau, churn=False):
    edges = tuple(ov.underlay.graph.edges)[:4] or ((0, 1),)
    return StochasticScenario(
        links=(_two_state(edges),),
        step=0.4 * tau, horizon=4 * tau,
        churn_agents=(0,) if churn else (),
        churn_hazard=0.15 if churn else 0.0,
    )


def _assert_parity(jax_res, ref_res):
    if np.isnan(ref_res.makespan):
        assert np.isnan(jax_res.makespan)
    else:
        assert jax_res.makespan == pytest.approx(
            ref_res.makespan, rel=1e-9
        )
    assert len(jax_res.flow_completion) == len(ref_res.flow_completion)
    for a, b in zip(jax_res.flow_completion, ref_res.flow_completion):
        if np.isnan(b):
            assert np.isnan(a)  # NaN semantics must survive the device
        else:
            assert a == pytest.approx(b, rel=1e-9)
    assert jax_res.cancelled_branches == ref_res.cancelled_branches


# ---------------------------------------------------------------------------
# Parity: simulate(engine="jax") vs engine="batched"
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 60), m=st.integers(3, 7), relay=st.booleans())
@settings(max_examples=12, deadline=None)
def test_jax_engine_matches_batched_static(seed, m, relay):
    """Property: the device engine reproduces the numpy batched
    engine's makespan and flow completions to rtol=1e-9 on random
    direct and relayed routings."""
    sol, ov = _random_instance(seed, m, relay=relay)
    _assert_parity(
        simulate(sol, ov, engine="jax"),
        simulate(sol, ov, engine="batched"),
    )


@given(seed=st.integers(0, 40), m=st.integers(3, 6))
@settings(max_examples=10, deadline=None)
def test_jax_engine_matches_batched_scenarios(seed, m):
    """Property: capacity phases and churn (including all-branch
    cancellation NaNs) price identically on the device."""
    sol, ov = _random_instance(seed, m)
    tau = max(float(sol.completion_time), 1.0)
    rng = np.random.default_rng(seed + 7)
    sc = Scenario(
        capacity_phases=(
            CapacityPhase(start=0.3 * tau, scale=0.5),
            CapacityPhase(start=0.9 * tau, scale=1.5),
        ),
        churn=(
            (ChurnEvent(agent=int(rng.integers(m)), time=0.5 * tau),)
            if rng.random() < 0.6 else ()
        ),
    )
    _assert_parity(
        simulate(sol, ov, scenario=sc, engine="jax"),
        simulate(sol, ov, scenario=sc, engine="batched"),
    )


def test_jax_capacity_phase_exact():
    # Same closed form the numpy engines are pinned to: halving C at
    # t=4 doubles the remaining 4s -> 12s.
    sol, ov = _line_instance()
    sc = Scenario(capacity_phases=(CapacityPhase(start=4.0, scale=0.5),))
    r = simulate(sol, ov, scenario=sc, engine="jax")
    assert r.makespan == pytest.approx(12.0)


def test_jax_rejects_unsupported_surface():
    sol, ov = _line_instance()
    with pytest.raises(ValueError, match="batched"):
        simulate(
            sol, ov, engine="jax",
            scenario=Scenario(
                cross_traffic=(CrossTraffic(src=0, dst=1, rate=1.0),)
            ),
        )
    with pytest.raises(ValueError, match="batched"):
        simulate(
            sol, ov, engine="jax",
            scenario=Scenario(
                stragglers=(StragglerEvent(agent=0, slowdown=2.0),)
            ),
        )
    with pytest.raises(ValueError, match="maxmin"):
        simulate(sol, ov, engine="jax", fairness="equal")
    with pytest.raises(ValueError, match="agent"):
        simulate(
            sol, ov, engine="jax",
            scenario=Scenario(churn=(ChurnEvent(agent=9, time=1.0),)),
        )


# ---------------------------------------------------------------------------
# Phased schedules
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 30), m=st.integers(3, 6))
@settings(max_examples=8, deadline=None)
def test_jax_phased_single_tree_parity(seed, m):
    """A phased schedule whose segments share one tree set lowers to
    the device scan and matches the batched swap loop."""
    sol, ov = _random_instance(seed, m)
    tau = max(float(sol.completion_time), 1.0)
    phased = PhasedRoutingSolution(
        demands=sol.demands, boundaries=(0.0, 0.5 * tau),
        solutions=(sol, sol), completion_time=tau,
        method="static", solve_seconds=0.0,
    )
    sc = Scenario(
        capacity_phases=(CapacityPhase(start=0.4 * tau, scale=0.5),)
    )
    _assert_parity(
        simulate_phased(phased, ov, scenario=sc, engine="jax"),
        simulate_phased(phased, ov, scenario=sc, engine="batched"),
    )


def test_jax_phased_rejects_rerouting_segments():
    """Segments with different trees re-route mid-run; volume carryover
    is host-side, so the device engine refuses rather than mispricing."""
    from repro.net.routing import RoutingSolution

    u = line_underlay(3)
    ov = build_overlay(u, [0, 1, 2])
    demands = tuple(demands_from_links([(0, 1)], 1e6, 3))[:1]
    direct = RoutingSolution(
        demands=demands, trees=(frozenset({(0, 1)}),),
        completion_time=8.0, method="direct", solve_seconds=0.0,
    )
    relay = RoutingSolution(
        demands=demands, trees=(frozenset({(0, 2), (2, 1)}),),
        completion_time=16.0, method="direct", solve_seconds=0.0,
    )
    phased = PhasedRoutingSolution(
        demands=demands, boundaries=(0.0, 2.0),
        solutions=(direct, relay), completion_time=8.0,
        method="time_expanded", solve_seconds=0.0,
    )
    with pytest.raises(ValueError, match="re-rout"):
        simulate_phased(phased, ov, engine="jax")


# ---------------------------------------------------------------------------
# Rollout batches: one launch, per-rollout parity, vmap determinism
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 25), m=st.integers(3, 6), churn=st.booleans())
@settings(max_examples=6, deadline=None)
def test_rollout_batch_matches_reference(seed, m, churn):
    """Property: one vmapped launch over a RealizationBatch matches the
    numpy loop of engine="batched" per rollout, rtol=1e-9 (this is the
    parity_manifest.txt registration for _rollout_batch_reference)."""
    sol, ov = _random_instance(seed, m)
    sto = _stochastic_for(ov, max(float(sol.completion_time), 1.0),
                          churn=churn)
    inc = compile_incidence(sol, ov)
    batch = sto.realization_batch(seed, 6, inc)
    fast = simulate_rollout_batch(sol, ov, batch, incidence=inc)
    ref = _rollout_batch_reference(sol, ov, batch, incidence=inc)
    assert len(fast) == len(ref) == 6
    for f, r in zip(fast, ref):
        _assert_parity(f, r)


def test_vmapped_batch_bitwise_matches_one_at_a_time():
    """Batching must not change a single bit: pricing rollout r inside
    an R=8 launch gives bitwise the result of launching r alone on the
    same boundary grid."""
    sol, ov = _random_instance(3, 5)
    sto = _stochastic_for(ov, max(float(sol.completion_time), 1.0),
                          churn=True)
    inc = compile_incidence(sol, ov)
    flow_size = np.array([d.size for d in sol.demands], dtype=np.float64)
    dev = device_incidence(inc, flow_size)
    batch = sto.realization_batch(11, 8, inc)
    together = rollout_batch_results(sol, dev, batch)
    for r in range(batch.num_rollouts):
        sub = dataclasses.replace(
            batch,
            capacity=batch.capacity[r:r + 1],
            churn=(batch.churn[r],),
            realizations=(batch.realizations[r],),
        )
        alone = rollout_batch_results(sol, dev, sub)[0]
        assert together[r].makespan == alone.makespan  # bitwise
        assert together[r].flow_completion == alone.flow_completion
        assert together[r].num_events == alone.num_events


def test_dense_capacity_tensor_is_bitwise_phase_caps():
    """The [R, P, E] tensor rows are bitwise what the numpy event loop
    evaluates per phase — engines diverge in fp drain grouping only,
    never in inputs."""
    sol, ov = _random_instance(5, 5)
    sto = _stochastic_for(ov, max(float(sol.completion_time), 1.0))
    inc = compile_incidence(sol, ov)
    reals = sto.sample_many(2, 4)
    batch = densify_realizations(reals, inc)
    assert batch.starts[0] == 0.0
    for r, sc in enumerate(reals):
        phases = sorted(sc.capacity_phases, key=lambda p: p.start)
        for p, t in enumerate(batch.starts):
            live = [ph for ph in phases if ph.start <= t]
            expect = (
                _phase_capacity_array(inc, live[-1])
                if live else inc.base_capacity
            )
            assert np.array_equal(batch.capacity[r, p], expect)


def test_batch_rejects_unsupported_realizations():
    sol, ov = _random_instance(0, 4)
    inc = compile_incidence(sol, ov)
    sc = Scenario(
        cross_traffic=(CrossTraffic(src=0, dst=1, rate=1.0),)
    )
    with pytest.raises(ValueError, match="batched"):
        densify_realizations((sc,), inc)


# ---------------------------------------------------------------------------
# Launch-scoped float64
# ---------------------------------------------------------------------------


def test_require_x64_guards_pricing_entries():
    """With the process flag off, the device entries still price in
    float64 — the launch opens its own x64 scope — and leave the flag
    off, so a model step traced afterwards keeps 32-bit defaults."""
    sol, ov = _line_instance()
    inc = compile_incidence(sol, ov)
    dev = device_incidence(
        inc, np.array([d.size for d in sol.demands], dtype=np.float64)
    )
    with jax.enable_x64(False):
        ((done, _cancelled, _events, _unfinished),) = run_rollouts(
            dev, np.zeros(1), inc.base_capacity[None, None, :],
            np.full((1, inc.num_branches), np.inf),
        )
        assert done.dtype == np.float64
        assert done[0] == pytest.approx(8.0, rel=1e-12)
        assert simulate_jax(sol, ov).makespan == pytest.approx(8.0)
        assert not jax.config.read("jax_enable_x64")


# ---------------------------------------------------------------------------
# Device-CSR contract (REPRO_VALIDATE=1)
# ---------------------------------------------------------------------------


def _device(seed=1, m=5):
    sol, ov = _random_instance(seed, m)
    inc = compile_incidence(sol, ov)
    fs = np.array([d.size for d in sol.demands], dtype=np.float64)
    return device_incidence(inc, fs)


def test_device_incidence_contract(monkeypatch):
    monkeypatch.setenv("REPRO_VALIDATE", "1")
    dev = _device()  # a fresh valid construction passes
    assert isinstance(dev, DeviceIncidence)
    nnz = dev.num_entries

    def corrupted(**kw):
        with pytest.raises(ContractViolation) as ei:
            dataclasses.replace(dev, **kw)
        return ei.value

    bad = dev.flat_branch.copy()
    bad[-1] = 0  # padding must point at the inert branch row
    assert corrupted(flat_branch=bad).invariant == "inert-padding"

    bad = dev.base_capacity.copy()
    bad[-1] = 2.0  # padding edge capacity must stay 1.0
    assert corrupted(base_capacity=bad).invariant == "inert-padding"

    bad = dev.flat_edge.copy()
    bad[0] = (bad[0] + 1) % dev.num_edges  # live prefix is bitwise
    assert corrupted(flat_edge=bad).invariant == "source-prefix"

    bad = dev.edge_edge.copy()
    bad[0] = dev.num_edges - 1  # breaks CSC ordering + prefix parity
    assert corrupted(edge_edge=bad).invariant == "source-prefix"

    assert corrupted(
        sizes=dev.sizes.astype(np.float32)
    ).invariant == "dtype"
    assert corrupted(
        num_entries=nnz + 1
    ).invariant == "source-extents"
    assert corrupted(
        sizes=dev.sizes[:dev.num_branches]  # bucket padding is required
    ).invariant == "padded-bucket"


def test_device_incidence_validation_off_by_default(monkeypatch):
    monkeypatch.delenv("REPRO_VALIDATE", raising=False)
    dev = _device()
    bad = dev.flat_branch.copy()
    bad[-1] = 0
    dataclasses.replace(dev, flat_branch=bad)  # no validation, no raise


# ---------------------------------------------------------------------------
# Designer / service plumbing
# ---------------------------------------------------------------------------


def test_designer_jax_engine_prices_like_batched():
    from repro.core.designer import design

    u = random_geometric_underlay(12, radius=0.5, seed=4)
    ov = build_overlay(u, list(u.graph.nodes)[:6])
    cats = compute_categories(ov)
    sto = _stochastic_for(ov, 8.0)
    kw = dict(overlay=ov, iterations=6, stochastic=sto,
              stochastic_rollouts=16, stochastic_seed=3)
    a = design("fmmd-wp", cats, 1e6, 6, engine="batched", **kw)
    b = design("fmmd-wp", cats, 1e6, 6, engine="jax", **kw)
    assert np.allclose(
        np.asarray(a.tau_samples), np.asarray(b.tau_samples), rtol=1e-9
    )
    for field in ("tau_mean", "tau_p95", "tau_p99"):
        assert getattr(b, field) == pytest.approx(
            getattr(a, field), rel=1e-9
        )
    assert np.isfinite(b.tau_p99)
    assert b.tau_p99 >= b.tau_p95 - 1e-12  # percentiles are ordered


def test_designer_jax_rejects_online_rerouting():
    from repro.core.designer import evaluate_design
    from repro.core.topology_baselines import ring_design

    u = random_geometric_underlay(12, radius=0.5, seed=4)
    ov = build_overlay(u, list(u.graph.nodes)[:5])
    cats = compute_categories(ov)
    sto = _stochastic_for(ov, 8.0)
    with pytest.raises(ValueError, match="reroute_per_phase"):
        evaluate_design(
            ring_design(5), cats, 1e6, 5, overlay=ov,
            stochastic=sto, reroute_per_phase=True, engine="jax",
        )


def test_service_config_validates_engine():
    from repro.runtime.design_service import ServiceConfig

    assert ServiceConfig(engine="jax").engine == "jax"
    with pytest.raises(ValueError, match="unknown pricing engine"):
        ServiceConfig(engine="turbo")
