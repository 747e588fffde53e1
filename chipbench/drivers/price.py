"""Pricing driver: closed-loop Monte-Carlo rollout launches.

Set-up builds the deployment through the program's API, draws a pool of
fading realization batches from ``--seed`` on the host, and makes one
launch to compile (or load) the kernel. The window then calls
``jax_engine.simulate_rollout_batch`` back to back, cycling through the
pool; each launch starts when the previous one's results are on the
host, as a design sweep's pricing calls do.

Check: a sample of the window's rollouts drawn from the seed, with the
one of longest makespan always in it, is priced again by the plain
reference (``reference/fluid.py``), and every flow's completion time is
compared.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from chipbench.edge_net import (
    EdgeNet,
    fading_states,
    flaky_links,
    lower_capacities,
)
from chipbench.reference import fluid
from chipbench.run import Check, span


@dataclasses.dataclass
class State:
    net: EdgeNet
    flaky: np.ndarray  # [L] bool: the link fades with the shared chain
    sol: object
    overlay: object
    incidence: object
    starts: np.ndarray
    states: list  # per pool batch: [R, P] chain states
    batches: list  # per pool batch: the program's RealizationBatch
    # Per launch: (pool batch, [R, H] flow completion times), one array a
    # launch, so the window holds no growing heap of result objects.
    results: list = dataclasses.field(default_factory=list)


def _launch(st: State, k: int):
    from repro.net import jax_engine

    return jax_engine.simulate_rollout_batch(
        st.sol, st.overlay, st.batches[k], incidence=st.incidence)


def setup(run) -> State:
    from repro.net.stochastic import RealizationBatch

    cfg, tr = run.cell.config, run.cell.traffic
    net = EdgeNet.from_config(cfg)
    flaky = flaky_links(net, int(tr["flaky_stride"]))
    sol, ov, inc = net.program_instance()
    steps, rollouts = int(tr["steps"]), int(tr["rollouts"])
    starts = float(tr["step_s"]) * np.arange(steps)
    edge_link = net.edge_links(inc.edges)
    states, batches = [], []
    for k in range(int(tr["pool"])):
        s = fading_states(run.seed, k, rollouts, steps, tr["transition"],
                          int(tr["initial"]))
        caps = lower_capacities(net, s, tr["scales"], flaky, edge_link)
        states.append(s)
        batches.append(RealizationBatch(starts=starts, capacity=caps,
                                        churn=((),) * rollouts,
                                        realizations=()))
    st = State(net, flaky, sol, ov, inc, starts, states, batches)
    _launch(st, 0)  # compiles, or loads from the persistent cache
    return st


def measure(run, st: State) -> None:
    rollouts = int(run.cell.traffic["rollouts"])
    pool = len(st.batches)
    launches, done = 0, []
    with run.window():
        t_start = time.perf_counter()
        deadline = t_start + run.window_seconds
        while True:
            k = launches % pool
            with span("launch"):
                try:
                    res = _launch(st, k)
                except RuntimeError:  # a starved lane: nothing returned
                    res = ()
            st.results.append((k, completions(res)))
            launches += 1
            done.append(time.perf_counter())
            if done[-1] >= deadline:
                break
        elapsed = time.perf_counter() - t_start
    run.window_s = elapsed
    run.attempted = launches * rollouts
    returned = sum(len(fc) for _, fc in st.results)
    run.failed = run.attempted - returned
    run.counts = {"launches": launches, "rollouts": returned}
    run.note_gaps(t_start, done)
    run.end_to_end["price_rollouts_per_s"] = returned / elapsed


def completions(results) -> np.ndarray:
    """[R, H] flow completion times of one launch's results."""
    return np.array([r.flow_completion for r in results], dtype=np.float64)


def _rel_err(got, want) -> float:
    got = np.asarray(got, dtype=np.float64)
    if got.shape != want.shape:
        return float("inf")
    err = np.abs(got - want) / np.abs(want)
    return float("inf") if not np.all(np.isfinite(err)) else float(err.max())


def sample(run, st: State) -> list:
    """(launch, rollout) pairs to check: drawn from the seed, plus the
    rollout of longest makespan in the window."""
    rollouts = int(run.cell.traffic["rollouts"])
    n = min(int(run.cell.traffic["check_rollouts"]),
            len(st.results) * rollouts)
    rng = np.random.default_rng([int(run.seed) % 2**64, 0xC4EC])
    flat = rng.choice(len(st.results) * rollouts, size=n, replace=False)
    pairs = [(int(i) // rollouts, int(i) % rollouts) for i in flat]
    longest, where = -np.inf, None
    for li, (_, fc) in enumerate(st.results):
        if len(fc):
            span_r = np.nanmax(fc, axis=1)
            r = int(np.argmax(span_r))
            if span_r[r] > longest:
                longest, where = float(span_r[r]), (li, r)
    if where is not None and where not in pairs:
        pairs[-1] = where
    return pairs


def reference_completions(st: State, k: int, r: int, scales,
                          dtype=np.float64) -> np.ndarray:
    net = st.net
    flow, path, edge_link = net.reference_tables()
    caps = lower_capacities(net, st.states[k][r:r + 1], scales, st.flaky,
                            edge_link)
    sizes = np.full(flow.size, net.exchange_bytes)
    return fluid.simulate(flow, path, sizes, st.starts, caps[0],
                          net.num_agents, dtype=dtype)


def check(run, st: State) -> None:
    scales = run.cell.traffic["scales"]
    worst = 0.0
    refs: dict = {}
    for li, r in sample(run, st):
        k, fc = st.results[li]
        if r >= len(fc):
            worst = float("inf")
            continue
        if (k, r) not in refs:
            refs[(k, r)] = reference_completions(st, k, r, scales)
        worst = max(worst, _rel_err(fc[r], refs[(k, r)]))
    limits = run.cell.limits
    run.checks.append(Check("flow_completion_rel_err", worst,
                            float(limits["flow_completion_rel_err"])))
    run.checks.append(Check("rollouts_missing", float(run.failed), 0.0))
