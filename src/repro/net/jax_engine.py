"""XLA-batched rollout engine: Monte-Carlo design pricing in one launch.

The numpy engines in ``net/simulator.py`` price one scenario at a time
from a Python event loop. This module ports the batched water-filling
engine (``engine="batched"``, the retained parity oracle) to jax: the
progressive-filling inner loop is a ``lax.while_loop`` over the fixed
CSR ``BranchIncidence`` (padded flat-entry arrays, int64 indices,
float64 throughout), the piecewise-constant scenario timeline is a
``lax.scan`` over per-phase capacity vectors (the
``CategoryIncidence.rescaled`` idea — swap the capacity vector, keep
the structure), and the whole stochastic batch runs in lockstep with
the rollout axis stored *last* on every array — hundreds of
realizations priced per device launch instead of one per Python loop
iteration.

Segment reductions over the incidence use bounded-degree tables
rather than CSR entry passes: ``branch_table``/``edge_table`` list
each row's neighbors padded to a static power-of-two width, so a
reduction is a handful of unrolled contiguous-row gathers over
[rows, R] arrays. On single-core CPU that is the difference between a
usable and an unusable kernel — XLA lowers ``segment_sum`` to
scatter-add (~25x slower per round) and even the cumsum-based
sorted-segment idiom pays ~5 ns/entry/rollout, while a batch-last row
gather runs at memory bandwidth (~1 µs per water-fill round per lane
at R=256).

Scope: ``fairness="maxmin"``, capacity phases, and churn — the paths
stochastic pricing actually exercises. Cross-traffic and straggler
events need the host event loop; entries here reject them with the
``engine="batched"`` fallback spelled out. Parity: per-rollout
makespan/flow-completion match ``engine="batched"`` to rtol=1e-9 on
the same realizations (property-tested; nightly-gated at 220 agents by
``benchmarks/rollout_scale.py``), and the event arithmetic — tie
detection by exact fp equality, breakpoint landing (``t = t_next``,
no drift), the 1e-9·κ finish threshold — mirrors the numpy loop term
for term. The capacity *drain* per water-fill round is grouped
(``smin × crossings`` versus numpy's sequential per-entry
subtraction), the same grouping difference that already separates
"batched" from "vectorized".

float64 is load-bearing and scoped to the launch: ``run_rollouts``
traces and runs ``_run_batch`` inside ``jax.enable_x64(True)``, so
pricing is float64 whatever the process flag says, and the flag it
leaves behind is the one it found — a model step traced later in the
same process keeps its 32-bit defaults.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.analysis.contracts import maybe_validate
from repro.net.simulator import (
    BranchIncidence,
    ChurnEvent,
    Scenario,
    SimResult,
    _collect_result,
    compile_incidence,
)
from repro.net.stochastic import (
    RealizationBatch,
    densify_realizations,
)


# ---------------------------------------------------------------------------
# Device-CSR layout
# ---------------------------------------------------------------------------


def _bucket(n: int) -> int:
    """Smallest power-of-two >= max(8, n + 1).

    Every axis is padded to a bucket so (a) nearby design sizes share
    one compiled XLA program instead of recompiling per branch count,
    and (b) each axis keeps at least one inert padding row — padding
    entries can always point at branch ``num_branches`` / edge
    ``num_edges`` even when the real count is itself a power of two.
    """
    return max(8, 1 << int(n).bit_length())


@dataclasses.dataclass(frozen=True)
class DeviceIncidence:
    """Padded device-CSR mirror of a ``BranchIncidence``.

    Arrays are host numpy (shipped to the device per launch); shapes
    are power-of-two buckets of the real sizes. Padding is inert by
    construction: padding entries point at the padding branch
    ``num_branches`` (never active, size 0) and the padding edge
    ``num_edges`` (capacity 1.0, crossed only by padding entries, so
    its count is always zero and its share always inf).

    Two entry orderings ride along so device segment reductions are
    sorted-segment: ``flat_branch``/``flat_edge`` are branch-major (as
    in the source incidence — ``flat_branch`` ascending) and
    ``edge_branch``/``edge_edge`` are edge-major (``edge_edge``
    ascending — the source's CSC order). ``branch_ptr``/``edge_ptr``
    extend the source CSR pointers over the padded rows (the pad row
    owns exactly the pad entries, every row past it is empty).

    The kernels themselves consume the bounded-degree *tables* derived
    from those pointers: ``branch_table[b]`` lists the edges branch
    ``b`` crosses (padded with the inert edge ``E``) and
    ``edge_table[e]`` lists the branches crossing edge ``e`` (padded
    with the inert branch ``B``). With the rollout axis stored *last*
    ([rows, R] arrays), a table row lookup is one contiguous-row
    gather — on single-core CPU that is ~60x cheaper per round than
    XLA's cumsum lowering over CSR entries, and orders of magnitude
    cheaper than its scatter-add segment sum. Prefixes are
    bitwise-equal to the source arrays (validated under
    ``REPRO_VALIDATE=1`` by
    ``repro.analysis.contracts.validate_device_incidence``).
    """

    source: BranchIncidence
    num_branches: int
    num_edges: int
    num_entries: int
    flat_branch: np.ndarray  # [Z] int64, branch-major; padding -> B
    flat_edge: np.ndarray  # [Z] int64, branch-major; padding -> E
    edge_branch: np.ndarray  # [Z] int64, edge-major; padding -> B
    edge_edge: np.ndarray  # [Z] int64, edge-major ascending; padding -> E
    branch_ptr: np.ndarray  # [B_pad+1] int64 CSR ptr into flat_* arrays
    edge_ptr: np.ndarray  # [E_pad+1] int64 CSR ptr into edge_* arrays
    branch_table: np.ndarray  # [B_pad, D] int32 edges per branch; pad -> E
    edge_table: np.ndarray  # [E_pad, K] int32 branches per edge; pad -> B
    base_capacity: np.ndarray  # [E_pad] float64; padding 1.0
    sizes: np.ndarray  # [B_pad] float64 per-branch demand; padding 0.0

    def __post_init__(self):
        # Padded-layout contract; no-op unless REPRO_VALIDATE=1
        # (repro.analysis.contracts.validate_device_incidence).
        maybe_validate(self)

    @property
    def padded_branches(self) -> int:
        return self.sizes.size

    @property
    def padded_edges(self) -> int:
        return self.base_capacity.size


def _table_width(max_degree: int) -> int:
    """Smallest power-of-two >= max(2, max_degree) — bucketed so nearby
    instances share compiled programs, floored at 2 so the kernels'
    unrolled table reduction always has a fixed minimum shape."""
    return max(2, 1 << max(0, int(max_degree) - 1).bit_length())


def _pack_table(
    ptr: np.ndarray, values: np.ndarray, rows: int, fill: int
) -> np.ndarray:
    """[rows, W] int32 table of each CSR row's values.

    ``W`` is the bucketed max real row degree; short rows and pad rows
    (real row count up to ``rows``) are filled with ``fill`` — the
    inert pad index whose mask value is always False, so table padding
    contributes exactly zero to every kernel reduction."""
    deg = np.diff(ptr)
    width = _table_width(int(deg.max(initial=0)))
    table = np.full((rows, width), fill, dtype=np.int32)
    real = deg.size
    cols = np.arange(width)[None, :]
    mask = cols < deg[:, None]
    table[:real][mask] = values
    return table


def device_incidence(
    inc: BranchIncidence, flow_size: np.ndarray
) -> DeviceIncidence:
    """Pad ``inc`` into the device layout.

    ``flow_size[h]`` is demand h's size in bytes; per-branch sizes are
    gathered through ``inc.flows``. The edge-major ordering reuses the
    source's CSC arrays (``edge_branch`` + the edge ids its ``edge_ptr``
    implies), so no re-sort happens here.
    """
    nb, ne = inc.num_branches, inc.num_edges
    nnz = inc.flat_branch.size
    bp, ep, zp = _bucket(nb), _bucket(ne), _bucket(nnz)
    fb = np.full(zp, nb, dtype=np.int64)
    fb[:nnz] = inc.flat_branch
    fe = np.full(zp, ne, dtype=np.int64)
    fe[:nnz] = inc.flat_edge
    eb = np.full(zp, nb, dtype=np.int64)
    eb[:nnz] = inc.edge_branch
    ee = np.full(zp, ne, dtype=np.int64)
    ee[:nnz] = np.repeat(
        np.arange(ne, dtype=np.int64), np.diff(inc.edge_ptr)
    )
    cap = np.ones(ep, dtype=np.float64)
    cap[:ne] = inc.base_capacity
    sizes = np.zeros(bp, dtype=np.float64)
    sizes[:nb] = flow_size[inc.flows]
    # Padded CSR pointers: the pad row (branch nb / edge ne) owns the
    # pad entries [nnz, zp); every row past it is empty at zp.
    bptr = np.full(bp + 1, zp, dtype=np.int64)
    bptr[: nb + 1] = inc.branch_ptr
    eptr = np.full(ep + 1, zp, dtype=np.int64)
    eptr[: ne + 1] = inc.edge_ptr
    return DeviceIncidence(
        source=inc,
        num_branches=nb,
        num_edges=ne,
        num_entries=nnz,
        flat_branch=fb,
        flat_edge=fe,
        edge_branch=eb,
        edge_edge=ee,
        branch_ptr=bptr,
        edge_ptr=eptr,
        branch_table=_pack_table(
            inc.branch_ptr, inc.flat_edge, bp, fill=ne
        ),
        edge_table=_pack_table(
            inc.edge_ptr, inc.edge_branch, ep, fill=nb
        ),
        base_capacity=cap,
        sizes=sizes,
    )


# ---------------------------------------------------------------------------
# Scenario lowering (host side)
# ---------------------------------------------------------------------------


def _check_supported(scenario: Scenario | None, fairness: str) -> None:
    if fairness != "maxmin":
        raise ValueError(
            "engine='jax' implements fairness='maxmin' only; price "
            "equal-share allocations with engine='batched'"
        )
    if scenario is not None and (
        scenario.cross_traffic or scenario.stragglers
    ):
        raise ValueError(
            "engine='jax' lowers capacity phases and churn only; "
            "cross-traffic and straggler events need the host event "
            "loop — price this scenario with engine='batched'"
        )


def branch_cancel_times(
    inc: BranchIncidence,
    flow_source: np.ndarray,
    churn: Sequence[ChurnEvent],
) -> np.ndarray:
    """Earliest departure cancelling each branch ([B] float64, +inf when
    none) — churn lowered to a static per-branch quantity so per-rollout
    departure times stay one dense vmap axis. A departure hits branches
    on overlay links touching the agent and all branches of flows it
    sources, exactly the numpy loop's rule."""
    cancel = np.full(inc.num_branches, np.inf, dtype=np.float64)
    src = flow_source[inc.flows]
    for ev in churn:
        hit = (
            (inc.links[:, 0] == ev.agent)
            | (inc.links[:, 1] == ev.agent)
            | (src == ev.agent)
        )
        np.minimum(
            cancel, np.where(hit, float(ev.time), np.inf), out=cancel
        )
    return cancel


def batch_cancel_times(
    inc: BranchIncidence,
    flow_source: np.ndarray,
    batch: RealizationBatch,
) -> np.ndarray:
    """Per-rollout branch cancellation times ([R, B] float64, +inf when
    none): ``branch_cancel_times`` applied to each realization's churn
    schedule — the host half of the churn lowering that
    ``rollout_batch_results`` (and the trace-lint registry) feeds to
    the device launch."""
    cancel = np.empty(
        (batch.num_rollouts, inc.num_branches), dtype=np.float64
    )
    for r, churn in enumerate(batch.churn):
        cancel[r] = branch_cancel_times(inc, flow_source, churn)
    return cancel


# ---------------------------------------------------------------------------
# Device kernels
# ---------------------------------------------------------------------------


def _table_any(mask, table):
    """OR-reduce ``mask`` rows through a bounded-degree table:
    ``out[i] = any(mask[table[i, k]] for k)``, unrolled over the static
    width. With the rollout axis last, every ``mask[table[:, k], :]``
    is a contiguous-row gather — the layout trick that keeps the
    per-round cost at memory bandwidth instead of XLA's scatter or
    cumsum lowerings (≥25x slower per round on single-core CPU)."""
    out = mask[table[:, 0], :]
    for k in range(1, table.shape[1]):
        out = jnp.logical_or(out, mask[table[:, k], :])
    return out


def _table_count(mask, table, dtype):
    """Count-reduce ``mask`` rows through a bounded-degree table:
    ``out[i] = sum(mask[table[i, k]] for k)`` (exact — a count is at
    most the static table width, so int16 suffices below 32768), same
    contiguous-row-gather layout as ``_table_any``."""
    out = mask[table[:, 0], :].astype(dtype)
    for k in range(1, table.shape[1]):
        out = out + mask[table[:, k], :]
    return out


def _waterfill(active, caps, branch_table, edge_table):
    """Batched water-filling on device — ``_maxmin_rates_batched`` with
    the per-round capacity drain grouped as ``smin × crossings``, every
    array carrying the rollout axis *last* ([B_pad, R] / [E_pad, R]).

    The loop is memory-bandwidth-bound, so the carried state is the
    cheapest exact encoding of numpy's:

    - Counts (unfrozen crossers per edge) are carried across rounds:
      because every frozen branch was unfrozen the round it froze, the
      drained crossings are exactly ``counts - counts_next`` — an
      exact integer difference matching numpy's incrementally
      maintained counts, and one fewer table reduction per round.
    - The share map is carried too, computed fused with the capacity
      drain from the just-updated ``(cap_left, counts)`` — the same
      operands numpy divides at the top of its next round, so the
      values are bitwise identical while the loop saves a full
      [E_pad, R] read-modify-write.
    - Rates are stamped in place the round a branch freezes
      (``where(freeze, smin, rates)``). A round-log + gather
      reconstruction was measured too: its ``dynamic_update_slice``
      blocks fusion across the unrolled round boundary and loses ~10%
      despite carrying less state.

    Tied edges are detected by exact fp equality with the lane's
    minimum share, and every unfrozen crosser of a tied edge freezes
    at ``smin``. Lanes converge independently: a lane with nothing
    unfrozen (or a non-finite minimum share) has an all-inf share map,
    which makes ``ok`` false and every update a no-op — the same
    per-lane masking ``vmap`` of a ``while_loop`` would apply.
    """
    num_b, num_r = active.shape
    cdtype = jnp.int16 if edge_table.shape[1] < 2**15 else jnp.int32

    def cond(state):
        unfrozen, stop = state[0], state[4]
        return jnp.any(
            jnp.logical_and(jnp.any(unfrozen, axis=0), ~stop)
        )

    def body(state):
        unfrozen, counts, cap_left, share, stop, rates = state
        smin = jnp.min(share, axis=0)
        ok = jnp.logical_and(jnp.isfinite(smin), ~stop)
        tied = share == smin[None, :]
        # No unfrozen mask on the tied pass: frozen branches crossing
        # a tied edge are filtered branch-side by ``& unfrozen`` below.
        hit = _table_any(tied, branch_table)
        freeze = jnp.logical_and(
            jnp.logical_and(hit, unfrozen), ok[None, :]
        )
        unfrozen = jnp.logical_and(unfrozen, jnp.logical_not(freeze))
        counts_next = _table_count(unfrozen, edge_table, cdtype)
        smin_safe = jnp.where(ok, smin, 0.0)
        rates = jnp.where(freeze, smin_safe[None, :], rates)
        # freeze ⊆ unfrozen, so counts - counts_next is exactly the
        # crossings drained this round. Draining as two fma passes
        # (instead of materializing the int->f64 cast of the
        # difference) measures ~15% faster per round. It is a third
        # grouping of numpy's sequential per-entry drain — "batched"
        # vs "vectorized" already differ the same way, and the parity
        # contract is rtol=1e-9 on results, not bitwise drains.
        cap_left = (
            cap_left
            - smin_safe[None, :] * counts
            + smin_safe[None, :] * counts_next
        )
        share = jnp.where(
            counts_next > 0,
            cap_left / counts_next.astype(jnp.float64),
            jnp.inf,
        )
        stop = jnp.logical_or(stop, jnp.logical_not(jnp.isfinite(smin)))
        return unfrozen, counts_next, cap_left, share, stop, rates

    counts0 = _table_count(active, edge_table, cdtype)
    share0 = jnp.where(
        counts0 > 0, caps / counts0.astype(jnp.float64), jnp.inf
    )
    state = (
        active, counts0, caps, share0,
        jnp.zeros((num_r,), dtype=bool),
        jnp.zeros((num_b, num_r), dtype=jnp.float64),
    )
    # Two rounds per loop iteration: a round past convergence is an
    # exact no-op (``ok`` false everywhere -> nothing freezes, nothing
    # drains, no rate is stamped), and the unroll lets XLA fuse across
    # the round boundary — measured ~20% faster than checking ``cond``
    # every round.
    state = lax.while_loop(cond, lambda s: body(body(s)), state)
    return state[5]


def _simulate_batch(caps_pp, cancel_time, active0, sizes, starts,
                    max_events, branch_table, edge_table):
    """All rollouts on device: ``lax.scan`` over the shared boundary
    grid, a ``lax.while_loop`` event loop per interval — the numpy
    event loop's arithmetic verbatim per lane (dt selection, exact
    boundary landing, finish threshold), with the rollout axis last on
    every array ([B_pad, R] state, [P, E_pad, R] capacities). Lanes
    advance independently: every update is masked by the lane's own
    loop condition (``live``), exactly the masking ``vmap`` of a
    ``while_loop`` applies, so per-lane results are bitwise those of a
    one-lane run. Churn applies at interval entry (every churn time is
    a grid boundary). Starvation (no positive rate, no future
    boundary) sets a per-lane flag the host raises on — exceptions
    cannot cross jit.
    """
    thresh = 1e-9 * sizes
    ends = jnp.concatenate(
        [starts[1:], jnp.full((1,), jnp.inf, dtype=jnp.float64)]
    )

    def phase_step(carry, xs):
        caps, t_start, t_end = xs
        t, remaining, done_time, cancelled, active, events, starved = carry
        newly = jnp.logical_and(active, cancel_time <= t_start)
        cancelled = jnp.logical_or(cancelled, newly)
        active = jnp.logical_and(active, jnp.logical_not(newly))

        def lanes_live(t_, act, ev, stv):
            return (
                jnp.any(act, axis=0)
                & (t_ < t_end)
                & jnp.logical_not(stv)
                & (ev < max_events)
            )

        def cond(s):
            t_, _rem, _done, act, ev, stv = s
            return jnp.any(lanes_live(t_, act, ev, stv))

        def body(s):
            t_, remaining_, done_, active_, events_, starved_ = s
            live = lanes_live(t_, active_, events_, starved_)
            # Lanes already done this interval enter the water-fill
            # with nothing unfrozen, so they cost no extra rounds and
            # their (zero) rates are discarded by the masks below.
            rates = _waterfill(
                jnp.logical_and(active_, live[None, :]), caps,
                branch_table, edge_table,
            )
            pos = jnp.any(
                jnp.where(active_, rates, 0.0) > 0.0, axis=0
            )
            starved_now = (
                jnp.logical_not(pos) & jnp.isinf(t_end) & live
            )
            dt0 = jnp.min(
                jnp.where(
                    active_,
                    remaining_ / jnp.maximum(rates, 1e-300),
                    jnp.inf,
                ),
                axis=0,
            )
            bdt = t_end - t_
            use_b = bdt < dt0
            dt = jnp.where(use_b, bdt, dt0)
            t_new = jnp.where(use_b, t_end, t_ + dt0)
            t_new = jnp.where(starved_now, t_, t_new)
            # All-nonpositive rates jump to the boundary without
            # draining (numpy's `continue` path); mixed-sign rounds
            # subtract for every active branch as numpy does.
            dt_eff = jnp.where(pos, dt, 0.0)
            update = jnp.logical_and(active_, live[None, :])
            remaining_ = jnp.where(
                update, remaining_ - rates * dt_eff[None, :], remaining_
            )
            finished = jnp.logical_and(
                update, remaining_ <= thresh[:, None]
            )
            done_ = jnp.where(
                finished, jnp.broadcast_to(t_new[None, :], done_.shape),
                done_,
            )
            active_ = jnp.logical_and(active_, jnp.logical_not(finished))
            return (
                jnp.where(live, t_new, t_), remaining_, done_, active_,
                events_ + live.astype(jnp.int64),
                jnp.logical_or(starved_, starved_now),
            )

        t, remaining, done_time, active, events, starved = lax.while_loop(
            cond, body, (t, remaining, done_time, active, events, starved)
        )
        return (
            t, remaining, done_time, cancelled, active, events, starved
        ), None

    num_b, num_r = active0.shape
    init = (
        jnp.zeros((num_r,), dtype=jnp.float64),
        jnp.broadcast_to(sizes[:, None], (num_b, num_r)),
        jnp.full((num_b, num_r), jnp.nan, dtype=jnp.float64),
        jnp.zeros((num_b, num_r), dtype=bool),
        active0,
        jnp.zeros((num_r,), dtype=jnp.int64),
        jnp.zeros((num_r,), dtype=bool),
    )
    carry, _ = lax.scan(phase_step, init, (caps_pp, starts, ends))
    _t, _remaining, done_time, cancelled, active, events, starved = carry
    return done_time, cancelled, active, events, starved


@jax.jit
def _run_batch(branch_table, edge_table, sizes, active0, starts, caps,
               cancel, max_events):
    """One XLA launch for the whole Monte-Carlo batch: ``caps`` is
    [P, E_pad, R] and ``cancel``/``active0`` are [B_pad, R] — rollout
    axis last throughout (see ``_simulate_batch``)."""
    return _simulate_batch(
        caps, cancel, active0, sizes, starts, max_events,
        branch_table, edge_table,
    )


# ---------------------------------------------------------------------------
# Host entry points
# ---------------------------------------------------------------------------


def device_args(
    dev: DeviceIncidence,
    starts: np.ndarray,
    caps: np.ndarray,
    cancel_times: np.ndarray,
    max_events: int = 100_000,
) -> tuple:
    """The exact argument tuple ``run_rollouts`` launches ``_run_batch``
    with: host-side padding of ``caps`` [R, P, E] / ``cancel_times``
    [R, B] into the device buckets, rollout axis moved last
    ([P, E_pad, R] / [B_pad, R] — see ``_simulate_batch`` for why the
    kernel wants that layout). Exposed so the trace lint
    (``repro.analysis.tracelint``) certifies ``_run_batch`` against the
    argument shapes the real host path produces, not a reconstruction.
    """
    caps = np.asarray(caps, dtype=np.float64)
    cancel_times = np.asarray(cancel_times, dtype=np.float64)
    rollouts = caps.shape[0]
    nb, ne = dev.num_branches, dev.num_edges
    starts = np.asarray(starts, dtype=np.float64)
    caps_p = np.ones(
        (starts.size, dev.padded_edges, rollouts), dtype=np.float64
    )
    caps_p[:, :ne, :] = np.transpose(caps, (1, 2, 0))
    cancel_p = np.full(
        (dev.padded_branches, rollouts), np.inf, dtype=np.float64
    )
    cancel_p[:nb, :] = cancel_times.T
    active0 = np.zeros((dev.padded_branches, rollouts), dtype=bool)
    active0[:nb, :] = True
    return (
        dev.branch_table, dev.edge_table, dev.sizes, active0, starts,
        caps_p, cancel_p, np.asarray(max_events, dtype=np.int64),
    )


def run_rollouts(
    dev: DeviceIncidence,
    starts: np.ndarray,
    caps: np.ndarray,
    cancel_times: np.ndarray,
    max_events: int = 100_000,
) -> list[tuple[np.ndarray, np.ndarray, int, int]]:
    """Run R rollouts in one launch; per rollout returns
    ``(done_time[B], cancelled[B], events, unfinished)`` on the real
    (unpadded) branches.

    ``caps`` is [R, P, E] on the source incidence's edges and
    ``cancel_times`` is [R, B]; padding to the device buckets happens
    in ``device_args``. Raises the numpy engines' starvation
    ``RuntimeError`` if any rollout starves (all-zero rates with no
    future boundary).
    """
    nb = dev.num_branches
    rollouts = np.asarray(caps).shape[0]
    with jax.enable_x64(True):  # the process's only float64 scope
        done, cancelled, active, events, starved = (
            np.asarray(a)
            for a in _run_batch(
                *device_args(dev, starts, caps, cancel_times, max_events)
            )
        )
    if bool(np.any(starved)):
        raise RuntimeError("starved branches; invalid routing/capacities")
    return [
        (
            done[:nb, r],
            cancelled[:nb, r],
            int(events[r]),
            int(active[:nb, r].sum()),
        )
        for r in range(rollouts)
    ]


def simulate_jax(
    sol,
    overlay,
    fairness: str = "maxmin",
    max_events: int = 100_000,
    scenario: Scenario | None = None,
    incidence: BranchIncidence | None = None,
    extra_boundaries: Sequence[float] = (),
) -> SimResult:
    """``simulate(engine="jax")``: one deterministic run on the device.

    Semantically ``engine="batched"`` for the supported scenario
    surface (maxmin fairness; capacity phases + churn), to rtol=1e-9.
    ``extra_boundaries`` adds grid boundaries (how ``simulate_phased``
    lands exactly on its segment starts).
    """
    _check_supported(scenario, fairness)
    if scenario is not None:
        scenario.validate()
        m = overlay.num_agents
        for ev in scenario.churn:
            if not 0 <= ev.agent < m:
                raise ValueError(
                    f"scenario references agent {ev.agent}, but the "
                    f"overlay has {m} agents"
                )
    if incidence is None:
        branches = sol.unicast_branches(overlay)
        if not branches:
            return SimResult(0.0, tuple(0.0 for _ in sol.demands), 0)
        incidence = compile_incidence(sol, overlay, branches)
    elif incidence.num_branches == 0:
        return SimResult(0.0, tuple(0.0 for _ in sol.demands), 0)
    flow_size = np.array([d.size for d in sol.demands], dtype=np.float64)
    flow_source = np.array(
        [d.source for d in sol.demands], dtype=np.int64
    )
    dev = device_incidence(incidence, flow_size)
    batch = densify_realizations(
        (scenario if scenario is not None else Scenario(),),
        incidence, extra_boundaries=extra_boundaries,
    )
    cancel = branch_cancel_times(
        incidence, flow_source, batch.churn[0]
    )
    ((done, cancelled, events, unfinished),) = run_rollouts(
        dev, batch.starts, batch.capacity, cancel[None], max_events
    )
    return _collect_result(
        sol, incidence.flows, done, cancelled, events, unfinished
    )


def rollout_batch_results(
    sol,
    dev: DeviceIncidence,
    batch: RealizationBatch,
    max_events: int = 100_000,
) -> tuple[SimResult, ...]:
    """Price every realization in ``batch`` against the precompiled
    ``dev`` in one vmapped launch — the designer's hot path. Returns
    one ``SimResult`` per rollout, in rollout order, with the numpy
    engines' NaN/cancellation semantics (``_collect_result``)."""
    inc = dev.source
    flow_source = np.array(
        [d.source for d in sol.demands], dtype=np.int64
    )
    cancel = batch_cancel_times(inc, flow_source, batch)
    outs = run_rollouts(
        dev, batch.starts, batch.capacity, cancel, max_events
    )
    return tuple(
        _collect_result(sol, inc.flows, done, cancelled, events, unfin)
        for done, cancelled, events, unfin in outs
    )


def simulate_rollout_batch(
    sol,
    overlay,
    batch: RealizationBatch,
    fairness: str = "maxmin",
    max_events: int = 100_000,
    incidence: BranchIncidence | None = None,
) -> tuple[SimResult, ...]:
    """Price a whole ``RealizationBatch`` in one XLA launch.

    The incidence is compiled (or taken precompiled) once for the
    activated-link set and shared by every rollout; registered against
    ``_rollout_batch_reference`` — the numpy ``engine="batched"``
    loop over the same realizations — in ``parity_manifest.txt``
    (per-rollout makespan/flow-completion parity at rtol=1e-9).
    """
    if fairness != "maxmin":
        raise ValueError(
            "engine='jax' implements fairness='maxmin' only; price "
            "equal-share allocations with engine='batched'"
        )
    if incidence is None:
        incidence = compile_incidence(sol, overlay)
    flow_size = np.array([d.size for d in sol.demands], dtype=np.float64)
    dev = device_incidence(incidence, flow_size)
    return rollout_batch_results(sol, dev, batch, max_events=max_events)


def _rollout_batch_reference(
    sol,
    overlay,
    batch: RealizationBatch,
    fairness: str = "maxmin",
    max_events: int = 100_000,
    incidence: BranchIncidence | None = None,
) -> tuple[SimResult, ...]:
    """Numpy oracle for ``simulate_rollout_batch``: the Python rollout
    loop over the batch's realizations with ``engine="batched"`` — the
    pre-device pricing path, kept as the parity reference the device
    engine is property-tested and nightly-gated against."""
    from repro.net.simulator import simulate

    return tuple(
        simulate(
            sol, overlay, fairness=fairness, max_events=max_events,
            scenario=sc, engine="batched", incidence=incidence,
        )
        for sc in batch.realizations
    )
