"""Distributed D-PSGD mixing as TPU collectives (hardware adaptation §4).

The mixing step x_i ← Σ_j W_ij x_j is realized three ways:

  * ``mix_dense``   — einsum with W over the stacked agent axis. GSPMD
    compiles this to all-gather + local contraction: the *Clique/J*
    communication pattern, O(m·κ) bytes per agent. Baseline.
  * ``mix_allreduce`` — exact mean over agents (only valid for W = J);
    compiles to a single all-reduce: what classic synchronous data
    parallelism does. Reference point for the roofline.
  * ``mix_sparse``  — a static schedule of ``ppermute`` rounds derived
    from W's sparsity (edge-coloring of the activated digraph): each
    agent only ships κ bytes per activated neighbor. This is the paper's
    payoff on the ICI fabric: collective bytes ∝ |E_a| instead of m².

The schedule is built once per designed W (it is a *hyperparameter*, like
the mixing matrix itself) and baked into the jitted step as constants.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class GossipSchedule:
    """Static ppermute schedule for a sparse mixing matrix.

    rounds[r]   — tuple of (src_agent, dst_agent) pairs; each agent
                  appears at most once as src and once as dst per round
                  (ppermute semantics: missing dsts receive zeros).
    weights[r]  — length-m vector; weights[r][dst] = W[dst, src] for the
                  edge delivered to dst in round r (0 if none).
    self_weight — length-m vector of W[a, a].
    """

    num_agents: int
    rounds: tuple[tuple[tuple[int, int], ...], ...]
    weights: tuple[tuple[float, ...], ...]
    self_weight: tuple[float, ...]


def build_schedule(w: np.ndarray, atol: float = 1e-12) -> GossipSchedule:
    """Greedy edge-coloring of the activated digraph into ppermute rounds."""
    w = np.asarray(w, dtype=np.float64)
    m = w.shape[0]
    edges = [
        (src, dst)
        for dst in range(m)
        for src in range(m)
        if src != dst and abs(w[dst, src]) > atol
    ]
    rounds: list[list[tuple[int, int]]] = []
    for e in edges:
        placed = False
        for r in rounds:
            if all(e[0] != f[0] and e[1] != f[1] for f in r):
                r.append(e)
                placed = True
                break
        if not placed:
            rounds.append([e])
    weights = []
    for r in rounds:
        vec = [0.0] * m
        for src, dst in r:
            vec[dst] = float(w[dst, src])
        weights.append(tuple(vec))
    return GossipSchedule(
        num_agents=m,
        rounds=tuple(tuple(r) for r in rounds),
        weights=tuple(weights),
        self_weight=tuple(float(w[a, a]) for a in range(m)),
    )


def mix_dense(params: Any, w: jnp.ndarray) -> Any:
    """x_i ← Σ_j W_ij x_j over the leading (stacked) agent axis."""
    return jax.tree.map(
        lambda p: jnp.einsum(
            "ab,b...->a...", w.astype(jnp.float32), p.astype(jnp.float32)
        ).astype(p.dtype),
        params,
    )


def mix_allreduce(params: Any) -> Any:
    """W = J: plain averaging (classic DP all-reduce)."""
    return jax.tree.map(
        lambda p: jnp.broadcast_to(
            jnp.mean(p.astype(jnp.float32), axis=0, keepdims=True), p.shape
        ).astype(p.dtype),
        params,
    )


def mix_sparse_shardmap(
    params: Any,
    schedule: GossipSchedule,
    mesh: jax.sharding.Mesh,
    agent_axes: tuple[str, ...],
    param_specs: Any,
) -> Any:
    """Sparse mixing via a ppermute schedule inside shard_map.

    ``agent_axes`` are the mesh axes whose product forms the agent space
    (e.g. ("data",) single-pod, ("pod", "data") multi-pod agents-on-data,
    ("pod",) for pod-level agents). Each leaf of ``params`` must have the
    stacked agent dim 0 sharded over exactly ``agent_axes`` (size-1 local
    slice inside the shard_map body).

    Weight lookup is a gather from a tiny constant table indexed by the
    rank's agent id — numerically identical to the dense einsum on the
    activated support (validated in tests).
    """
    from jax.sharding import PartitionSpec as P

    m = schedule.num_agents
    axis_sizes = [mesh.shape[a] for a in agent_axes]
    if int(np.prod(axis_sizes)) != m:
        raise ValueError(
            f"agent axes {agent_axes} (={axis_sizes}) != num_agents {m}"
        )

    self_w = jnp.asarray(schedule.self_weight, jnp.float32)
    round_w = [jnp.asarray(w, jnp.float32) for w in schedule.weights]
    perms = [tuple(r) for r in schedule.rounds]

    def agent_id():
        idx = jnp.zeros((), jnp.int32)
        for a in agent_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def body(p):
        aid = agent_id()

        def mix_leaf(x):
            acc = x.astype(jnp.float32) * self_w[aid]
            for r, perm in enumerate(perms):
                recv = jax.lax.ppermute(x, agent_axes, perm)
                acc = acc + recv.astype(jnp.float32) * round_w[r][aid]
            return acc.astype(x.dtype)

        return jax.tree.map(mix_leaf, p)

    # in/out specs mirror the jit-level param specs (leaf dim0 on agents).
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs,),
        out_specs=param_specs,
        check_vma=False,
    )(params)


def mix_sparse_flat(
    params: Any,
    schedule: GossipSchedule,
    mesh: jax.sharding.Mesh,
    agent_axes: tuple[str, ...],
    slice_axes: tuple[str, ...] = ("model",),
) -> Any:
    """Sparse gossip for layouts whose params are REPLICATED over
    ``slice_axes`` (e.g. the data_dp layout: small models, no TP).

    Naively ppermuting replicated leaves would ship κ from every replica
    (|slice_axes|× redundant traffic). Instead the whole tree is raveled
    to one [A, N_pad] buffer sliced over ``slice_axes``: each replica
    ppermutes only its 1/|slice| slice, and the combined result is
    written back replicated (an all-gather of N/|slice| per chip —
    amortized across every leaf at once).
    """
    from jax.sharding import PartitionSpec as P

    leaves, treedef = jax.tree.flatten(params)
    slice_size = 1
    for a in slice_axes:
        slice_size *= mesh.shape[a]
    sizes = [int(np.prod(l.shape[1:])) for l in leaves]
    a_dim = leaves[0].shape[0]
    total = sum(sizes)
    pad = (-total) % slice_size
    # Ship in the native dtype when uniform (bf16 halves gossip bytes);
    # the per-edge accumulation is fp32 either way (mix_leaf).
    dtypes = {l.dtype for l in leaves}
    wire_dtype = leaves[0].dtype if len(dtypes) == 1 else jnp.float32
    flat = jnp.concatenate(
        [l.reshape(a_dim, -1).astype(wire_dtype) for l in leaves], axis=1
    )
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    agent_spec = agent_axes if len(agent_axes) > 1 else agent_axes[0]
    slice_spec = slice_axes if len(slice_axes) > 1 else slice_axes[0]
    spec = P(agent_spec, slice_spec)
    flat = jax.lax.with_sharding_constraint(
        flat, jax.sharding.NamedSharding(mesh, spec)
    )
    mixed = mix_sparse_shardmap(flat, schedule, mesh, agent_axes, spec)
    mixed = jax.lax.with_sharding_constraint(
        mixed, jax.sharding.NamedSharding(mesh, P(agent_spec, None))
    )
    out, off = [], 0
    for l, n in zip(leaves, sizes):
        out.append(
            mixed[:, off : off + n].reshape(l.shape).astype(l.dtype)
        )
        off += n
    return jax.tree.unflatten(treedef, out)


def effective_mixing_matrix(w: np.ndarray, rounds: int = 1) -> np.ndarray:
    """W^rounds — the matrix one model update sees under multi-round
    graph gossip (``rounds`` back-to-back exchanges on the same overlay
    before the local step; arxiv 2506.10607). ρ(Wʳ − J) = ρ(W − J)ʳ, so
    extra rounds buy convergence speed at r× the per-update network
    price — ``priced_training.GossipStrategy`` charges exactly that.
    ``rounds=1`` returns the float64 view of ``w`` (one-shot mixing).
    """
    if rounds < 1:
        raise ValueError(f"gossip rounds must be >= 1: {rounds}")
    w = np.asarray(w, dtype=np.float64)
    return np.linalg.matrix_power(w, rounds) if rounds > 1 else w


def gossip_collective_bytes(
    schedule: GossipSchedule, kappa_bytes: float, gossip_rounds: int = 1
) -> float:
    """Modeled per-iteration gossip traffic (all agents, both directions).

    Each directed activated edge ships κ bytes; compare with clique
    all-gather: m·(m−1)·κ. ``gossip_rounds`` scales the figure for a
    multi-round strategy (the ppermute schedule replays per round).
    """
    if gossip_rounds < 1:
        raise ValueError(f"gossip rounds must be >= 1: {gossip_rounds}")
    return (
        kappa_bytes * sum(len(r) for r in schedule.rounds) * gossip_rounds
    )
