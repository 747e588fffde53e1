"""The edge-network pricing deployment and its Markov-fading traffic.

The deployment is the source paper's evaluation network (arXiv:2504.12210,
Sec. IV-A): a Roofnet-sized wireless mesh of ``num_nodes`` nodes and
``num_links`` links, every link ``link_bytes_per_s`` in each direction;
the ``num_agents`` lowest-degree nodes are the agents; every agent
multicasts its ``exchange_bytes`` model to its overlay neighbours, each
branch on the hop-count shortest underlay path. Roofnet's measured link
list is not available offline, so the links are placed as a random
geometric mesh with Roofnet's counts, from ``placement_seed``.

Fading: one two-state Markov chain shared by every ``flaky_stride``-th
underlay link (in sorted order), both directions, re-drawn on a fixed grid
of boundaries ``step_s`` seconds apart.

The deployment is described here once, as plain arrays, and handed two
ways: to the program through its own API (``program_instance``) and to
the plain reference as branch/edge tables (``reference_tables``). The
fading realizations are drawn here and lowered onto whichever edge list
the caller names, so both sides see bitwise the same capacities.
"""

from __future__ import annotations

import dataclasses
import itertools

import networkx as nx
import numpy as np

# What this module builds, and what a configuration file must state.
SUPPORTED = {"agents": "lowest_degree", "overlay": "clique",
             "routing": "hop_count_shortest_path", "fairness": "maxmin",
             "dtype": "float64"}


def mesh_links(num_nodes: int, num_links: int, seed: int):
    """Undirected links of a connected random geometric mesh with exactly
    ``num_links`` links: the shortest candidate links first, components
    joined by their closest node pair, then the longest links that are
    not bridges trimmed."""
    rng = np.random.default_rng(seed)
    pts = rng.random((num_nodes, 2))
    d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    order = sorted(
        ((i, j) for i in range(num_nodes) for j in range(i + 1, num_nodes)),
        key=lambda e: d2[e[0], e[1]],
    )
    g = nx.Graph()
    g.add_nodes_from(range(num_nodes))
    g.add_edges_from(order[:num_links])
    while not nx.is_connected(g):
        comps = list(nx.connected_components(g))
        best = None
        for a, b in itertools.combinations(range(len(comps)), 2):
            for u in comps[a]:
                for v in comps[b]:
                    if best is None or d2[u, v] < d2[best[0], best[1]]:
                        best = (u, v)
        g.add_edge(*best)
    extra = g.number_of_edges() - num_links
    for u, v in sorted(g.edges, key=lambda e: -d2[e[0], e[1]]):
        if extra <= 0:
            break
        g.remove_edge(u, v)
        if nx.is_connected(g):
            extra -= 1
        else:
            g.add_edge(u, v)
    return list(g.edges)


@dataclasses.dataclass(frozen=True)
class EdgeNet:
    num_nodes: int
    links: tuple  # undirected (u, v) in the graph's own order
    capacity: float  # bytes/s of every link, each direction
    agents: tuple  # underlay node of each agent
    exchange_bytes: float

    @classmethod
    def from_config(cls, cfg: dict) -> "EdgeNet":
        stated = {k: cfg.get(k, v) for k, v in SUPPORTED.items()}
        if stated != SUPPORTED:
            raise ValueError(f"the edge deployment is {SUPPORTED}, "
                             f"the configuration states {stated}")
        n = int(cfg["num_nodes"])
        links = tuple(mesh_links(n, int(cfg["num_links"]),
                                 int(cfg["placement_seed"])))
        degree = np.zeros(n, dtype=np.int64)
        for u, v in links:
            degree[u] += 1
            degree[v] += 1
        ranked = sorted(range(n), key=lambda a: (degree[a], a))
        agents = tuple(ranked[: int(cfg["num_agents"])])
        return cls(n, links, float(cfg["link_bytes_per_s"]), agents,
                   float(cfg["exchange_bytes"]))

    @property
    def num_agents(self) -> int:
        return len(self.agents)

    def graph(self) -> nx.Graph:
        g = nx.Graph()
        g.add_nodes_from(range(self.num_nodes))
        for u, v in self.links:
            g.add_edge(u, v, capacity=self.capacity)
        return g

    def overlay_links(self) -> list[tuple[int, int]]:
        """The clique: every pair of agents (agent indices)."""
        return list(itertools.combinations(range(self.num_agents), 2))

    def link_index(self) -> dict:
        """Undirected link index of each directed underlay edge."""
        out = {}
        for k, (u, v) in enumerate(self.links):
            out[(u, v)] = out[(v, u)] = k
        return out

    # -- the program's view ------------------------------------------------

    def program_instance(self):
        """(RoutingSolution, OverlayNetwork, BranchIncidence) built with the
        program's own API: the inputs ``simulate_rollout_batch`` takes."""
        from repro.net import (
            Underlay,
            build_overlay,
            compute_categories,
            demands_from_links,
            route_direct,
        )
        from repro.net.simulator import compile_incidence

        ov = build_overlay(Underlay(graph=self.graph()), list(self.agents))
        demands = demands_from_links(self.overlay_links(),
                                     self.exchange_bytes, self.num_agents)
        sol = route_direct(demands, compute_categories(ov),
                           self.exchange_bytes)
        return sol, ov, compile_incidence(sol, ov)

    # -- the reference's view ---------------------------------------------

    def reference_tables(self):
        """Plain tables of the same deployment: per branch its flow (the
        source agent) and its directed edges (-1 pads), edge ``2k`` being
        link k's ``u -> v`` direction and ``2k + 1`` its ``v -> u``; per
        directed edge its link."""
        g = self.graph()
        directed = {}
        for k, (u, v) in enumerate(self.links):
            directed[(u, v)], directed[(v, u)] = 2 * k, 2 * k + 1
        route = {}
        for i, j in self.overlay_links():
            p = nx.shortest_path(g, self.agents[i], self.agents[j])
            route[(i, j)] = p
            route[(j, i)] = p[::-1]
        flow, paths = [], []
        for src in range(self.num_agents):
            for dst in range(self.num_agents):
                if dst != src:
                    p = route[(src, dst)]
                    flow.append(src)
                    paths.append([directed[e] for e in zip(p[:-1], p[1:])])
        width = max(len(p) for p in paths)
        path = np.full((len(paths), width), -1, dtype=np.int64)
        for b, p in enumerate(paths):
            path[b, : len(p)] = p
        edge_link = np.repeat(np.arange(len(self.links)), 2)
        return np.asarray(flow, np.int64), path, edge_link

    def edge_links(self, edges) -> np.ndarray:
        """Undirected link of each directed underlay edge ``(u, v)``."""
        index = self.link_index()
        return np.array([index[tuple(e)] for e in edges], dtype=np.int64)


def flaky_links(net: EdgeNet, stride: int) -> np.ndarray:
    """[L] bool: every ``stride``-th link, in sorted order, fades."""
    order = sorted(range(len(net.links)),
                   key=lambda k: tuple(sorted(net.links[k])))
    out = np.zeros(len(net.links), dtype=bool)
    out[order[::stride]] = True
    return out


def fading_states(seed: int, pool_index: int, rollouts: int, steps: int,
                  transition, initial: int) -> np.ndarray:
    """[rollouts, steps] int8 chain states, bitwise determined by
    ``(seed, pool_index)``: the initial state holds on the first
    interval, and the chain steps at every later boundary."""
    rng = np.random.default_rng([int(seed) % 2**64, int(pool_index)])
    p = np.asarray(transition, dtype=np.float64)
    states = np.empty((rollouts, steps), dtype=np.int8)
    states[:, 0] = initial
    u = rng.random((rollouts, steps))
    for k in range(1, steps):
        prev = states[:, k - 1]
        cum = np.cumsum(p[prev], axis=1)
        states[:, k] = (u[:, k, None] >= cum[:, :-1]).sum(axis=1)
    return states


def lower_capacities(net: EdgeNet, states: np.ndarray, scales,
                     flaky: np.ndarray, edge_link: np.ndarray) -> np.ndarray:
    """[R, P, E] float64 capacities on the directed edges whose links are
    ``edge_link``: the link capacity times the chain's scale where the
    link fades, else the link capacity."""
    factor = np.asarray(scales, dtype=np.float64)[states]  # [R, P]
    fades = flaky[edge_link]
    caps = np.full(states.shape + edge_link.shape, net.capacity,
                   dtype=np.float64)
    caps[:, :, fades] = net.capacity * factor[:, :, None]
    return caps
