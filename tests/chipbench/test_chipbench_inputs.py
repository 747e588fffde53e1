"""Inputs from the seed: the same seed gives the same inputs, another
seed other inputs; and the two views of the edge deployment agree."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import inputs  # noqa: E402
from chipbench.reference import fluid  # noqa: E402
from chipbench.edge_net import (  # noqa: E402
    EdgeNet,
    fading_states,
    flaky_links,
    lower_capacities,
)

NET = {"num_nodes": 38, "num_links": 219, "placement_seed": 0,
       "link_bytes_per_s": 125000.0, "num_agents": 6,
       "exchange_bytes": 94.47e6}
BIG = 2**31 + 12345  # seeds run past 32 bits


def test_fading_is_a_function_of_the_seed():
    p = ((0.8, 0.2), (0.5, 0.5))
    a = fading_states(BIG, 0, 64, 10, p, 0)
    assert np.array_equal(a, fading_states(BIG, 0, 64, 10, p, 0))
    assert not np.array_equal(a, fading_states(BIG + 1, 0, 64, 10, p, 0))
    assert not np.array_equal(a, fading_states(BIG, 1, 64, 10, p, 0))
    assert (a[:, 0] == 0).all() and set(np.unique(a)) <= {0, 1}
    # Stationary share of the degraded state is 0.2 / 0.7.
    long = fading_states(7, 0, 4000, 10, p, 0)[:, 5:]
    assert abs(long.mean() - 0.2 / 0.7) < 0.02


def test_the_program_and_the_reference_see_one_deployment():
    from repro.net.topology import lowest_degree_nodes, roofnet_like

    net = EdgeNet.from_config(NET)
    program_mesh = roofnet_like(seed=NET["placement_seed"])
    assert ({frozenset(e) for e in net.links}
            == {frozenset(e) for e in program_mesh.graph.edges})
    assert list(net.agents) == lowest_degree_nodes(program_mesh, 6)
    sol, ov, inc = net.program_instance()
    flow, path, edge_link = net.reference_tables()
    m = NET["num_agents"]
    assert inc.num_branches == flow.size == m * (m - 1)
    # Branch by branch: the same source and the same links, in order.
    index = net.link_index()
    prog = sorted((int(inc.flows[b]), tuple(
        index[inc.edges[e]] for e in
        inc.flat_edge[inc.branch_ptr[b]:inc.branch_ptr[b + 1]]))
        for b in range(inc.num_branches))
    ref = sorted((int(flow[b]), tuple(int(edge_link[e]) for e in path[b]
                                      if e >= 0))
                 for b in range(flow.size))
    assert prog == ref
    flaky = flaky_links(net, 3)
    assert flaky.sum() == len(net.links) // 3 + (len(net.links) % 3 > 0)
    states = fading_states(BIG, 0, 3, 10, ((0.8, 0.2), (0.5, 0.5)), 0)
    a = lower_capacities(net, states, (1.0, 0.35), flaky,
                         net.edge_links(inc.edges))
    np.testing.assert_array_equal(a[:, 0], net.capacity)
    assert set(np.unique(a)) <= {net.capacity, 0.35 * net.capacity}
    b = lower_capacities(net, states, (1.0, 0.35), flaky, edge_link)
    ref_edge = [2 * index[e] + (net.links[index[e]] != tuple(e))
                for e in inc.edges]
    np.testing.assert_array_equal(a, b[:, :, ref_edge])
    assert (a != net.capacity).any()


def test_a_deployment_the_generator_does_not_build_is_refused():
    with pytest.raises(ValueError, match="routing"):
        EdgeNet.from_config(dict(NET, routing="milp"))


def test_fluid_reference_hand_checked():
    # Two branches share edge 0 (capacity 2); the second also crosses
    # edge 1 (capacity 0.5). Max-min: 0.5 to the second, 1.5 to the first.
    path = np.array([[0, -1], [0, 1]])
    rates = fluid.maxmin_rates(np.array([True, True]), path,
                               np.array([2.0, 0.5]))
    np.testing.assert_allclose(rates, [1.5, 0.5])
    # Sizes 3 and 3: the first is done at t=2, then the second gets 0.5
    # alone (edge 1 binds) and needs 2 more bytes: done at t=6. With the
    # capacity of edge 1 doubled from t=4, it is done at t=5.
    flow = np.array([0, 1])
    sizes = np.array([3.0, 3.0])
    one = fluid.simulate(flow, path, sizes, np.zeros(1),
                         np.array([[2.0, 0.5]]), 2)
    np.testing.assert_allclose(one, [2.0, 6.0])
    two = fluid.simulate(flow, path, sizes, np.array([0.0, 4.0]),
                         np.array([[2.0, 0.5], [2.0, 1.0]]), 2)
    np.testing.assert_allclose(two, [2.0, 5.0])


def _shapes():
    f32 = jnp.float32
    return {"blocks": {"b0_attn": {
                "norm1": {"scale": jax.ShapeDtypeStruct((2, 2, 8), f32)},
                "mixer": {"wq": {
                    "kernel": jax.ShapeDtypeStruct((2, 2, 8, 8), f32),
                    "bias": jax.ShapeDtypeStruct((2, 2, 8), f32)}}}},
            "embed": {"table": jax.ShapeDtypeStruct((2, 32, 8), f32)}}


def test_weights_are_a_function_of_the_seed():
    make = inputs.param_maker(_shapes())
    a = make(inputs.seed_key(BIG, 0))
    b = make(inputs.seed_key(BIG, 0))
    c = make(inputs.seed_key(BIG + 1, 0))
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    blk = a["blocks"]["b0_attn"]
    assert (np.asarray(blk["norm1"]["scale"]) == 1).all()
    k = np.asarray(blk["mixer"]["wq"]["kernel"])
    assert not np.array_equal(k, np.asarray(
        c["blocks"]["b0_attn"]["mixer"]["wq"]["kernel"]))
    assert not np.array_equal(k[0], k[1])  # agents start apart
    assert np.abs(k).max() <= 2 * 8**-0.5 + 1e-6


@pytest.mark.parametrize("vocab", [512, 151936])
def test_tokens_are_a_function_of_the_seed(vocab):
    make = inputs.token_maker((4, 1, 3, 65), vocab)
    a = np.asarray(make(inputs.seed_key(BIG, 1), 0))
    assert np.array_equal(a, np.asarray(make(inputs.seed_key(BIG, 1), 0)))
    assert not np.array_equal(a, np.asarray(make(inputs.seed_key(BIG, 1), 1)))
    assert a.dtype == np.int32 and a.min() >= 0 and a.max() < vocab
    # Each agent's most frequent token differs: the streams are non-IID.
    big = np.asarray(inputs.token_maker((4, 4096), vocab)(
        inputs.seed_key(3, 1), 0))
    tops = {int(np.bincount(row, minlength=vocab).argmax()) for row in big}
    assert len(tops) == 4
