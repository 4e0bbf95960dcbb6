"""Time-aware state split: Topology/QueueState semantics, fluid drain
properties, constructor validation, and static-path bit-identity."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import jobs as J, network as N, schedule, solve
from repro.core.state import QueueState, Topology, advance, backlog_seconds
from util import random_instance


# -- advance / drain properties ---------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_advance_never_negative_and_clock_moves(seed):
    rng = np.random.default_rng(seed)
    net, _ = random_instance(rng, with_queues=True)
    dt = float(rng.uniform(0, 5))
    st2 = advance(net.topology, net.state, dt)
    assert (np.asarray(st2.q_node) >= 0).all()
    assert (np.asarray(st2.q_link) >= 0).all()
    np.testing.assert_allclose(float(st2.clock),
                               float(net.state.clock) + dt, rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_advance_composes(seed):
    """Fluid drain composes: advance(a).advance(b) == advance(a+b)."""
    rng = np.random.default_rng(seed)
    net, _ = random_instance(rng, with_queues=True)
    a, b = rng.uniform(0, 2, size=2)
    two = net.state.advance(net.topology, a).advance(net.topology, b)
    one = net.state.advance(net.topology, a + b)
    np.testing.assert_allclose(np.asarray(two.q_node),
                               np.asarray(one.q_node), atol=1e-4)
    np.testing.assert_allclose(np.asarray(two.q_link),
                               np.asarray(one.q_link), atol=1e-4)


def test_advance_exact_drain_rate():
    net = N.make_network(2, [(0, 1, 4.0)], [2.0, 0.0])
    state = net.state.with_queues(jnp.asarray([6.0, 0.0]),
                                  net.q_link.at[0, 1].set(8.0))
    st2 = advance(net.topology, state, 1.0)
    np.testing.assert_allclose(np.asarray(st2.q_node), [4.0, 0.0])
    assert np.asarray(st2.q_link)[0, 1] == 4.0  # drained at mu_link
    st3 = advance(net.topology, state, 100.0)   # fully drained, clipped at 0
    assert float(np.asarray(st3.q_node).max()) == 0.0
    assert float(np.asarray(st3.q_link).max()) == 0.0


def test_backlog_seconds_worst_resource():
    net = N.make_network(2, [(0, 1, 4.0)], [2.0, 0.0])
    state = net.state.with_queues(jnp.asarray([6.0, 0.0]),
                                  net.q_link.at[0, 1].set(8.0))
    # node wait 6/2 = 3s > link wait 8/4 = 2s
    np.testing.assert_allclose(backlog_seconds(net.topology, state), 3.0)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_bound_dominates_simulation_on_drained_state(seed):
    """bound >= simulated completion still holds after advance(dt)."""
    rng = np.random.default_rng(seed)
    net, jobs = random_instance(rng, num_jobs=3, with_queues=True)
    net = net.advance(float(rng.uniform(0, 3)))
    batch = J.batch_jobs(jobs)
    plan = solve(net, batch, method="greedy")
    if plan.makespan_bound >= 1e29:
        return
    sim = schedule.simulate(net, batch, plan.assign, plan.order)
    assert sim.makespan <= plan.makespan_bound * (1 + 1e-5)


# -- view composition --------------------------------------------------------

def test_network_is_composed_view():
    net, _ = N.small_topology()
    assert isinstance(net.topology, Topology)
    assert isinstance(net.state, QueueState)
    assert net.topology.view(net.state).mu_node is net.mu_node
    # with_queues preserves topology (identity) and clock
    q = jnp.ones_like(net.q_node)
    net2 = net.with_queues(q, net.q_link)
    assert net2.topology is net.topology
    assert float(net2.clock) == float(net.clock)


def test_solve_accepts_topology_and_state():
    rng = np.random.default_rng(3)
    net, jobs = random_instance(rng, num_jobs=2, with_queues=True)
    batch = J.batch_jobs(jobs)
    a = solve(net, batch, method="greedy")
    b = solve(net.topology, batch, method="greedy", state=net.state)
    np.testing.assert_array_equal(a.assign, b.assign)
    np.testing.assert_array_equal(a.bounds, b.bounds)
    with pytest.raises(ValueError):
        solve(net, batch, state=net.state)  # state only with Topology


def test_plan_net_roundtrips_clock():
    from repro.core.plan import Plan
    rng = np.random.default_rng(4)
    net, jobs = random_instance(rng, num_jobs=2)
    net = net.advance(1.5)
    plan = solve(net, batch := J.batch_jobs(jobs), method="greedy")
    rt = Plan.from_dict(plan.to_dict())
    np.testing.assert_allclose(float(rt.net.clock), float(plan.net.clock))
    np.testing.assert_array_equal(np.asarray(rt.net.q_node),
                                  np.asarray(plan.net.q_node))


# -- static-path bit-identity (acceptance criterion) -------------------------

# Deliberately duplicated from benchmarks/common.py: the test pins the seed
# solver's golden values independently, so a bad re-capture of the bench-side
# reference cannot silently re-baseline this regression gate too.
QUICKSTART_BOUNDS = [
    0.9737289547920227, 2.1123697757720947, 0.7822328209877014,
    0.17777971923351288, 0.17777971923351288, 0.334226131439209,
    0.25363287329673767, 0.5179324150085449,
]
QUICKSTART_ORDER = [3, 4, 6, 5, 7, 2, 0, 1]


def _quickstart_instance():
    from repro.configs import registry
    net, _ = N.small_topology(capacity_scale=1e-3)
    rng = np.random.default_rng(0)
    jobs = []
    for i, kind in enumerate(["vgg19"] * 2 + ["resnet34"] * 6):
        src, dst = rng.choice(5, size=2, replace=False)
        jobs.append(registry.get(kind).make_job(f"{kind}-{i}",
                                                int(src), int(dst)))
    return net, J.batch_jobs(jobs)


@pytest.mark.parametrize("method", ["greedy", "lazy"])
def test_static_solve_bit_identical_after_split(method):
    """The Topology/QueueState split must not move the static path by a ULP:
    bounds recorded from the pre-split solver reproduce exactly."""
    net, batch = _quickstart_instance()
    plan = solve(net, batch, method=method)
    assert plan.bounds.tolist() == QUICKSTART_BOUNDS
    assert plan.order.tolist() == QUICKSTART_ORDER


# -- constructor validation (satellite) --------------------------------------

def test_make_network_rejects_bad_inputs():
    with pytest.raises(ValueError, match="node_caps"):
        N.make_network(2, [(0, 1, 1.0)], [1.0, -2.0])
    with pytest.raises(ValueError, match="node_caps"):
        N.make_network(2, [(0, 1, 1.0)], [1.0, float("nan")])
    with pytest.raises(ValueError, match="node_caps must have shape"):
        N.make_network(2, [(0, 1, 1.0)], [1.0, 1.0, 1.0])
    with pytest.raises(ValueError, match=r"edges\[0\]"):
        N.make_network(2, [(0, 1, -5.0)], [1.0, 1.0])
    with pytest.raises(ValueError, match=r"edges\[1\]"):
        N.make_network(2, [(0, 1, 1.0), (0, 2, 1.0)], [1.0, 1.0])
    with pytest.raises(ValueError, match="self-loop"):
        N.make_network(2, [(1, 1, 1.0)], [1.0, 1.0])
    with pytest.raises(ValueError, match="num_nodes"):
        N.make_network(0, [], [])


def test_jobs_reject_bad_inputs():
    good_comp = np.ones(3, np.float32)
    good_data = np.ones(4, np.float32)
    with pytest.raises(ValueError, match="comp"):
        J.InferenceJob("j", 0, 1, -good_comp, good_data)
    with pytest.raises(ValueError, match="comp"):
        J.InferenceJob("j", 0, 1, good_comp * np.nan, good_data)
    with pytest.raises(ValueError, match="data"):
        J.InferenceJob("j", 0, 1, good_comp, np.ones(3, np.float32))
    with pytest.raises(ValueError, match="data"):
        J.InferenceJob("j", 0, 1, good_comp, -good_data)
    with pytest.raises(ValueError, match="src/dst"):
        J.InferenceJob("j", -1, 1, good_comp, good_data)


def test_batch_jobs_pad_to():
    jobs = [J.InferenceJob("a", 0, 1, np.ones(2, np.float32),
                           np.ones(3, np.float32))]
    b = J.batch_jobs(jobs, pad_to=5)
    assert b.max_layers == 5
    assert int(b.num_layers[0]) == 2
    with pytest.raises(ValueError, match="pad_to"):
        J.batch_jobs(jobs, pad_to=1)
