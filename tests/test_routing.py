"""Single-job routing DP: exactness vs the bitmask ILP oracle + invariants."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import exact, jobs as J, routing
from util import random_instance


def _route(net, job):
    return routing.route_single(net, jnp.asarray(job.comp),
                                jnp.asarray(job.data), job.src, job.dst,
                                job.num_layers)


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.booleans())
def test_dp_matches_ilp_oracle(seed, with_queues):
    """Theorem 1, constructively: the DP value equals the exact ILP optimum
    (once-per-node z_u waiting semantics) on randomized instances."""
    rng = np.random.default_rng(seed)
    net, jobs = random_instance(rng, num_jobs=1, with_queues=with_queues)
    job = jobs[0]
    r = _route(net, job)
    c_exact, _ = exact.exact_route_bitmask(net, job.comp, job.data,
                                           job.src, job.dst)
    got = float(r.cost)
    if c_exact >= 1e29:
        assert got >= 1e29
    else:
        np.testing.assert_allclose(got, c_exact, rtol=2e-5)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_assignment_reproduces_cost(seed):
    """cost_given_assignment(DP's own assignment) == the DP optimum."""
    rng = np.random.default_rng(seed)
    net, jobs = random_instance(rng, num_jobs=1, with_queues=True)
    job = jobs[0]
    r = _route(net, job)
    if float(r.cost) >= 1e29:
        return
    val = routing.cost_given_assignment(
        net, jnp.asarray(job.comp), jnp.asarray(job.data), job.src, job.dst,
        job.num_layers, r.assign)
    np.testing.assert_allclose(float(val), float(r.cost), rtol=2e-5)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_assignment_on_compute_nodes(seed):
    rng = np.random.default_rng(seed)
    net, jobs = random_instance(rng, num_jobs=1)
    job = jobs[0]
    r = _route(net, job)
    if float(r.cost) >= 1e29:
        return
    mu = np.asarray(net.mu_node)
    for l in range(job.num_layers):
        assert mu[int(r.assign[l])] > 0, "layer assigned to compute-less node"


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_queueing_only_increases_cost(seed):
    """Monotonicity: adding queue backlog can only increase the bound."""
    rng = np.random.default_rng(seed)
    net, jobs = random_instance(rng, num_jobs=1)
    job = jobs[0]
    c0 = float(_route(net, job).cost)
    qn = jnp.asarray(rng.uniform(0, 2, net.num_nodes), jnp.float32)
    ql = jnp.asarray(rng.uniform(0, 2, (net.num_nodes,) * 2), jnp.float32)
    ql = ql * (net.mu_link > 0)
    c1 = float(_route(net.with_queues(qn * (net.mu_node > 0), ql), job).cost)
    assert c1 >= c0 - 1e-4 * abs(c0)


def test_commit_accounting():
    """commit adds exactly c_l to each assigned node and d_l along paths."""
    rng = np.random.default_rng(3)
    net, jobs = random_instance(rng, num_jobs=1)
    job = jobs[0]
    r = _route(net, job)
    if float(r.cost) >= 1e29:
        pytest.skip("disconnected draw")
    net2 = routing.commit_assignment(
        net, jnp.asarray(job.comp), jnp.asarray(job.data), job.src, job.dst,
        job.num_layers, r.assign)
    added_comp = float(jnp.sum(net2.q_node - net.q_node))
    np.testing.assert_allclose(added_comp, float(job.comp.sum()), rtol=1e-5)
    # every link increment is a positive multiple of some d_l on a real link
    dq = np.asarray(net2.q_link - net.q_link)
    assert (dq >= -1e-6).all()
    assert (dq[np.asarray(net.mu_link) == 0] == 0).all()


def test_paths_connect_assignments():
    rng = np.random.default_rng(11)
    net, jobs = random_instance(rng, num_jobs=1)
    job = jobs[0]
    r = _route(net, job)
    if float(r.cost) >= 1e29:
        pytest.skip("disconnected draw")
    paths = routing.extract_paths(
        net, jnp.asarray(job.comp), jnp.asarray(job.data), job.src, job.dst,
        job.num_layers, r.assign)
    nodes = [job.src] + [int(r.assign[l]) for l in range(job.num_layers)] \
        + [job.dst]
    mu = np.asarray(net.mu_link)
    for l, hops in enumerate(paths):
        cur = nodes[l]
        for (u, v) in hops:
            assert u == cur and mu[u, v] > 0
            cur = v
        assert cur == nodes[l + 1]
