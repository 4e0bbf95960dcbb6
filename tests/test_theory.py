"""Theorem 1 (total unimodularity) and Theorem 2 (approximation ratio)."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (bounds, exact, greedy, jobs as J, layered_graph,
                        network as N, schedule)
from util import random_instance


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_constraint_matrix_is_tu(seed):
    """Random square submatrices of [A1; A2] have det in {-1, 0, 1}."""
    rng = np.random.default_rng(seed)
    net, jobs = random_instance(rng, num_jobs=1)
    job = jobs[0]
    ilp = layered_graph.build_ilp(net, job.num_layers, job.src, job.dst,
                                  job.comp, job.data)
    mat = np.vstack([ilp.a1, ilp.a2])
    dets = layered_graph.random_square_submatrix_dets(
        mat, trials=150, max_k=8, seed=seed)
    np.testing.assert_allclose(dets, np.round(dets), atol=1e-7)
    assert np.all(np.abs(np.round(dets)) <= 1)


def test_b2_is_unit_flow():
    rng = np.random.default_rng(0)
    net, jobs = random_instance(rng, num_jobs=1)
    job = jobs[0]
    ilp = layered_graph.build_ilp(net, job.num_layers, job.src, job.dst,
                                  job.comp, job.data)
    assert ilp.b2.sum() == 0
    assert sorted(np.unique(ilp.b2)) in ([-1.0, 0.0, 1.0], [-1.0, 1.0])


def test_theorem2_alpha_bound_tiny():
    """Greedy completion <= alpha * T* on a brute-forced tiny instance."""
    G = 1.0
    net = N.make_network(3, [(0, 1, 10.0), (1, 2, 10.0), (0, 2, 10.0)],
                         [2 * G, 1 * G, 0])
    jobs = [
        J.InferenceJob("a", 0, 2, np.array([2.0], np.float32),
                       np.array([1.0, 1.0], np.float32)),
        J.InferenceJob("b", 2, 0, np.array([3.0], np.float32),
                       np.array([1.0, 0.5], np.float32)),
    ]
    batch = J.batch_jobs(jobs)
    sol = greedy.greedy_route(net, batch)
    sim = sol.simulate(net, batch)
    tstar = exact.brute_force_makespan(net, batch)
    a = bounds.alpha(net, jobs)
    assert sim.makespan <= a * tstar * (1 + 1e-6), (sim.makespan, a, tstar)
    assert sol.makespan_bound <= a * tstar * (1 + 1e-6)


def test_corollary1_zero_delay_identical_caps():
    """Zero network delay + identical caps: greedy <= (2 - 1/|V|) T*."""
    big = 1e12
    net = N.make_network(4, [(0, 1, big), (1, 2, big), (2, 3, big),
                             (3, 0, big)], [1.0, 1.0, 1.0, 1.0])
    rng = np.random.default_rng(2)
    jobs = [J.InferenceJob(f"j{i}", int(rng.integers(4)),
                           int(rng.integers(4)),
                           np.array([rng.uniform(0.5, 2)], np.float32),
                           np.array([1e-9, 1e-9], np.float32))
            for i in range(3)]
    batch = J.batch_jobs(jobs)
    sol = greedy.greedy_route(net, batch)
    sim = sol.simulate(net, batch)
    tstar = exact.brute_force_makespan(net, batch)
    factor = bounds.corollary1_factor(net)
    assert sim.makespan <= factor * tstar * (1 + 1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_lemma8_lower_bounds(seed):
    """Lemma 8: S_j^SS and the component-average lower-bound T*."""
    rng = np.random.default_rng(seed)
    net, jobs = random_instance(rng, num_jobs=2)
    batch = J.batch_jobs(jobs)
    s_ss, avg_lb = bounds.service_lower_bounds(net, batch)
    if np.any(s_ss >= 1e29):
        return
    sol = greedy.greedy_route(net, batch)
    sim = schedule.simulate(net, batch, sol.assign, sol.order)
    # any achievable completion upper-bounds T*, which dominates the LBs
    assert sim.makespan >= max(s_ss.max(), avg_lb) * (1 - 1e-5)
