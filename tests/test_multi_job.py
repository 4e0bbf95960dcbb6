"""Greedy (Alg 1), SA (Alg 2), the actual-system simulator, and their
relationships: evaluator consistency, bound >= simulation, SA quality."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import (annealing, greedy, jobs as J, network as N,
                        schedule)
from util import random_instance


def _fig1():
    G = 1e9
    net = N.make_network(
        6, [(0, 1, 1e15), (1, 2, 1e15), (3, 4, 1e15), (4, 5, 1e15),
            (0, 4, 1e15), (3, 1, 1e15)],
        [0, 25 * G, 0, 0, 50 * G, 0])
    j1 = J.InferenceJob("j1", 0, 2, np.array([25 * G], np.float32),
                        np.array([1., 1.], np.float32))
    j2 = J.InferenceJob("j2", 3, 5, np.array([50 * G], np.float32),
                        np.array([1., 1.], np.float32))
    return net, J.batch_jobs([j1, j2])


def test_fig1_greedy_and_sa():
    """Fig. 1: SA finds the completion-time-aware split (makespan 1.0s)."""
    net, batch = _fig1()
    sol = greedy.greedy_route(net, batch)
    sim = sol.simulate(net, batch)
    assert sim.makespan <= sol.bound() + 1e-6
    sa = annealing.anneal(net, batch, seed=0, d=0.98, num_chains=4)
    assert sa.bound() <= 1.0 + 1e-3    # the (u, v)-disjoint optimum
    sim2 = sa.simulate(net, batch)
    np.testing.assert_allclose(sim2.makespan, 1.0, rtol=1e-3)


def test_greedy_bounds_nondecreasing():
    """Queues only grow during greedy => later jobs have >= bounds."""
    rng = np.random.default_rng(0)
    net, jobs = random_instance(rng, num_jobs=5)
    batch = J.batch_jobs(jobs)
    sol = greedy.greedy_route(net, batch)
    ordered = sol.bounds[sol.order]
    assert (np.diff(ordered) >= -1e-5 * np.abs(ordered[:-1])).all()


def test_evaluator_matches_greedy_bound():
    rng = np.random.default_rng(1)
    net, jobs = random_instance(rng, num_jobs=4)
    batch = J.batch_jobs(jobs)
    sol = greedy.greedy_route(net, batch)
    val = float(annealing.evaluate_solution(
        net, batch, jnp.asarray(sol.assign), jnp.asarray(sol.order)))
    np.testing.assert_allclose(val, sol.makespan_bound, rtol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_bound_dominates_simulation(seed):
    """The fictitious-system objective upper-bounds the simulated actual
    completion time (the paper's §III-B claim), on random instances."""
    rng = np.random.default_rng(seed)
    net, jobs = random_instance(rng, num_jobs=3)
    batch = J.batch_jobs(jobs)
    sol = greedy.greedy_route(net, batch)
    if sol.makespan_bound >= 1e29:
        return
    sim = schedule.simulate(net, batch, sol.assign, sol.order)
    assert sim.makespan <= sol.makespan_bound * (1 + 1e-5)


def test_sa_warm_start_never_worse_than_greedy():
    rng = np.random.default_rng(5)
    net, jobs = random_instance(rng, num_jobs=4)
    batch = J.batch_jobs(jobs)
    sol = greedy.greedy_route(net, batch)
    sa = annealing.anneal(net, batch, seed=2, d=0.97, num_chains=2,
                          init="greedy", block_move_prob=0.3)
    assert sa.bound() <= sol.bound() * (1 + 1e-5)


def test_replay_matches_greedy():
    rng = np.random.default_rng(7)
    net, jobs = random_instance(rng, num_jobs=4)
    batch = J.batch_jobs(jobs)
    sol = greedy.greedy_route(net, batch)
    bounds, paths, final = schedule.replay_solution(
        net, batch, sol.assign, sol.order)
    np.testing.assert_allclose(bounds, sol.bounds, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(final.q_node),
                               np.asarray(sol.net.q_node), rtol=1e-4)


def test_simulator_single_job_analytic():
    """One job, one compute node, serial path: completion = sum of terms."""
    net = N.make_network(3, [(0, 1, 10.0), (1, 2, 5.0)], [0, 2.0, 0])
    job = J.InferenceJob("j", 0, 2, np.array([4.0], np.float32),
                         np.array([10.0, 5.0], np.float32))
    batch = J.batch_jobs([job])
    sol = greedy.greedy_route(net, batch)
    # input 10B over link(0,1)@10 = 1s; compute 4/2 = 2s; out 5B over (1,2)@5 = 1s
    np.testing.assert_allclose(sol.makespan_bound, 4.0, rtol=1e-5)
    sim = schedule.simulate(net, batch, sol.assign, sol.order)
    np.testing.assert_allclose(sim.makespan, 4.0, rtol=1e-5)


def test_preemption_priority_order():
    """Two identical jobs on one node: priority-1 job finishes first."""
    net = N.make_network(2, [(0, 1, 1e9)], [0, 1.0])
    jobs = [J.InferenceJob(f"j{i}", 0, 1, np.array([1.0], np.float32),
                           np.array([0.0, 0.0], np.float32))
            for i in range(2)]
    batch = J.batch_jobs(jobs)
    sol = greedy.greedy_route(net, batch)
    sim = schedule.simulate(net, batch, sol.assign, sol.order)
    first = sol.order[0]
    assert sim.completion[first] <= sim.completion[sol.order[1]]
    np.testing.assert_allclose(sorted(sim.completion), [1.0, 2.0], rtol=1e-6)


def test_lazy_greedy_matches_eager():
    """Lazy greedy (monotone-cost caching) = Algorithm 1 up to ties."""
    from repro.core import greedy as G
    for seed in range(4):
        rng = np.random.default_rng(seed + 100)
        net, jobs = random_instance(rng, num_jobs=6)
        batch = J.batch_jobs(jobs)
        eager = G.greedy_route(net, batch)
        lazy = G.greedy_route(net, batch, lazy=True)
        np.testing.assert_allclose(lazy.makespan_bound, eager.makespan_bound,
                                   rtol=1e-5)
        assert lazy.meta["n_routings"] <= 6 * 6
