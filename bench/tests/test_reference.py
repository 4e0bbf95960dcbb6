"""The reference's cost model against a dense oracle, and at scale.

The oracle is the layer dynamic program over dense Floyd-Warshall
closures ``T [D, V, V]``: another algorithm for the same numbers, kept
here only, since it costs D·V³ a round.  Both run in float64 under seeded
nonzero queues, so they may differ by the order of their sums alone."""
import numpy as np
import pytest

import benchutil  # noqa: F401  (puts the repo and src/ on the path)
from bench import reference as R, traffic as T
from repro.scenarios import topologies

RTOL = 1e-12


def oracle_closures(net: R.Net, d_vals, q_link):
    d = np.asarray(d_vals, np.float64)
    with np.errstate(invalid="ignore"):
        w = (d[:, None, None] + q_link[None]) * net.inv_link[None]
    t = np.where(np.isnan(w), np.inf, w)
    for k in range(net.V):
        t = np.minimum(t, t[:, :, k:k + 1] + t[:, k:k + 1, :])
    return t


def oracle_costs(net: R.Net, q_node, q_link, jobs):
    vals = np.unique(np.concatenate([j.data for j in jobs]))
    t = oracle_closures(net, vals, q_link)
    nw = net.node_wait(q_node)
    out = []
    for job in jobs:
        di = np.searchsorted(vals, job.data)
        g = t[di[0], job.src] + nw
        for l in range(job.num_layers):
            moved = np.min(g[:, None] + t[di[l]], axis=0) + nw
            g = np.minimum(g, moved) + job.comp[l] * net.inv_node
        out.append(float(np.min(g + t[di[-1]][:, job.dst])))
    return np.array(out)


def paper_jobs(ingress, egress, n: int, rng) -> list:
    """``n`` paper-mix jobs, no two alike: each on its own endpoints."""
    dep = T.load_deployment(
        T.load_workload("usb-paper.b32-refdrain")["config_file"])
    kinds = rng.choice(len(dep.profiles), size=n, p=dep.weights)
    pairs = [(s, d) for s in ingress for d in egress if d != s]
    picks = rng.choice(len(pairs), size=n, replace=False)
    jobs = []
    for j, (kind, p) in enumerate(zip(kinds, picks)):
        comp, data = dep.profiles[kind]
        jobs.append(R.Job(f"j{j}", *pairs[p], np.asarray(comp, np.float64),
                          np.asarray(data, np.float64)))
    return jobs


def leave_the_source(jobs) -> None:
    """Make each job's input 1e4 times heavier and its activations 1e4
    times lighter, in place: its best route then computes layer 1 at the
    source and moves on where the source is slow, so the back-pointers of
    a move are exercised (the paper's jobs never move)."""
    for job in jobs:
        job.data[0] *= 1e4
        job.data[1:] *= 1e-4


def seeded_queues(mu_node, mu_link, jobs, rng):
    """Queues of a few jobs' work on every node and link."""
    comp = np.mean([j.comp.mean() for j in jobs])
    data = np.mean([j.data.mean() for j in jobs])
    q_node = rng.uniform(0.0, 3.0, mu_node.shape) * comp
    q_link = np.where(mu_link > 0, rng.uniform(0.0, 3.0, mu_link.shape),
                      0.0) * data
    return q_node, q_link


def fabric(name: str):
    if name == "usnet":
        dep = T.load_deployment(
            T.load_workload("usb-paper.b32-refdrain")["config_file"])
        return dep.mu_node, dep.mu_link, dep.ingress, dep.egress
    net, _, ingress, egress = topologies.fat_tree(k=int(name[1:]))
    return (np.asarray(net.mu_node, np.float64),
            np.asarray(net.mu_link, np.float64), ingress, egress)


def routed_bounds(net, q_node, q_link, jobs, routes):
    """Each job's route priced hop by hop, on the hops of
    :func:`reference.route_paths`."""
    return np.array([
        R.route_cost(net, q_node, q_link, job, assign,
                     R.route_paths(net, q_link, job, assign))
        for job, assign in zip(jobs, routes)])


@pytest.mark.parametrize("name,n_jobs", [("usnet", 16), ("k4", 12),
                                         ("k8", 16)])
def test_relaxation_matches_dense_closures(name, n_jobs):
    mu_node, mu_link, ingress, egress = fabric(name)
    rng = np.random.default_rng(20081)
    jobs = paper_jobs(ingress, egress, n_jobs, rng)
    leave_the_source(jobs[::2])
    q_node, q_link = seeded_queues(mu_node, mu_link, jobs, rng)
    net = R.Net(mu_node, mu_link)
    costs, routes = R.optimal_costs(net, q_node, q_link, jobs,
                                    want_routes=True)
    assert any(len(set(assign)) > 1 for assign in routes)
    costs = np.array(costs)
    ref = oracle_costs(net, q_node, q_link, jobs)
    np.testing.assert_allclose(costs, ref, rtol=RTOL, atol=0)
    assert costs.tolist() == R.optimal_costs(net, q_node, q_link, jobs)
    lo = np.sort(ref)
    if lo[1] - lo[0] > 1e-9 * lo[0]:
        assert np.argmin(costs) == np.argmin(ref)
    np.testing.assert_allclose(
        routed_bounds(net, q_node, q_link, jobs, routes), costs,
        rtol=RTOL, atol=0)


def test_one_round_at_k16():
    """A k=16 fat-tree (V=1344, 6,144 directed links), 32 paper-mix jobs:
    one round, where dense closures would take minutes a data size."""
    mu_node, mu_link, ingress, egress = fabric("k16")
    rng = np.random.default_rng(1344)
    jobs = paper_jobs(ingress, egress, 32, rng)
    q_node, q_link = seeded_queues(mu_node, mu_link, jobs, rng)
    net = R.Net(mu_node, mu_link)
    assert net.V == 1344 and net.tail.shape[0] == 6144
    costs, routes = R.optimal_costs(net, q_node, q_link, jobs,
                                    want_routes=True)
    costs = np.array(costs)
    assert np.all(np.isfinite(costs)) and np.all(costs > 0)
    np.testing.assert_allclose(
        routed_bounds(net, q_node, q_link, jobs, routes), costs,
        rtol=RTOL, atol=0)
