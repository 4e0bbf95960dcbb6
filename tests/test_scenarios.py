"""Scenario catalog: make_scenario(name, seed) is the single entry point —
coverage of all topology families, seed determinism, traffic mixes, the
fat-tree against an independent construction, and ``mean_service_s``."""
import collections

import numpy as np
import pytest

from repro.core import jobs as J, routing, solve
from repro.scenarios import (FAMILIES, MIXES, available_scenarios,
                             make_scenario, make_traffic)


def test_catalog_covers_required_families():
    names = available_scenarios()
    assert {"paper-small", "us-backbone", "edge-cloud", "random-geometric",
            "star"} <= set(names)
    assert len(names) >= 4


@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_every_family_builds_and_routes(name):
    sc = make_scenario(name, seed=0)
    assert sc.num_nodes >= 2
    assert sc.ingress and sc.egress
    assert all(0 <= i < sc.num_nodes for i in sc.ingress + sc.egress)
    # compute reachable: at least one node has capacity
    assert float(np.asarray(sc.topology.mu_node).max()) > 0
    rng = np.random.default_rng(0)
    jobs = sc.sample_jobs(rng, 2)
    assert all(j.num_layers <= sc.max_layers for j in jobs)
    plan = solve(sc.topology, J.batch_jobs(jobs, pad_to=sc.max_layers),
                 method="greedy", state=sc.topology.empty_state())
    assert plan.makespan_bound < 1e29  # routable: src reaches dst
    assert sc.mean_service_s > 0 and np.isfinite(sc.mean_service_s)
    assert sc.nominal_rate(0.5) > 0


def test_scenarios_deterministic_in_seed():
    for name in ("random-geometric", "edge-cloud", "star"):
        a = make_scenario(name, seed=7)
        b = make_scenario(name, seed=7)
        np.testing.assert_array_equal(np.asarray(a.topology.mu_link),
                                      np.asarray(b.topology.mu_link))
        ja = a.sample_jobs(np.random.default_rng(1), 3)
        jb = b.sample_jobs(np.random.default_rng(1), 3)
        for x, y in zip(ja, jb):
            assert (x.src, x.dst) == (y.src, y.dst)
            np.testing.assert_array_equal(x.comp, y.comp)
    # seeded generators actually vary with the seed
    g7 = make_scenario("random-geometric", seed=7)
    g8 = make_scenario("random-geometric", seed=8)
    assert not np.array_equal(np.asarray(g7.topology.mu_link),
                              np.asarray(g8.topology.mu_link))


def test_traffic_selection_by_name_and_kwarg():
    assert make_scenario("us-backbone:lm").traffic.name == "lm"
    assert make_scenario("us-backbone", traffic="lm").traffic.name == "lm"
    assert make_scenario("us-backbone").traffic.name == "paper"
    with pytest.raises(ValueError, match="either in the name"):
        make_scenario("us-backbone:lm", traffic="paper")
    with pytest.raises(ValueError, match="unknown scenario family"):
        make_scenario("not-a-family")
    with pytest.raises(ValueError, match="unknown traffic mix"):
        make_traffic("not-a-mix")


def test_traffic_mixes_cost_profiles():
    assert set(MIXES) >= {"paper", "lm", "synthetic", "conv"}
    rng = np.random.default_rng(0)
    for mix_name in MIXES:
        mix = make_traffic(mix_name)
        job = mix.sample(rng, "j", 0, 1)
        assert job.num_layers <= mix.max_layers
        assert mix.mean_flops() > 0


def test_src_dst_distinct_when_possible():
    sc = make_scenario("star", seed=0)
    rng = np.random.default_rng(0)
    for _ in range(20):
        s, d = sc.sample_src_dst(rng)
        assert s != d


# -- the k-ary fat-tree ------------------------------------------------------

def _fat_tree_by_address(k: int, scale: float = 1e-3):
    """The k-ary fat-tree written from Al-Fares et al. (SIGCOMM 2008, §3)
    by its addresses: pod switches ``10.pod.switch.1`` (switch 0..k/2-1
    edge, k/2..k-1 aggregation), hosts ``10.pod.switch.id`` (id 2..k/2+1),
    core switches ``10.k.j.i`` (j, i in 1..k/2).  Every edge switch links
    to its k/2 hosts and to every aggregation switch of its pod; core
    switch ``(j, i)`` links to aggregation switch ``k/2 + j - 1`` of
    every pod.  Nodes are numbered in the documented order: core by
    (j, i); pod by pod its aggregation then its edge switches; hosts by
    (pod, edge switch, id).  Returns ``(mu_node, mu_link)``, float32."""
    half = k // 2
    core = [(k, j, i) for j in range(1, half + 1) for i in range(1, half + 1)]
    pods = []
    for pod in range(k):
        pods += [(pod, sw, 1) for sw in range(half, k)]     # aggregation
        pods += [(pod, sw, 1) for sw in range(half)]        # edge
    hosts = [(pod, sw, hid) for pod in range(k) for sw in range(half)
             for hid in range(2, half + 2)]
    index = {a: n for n, a in enumerate(core + pods + hosts)}
    links = set()
    for pod in range(k):
        for sw in range(half):
            for hid in range(2, half + 2):
                links.add((index[(pod, sw, 1)], index[(pod, sw, hid)]))
            for agg in range(half, k):
                links.add((index[(pod, sw, 1)], index[(pod, agg, 1)]))
        for j in range(1, half + 1):
            for i in range(1, half + 1):
                links.add((index[(pod, half + j - 1, 1)], index[(k, j, i)]))
    v = len(index)
    mu_link = np.zeros((v, v), np.float32)
    for a, b in links:
        mu_link[a, b] = mu_link[b, a] = 125e6 * scale
    gflops = [30, 50, 200, 100, 70]
    mu_node = np.zeros(v, np.float32)
    for h, addr in enumerate(hosts):
        mu_node[index[addr]] = gflops[h % 5] * 1e9
    return mu_node, mu_link


@pytest.mark.parametrize("k", [4, 8])
def test_fat_tree_equals_the_construction_by_address(k):
    from repro.scenarios.topologies import fat_tree
    net, names, ingress, egress = fat_tree(k=k)
    mu_node, mu_link = _fat_tree_by_address(k)
    got_node, got_link = np.asarray(net.mu_node), np.asarray(net.mu_link)
    assert got_node.dtype == mu_node.dtype and got_link.dtype == np.float32
    assert got_node.tobytes() == mu_node.tobytes()
    assert got_link.tobytes() == mu_link.tobytes()
    per_pod = (k // 2) ** 2
    first = (k // 2) ** 2 + k * k
    assert ingress == [first + p * per_pod for p in range(k)]
    assert egress == [first + p * per_pod + per_pod - 1 for p in range(k)]
    assert len(names) == len(set(names)) == mu_node.shape[0]


@pytest.mark.parametrize("k", [4, 8])
def test_fat_tree_structure(k):
    from repro.scenarios.topologies import fat_tree
    net, names, _, _ = fat_tree(k=k)
    adj = np.asarray(net.mu_link) > 0
    half = k // 2
    kinds = collections.Counter(n.rstrip("0123456789.") for n in names)
    assert kinds == {"core": half * half, "agg": k * half, "edge": k * half,
                     "host": k ** 3 // 4}
    assert adj.sum() // 2 == 3 * k ** 3 // 4
    deg = adj.sum(axis=1)
    hosts = [i for i, n in enumerate(names) if n.startswith("host")]
    switches = [i for i, n in enumerate(names) if not n.startswith("host")]
    assert set(deg[switches].tolist()) == {k}
    assert set(deg[hosts].tolist()) == {1}
    assert (np.asarray(net.mu_node)[switches] == 0).all()
    assert (np.asarray(net.mu_node)[hosts] > 0).all()
    # host-to-host BFS diameter: up to the core and down again
    longest = 0
    for h in hosts:
        dist = {h: 0}
        frontier = [h]
        while frontier:
            nxt = []
            for u in frontier:
                for w in np.flatnonzero(adj[u]).tolist():
                    if w not in dist:
                        dist[w] = dist[u] + 1
                        nxt.append(w)
            frontier = nxt
        assert len(dist) == len(names)
        longest = max(longest, max(dist[g] for g in hosts))
    assert longest == 6


def test_fat_tree_ignores_seed_and_defaults_to_k8():
    a, b = make_scenario("fat-tree", seed=0), make_scenario("fat-tree",
                                                            seed=9)
    assert a.num_nodes == 208 and a.traffic.name == "paper"
    assert np.asarray(a.topology.mu_link).tobytes() == \
        np.asarray(b.topology.mu_link).tobytes()
    with pytest.raises(ValueError, match="even k"):
        make_scenario("fat-tree", k=5)


@pytest.mark.parametrize("name, pinned", [
    ("us-backbone:paper", 0.7392962044104934),
    ("us-backbone:lm", 6.548514291644096),
])
def test_mean_service_unchanged_by_the_deduped_build(name, pinned):
    """The deduped closure build gives the per-job build's value bit for
    bit, so the benchmark's pinned ``mean_service_s`` stays valid."""
    sc = make_scenario(name, seed=0)
    rng = np.random.default_rng(sc.seed + 0x5EED)
    batch = J.batch_jobs(sc.sample_jobs(rng, 32))
    per_job = float(np.asarray(routing.route_batch(
        sc.topology.view(), batch).cost, np.float64).mean())
    assert sc.mean_service_s == per_job == pinned
