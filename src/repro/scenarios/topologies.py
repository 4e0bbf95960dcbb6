"""Parameterized topology generators: the scenario catalog's network side.

Every generator returns ``(net, names, ingress, egress)`` where ``net`` is
a fresh :class:`~repro.core.network.ComputeNetwork` (empty queues), and
ingress/egress are the node sets traffic enters/leaves through.  All
generators are deterministic in ``seed`` (``fat_tree`` has no random part
and ignores it).

Families:
  * ``paper_small``      — the paper's 5-node Fig. 2 topology.
  * ``us_backbone``      — the paper's 24-node USNET backbone (Fig. 4).
  * ``edge_cloud``       — k edge sites -> aggregation tier -> cloud; edge
                           nodes have thin compute and thin uplinks, the
                           cloud is fat on both (split-computing setting).
  * ``random_geometric`` — nodes in the unit square, links within a radius
                           (capacity falls with distance), chained into one
                           component; heterogeneous compute.
  * ``star``             — cellular: one hub with fat compute, leaves with
                           thin local compute and mixed-rate uplinks.
  * ``fat_tree``         — the k-ary fat-tree datacenter fabric of Al-Fares
                           et al. (SIGCOMM 2008, §3); k=8 gives V=208.
"""
from __future__ import annotations

import numpy as np

from repro.core import network as N

G = 1e9
MB = 1e6


def paper_small(seed: int = 0, *, capacity_scale: float = 1e-3):
    net, names = N.small_topology(capacity_scale=capacity_scale)
    return net, names, [0], [4]


def us_backbone(seed: int = 0, *, capacity_scale: float = 1e-3):
    net, names = N.us_backbone(capacity_scale=capacity_scale, seed=seed)
    # coastal ingress, interior egress (fixed, documented choice)
    return net, names, [0, 5, 10, 20], [4, 9, 15, 23]


def edge_cloud(seed: int = 0, *, n_edge: int = 6, n_agg: int = 2,
               capacity_scale: float = 1e-3):
    """Edge sites -> aggregation -> cloud hierarchy.

    Node order: [edge_0..edge_{k-1}, agg_0..agg_{m-1}, cloud].  Edge nodes
    carry thin compute (they *can* run early layers locally), aggregation
    nodes are pure forwarders, the cloud node is fat.
    """
    rng = np.random.default_rng(seed)
    v = n_edge + n_agg + 1
    cloud = v - 1
    caps = [float(rng.uniform(5, 15)) * G for _ in range(n_edge)] \
        + [0.0] * n_agg + [300 * G]
    edges = []
    for e in range(n_edge):
        agg = n_edge + (e % n_agg)
        edges.append((e, agg, float(rng.choice([125, 375])) * MB))
    for a in range(n_agg):
        edges.append((n_edge + a, cloud, 1000 * MB))
    if n_agg > 1:  # ring over the aggregation tier for cross-site paths
        for a in range(n_agg):
            edges.append((n_edge + a, n_edge + (a + 1) % n_agg, 375 * MB))
    edges = [(u, w, c * capacity_scale) for u, w, c in edges]
    names = [f"edge{i}" for i in range(n_edge)] \
        + [f"agg{i}" for i in range(n_agg)] + ["cloud"]
    net = N.make_network(v, edges, caps)
    return net, names, list(range(n_edge)), list(range(n_edge))


def random_geometric(seed: int = 0, *, num_nodes: int = 12,
                     radius: float = 0.45, capacity_scale: float = 1e-3):
    """Random geometric mesh: connect nodes within ``radius``; capacity
    decays with distance.  Components are chained by nearest cross-links so
    the graph is always connected."""
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0.0, 1.0, size=(num_nodes, 2))
    caps_cycle = [30, 50, 200, 100, 70]
    caps = [caps_cycle[int(rng.integers(0, 5))] * G for _ in range(num_nodes)]
    edges = []
    dist = np.linalg.norm(pos[:, None] - pos[None, :], axis=-1)
    for u in range(num_nodes):
        for w in range(u + 1, num_nodes):
            if dist[u, w] <= radius:
                cap = (375 if dist[u, w] < radius / 2 else 125) * MB
                edges.append((u, w, cap))
    # Union-find to chain components with their closest cross pair.
    parent = list(range(num_nodes))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for u, w, _ in edges:
        parent[find(u)] = find(w)
    while len({find(i) for i in range(num_nodes)}) > 1:
        roots = {}
        for i in range(num_nodes):
            roots.setdefault(find(i), []).append(i)
        comps = list(roots.values())
        best = None
        for a in comps[0]:
            for comp in comps[1:]:
                for b in comp:
                    if best is None or dist[a, b] < dist[best[0], best[1]]:
                        best = (a, b)
        edges.append((best[0], best[1], 125 * MB))
        parent[find(best[0])] = find(best[1])
    edges = [(u, w, c * capacity_scale) for u, w, c in edges]
    names = [f"g{i}" for i in range(num_nodes)]
    net = N.make_network(num_nodes, edges, caps)
    ingress = sorted(int(i) for i in rng.choice(num_nodes, 3, replace=False))
    egress = sorted(int(i) for i in rng.choice(num_nodes, 3, replace=False))
    return net, names, ingress, egress


def star(seed: int = 0, *, num_leaves: int = 8, capacity_scale: float = 1e-3):
    """Cellular star: hub node 0 (fat compute), leaves with thin compute."""
    rng = np.random.default_rng(seed)
    v = num_leaves + 1
    caps = [200 * G] + [float(rng.uniform(10, 40)) * G
                        for _ in range(num_leaves)]
    edges = [(0, 1 + i, float(rng.choice([125, 375])) * MB * capacity_scale)
             for i in range(num_leaves)]
    names = ["hub"] + [f"leaf{i}" for i in range(num_leaves)]
    net = N.make_network(v, edges, caps)
    leaves = list(range(1, v))
    return net, names, leaves, leaves


def fat_tree(seed: int = 0, *, k: int = 8, capacity_scale: float = 1e-3):
    """The k-ary fat-tree of Al-Fares, Loukissas and Vahdat (SIGCOMM 2008,
    §3): k pods of k/2 aggregation and k/2 edge switches, (k/2)^2 core
    switches and k^3/4 hosts, every link 125 MB/s (1 GbE commodity links,
    times ``capacity_scale``), bidirectional.

    Each edge switch links to k/2 hosts and to every aggregation switch of
    its pod; aggregation switch ``a`` of every pod links to core switches
    ``a*k/2 .. a*k/2 + k/2 - 1`` (one port per pod on each core switch).

    Node order: the (k/2)^2 core switches; then pod by pod its k/2
    aggregation switches followed by its k/2 edge switches; then the
    hosts in edge-switch order (host ``h`` hangs off edge switch
    ``h // (k/2)`` counted across pods).  Switches carry no compute; host
    ``h`` gets 30, 50, 200, 100, 70 GFLOP/s cycled by ``h`` (arXiv:2111.07006's
    values, as ``us_backbone`` cycles them).  Ingress is the first host
    of each pod and egress the last, so k*k ordered pairs, k of them
    within a pod.  The fabric has no random part: ``seed`` is ignored.
    """
    if k < 2 or k % 2:
        raise ValueError(f"fat_tree needs an even k >= 2, got {k}")
    half = k // 2
    n_core, n_pod_sw, n_host = half * half, k * k, k * half * half
    v = n_core + n_pod_sw + n_host
    cap = 125 * MB * capacity_scale

    def agg(p, a):
        return n_core + p * k + a

    def edge(p, e):
        return n_core + p * k + half + e

    def host(h):
        return n_core + n_pod_sw + h

    edges = []
    for p in range(k):
        for a in range(half):
            edges += [(agg(p, a), a * half + c, cap) for c in range(half)]
            edges += [(agg(p, a), edge(p, e), cap) for e in range(half)]
        for e in range(half):
            first = (p * half + e) * half
            edges += [(edge(p, e), host(first + i), cap) for i in range(half)]
    caps_cycle = [30, 50, 200, 100, 70]
    caps = [0.0] * (n_core + n_pod_sw) \
        + [caps_cycle[h % 5] * G for h in range(n_host)]
    names = [f"core{c}" for c in range(n_core)]
    for p in range(k):
        names += [f"agg{p}.{a}" for a in range(half)]
        names += [f"edge{p}.{e}" for e in range(half)]
    names += [f"host{h}" for h in range(n_host)]
    per_pod = half * half
    ingress = [host(p * per_pod) for p in range(k)]
    egress = [host(p * per_pod + per_pod - 1) for p in range(k)]
    return N.make_network(v, edges, caps), names, ingress, egress


FAMILIES = {
    "paper-small": paper_small,
    "us-backbone": us_backbone,
    "edge-cloud": edge_cloud,
    "random-geometric": random_geometric,
    "star": star,
    "fat-tree": fat_tree,
}
