"""Plain reference of the served path's semantics, in numpy.

It imports nothing of the program.  Three parts, each the straightforward
form of what the program computes:

* **Algorithm 1's cost model** (arXiv:2111.07006 §III).  For a data size
  ``d`` the weight of link (u, v) is ``(d + Q_uv) / mu_uv``.  A job's
  bound on a route is the transfer of ``data[0]`` from its source to the
  first layer's node, then per layer the node's wait ``Q_u / mu_u``
  (charged on entering a node, not for staying), its compute
  ``comp[l] / mu_u`` and the transfer of that layer's output to the next
  node, and last the transfer of ``data[L]`` to the destination.
  :func:`optimal_costs` is the layer dynamic program over that objective.
  Each of its transfer steps, ``g'[v] = min_u g[u] + dist(u, v)``, is a
  shortest path from every node at once with ``g`` as the start
  potentials, so :func:`relax` takes it by Bellman-Ford sweeps over the
  directed link list until nothing changes: no all-pairs closure is
  formed, and the work scales with the links, not with V³.
  :func:`route_cost` prices one given route hop by hop; :func:`commit`
  adds a routed job's work to the queues; :func:`greedy` is Algorithm 1
  itself (each round commits the remaining job of least bound), with each
  transfer's hops from :func:`route_paths`.
* **Preempt-resume strict-priority service** (:class:`Timelines`).  Jobs
  are served in priority order: a job never waits for one of lower
  priority, so each job's stages are laid into the free time that the
  jobs before it left on each resource.  That gives every completion
  without an event loop.
* :func:`residual_queues` reads the backlog a resource still holds at a
  given instant from the same timelines.

``Prec`` fixes the arithmetic: float64 for the reference, float32 or
bfloat16 (every operation rounded) for the controls that must fail.
"""
from __future__ import annotations

import bisect
import dataclasses

import ml_dtypes
import numpy as np


@dataclasses.dataclass(frozen=True)
class Prec:
    """Arithmetic of one reference run: ``dtype`` holds the values and
    ``r`` rounds the result of every operation."""

    name: str

    @property
    def dtype(self):
        return np.float64 if self.name == "float64" else np.float32

    def r(self, x):
        if self.name == "bfloat16":
            return np.asarray(x, np.float32).astype(
                ml_dtypes.bfloat16).astype(np.float32)
        return np.asarray(x, self.dtype)


F64 = Prec("float64")

# Two instants closer than this (relative to the clock) are one instant.
TIE = 1e-12


@dataclasses.dataclass(frozen=True)
class Job:
    """One request's work: ``comp [L]`` FLOPs, ``data [L+1]`` bytes."""

    name: str
    src: int
    dst: int
    comp: np.ndarray
    data: np.ndarray

    @property
    def num_layers(self) -> int:
        return int(self.comp.shape[0])


@dataclasses.dataclass(frozen=True)
class Links:
    """Directed links grouped by the node each enters, for :func:`relax`.

    Link ``i`` leaves node ``src[i]``; ``order[i]`` is its index in
    :class:`Net`'s link order, in which weights are kept.  The links that
    enter node ``v`` are ``ptr[v]:ptr[v + 1]``, ordered by ``src``;
    ``starts`` and ``nodes`` list the groups that are not empty."""

    src: np.ndarray
    order: np.ndarray
    ptr: np.ndarray
    starts: np.ndarray
    nodes: np.ndarray

    @classmethod
    def grouped(cls, src, dst, order, V: int) -> "Links":
        """From links already sorted by ``(dst, src)``."""
        ptr = np.searchsorted(dst, np.arange(V + 1))
        nodes = np.flatnonzero(np.diff(ptr))
        return cls(src, order, ptr, ptr[nodes], nodes)


class Net:
    """Capacities in one precision, with their reciprocals, and the
    directed links (``mu_link > 0``, no self-loop) in two groupings:
    ``fwd`` by the node a link enters, and ``rev``, the links reversed,
    by the node it leaves."""

    def __init__(self, mu_node, mu_link, prec: Prec = F64):
        self.prec = prec
        self.V = V = int(np.asarray(mu_node).shape[0])
        mu_node = np.asarray(mu_node, np.float64)
        mu_link = np.asarray(mu_link, np.float64)
        with np.errstate(divide="ignore"):
            inv_l = np.where(mu_link > 0, 1.0 / np.where(mu_link > 0,
                                                         mu_link, 1.0),
                             np.inf)
            inv_n = np.where(mu_node > 0, 1.0 / np.where(mu_node > 0,
                                                         mu_node, 1.0),
                             np.inf)
        np.fill_diagonal(inv_l, 0.0)
        self.mu_node = prec.r(mu_node)
        self.mu_link = prec.r(mu_link)
        self.inv_link = prec.r(inv_l)
        self.inv_node = prec.r(inv_n)
        tail, head = np.nonzero((mu_link > 0) & ~np.eye(V, dtype=bool))
        by_head = np.lexsort((tail, head))
        self.tail, self.head = tail[by_head], head[by_head]
        self.inv_e = self.inv_link[self.tail, self.head]
        self.fwd = Links.grouped(self.tail, self.head,
                                 np.arange(self.tail.shape[0]), V)
        by_tail = np.lexsort((self.head, self.tail))
        self.rev = Links.grouped(self.head[by_tail], self.tail[by_tail],
                                 by_tail, V)

    def node_wait(self, q_node):
        r = self.prec.r
        return np.where(self.mu_node > 0,
                        r(q_node / np.where(self.mu_node > 0,
                                            self.mu_node, 1)), 0.0)

    def link_weights(self, d_vals, q_link):
        """[D, E] link weights ``(d + Q_uv) * inv_uv`` for the data sizes
        ``d_vals`` under link queues ``q_link``, in link order."""
        r = self.prec.r
        d = r(np.asarray(d_vals, np.float64))
        q = q_link[self.tail, self.head]
        return r(r(d[:, None] + q[None]) * self.inv_e[None])

    def points(self, at) -> np.ndarray:
        """[n, V] potentials: 0 at node ``at[j]`` of row ``j``, else inf."""
        g = np.full((len(at), self.V), np.inf, self.prec.dtype)
        g[np.arange(len(at)), np.asarray(at, np.int64)] = 0.0
        return g

    def toward(self, w, at) -> np.ndarray:
        """[n, V] distance from each node to node ``at[j]`` under row
        ``j`` of the weights ``w [n, E]``: a relaxation over the reversed
        links."""
        return relax(self.points(at), w[:, self.rev.order], self.rev,
                     self.prec.r)[0]


def relax(g, w, links: Links, r, *, origin: bool = False):
    """``h[j, v] = min_u g[j, u] + dist_j(u, v)``, with ``dist_j`` the
    shortest path under row ``j`` of the link weights ``w [J, E]`` (one
    per link of ``links``, in that order) and ``dist_j(v, v) = 0``.

    Vectorised Bellman-Ford: each sweep relaxes every link from the
    previous sweep's ``h`` (one ``minimum.reduceat`` over the links that
    enter each node), until a sweep changes nothing.  Every add is rounded
    by ``r``; an add never lowers a value, so no cycle improves a path and
    the sweeps end.  With ``origin`` also ``u``, the node each ``h[j, v]``
    starts from (``v`` itself where nothing improved on ``g``)."""
    h = np.array(g)
    J, V = h.shape
    org = (np.broadcast_to(np.arange(V), (J, V)).copy() if origin
           else None)
    size = np.diff(links.ptr)[links.nodes]
    at = np.arange(links.src.shape[0])
    rows = np.arange(J)[:, None]
    while True:
        cand = r(h[:, links.src] + w)                           # [J, E]
        best = np.minimum.reduceat(cand, links.starts, axis=1)  # [J, H]
        better = best < h[:, links.nodes]
        if not better.any():
            return h, org
        if origin:
            hit = cand == np.repeat(best, size, axis=1)
            first = np.minimum.reduceat(np.where(hit, at, at.shape[0]),
                                        links.starts, axis=1)
            org[:, links.nodes] = np.where(
                better, org[rows, links.src[first]], org[:, links.nodes])
        h[:, links.nodes] = np.where(better, best, h[:, links.nodes])


def _data_index(jobs: list[Job]):
    """Unique data sizes of a job list, and per job the index of each of
    its ``L+1`` sizes."""
    vals = np.unique(np.concatenate([j.data.astype(np.float64)
                                     for j in jobs]))
    idx = [np.searchsorted(vals, j.data.astype(np.float64)) for j in jobs]
    return vals, idx


def optimal_costs(net: Net, q_node, q_link, jobs: list[Job],
                  *, want_routes: bool = False):
    """Least bound of each job under the given queues (the layer dynamic
    program, all jobs at once, each transfer one :func:`relax`); with
    ``want_routes`` also each job's node per layer."""
    r = net.prec.r
    vals, idx = _data_index(jobs)
    w = net.link_weights(vals, q_link)                      # [D, E]
    nw = net.node_wait(q_node)
    J = len(jobs)
    nl = np.array([j.num_layers for j in jobs])
    lmax = int(nl.max())
    di = np.zeros((J, lmax + 1), np.int64)
    comp = np.zeros((J, lmax), np.float64)
    for k, (job, ix) in enumerate(zip(jobs, idx)):
        di[k, :ix.shape[0]] = ix
        comp[k, :job.num_layers] = job.comp
    comp = r(comp)
    rows = np.arange(J)
    src = [j.src for j in jobs]
    dst = [j.dst for j in jobs]
    g, _ = relax(net.points(src), w[di[:, 0]], net.fwd, r)
    g = r(g + nw[None])                                     # [J, V]
    bps = []
    for l in range(1, lmax + 1):
        h, move_bp = relax(g, w[di[:, l - 1]], net.fwd, r,
                           origin=want_routes)
        moved = r(h + nw[None])
        stay = g <= moved
        new = r(np.minimum(g, moved)
                + r(comp[:, l - 1, None] * net.inv_node[None]))
        active = (l <= nl)[:, None]
        g = np.where(active, new, g)
        if want_routes:
            bps.append(np.where(active & ~stay, move_bp, -1))
    total = r(g + net.toward(w[di[rows, nl]], dst))         # [J, V]
    best = np.argmin(total, axis=1)
    out = [float(x) for x in total[rows, best]]
    if not want_routes:
        return out
    routes = []
    for k in range(J):
        cur, assign = int(best[k]), [0] * int(nl[k])
        for l in range(int(nl[k]), 0, -1):
            assign[l - 1] = cur
            if bps[l - 1][k, cur] >= 0:
                cur = int(bps[l - 1][k, cur])
        routes.append(assign)
    return out, routes


def shortest_path(net: Net, w, to_b, a: int,
                  b: int) -> list[tuple[int, int]]:
    """Hops from ``a`` to ``b`` under link weights ``w [E]``, with
    ``to_b [V]`` each node's distance to ``b``: each next hop minimises
    link weight plus remaining distance (ties to the lower node)."""
    rv = net.rev
    hops, cur = [], a
    for _ in range(net.V):
        if cur == b:
            break
        out = slice(rv.ptr[cur], rv.ptr[cur + 1])           # cur's links
        cand = w[rv.order[out]] + to_b[rv.src[out]]
        nxt = int(rv.src[out][np.argmin(cand)])
        hops.append((cur, nxt))
        cur = nxt
    return hops


def route_paths(net: Net, q_link, job: Job, assign) -> list:
    """The hops of each of ``job``'s ``L+1`` transfers on the route
    ``assign``: one relaxation toward every transfer's end, then
    :func:`shortest_path` along it."""
    vals, idx = _data_index([job])
    w = net.link_weights(vals, q_link)[idx[0]]              # [L+1, E]
    nodes = [job.src] + [int(a) for a in assign] + [job.dst]
    to_b = net.toward(w, nodes[1:])
    return [shortest_path(net, w[l], to_b[l], nodes[l], nodes[l + 1])
            for l in range(len(nodes) - 1)]


def route_cost(net: Net, q_node, q_link, job: Job, assign, paths) -> float:
    """Bound of one job on a given route (its node per layer and the hops
    of each transfer); ``inf`` where the route does not connect."""
    L = job.num_layers
    nodes = [job.src] + [int(a) for a in assign[:L]] + [job.dst]
    if len(paths) != L + 1:
        return np.inf
    nw = net.node_wait(q_node)
    total = 0.0
    for l in range(L + 1):
        hops = [tuple(int(x) for x in h) for h in paths[l]]
        a, b = nodes[l], nodes[l + 1]
        if a == b:
            if hops:
                return np.inf
        elif (not hops or hops[0][0] != a or hops[-1][1] != b
              or any(hops[i][1] != hops[i + 1][0]
                     for i in range(len(hops) - 1))):
            return np.inf
        for u, v in hops:
            total += (float(job.data[l]) + float(q_link[u, v])) * float(
                net.inv_link[u, v])
        if l < L:
            u = nodes[l + 1]
            if l == 0 or u != nodes[l]:
                total += float(nw[u])
            total += float(job.comp[l]) * float(net.inv_node[u])
    return total


def commit(q_node, q_link, job: Job, assign, paths) -> None:
    """Add one routed job's work to the queues, in place."""
    for l in range(job.num_layers):
        q_node[int(assign[l])] += job.comp[l]
    for l, hops in enumerate(paths[:job.num_layers + 1]):
        for u, v in hops:
            q_link[int(u), int(v)] += job.data[l]


def greedy(net: Net, q_node, q_link, jobs: list[Job]):
    """Algorithm 1 in ``net``'s precision: returns rounds of
    ``(job index, bound, assign, paths)`` in commit order."""
    r = net.prec.r
    q_node = r(np.array(q_node, np.float64))
    q_link = r(np.array(q_link, np.float64))
    left = list(range(len(jobs)))
    rounds = []
    while left:
        costs, routes = optimal_costs(net, q_node, q_link,
                                      [jobs[i] for i in left],
                                      want_routes=True)
        k = int(np.argmin(costs))
        i, job, assign = left[k], jobs[left[k]], routes[k]
        paths = route_paths(net, q_link, job, assign)
        rounds.append((i, costs[k], assign, paths))
        for l in range(job.num_layers):
            q_node[assign[l]] = r(q_node[assign[l]] + job.comp[l])
        for l, hops in enumerate(paths):
            for u, v in hops:
                q_link[u, v] = r(q_link[u, v] + job.data[l])
        left.pop(k)
    return rounds


def stages(job: Job, assign, paths):
    """(resource, work) in precedence order: layer ``l``'s output hops,
    then layer ``l+1``'s compute.  A resource is ``("node", u)`` or
    ``("link", u, v)``."""
    out = []
    for l in range(job.num_layers + 1):
        for u, v in paths[l]:
            out.append((("link", int(u), int(v)), float(job.data[l])))
        if l < job.num_layers:
            out.append((("node", int(assign[l])), float(job.comp[l])))
    return out


class Timelines:
    """Busy intervals per resource, filled job by job in priority order.

    ``prec`` is float64 for the reference; the float32 control rounds
    every time and rate to float32."""

    def __init__(self, mu_node, mu_link, prec: Prec = F64):
        self.prec = prec
        self.mu_node = np.asarray(mu_node, np.float64)
        self.mu_link = np.asarray(mu_link, np.float64)
        self._iv: dict = {}

    def rate(self, key) -> float:
        return float(self.mu_node[key[1]] if key[0] == "node"
                     else self.mu_link[key[1], key[2]])

    def serve(self, key, arrival: float, work: float) -> float:
        """Lay ``work`` on ``key`` into the free time from ``arrival``;
        return its finish.  Instants within ``TIE`` (relative) of each
        other are simultaneous, and a stage that finishes as a job before
        it starts on the resource does not wait for it."""
        f = float if self.prec.name == "float64" else np.float32
        starts, ends = self._iv.setdefault(key, ([], []))
        rate = f(self.rate(key))
        left = f(work)
        a = f(arrival)
        n = len(starts)
        i = bisect.bisect_right(ends, a)
        if i < n and starts[i] <= a:
            lo, s0, cur, j = i, starts[i], ends[i], i + 1
        elif i > 0 and ends[i - 1] >= a:
            lo, s0, cur, j = i - 1, starts[i - 1], a, i
        else:
            lo, s0, cur, j = i, a, a, i
        while True:
            nxt = starts[j] if j < n else np.inf
            fin = f(cur + left / rate)
            if fin <= nxt + TIE * max(1.0, abs(nxt)):
                if j < n and fin >= nxt:
                    starts[lo:j + 1] = [s0]
                    ends[lo:j + 1] = [ends[j]]
                else:
                    starts[lo:j] = [s0]
                    ends[lo:j] = [fin]
                return fin
            left = f(left - f((nxt - cur) * rate))
            cur = ends[j]
            j += 1

    def run(self, job_stages, release: float) -> float:
        """Serve one job (lower in priority than every job already laid)
        from ``release``; return its completion."""
        t = release
        for key, work in job_stages:
            t = self.serve(key, t, work)
        return float(t)

    def busy_before(self, key, t: float) -> float:
        iv = self._iv.get(key)
        if iv is None:
            return 0.0
        starts, ends = iv
        n = bisect.bisect_left(starts, t)
        busy = sum(e - s for s, e in zip(starts[:n], ends[:n]))
        if n and ends[n - 1] > t:
            busy -= ends[n - 1] - t
        return float(busy)


def residual_queues(tl: Timelines, committed: dict, t: float, V: int):
    """Backlog at ``t`` per resource: work committed so far minus what its
    resource served before ``t``.  ``committed`` maps resource keys to the
    total work committed on them."""
    q_node = np.zeros((V,), np.float64)
    q_link = np.zeros((V, V), np.float64)
    for key, work in committed.items():
        left = max(work - tl.rate(key) * tl.busy_before(key, t), 0.0)
        if key[0] == "node":
            q_node[key[1]] = left
        else:
            q_link[key[1], key[2]] = left
    return q_node, q_link
