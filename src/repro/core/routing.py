"""Single-job optimal routing on the layered graph (constructive Theorem 1).

The paper proves the single-job ILP (1)-(5) is totally unimodular, hence its
LP relaxation is integral and the optimum is a single s_0 -> t_L path in the
layered graph.  We compute that optimum directly with a layer dynamic
program over min-plus transfer closures:

    g_0[u]  = T_0[src, u] + nw[u]
    g_l[u]  = min( g_{l-1}[u],                       # continue the run at u
                   min_v g_{l-1}[v] + T_{l-1}[v, u]  # move, charge node wait
                       + nw[u] )
              + c_l * cinv[u]
    answer  = min_u g_L[u] + T_L[u, dst]

where T_l is the min-cost transfer closure for layer-l output (see
``shortest_path.transfer_closure``), ``nw[u] = Q_u / mu_u`` the node waiting
bound and ``cinv[u] = 1/mu_u``.  Moving into a node charges its waiting
term; continuing a consecutive run does not — this mirrors the ILP's z_u
(charged once per node).  The two objectives can differ only if the optimum
*returns* to a node for a non-adjacent layer (then the DP charges the wait
twice); ``exact.py`` provides a bitmask-exact oracle and the property tests
quantify the gap (zero on all randomized instances tried).  Spuriously
dominated candidates inside the min (e.g. a "move" from v == u) are never
uniquely optimal by the triangle inequality of the closure, so the DP value
is the optimum of its objective.

Everything is shape-static (Lmax padding, masks) => jit- and vmap-able; the
multi-job greedy vmaps :func:`route_single` over the job batch.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from .network import INF, ComputeNetwork, node_invrate, node_wait
from .jobs import JobBatch
from .shortest_path import (Closures, closures_for, layer_edge_weights,
                            transfer_closure, reconstruct_path)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Route:
    cost: jax.Array        # scalar: upper bound on this job's completion time
    assign: jax.Array      # [Lmax] int32: compute node of each (real) layer


def _dp_fwd(t: jax.Array, comp: jax.Array, src: jax.Array, dst: jax.Array,
            num_layers: jax.Array, cinv: jax.Array, nw: jax.Array):
    """Forward half of the layer DP: cost + the backpointer tables.

    t: [Lmax+1, V, V]; comp: [Lmax]; cinv/nw: [V].  Returns
    ``(cost, total [V], bps [Lmax, V])`` — everything vectorized; the
    sequential per-job backpointer walk lives in :func:`_dp_back` so
    callers that only need the winning job's assignment (the fused greedy
    round: J forward DPs, ONE committed job) can skip J-1 walks.
    """
    lmax = comp.shape[0]
    g0 = t[0, src, :] + nw
    layer_ids = jnp.arange(1, lmax + 1)

    def step(g, xs):
        l, c_l, t_prev = xs
        active = l <= num_layers
        move = jnp.min(g[:, None] + t_prev, axis=0)          # [V]
        move_bp = jnp.argmin(g[:, None] + t_prev, axis=0)    # [V]
        moved = move + nw
        stay_wins = g <= moved
        # Golden-locked DP recurrence with no fused form (the mul adds to a
        # min, not a sum); the forward scan is never unrolled, and fused &
        # ref solvers trace this same function, so its rounding is common
        # to both sides of the parity gate.
        # repro-lint: disable=RL001 -- no fused form; rounding is shared
        new_g = jnp.minimum(g, moved) + c_l * cinv
        new_g = jnp.minimum(new_g, INF)
        bp = jnp.where(stay_wins, -1, move_bp).astype(jnp.int32)
        g_out = jnp.where(active, new_g, g)
        bp_out = jnp.where(active, bp, jnp.full_like(bp, -1))
        return g_out, bp_out

    g_final, bps = jax.lax.scan(step, g0, (layer_ids, comp, t[:-1]))
    t_last = jnp.take(t, num_layers, axis=0)                  # [V, V]
    total = g_final + t_last[:, dst]
    return jnp.minimum(jnp.min(total), INF), total, bps


def _dp_back(total: jax.Array, bps: jax.Array) -> jax.Array:
    """Walk backpointers Lmax..1 to recover the compute node of each layer.

    Integer gathers only — bit-identity with the full DP's assignment is
    structural, not a float-rounding question (which also makes the
    ``unroll`` safe: there is no float mul-add for LLVM to re-contract,
    so the unrolled loop is the same gather chain with less XLA:CPU
    loop machinery).  Lint rule RL002 (unsafe-unroll) admits exactly this
    kind of body — the *forward* DP must never unroll (RL001's pragma in
    ``_dp_fwd`` documents why)."""
    u_star = jnp.argmin(total).astype(jnp.int32)

    def back(cur, bp_l):
        prev = jnp.where(bp_l[cur] < 0, cur, bp_l[cur])
        return prev, cur

    _, assign_rev = jax.lax.scan(back, u_star, bps, reverse=True, unroll=8)
    return assign_rev


def _dp(t: jax.Array, comp: jax.Array, src: jax.Array, dst: jax.Array,
        num_layers: jax.Array, cinv: jax.Array, nw: jax.Array) -> Route:
    """Run the layer DP given the per-layer transfer closures ``t``.

    t: [Lmax+1, V, V]; comp: [Lmax]; cinv/nw: [V].
    """
    cost, total, bps = _dp_fwd(t, comp, src, dst, num_layers, cinv, nw)
    return Route(cost=cost, assign=_dp_back(total, bps))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def route_single(net: ComputeNetwork, comp: jax.Array, data: jax.Array,
                 src: jax.Array, dst: jax.Array, num_layers: jax.Array,
                 *, closures: Closures | None = None,
                 use_pallas: bool | None = None) -> Route:
    """Optimally route one job (paper formulation (1)-(5)) given queues in ``net``.

    ``closures`` (if given) must have been built against this same
    (net, data) — pass it to share one closure stack across routing, commit,
    and path extraction instead of rebuilding it here.
    """
    if closures is None:
        closures = closures_for(net, data, use_pallas=use_pallas)
    return _dp(closures.t, comp, src, dst, num_layers, node_invrate(net),
               node_wait(net))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def route_batch(net: ComputeNetwork, batch: JobBatch,
                *, closures: Closures | None = None,
                use_pallas: bool | None = None) -> Route:
    """vmap of :func:`route_single` over a padded job batch (shared queues).

    ``closures``: optional [J, ...]-stacked artifact from
    ``shortest_path.build_closures_batch`` (vmapped through per job).
    """
    fn = lambda c, d, s, t_, n, cl: route_single(
        net, c, d, s, t_, n, closures=cl, use_pallas=use_pallas)
    return jax.vmap(fn)(batch.comp, batch.data, batch.src, batch.dst,
                        batch.num_layers, closures)


def route_batch_fwd(net: ComputeNetwork, batch: JobBatch,
                    *, closures: Closures):
    """Forward-only :func:`route_batch`: costs + backpointer tables.

    Returns ``(cost [J], total [J, V], bps [J, Lmax, V])``.  The per-job
    backpointer *walk* (a sequential chain of scalar gathers — the only
    non-vectorizable piece of the DP) is deferred to
    :func:`assign_from_backpointers`, so a caller that commits a single
    job per round recovers exactly one assignment instead of J.
    """
    cinv, nw = node_invrate(net), node_wait(net)
    return jax.vmap(
        lambda c, s, t_, n, cl: _dp_fwd(cl.t, c, s, t_, n, cinv, nw)
    )(batch.comp, batch.src, batch.dst, batch.num_layers, closures)


def assign_from_backpointers(total: jax.Array, bps: jax.Array) -> jax.Array:
    """One job's [Lmax] assignment from its :func:`route_batch_fwd` row —
    bit-identical to the corresponding ``route_batch(...).assign`` row."""
    return _dp_back(total, bps)


@jax.jit
def cost_given_assignment(net: ComputeNetwork, comp: jax.Array, data: jax.Array,
                          src: jax.Array, dst: jax.Array, num_layers: jax.Array,
                          assign: jax.Array,
                          *, closures: Closures | None = None) -> jax.Array:
    """Objective (1) for a *fixed* compute-node assignment (paths free).

    Transfers between consecutive compute nodes take min-cost paths under the
    current queues; node waits are charged once per consecutive run.  Used by
    the simulated-annealing evaluator.
    """
    t = transfer_closure(net, data) if closures is None else closures.t
    cinv = node_invrate(net)
    nw = node_wait(net)
    lmax = comp.shape[0]

    a1 = assign[0]
    # repro-lint: disable=RL001 -- mirrors _dp_fwd's rounding term-for-term
    cost0 = t[0, src, a1] + nw[a1] + comp[0] * cinv[a1]

    def step(carry, xs):
        total, prev = carry
        l, c_l = xs                      # l in 2..Lmax, layer l at assign[l-1]
        cur = assign[l - 1]
        active = l <= num_layers
        # repro-lint: disable=RL001 -- mirrors _dp_fwd's rounding (as cost0)
        seg = t[l - 1, prev, cur] + jnp.where(cur == prev, 0.0, nw[cur]) \
            + c_l * cinv[cur]
        total = jnp.where(active, total + seg, total)
        prev = jnp.where(active, cur, prev)
        return (total, prev), None

    (total, last), _ = jax.lax.scan(
        step, (cost0, a1), (jnp.arange(2, lmax + 1), comp[1:]))
    t_last = jnp.take(t, num_layers, axis=0)
    return jnp.minimum(total + t_last[last, dst], INF)


def _commit_impl(net: ComputeNetwork, comp: jax.Array, data: jax.Array,
                 src: jax.Array, dst: jax.Array, num_layers: jax.Array,
                 assign: jax.Array, closures: Closures | None,
                 ) -> tuple[ComputeNetwork, jax.Array]:
    """Shared commit body; also returns the per-layer hop lists it charged.

    The hops come out of the *same* ``reconstruct_path`` calls inside the
    same per-layer scan that charges q_link, so emitting them changes no
    arithmetic — :func:`commit_assignment` discards them,
    :func:`commit_with_hops` hands them to callers that want
    ``plan.paths`` without a second extraction pass.
    """
    v = net.num_nodes
    if closures is None:
        closures = closures_for(net, data)
    t = closures.t                              # [Lmax+1, V, V]
    w = (layer_edge_weights(net, data) if closures.w is None
         else closures.w)                       # cheap when absent
    lmax = comp.shape[0]

    q_node = net.q_node
    for_l = jnp.arange(lmax + 1)
    # endpoints of the layer-l transfer: node_l -> node_{l+1} with node_0 =
    # src and node_{num_layers+1} = dst; layers beyond num_layers are masked.
    src32 = jnp.asarray(src, jnp.int32).reshape(1)
    dst32 = jnp.asarray(dst, jnp.int32)
    starts = jnp.concatenate([src32, assign]).astype(jnp.int32)   # node_l
    ends = jnp.concatenate([assign, dst32.reshape(1)]).astype(jnp.int32)
    ends = jnp.where(for_l == num_layers, dst32, ends)

    q_node = q_node + jnp.zeros_like(q_node).at[assign].add(
        jnp.where(jnp.arange(lmax) < num_layers, comp, 0.0))

    # Reconstruct all L+1 layer paths in one vmapped walk (the per-layer
    # walks are independent given (w, t)); the q_link charges then replay
    # layer-by-layer in the same scan order as before, so the accumulated
    # floats are bitwise identical to the per-layer sequential version.
    hops = jax.vmap(
        lambda wl, tl, a, bb: reconstruct_path(wl, tl, a, bb, max_hops=v)
    )(w, t, starts, ends)                       # [Lmax+1, V, 2]

    def add_layer(ql, xs):
        l, hops_l = xs
        active = l <= num_layers
        d_l = data[l]
        us, vs = hops_l[:, 0], hops_l[:, 1]
        valid = (us >= 0) & active & (us != vs)
        add = jnp.where(valid, d_l, 0.0)
        ql = ql.at[jnp.maximum(us, 0), jnp.maximum(vs, 0)].add(add)
        return ql, None

    # unroll=4: tiny per-layer bodies, same sequential charge order (and
    # therefore bitwise-identical accumulation) with less loop overhead.
    q_link, _ = jax.lax.scan(add_layer, net.q_link, (for_l, hops), unroll=4)
    return net.with_queues(q_node, q_link), hops


@jax.jit
def commit_assignment(net: ComputeNetwork, comp: jax.Array, data: jax.Array,
                      src: jax.Array, dst: jax.Array, num_layers: jax.Array,
                      assign: jax.Array,
                      *, closures: Closures | None = None) -> ComputeNetwork:
    """Algorithm 1 line 3: add the routed job's load to the queues.

    q_node[a_l] += c_l for each real layer l; q_link[u, v] += d_l for every
    hop of the min-cost path carrying layer-l output (l = 0..L, with node_0 =
    src and node_{L+1} = dst).  Pass ``closures`` to reuse the caller's
    (w, t) stack instead of recomputing both here.
    """
    net2, _ = _commit_impl(net, comp, data, src, dst, num_layers, assign,
                           closures)
    return net2


def commit_with_hops(net: ComputeNetwork, comp: jax.Array, data: jax.Array,
                     src: jax.Array, dst: jax.Array, num_layers: jax.Array,
                     assign: jax.Array,
                     *, closures: Closures | None = None,
                     ) -> tuple[ComputeNetwork, jax.Array]:
    """:func:`commit_assignment` that also returns its hop lists.

    ``hops`` is [Lmax+1, V, 2] int32 — for each layer the explicit (u, v)
    transfer hops the commit charged, padded with (-1, -1); exactly the
    rows :func:`reconstruct_path` walks, so formatting them with
    :func:`hops_to_paths` reproduces :func:`extract_paths` without a
    second reconstruction.  The fused solver's round scan commits through
    here and emits the hops as ``plan.paths``.  Not jitted: the scan
    traces it inside its own program (jitting at this level would just add
    a dispatch for eager callers, who should prefer ``commit_assignment``).
    """
    return _commit_impl(net, comp, data, src, dst, num_layers, assign,
                        closures)


def hops_to_paths(hops, num_layers: int) -> list:
    """Format a concrete [Lmax+1, V, 2] hop tensor as ``plan.paths`` lists.

    Matches :func:`extract_paths` output exactly: one list of (u, v) int
    tuples per real layer 0..num_layers, truncated at the first (-1, -1)
    padding row.  One vectorized hop count, then ``tolist`` on the sliced
    *real* hops only — real paths are a few hops while the buffer holds V
    rows of mostly (-1, -1) padding, and the fused solver formats every
    layer of every round through here, so converting the padding to
    Python ints would be a measurable slice of its ``greedy.paths`` span.
    """
    import numpy as np
    live = np.asarray(hops)[:int(num_layers) + 1]
    n_real = (live[:, :, 0] >= 0).sum(1).tolist()
    return [list(map(tuple, live[l, :n].tolist()))
            for l, n in enumerate(n_real)]


@functools.partial(jax.jit, static_argnames=("max_hops",))
def _paths_device(w: jax.Array, t: jax.Array, starts: jax.Array,
                  ends: jax.Array, *, max_hops: int) -> jax.Array:
    """vmap of :func:`reconstruct_path` over the layer axis -> [L+1, max_hops, 2]."""
    fn = functools.partial(reconstruct_path, max_hops=max_hops)
    return jax.vmap(fn)(w, t, starts, ends)


def extract_paths(net: ComputeNetwork, comp, data, src, dst, num_layers,
                  assign, *, closures: Closures | None = None):
    """Host-side helper: explicit per-layer hop lists for the event simulator.

    One vmapped ``reconstruct_path`` over all L+1 layers and a single
    ``device_get`` (the seed's per-hop host loop is kept as
    :func:`extract_paths_ref` for parity testing).
    """
    import numpy as np
    v = net.num_nodes
    if closures is None:
        closures = closures_for(net, data)
    w = (layer_edge_weights(net, data) if closures.w is None
         else closures.w)
    L = int(num_layers)
    assign_h = np.asarray(jax.device_get(assign))
    nodes = np.array([int(src)] + [int(assign_h[l]) for l in range(L)]
                     + [int(dst)], np.int32)
    hops = jax.device_get(_paths_device(
        w[: L + 1], closures.t[: L + 1],
        jnp.asarray(nodes[:-1]), jnp.asarray(nodes[1:]), max_hops=v))
    paths = []
    for l in range(L + 1):
        layer = hops[l]
        n_real = int((layer[:, 0] >= 0).sum())
        paths.append([(int(u), int(vv)) for u, vv in layer[:n_real]])
    return paths


def extract_paths_ref(net: ComputeNetwork, comp, data, src, dst, num_layers,
                      assign):
    """Reference per-hop host loop (seed implementation) for parity tests."""
    import numpy as np
    v = net.num_nodes
    w = jax.device_get(layer_edge_weights(net, data))
    t = jax.device_get(transfer_closure(net, data))
    assign = np.asarray(jax.device_get(assign))
    L = int(num_layers)
    nodes = [int(src)] + [int(assign[l]) for l in range(L)] + [int(dst)]
    paths = []
    for l in range(L + 1):
        a, b = nodes[l], nodes[l + 1]
        hops = []
        cur = a
        for _ in range(v):
            if cur == b:
                break
            cand = w[l][cur] + t[l][:, b]
            cand[cur] = np.inf  # never take the zero-cost self-loop
            nxt = int(np.argmin(cand))
            hops.append((cur, nxt))
            cur = nxt
        paths.append(hops)
    return paths
