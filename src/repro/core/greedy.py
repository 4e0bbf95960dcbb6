"""Algorithm 1: greedy multi-job routing — fused single-dispatch solver.

The default :func:`greedy_route` folds the whole solve into **one jitted
``lax.scan`` over priority rounds**: per round the closure stack is rebuilt
for the current queues (through the two-level dedupe of
``shortest_path.dedupe_plan`` — unique data rows, then unique data-size
scalars), every job is routed against it (a vmapped batch of single-job
DPs), the earliest-finishing unrouted job takes the next priority slot, and
its load is committed to the queues — all on device, exactly one dispatch
per solve and one host sync for the results.  The commit walks the chosen
job's per-layer transfer paths to charge the link queues; the scan emits
those hops, so ``extract_paths=True`` only formats them on the host — the
same walk, on the same operands, that ``greedy_route_ref`` runs per round.

:func:`greedy_route_ref` keeps the previous host-driven round loop (one
closure build + one jitted round per priority level, with per-round
``int(j)``/``float(cost)`` syncs) — the parity reference the property tests
and CI gate the fused solver against, bit-identical in assign/order/bounds
and committed queues.  ``lazy=True`` / ``share_closures=False`` delegate to
it (the lazy probe loop is inherently data-dependent and the no-reuse mode
exists only to benchmark the closure-reuse win).

:func:`greedy_route_windows` is the cross-arrival entry: W queued arrival
windows solved in one padded multi-window dispatch (an outer scan threads
the committed queues from each window into the next), bit-identical to W
sequential fused solves.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .network import ComputeNetwork
from . import jobs as J
from .jobs import JobBatch
from .plan import Plan
from . import routing
from . import shortest_path as SP
from . import telemetry

# Deprecated alias (one release): greedy now returns the canonical Plan.
GreedySolution = Plan


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _round(net: ComputeNetwork, batch: JobBatch, routed: jax.Array,
           closures: SP.Closures | None = None,
           *, use_pallas: bool | None = None):
    r = routing.route_batch(net, batch, closures=closures,
                            use_pallas=use_pallas)
    # Mask routed jobs with true inf, not the finite INF sentinel: an
    # unroutable job's cost clips to exactly INF and would tie with (and at
    # a lower index, win over) the mask, double-committing a routed job.
    costs = jnp.where(routed, jnp.inf, r.cost)
    j = jnp.argmin(costs).astype(jnp.int32)
    cl_j = None if closures is None else closures.job(j)
    net2 = routing.commit_assignment(
        net, batch.comp[j], batch.data[j], batch.src[j], batch.dst[j],
        batch.num_layers[j], r.assign[j], closures=cl_j)
    return j, r.cost[j], r.assign[j], net2


def _job_paths(pre_net: ComputeNetwork, batch: JobBatch, j: int, assign_row,
               closures):
    """Explicit transfer hops for job ``j`` against the pre-commit state.

    Reuses the round's already-built closure stack, so a solve that wants
    paths pays one extraction pass per round — not the full
    ``replay_solution`` (closure rebuild + bound re-eval + re-commit) the
    serving scheduler otherwise runs per arrival to fill ``plan.paths``.
    The hops are chosen against the queue state seen at the job's priority
    level, exactly the Alg. 1 / Alg. 2 semantics ``replay_solution``
    implements — the parity test asserts equality.
    """
    cl = None if closures is None else closures.job(j)
    return routing.extract_paths(
        pre_net, batch.comp[j], batch.data[j], batch.src[j], batch.dst[j],
        batch.num_layers[j], assign_row, closures=cl)


# ---------------------------------------------------------------------------
# Fused single-dispatch solver
# ---------------------------------------------------------------------------

# Each execution counts one ``fused_dispatches`` (``telemetry.call_counted``,
# which also reports the compile behind ``plan.meta["jit_compiled"]``), so
# the one-dispatch-per-solve property is directly assertable.  Lint rule
# RL003 (host-sync-in-device) keeps syncs out of the scanned round loop
# and RL006 (dispatch-accounting) makes every solver thread the accounting
# into plan.meta.

def _fused_rounds(net0: ComputeNetwork, batch: JobBatch,
                  dplan: SP.DedupePlan, routed0: jax.Array,
                  *, use_pallas: bool | None = None):
    """The on-device Alg. 1 round loop (scan body shared by both solvers).

    Jobs flagged in ``routed0`` are treated as already placed (the
    multi-window solver marks padding jobs this way).  Rounds after every
    real job is routed are no-ops: the commit is computed but the queue
    carry keeps its old values (a select between equal floats is exact,
    so live rounds are bit-identical to the unguarded loop) and the
    emitted job index is -1.

    Besides (job, cost, assign) each round emits the hops its commit
    charged (``[Lmax+1, V, 2]`` int32, see ``routing.commit_with_hops``):
    they are ``plan.paths`` of the round's job, so no per-round ``[V, V]``
    snapshot leaves the device and no second walk runs.
    """
    J = batch.num_jobs

    def body(carry, _):
        q_node, q_link, routed = carry
        cur = net0.with_queues(q_node, q_link)
        with jax.named_scope("closure"):
            cl = SP.closures_for_dedup(cur, dplan, use_pallas=use_pallas)
        # Forward DP only: the sequential backpointer walk is the one
        # non-vectorizable piece of the routing, and the round commits a
        # single job — so walk exactly one table, not all J (the walk is
        # pure integer gathers, bit-identical to route_batch's row).
        with jax.named_scope("route_fwd"):
            cost, total, bps = routing.route_batch_fwd(cur, batch,
                                                       closures=cl)
        # True inf mask (not the finite INF sentinel): see _round above.
        costs = jnp.where(routed, jnp.inf, cost)
        j = jnp.argmin(costs).astype(jnp.int32)
        assign_j = routing.assign_from_backpointers(total[j], bps[j])
        any_left = jnp.any(~routed)
        with jax.named_scope("commit"):
            net2, hops_j = routing.commit_with_hops(
                cur, batch.comp[j], batch.data[j], batch.src[j],
                batch.dst[j], batch.num_layers[j], assign_j,
                closures=cl.job(j))
        qn2 = jnp.where(any_left, net2.q_node, q_node)
        ql2 = jnp.where(any_left, net2.q_link, q_link)
        out_j = jnp.where(any_left, j, jnp.int32(-1))
        return ((qn2, ql2, routed.at[j].set(True)),
                (out_j, cost[j], assign_j, hops_j))

    (q_node, q_link, _), ys = jax.lax.scan(
        body, (net0.q_node, net0.q_link, routed0), None, length=J)
    return ys, q_node, q_link


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _fused_solve(net: ComputeNetwork, batch: JobBatch, dplan: SP.DedupePlan,
                 routed0: jax.Array, *, use_pallas: bool | None = None):
    return _fused_rounds(net, batch, dplan, routed0, use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _fused_solve_many(net: ComputeNetwork, batches: JobBatch,
                      dplans: SP.DedupePlan, valid: jax.Array,
                      *, use_pallas: bool | None = None):
    """W windows in one program: an outer scan carries the queues across
    windows (window w+1 solves against window w's committed state)."""

    def solve_window(carry, xs):
        q_node, q_link = carry
        batch_w, dplan_w, valid_w = xs
        cur = net.with_queues(q_node, q_link)
        ys, qn2, ql2 = _fused_rounds(cur, batch_w, dplan_w, ~valid_w,
                                     use_pallas=use_pallas)
        return (qn2, ql2), (ys, qn2, ql2)

    _, outs = jax.lax.scan(solve_window, (net.q_node, net.q_link),
                           (batches, dplans, valid))
    return outs


def _fused_meta(J: int, *, rounds: int, windows: int = 1,
                compiled: bool = False) -> dict:
    # n_routings/rounds_per_dispatch report the *padded* scan work (what
    # the device actually ran), "jobs" the real window size.
    return {"n_routings": rounds * rounds, "jobs": J, "fused": True,
            "dispatches": 1, "rounds_per_dispatch": windows * rounds,
            "windows_per_dispatch": windows, "jit_compiled": bool(compiled)}


def _round_paths(order, hops, num_layers) -> dict[int, list]:
    """``plan.paths`` of a solve's kept rounds: round ``p`` committed job
    ``order[p]`` along ``hops[p]``."""
    return {int(j): routing.hops_to_paths(hops[p], num_layers[j])
            for p, j in enumerate(order)}


def _assemble_plan(batch: JobBatch, net: ComputeNetwork, order, costs,
                   assigns, paths, meta: dict) -> Plan:
    """Host-side Plan assembly from one window's stacked round outputs."""
    J, lmax = batch.num_jobs, batch.max_layers
    order = np.asarray(order[:J])
    assign = np.zeros((J, lmax), np.int32)
    bounds = np.zeros((J,), np.float64)
    assign[order] = np.asarray(assigns[:J])
    bounds[order] = np.asarray(costs[:J], np.float64)
    return Plan.from_order(assign, order, bounds, solver="greedy",
                           meta=meta, net=net, paths=paths)


def greedy_route(net: ComputeNetwork, batch: JobBatch,
                 *, use_pallas: bool | None = None,
                 lazy: bool = False, share_closures: bool = True,
                 extract_paths: bool = False) -> Plan:
    """Run Algorithm 1 to completion — one device dispatch, one host sync.

    Semantics (and bit-exact results) match :func:`greedy_route_ref`;
    ``lazy=True`` and ``share_closures=False`` delegate to it (the lazy
    probe loop is host-driven by design, and no-reuse mode exists only to
    benchmark the closure-reuse win).  ``extract_paths=True`` fills
    ``plan.paths`` from the hops each round's commit charged, fetched in
    the same host sync as the results.  ``plan.meta`` reports the
    fused-dispatch accounting (``fused``/``dispatches``/
    ``rounds_per_dispatch``) plus ``jit_compiled`` —
    True when this call traced+compiled a new shape signature, the wall
    the serving warm-up exists to keep out of latency models.  A host
    batch (the serving scheduler's) is staged with no fetch; a device
    batch is fetched once first (``batch_fetches``).
    """
    if lazy or not share_closures:
        return greedy_route_ref(net, J.device_batch(batch),
                                use_pallas=use_pallas,
                                lazy=lazy, share_closures=share_closures,
                                extract_paths=extract_paths)
    with telemetry.span("greedy.stage"):
        host = J.host_batch(batch)
        padded, dplan, routed0 = _stage_window(host)
    with telemetry.span("greedy.dispatch"):
        out, compiled = telemetry.call_counted(
            "fused_dispatches", _fused_solve, net, padded, dplan, routed0,
            use_pallas=use_pallas)
    (order, costs, assigns, hops), q_node, q_link = out
    with telemetry.span("greedy.fetch"):
        order, costs, assigns, hops = telemetry.to_host(
            (order, costs, assigns, hops if extract_paths else None))
    # drop padding rounds; every round is real in the common unpadded
    # serving case, where the mask gathers would be pure eager overhead
    keep = slice(None) if (order >= 0).all() else order >= 0
    paths = None
    if extract_paths:
        with telemetry.span("greedy.paths"):
            paths = _round_paths(order[keep], hops[keep], host.num_layers)
    with telemetry.span("greedy.assemble"):
        return _assemble_plan(
            host, net.with_queues(q_node, q_link), order[keep],
            costs[keep], assigns[keep], paths,
            meta=_fused_meta(host.num_jobs, rounds=padded.num_jobs,
                             compiled=compiled))


def _stage_window(batch: JobBatch) -> tuple:
    """:func:`_fused_solve`'s operands for one window, in one upload: the
    batch padded to a power of two, its bucketed dedupe plan, and the
    pre-routed mask that keeps the dummy jobs out of every round.  A host
    batch (the scheduler's) is staged with no fetch."""
    batch = J.host_batch(batch)
    n = batch.num_jobs
    j_pad = _next_pow2(n)
    padded = _pad_batch(batch, j_pad)
    return telemetry.to_device(
        (padded, _bucket_dplan(SP.dedupe_plan_host(padded)),
         np.arange(j_pad) >= n))


def _stage_windows(batches: list[JobBatch]) -> tuple:
    """:func:`_fused_solve_many`'s operands for W windows: the per-window
    padded host batches, then, in one upload, the stacked batches, the
    stacked dedupe plans and the [W, J] mask of real jobs."""
    hosts = [J.host_batch(b) for b in batches]
    j_max = _next_pow2(max(b.num_jobs for b in hosts))
    padded = [_pad_batch(b, j_max) for b in hosts]
    dplans = [SP.dedupe_plan_host(b) for b in padded]
    u_max = _next_pow2(max(d.uniq.shape[0] for d in dplans))
    d_max = _next_pow2(max(d.d_vals.shape[0] for d in dplans))
    dplans = [_pad_dplan(d, u_max, d_max) for d in dplans]
    dplans = jax.tree_util.tree_map(lambda *leaves: np.stack(leaves),
                                    *dplans)
    valid = np.array(
        [[1] * b.num_jobs + [0] * (j_max - b.num_jobs) for b in hosts],
        bool)
    stacked = jax.tree_util.tree_map(lambda *leaves: np.stack(leaves),
                                     *padded)
    return (padded,) + telemetry.to_device((stacked, dplans, valid))


def _next_pow2(n: int) -> int:
    """Smallest power of two >= n (shape-bucketing for jit signatures).

    Serving windows arrive at every size in [1, max_batch]; without
    bucketing each distinct (J, U, D) triple would compile its own fused
    program (seconds each).  Rounding all three up to powers of two caps
    the signature count at a handful per deployment — and padding is
    bit-exact: dummy jobs are pre-routed and duplicated dedupe rows gather
    onto the same values (the parity suite runs padded next to unpadded).
    """
    return 1 << max(0, (n - 1).bit_length())


def _pad_batch(batch: JobBatch, j_to: int) -> JobBatch:
    """Pad a window's host batch to ``j_to`` jobs with inert dummies (zero
    compute/data, src=dst=0) — they are pre-routed in the fused scan, so
    they never route, commit, or perturb real jobs' values."""
    n = batch.num_jobs
    if n == j_to:
        return batch
    pad = j_to - n

    def pad0(x):
        return np.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))

    return JobBatch(
        src=pad0(batch.src), dst=pad0(batch.dst), comp=pad0(batch.comp),
        data=pad0(batch.data),
        num_layers=pad0(batch.num_layers) + np.array([0] * n + [1] * pad,
                                                     np.int32))


def _pad_dplan(dplan: SP.DedupePlan, u_to: int, d_to: int) -> SP.DedupePlan:
    """Pad a host dedupe plan to common unique-row/-scalar counts.  Padding
    rows duplicate existing entries, so the closure work grows but every
    real gather lands on the same values — bit-identical results."""
    uniq, inv, d_vals, d_idx = dplan.uniq, dplan.inv, dplan.d_vals, dplan.d_idx
    u_pad, d_pad = u_to - uniq.shape[0], d_to - d_vals.shape[0]
    if u_pad:
        uniq = np.concatenate([uniq, np.repeat(uniq[:1], u_pad, axis=0)])
        d_idx = np.concatenate([d_idx, np.repeat(d_idx[:1], u_pad, axis=0)])
    if d_pad:
        d_vals = np.concatenate([d_vals, np.repeat(d_vals[:1], d_pad)])
    return SP.DedupePlan(uniq=uniq, inv=inv, d_vals=d_vals,
                         d_idx=d_idx.astype(np.int32))


def _bucket_dplan(dplan: SP.DedupePlan) -> SP.DedupePlan:
    """Round the dedupe plan's unique-row/-scalar counts up to powers of
    two (see :func:`_next_pow2`) so batches with slightly different model
    mixes share one compiled program."""
    u, d = dplan.uniq.shape[0], dplan.d_vals.shape[0]
    return _pad_dplan(dplan, _next_pow2(u), _next_pow2(d))


def greedy_route_windows(net: ComputeNetwork, batches: list[JobBatch],
                         *, use_pallas: bool | None = None,
                         extract_paths: bool = False) -> list[Plan]:
    """Cross-arrival batching: W windows, one dispatch, W chained plans.

    Window w+1 is solved against window w's committed queues — exactly the
    state W sequential :func:`greedy_route` calls would thread through —
    and each returned plan is bit-identical to its sequential counterpart
    (ragged window sizes are padded with inert jobs; each plan's ``net``
    carries that window's post-commit queues).  All windows must share the
    layer width (``batch_jobs(pad_to=)``).
    """
    if not batches:
        return []
    if len(batches) == 1:
        return [greedy_route(net, batches[0], use_pallas=use_pallas,
                             extract_paths=extract_paths)]
    lmax = {b.max_layers for b in batches}
    if len(lmax) != 1:
        raise ValueError(
            f"windows must share a padded layer width (batch_jobs(pad_to=)); "
            f"got {sorted(lmax)}")
    with telemetry.span("greedy.stage"):
        padded, stacked, dplans, valid = _stage_windows(batches)
    j_max = padded[0].num_jobs
    with telemetry.span("greedy.dispatch"):
        outs, compiled = telemetry.call_counted(
            "fused_dispatches", _fused_solve_many, net, stacked, dplans,
            valid, use_pallas=use_pallas)
    (orders, costs, assigns, hops), q_nodes, q_links = outs
    with telemetry.span("greedy.fetch"):
        # host copies: per-window numpy indexing is free, while indexing
        # the device arrays with python ints / numpy masks would
        # implicitly stage the indices
        orders, costs, assigns, q_nodes, q_links, hops = telemetry.to_host(
            (orders, costs, assigns, q_nodes, q_links,
             hops if extract_paths else None))
    plans = []
    for w, batch in enumerate(batches):
        J = batch.num_jobs
        keep = orders[w] >= 0
        order_w = orders[w][keep]
        paths = None
        if extract_paths:
            with telemetry.span("greedy.paths"):
                paths = _round_paths(order_w, hops[w][keep],
                                     padded[w].num_layers)
        with telemetry.span("greedy.assemble"):
            plans.append(_assemble_plan(
                batch, net.with_queues(*telemetry.to_device(
                    (q_nodes[w], q_links[w]))), order_w,
                costs[w][keep], assigns[w][keep], paths,
                meta=_fused_meta(J, rounds=j_max, windows=len(batches),
                                 compiled=compiled)))
    return plans


# ---------------------------------------------------------------------------
# Reference host-driven loop (parity gate) + lazy greedy
# ---------------------------------------------------------------------------

def greedy_route_ref(net: ComputeNetwork, batch: JobBatch,
                     *, use_pallas: bool | None = None,
                     lazy: bool = False, share_closures: bool = True,
                     extract_paths: bool = False) -> Plan:
    """Host-driven Algorithm 1 round loop (the fused solver's parity
    reference).

    Each round builds the batched closure stack once
    (``build_closures_batch``), routes every job in one jitted ``_round``,
    and syncs the selected job back to the host — ~4 dispatches and two
    scalar transfers per round.  ``share_closures=True`` (default) shares
    that stack between routing and commit; ``False`` reproduces the seed
    behavior (every call rebuilds its own closures) — kept for
    benchmarking the reuse win, not for production use.

    ``extract_paths=True`` additionally fills ``plan.paths`` (explicit
    per-layer transfer hops) during the solve, one extraction per round
    against the round's closures.

    ``lazy=True`` is the beyond-paper *lazy greedy* (EXPERIMENTS.md §Perf):
    queues only grow, so every job's completion bound is monotone
    non-decreasing across rounds — a stale cached bound is a valid lower
    bound.  Each round re-routes only the cached argmin until it proves
    itself fresh-minimal, committing after O(1) expected re-routes instead
    of re-routing all J jobs.  Produces a solution with the same guarantee
    (it IS Algorithm 1 up to tie-breaking).
    """
    if lazy:
        return _greedy_lazy(net, batch, use_pallas=use_pallas,
                            share_closures=share_closures,
                            extract_paths=extract_paths)
    J, lmax = batch.num_jobs, batch.max_layers
    routed = jnp.zeros((J,), bool)
    order = np.zeros((J,), np.int32)
    assign = np.zeros((J, lmax), np.int32)
    bounds = np.zeros((J,), np.float64)
    paths: dict[int, list] | None = {} if extract_paths else None
    cur = net
    dedupe = SP.dedupe_data(batch) if share_closures else None
    for p in range(J):
        closures = (SP.build_closures_batch(cur, batch, dedupe=dedupe,
                                            use_pallas=use_pallas)
                    if share_closures else None)
        j, cost, a, nxt = _round(cur, batch, routed, closures,
                                 use_pallas=use_pallas)
        j = int(j)
        order[p] = j
        bounds[j] = float(cost)
        assign[j] = np.asarray(a)
        if paths is not None:
            paths[j] = _job_paths(cur, batch, j, assign[j], closures)
        cur = nxt
        routed = routed.at[j].set(True)
    return Plan.from_order(assign, order, bounds, solver="greedy",
                           meta={"n_routings": J * J}, net=cur, paths=paths)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _route_one(net, batch, j, closures=None, *, use_pallas=None):
    cl = None if closures is None else closures.job(j)
    r = routing.route_single(net, batch.comp[j], batch.data[j], batch.src[j],
                             batch.dst[j], batch.num_layers[j], closures=cl,
                             use_pallas=use_pallas)
    return r.cost, r.assign


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _commit_one(net, batch, j, assign, closures=None, *, use_pallas=None):
    cl = None if closures is None else closures.job(j)
    return routing.commit_assignment(
        net, batch.comp[j], batch.data[j], batch.src[j], batch.dst[j],
        batch.num_layers[j], jnp.asarray(assign), closures=cl)


def _greedy_lazy(net: ComputeNetwork, batch: JobBatch,
                 *, use_pallas: bool | None = None,
                 share_closures: bool = True,
                 extract_paths: bool = False) -> Plan:
    J, lmax = batch.num_jobs, batch.max_layers
    dedupe = SP.dedupe_data(batch) if share_closures else None

    def fresh_closures(n):
        return (SP.build_closures_batch(n, batch, dedupe=dedupe,
                                        use_pallas=use_pallas)
                if share_closures else None)

    closures = fresh_closures(net)
    paths: dict[int, list] | None = {} if extract_paths else None
    r0 = routing.route_batch(net, batch, closures=closures,
                             use_pallas=use_pallas)
    # Cached lower bounds stay on device; selection is a device argmin over
    # the masked vector (one scalar transfer per probe, no J-wide ping-pong).
    cost = jnp.asarray(r0.cost)                      # [J] cached lower bounds
    assign_c = np.array(r0.assign)                   # (writable host copy)
    fresh = np.ones((J,), bool)
    active = jnp.ones((J,), bool)

    order = np.zeros((J,), np.int32)
    assign = np.zeros((J, lmax), np.int32)
    bounds = np.zeros((J,), np.float64)
    cur = net
    n_routings = J
    for p in range(J):
        while True:
            # inf (not the finite INF sentinel) so routed jobs can never tie
            # with an unroutable active job's clipped-to-INF bound
            j = int(jnp.argmin(jnp.where(active, cost, jnp.inf)))
            if fresh[j]:
                break
            c, a = _route_one(cur, batch, j, closures, use_pallas=use_pallas)
            cost = cost.at[j].set(c)
            assign_c[j] = np.asarray(a)
            fresh[j] = True
            n_routings += 1
        order[p] = j
        bounds[j] = float(cost[j])
        assign[j] = assign_c[j]
        if paths is not None:
            paths[j] = _job_paths(cur, batch, j, assign_c[j], closures)
        active = active.at[j].set(False)
        cur = _commit_one(cur, batch, j, assign_c[j], closures,
                          use_pallas=use_pallas)
        if p + 1 < J:
            closures = fresh_closures(cur)
            fresh[:] = False
            fresh[j] = True  # routed jobs are never probed again
    return Plan.from_order(assign, order, bounds, solver="lazy",
                           meta={"n_routings": n_routings}, net=cur,
                           paths=paths)
