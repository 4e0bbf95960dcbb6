"""Algorithm 1: greedy multi-job routing — fused single-dispatch solver.

The default :func:`greedy_route` folds the whole solve into **one jitted
``lax.scan`` over priority rounds**: per round the closure stack is rebuilt
for the current queues (through the two-level dedupe of
``shortest_path.dedupe_plan`` — unique data rows, then unique data-size
scalars), every job is routed against it (a vmapped batch of single-job
DPs), the earliest-finishing unrouted job takes the next priority slot, and
its load is committed to the queues — all on device, exactly one dispatch
per solve and one host sync for the results.  ``extract_paths=True`` adds
one batched post-pass (``_paths_post``) that replays the reference path
extraction against the scan's own per-round queue snapshots and closure
stacks — see the note there on the FMA-proof edge-weight form that keeps
it bit-identical to ``greedy_route_ref``.

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

from .network import INF, ComputeNetwork, link_invrate
from .jobs import JobBatch
from .plan import Plan
from . import routing
from . import shortest_path as SP

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

# Host-level dispatch telemetry for the fused path: one increment per
# ``_fused_solve``/``_fused_solve_many`` *execution* (unlike trace-time
# counters — see kernels/ops.dispatch_counts — these count real dispatches,
# so the one-dispatch-per-solve property is directly assertable).  Two
# lint rules guard this contract statically: RL003 (host-sync-in-device)
# keeps syncs out of the scanned round loop, and RL006
# (dispatch-accounting) makes every solver thread these numbers into
# plan.meta; tests/test_fused.py adds the runtime transfer_guard check.
_n_fused_dispatches = 0


def fused_dispatch_count() -> int:
    """Fused-solver dispatches since the last reset (one per solve)."""
    return _n_fused_dispatches


def reset_fused_dispatch_count() -> None:
    global _n_fused_dispatches
    _n_fused_dispatches = 0


def _bump_dispatch(fn) -> int:
    """Count one dispatch; return ``fn``'s jit cache size before the call.

    jax caches compiled executables per abstract signature; a growing
    cache size after the call means this signature was new.  The *pre*
    -call size is recorded here and compared by :func:`_took_compile`.
    """
    global _n_fused_dispatches
    _n_fused_dispatches += 1
    return fn._cache_size()


def _took_compile(fn, size_before: int) -> bool:
    return fn._cache_size() > size_before


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

    Besides (job, cost, assign) each round also emits its pre-commit link
    queues and the chosen job's closure stack — the inputs
    :func:`_paths_post` needs to replay the reference path extraction
    without re-running the solve.
    """
    J = batch.num_jobs

    def body(carry, _):
        q_node, q_link, routed = carry
        cur = net0.with_queues(q_node, q_link)
        cl = SP.closures_for_dedup(cur, dplan, use_pallas=use_pallas)
        # Forward DP only: the sequential backpointer walk is the one
        # non-vectorizable piece of the routing, and the round commits a
        # single job — so walk exactly one table, not all J (the walk is
        # pure integer gathers, bit-identical to route_batch's row).
        cost, total, bps = routing.route_batch_fwd(cur, batch, closures=cl)
        # True inf mask (not the finite INF sentinel): see _round above.
        costs = jnp.where(routed, jnp.inf, cost)
        j = jnp.argmin(costs).astype(jnp.int32)
        assign_j = routing.assign_from_backpointers(total[j], bps[j])
        any_left = jnp.any(~routed)
        net2 = routing.commit_assignment(
            cur, batch.comp[j], batch.data[j], batch.src[j], batch.dst[j],
            batch.num_layers[j], assign_j, closures=cl.job(j))
        qn2 = jnp.where(any_left, net2.q_node, q_node)
        ql2 = jnp.where(any_left, net2.q_link, q_link)
        out_j = jnp.where(any_left, j, jnp.int32(-1))
        return ((qn2, ql2, routed.at[j].set(True)),
                (out_j, cost[j], assign_j, q_link, cl.t[j]))

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
                compiled: bool = False, paths: bool = False) -> dict:
    # n_routings/rounds_per_dispatch report the *padded* scan work (what
    # the device actually ran), "jobs" the real window size.
    return {"n_routings": rounds * rounds, "jobs": J, "fused": True,
            "dispatches": 1, "rounds_per_dispatch": windows * rounds,
            "windows_per_dispatch": windows, "path_dispatches": int(paths),
            "jit_compiled": bool(compiled)}


@functools.partial(jax.jit, static_argnames=("max_hops",))
def _walk_paths(data: jax.Array, ql_pre: jax.Array, inv: jax.Array,
                t: jax.Array, starts: jax.Array, ends: jax.Array,
                *, max_hops: int) -> jax.Array:
    """[P, Lmax+1] batched path walks -> hops [P, Lmax+1, max_hops, 2].

    Rebuilds the per-round edge weights in the same program as the walks
    (one dispatch instead of a chain of eager ops feeding a jit call).
    The ``(d + Q) * inv`` expression matches
    ``shortest_path.layer_edge_weights`` exactly — its last rounding is
    the multiply, so it is contraction-proof in any program context and
    the weights stay bit-identical to the reference extraction's.
    """
    w = jnp.minimum((data[:, :, None, None] + ql_pre[:, None]) * inv, INF)
    fn = functools.partial(routing.reconstruct_path, max_hops=max_hops)
    return jax.vmap(jax.vmap(fn))(w, t, starts, ends)


def _paths_post(net0: ComputeNetwork, batch: JobBatch, order, assigns,
                ql_pre, t_sel, num_layers_h) -> dict[int, list]:
    """One batched post-pass: ``plan.paths`` for every round of a solve.

    Replays exactly what :func:`greedy_route_ref` does per round
    (``routing.extract_paths`` against the pre-commit queues) from the
    scan's emitted snapshots: per-round link queues ``ql_pre [P, V, V]``
    and the committed job's closure stack ``t_sel [P, Lmax+1, V, V]``.

    The edge weights are rebuilt inside :func:`_walk_paths` (one jit
    dispatch for weights + walks) with ``layer_edge_weights``'s exact
    ``(d + Q) * inv`` expression against each round's pre-commit queues —
    that form's last rounding is the multiply, so LLVM cannot contract it
    into an FMA and the rebuild is bit-identical to the reference
    extraction's weights under every program context.  ``t`` (no
    contractible pattern) is taken from the scan and matches the
    reference's jit-built closures bit-for-bit.
    """
    order = np.asarray(order)
    if order.size == 0:
        return {}
    L_sel = np.asarray(num_layers_h)[order]
    hops = jax.device_get(_walk_paths(
        *_walk_operands(net0, batch, order, assigns, ql_pre, t_sel, L_sel),
        max_hops=net0.num_nodes))
    return {int(j): routing.hops_to_paths(hops[p], int(L_sel[p]))
            for p, j in enumerate(order)}


def _walk_operands(net0: ComputeNetwork, batch: JobBatch, order, assigns,
                   ql_pre, t_sel, L_sel) -> tuple:
    """:func:`_walk_paths`'s operands for the rounds in ``order`` (whose
    jobs have ``L_sel`` layers), staged on the device."""
    assigns = np.asarray(assigns)
    lmax = batch.max_layers
    src_h, dst_h, data_h = (np.asarray(jax.device_get(x))
                            for x in (batch.src, batch.dst, batch.data))
    src_sel, dst_sel = src_h[order], dst_h[order]
    # Per-layer walk endpoints: node_l -> node_{l+1} with node_0 = src and
    # dst from layer num_layers on (layers past num_layers are dropped by
    # the formatter, their walks are dead weight in the batched call).
    starts = np.concatenate([src_sel[:, None], assigns], 1).astype(np.int32)
    ends = np.concatenate([assigns, dst_sel[:, None]], 1)
    ends = np.where(np.arange(lmax + 1)[None, :] >= L_sel[:, None],
                    dst_sel[:, None], ends).astype(np.int32)

    # device_put (not jnp.asarray): staging is an *explicit* transfer so
    # the solver path stays clean under jax.transfer_guard("disallow")
    # (the runtime complement of lint rule RL003; see tests/test_fused.py).
    return (jax.device_put(data_h[order]), jax.device_put(ql_pre),
            link_invrate(net0), jax.device_put(t_sel), jax.device_put(starts),
            jax.device_put(ends))


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
    ``plan.paths`` in one batched post-pass over the scan's emitted
    snapshots (see :func:`_paths_post`).  ``plan.meta`` reports the
    fused-dispatch accounting (``fused``/``dispatches``/
    ``rounds_per_dispatch``/``path_dispatches``) plus ``jit_compiled`` —
    True when this call traced+compiled a new shape signature, the wall
    the serving warm-up exists to keep out of latency models.
    """
    if lazy or not share_closures:
        return greedy_route_ref(net, batch, use_pallas=use_pallas,
                                lazy=lazy, share_closures=share_closures,
                                extract_paths=extract_paths)
    J = batch.num_jobs
    padded, dplan, routed0 = _stage_window(batch)
    size0 = _bump_dispatch(_fused_solve)
    out = _fused_solve(net, padded, dplan, routed0, use_pallas=use_pallas)
    compiled = _took_compile(_fused_solve, size0)
    (order, costs, assigns, ql_pre, t_sel), q_node, q_link = out
    order, costs, assigns, num_layers_h = jax.device_get(
        (order, costs, assigns, batch.num_layers))
    # drop padding rounds; every round is real in the common unpadded
    # serving case, where the mask gathers would be pure eager overhead
    keep = slice(None) if (order >= 0).all() else order >= 0
    paths = None
    if extract_paths:
        # host copies before mask-slicing: indexing a device array with a
        # numpy mask is an implicit h2d of the indices (trips the
        # transfer_guard("disallow") the parity tests run under)
        ql_h, t_h = jax.device_get((ql_pre, t_sel))
        paths = _paths_post(net, batch, order[keep], assigns[keep],
                            ql_h[keep], t_h[keep], num_layers_h)
    return _assemble_plan(
        batch, net.with_queues(q_node, q_link), order[keep], costs[keep],
        assigns[keep], paths,
        meta=_fused_meta(J, rounds=padded.num_jobs, compiled=compiled,
                         paths=extract_paths))


def _stage_window(batch: JobBatch) -> tuple:
    """:func:`_fused_solve`'s operands for one window: the batch padded to
    a power of two, its bucketed dedupe plan, and the pre-routed mask that
    keeps the dummy jobs out of every round."""
    J = batch.num_jobs
    j_pad = _next_pow2(J)
    padded = _pad_batch(batch, j_pad)
    return (padded, _bucket_dplan(SP.dedupe_plan(padded)),
            jax.device_put(np.arange(j_pad) >= J))


def _stage_windows(batches: list[JobBatch]) -> tuple:
    """:func:`_fused_solve_many`'s operands for W windows: the per-window
    padded batches (unstacked), then the stacked batches, stacked dedupe
    plans and the [W, J] mask of real jobs."""
    j_max = _next_pow2(max(b.num_jobs for b in batches))
    padded = [_pad_batch(b, j_max) for b in batches]
    dplans = [SP.dedupe_plan(b) for b in padded]
    u_max = _next_pow2(max(np.asarray(d.uniq).shape[0] for d in dplans))
    d_max = _next_pow2(max(np.asarray(d.d_vals).shape[0] for d in dplans))
    dplans = [_pad_dplan(d, u_max, d_max) for d in dplans]
    stack = lambda xs: jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *xs)
    valid = jax.device_put(np.array(
        [[1] * b.num_jobs + [0] * (j_max - b.num_jobs) for b in batches],
        bool))
    return padded, stack(padded), stack(dplans), valid


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
    """Pad a window's batch to ``j_to`` jobs with inert dummies (zero
    compute/data, src=dst=0) — they are pre-routed in the fused scan, so
    they never route, commit, or perturb real jobs' values."""
    J = batch.num_jobs
    if J == j_to:
        return batch
    pad = j_to - J

    def pad0(x):
        width = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return jax.device_put(np.pad(np.asarray(x), width))

    return JobBatch(src=pad0(batch.src), dst=pad0(batch.dst),
                    comp=pad0(batch.comp), data=pad0(batch.data),
                    num_layers=pad0(batch.num_layers) + jax.device_put(
                        np.array([0] * J + [1] * pad, np.int32)))


def _pad_dplan(dplan: SP.DedupePlan, u_to: int, d_to: int) -> SP.DedupePlan:
    """Pad a dedupe plan to common unique-row/-scalar counts.  Padding rows
    duplicate existing entries, so the closure work grows but every real
    gather lands on the same values — bit-identical results."""
    uniq, inv = np.asarray(dplan.uniq), np.asarray(dplan.inv)
    d_vals, d_idx = np.asarray(dplan.d_vals), np.asarray(dplan.d_idx)
    u_pad, d_pad = u_to - uniq.shape[0], d_to - d_vals.shape[0]
    if u_pad:
        uniq = np.concatenate([uniq, np.repeat(uniq[:1], u_pad, axis=0)])
        d_idx = np.concatenate([d_idx, np.repeat(d_idx[:1], u_pad, axis=0)])
    if d_pad:
        d_vals = np.concatenate([d_vals, np.repeat(d_vals[:1], d_pad)])
    return SP.DedupePlan(uniq=jax.device_put(uniq),
                         inv=jax.device_put(inv),
                         d_vals=jax.device_put(d_vals),
                         d_idx=jax.device_put(d_idx.astype(np.int32)))


def _bucket_dplan(dplan: SP.DedupePlan) -> SP.DedupePlan:
    """Round the dedupe plan's unique-row/-scalar counts up to powers of
    two (see :func:`_next_pow2`) so batches with slightly different model
    mixes share one compiled program."""
    u, d = np.asarray(dplan.uniq).shape[0], np.asarray(dplan.d_vals).shape[0]
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
    padded, stacked, dplans, valid = _stage_windows(batches)
    j_max = padded[0].num_jobs
    size0 = _bump_dispatch(_fused_solve_many)
    outs = _fused_solve_many(net, stacked, dplans, valid,
                             use_pallas=use_pallas)
    compiled = _took_compile(_fused_solve_many, size0)
    (orders, costs, assigns, ql_pre, t_sel), q_nodes, q_links = outs
    orders, costs, assigns = jax.device_get((orders, costs, assigns))
    # host copies: per-window numpy indexing is free (d2h is zero-copy on
    # CPU), while indexing the device arrays with python ints / numpy
    # masks would implicitly stage the indices — tripping the
    # transfer_guard("disallow") the parity tests run under
    q_nodes, q_links = jax.device_get((q_nodes, q_links))
    if extract_paths:
        ql_pre, t_sel = jax.device_get((ql_pre, t_sel))
    plans = []
    for w, batch in enumerate(batches):
        J = batch.num_jobs
        keep = orders[w] >= 0
        order_w = orders[w][keep]
        paths = None
        if extract_paths:
            paths = _paths_post(
                net, padded[w], order_w, assigns[w][keep], ql_pre[w][keep],
                t_sel[w][keep],
                np.asarray(jax.device_get(padded[w].num_layers)))
        plans.append(_assemble_plan(
            batch, net.with_queues(jax.device_put(q_nodes[w]),
                                   jax.device_put(q_links[w])), order_w,
            costs[w][keep], assigns[w][keep], paths,
            meta=_fused_meta(J, rounds=j_max, windows=len(batches),
                             compiled=compiled, paths=extract_paths)))
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
