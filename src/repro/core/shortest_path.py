"""Shortest-path machinery on the physical network, per DNN layer.

For layer l, every intra-layer edge (u, v) of the layered graph costs

    w_l(u, v) = (d_l + Q_uv) / mu_uv        (service + waiting, paper §III-B)

``transfer_closure`` returns the [L+1, V, V] tensor T where T[l, u, v] is the
cheapest way to move layer-l output from u to v (possibly multi-hop).  It is
the min-plus closure of w_l, the kernel hot-spot (see kernels/minplus.py).

:class:`Closures` bundles (w, T) for one (net, data) so the stack is built
**once** per queue state and shared by everything that needs it — routing,
commit, cost evaluation, path extraction.  ``build_closures`` /
``build_closures_batch`` are the counted host-level builders (the greedy
driver calls them once per round; the ``closure_builds`` counter of
:mod:`~repro.core.telemetry` powers the regression test asserting exactly
that); ``closures_for`` is the uncounted pure builder safe to call under
jit/scan tracing.

``reconstruct_path`` recovers an explicit hop list from the closure: from u
toward v, the next hop is argmin_w  w_l(u, w) + T[l, w, v].  Walking this
greedy next-hop V-1 times yields a shortest path; it is used to commit link
loads in the greedy algorithm and to hand explicit paths to the event
simulator.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from . import jobs as J, telemetry
from .network import INF, ComputeNetwork, link_invrate, link_wait


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Closures:
    """Per-layer edge weights and their min-plus closures for one queue state.

    ``w``/``t`` are [Lmax+1, V, V] for a single data-size vector, or carry a
    leading [J] axis when built for a batch (``build_closures_batch``); the
    batched stack vmaps straight through ``route_single``.

    ``w`` may be ``None``: it is elementwise-cheap to recompute from
    (net, data), so batch-stacked artifacts omit it rather than materialize
    a J-fold gather that only ever serves one job's commit — consumers that
    need ``w`` (commit, path extraction) rebuild it from the job's data when
    absent.  ``t`` — the expensive part — is always present.
    """

    w: jax.Array | None  # layer edge weights w_l(u, v), or None (recompute)
    t: jax.Array         # min-plus closure T_l = closure(w_l)

    def job(self, j) -> "Closures":
        """Slice one job's closures out of a batch-stacked artifact."""
        return Closures(w=None if self.w is None else self.w[j], t=self.t[j])


def closure_build_count() -> int:
    """Host-level closure builds so far in this process (one per
    ``build_closures``/``build_closures_batch`` call; in-jit fallback builds
    are not counted)."""
    return telemetry.counter("closure_builds")


def layer_edge_weights(net: ComputeNetwork, data_sizes: jax.Array) -> jax.Array:
    """[..., L+1, V, V] per-layer intra-layer edge weights.

    data_sizes: [..., L+1] bytes (d_0 .. d_L; leading batch dims allowed).
    Absent edges get INF; the diagonal is 0 (staying put is free).
    """
    inv = link_invrate(net)  # [V, V], INF off-graph, 0 diag
    # Computed in the paper's literal form (d_l + Q_uv) / mu_uv rather than
    # d_l/mu + Q/mu: the multiply is the LAST rounding, so there is no
    # mul-feeding-add for LLVM to contract into an FMA.  The split form is
    # contraction-unstable — whether XLA/LLVM fuses ``d*inv + wait`` into
    # an FMA depends on the surrounding program, so the fused round scan,
    # the standalone closure build, and eager execution each rounded the
    # last ulp differently once queues were nonzero, breaking bitwise
    # solver parity (lax.optimization_barrier does not stop the
    # contraction on CPU).  At Q == 0 this form reproduces ``d * inv``
    # bit-for-bit, so pre-change golden traces are unaffected.  Lint rule
    # RL001 (contraction-hazard) enforces this multiply-last form across
    # every numerics module — `python -m repro.lint --list-rules`.
    w = (data_sizes[..., :, None, None] + net.q_link) * inv
    return jnp.minimum(w, INF)


def closures_for(net: ComputeNetwork, data_sizes: jax.Array,
                 *, use_pallas: bool | None = None) -> Closures:
    """Uncounted :class:`Closures` builder (safe under jit/scan tracing)."""
    w = layer_edge_weights(net, data_sizes)
    return Closures(w=w, t=ops.minplus_closure(w, use_pallas=use_pallas))


def build_closures(net: ComputeNetwork, data_sizes: jax.Array,
                   *, use_pallas: bool | None = None) -> Closures:
    """Counted host-level :class:`Closures` build for one data-size vector."""
    telemetry.count("closure_builds")
    return closures_for(net, data_sizes, use_pallas=use_pallas)


def dedupe_data(batch) -> tuple[jax.Array, jax.Array]:
    """(unique [U, Lmax+1] data rows, [J] inverse index) for a job batch.

    Host-level (needs concrete ``batch.data``); constant across greedy
    rounds, so drivers hoist it out of the round loop.
    """
    # explicit staging: keeps solver drivers transfer_guard("disallow")-clean
    return telemetry.to_device(_dedupe_rows(batch))


def _dedupe_rows(batch) -> tuple[np.ndarray, np.ndarray]:
    data = J.host_batch(batch).data
    uniq, inv = np.unique(data, axis=0, return_inverse=True)
    return uniq, inv.reshape(-1).astype(np.int32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class DedupePlan:
    """Host-precomputed dedupe structure for one job batch.

    Row level: ``uniq [U, Lmax+1]`` unique data rows with ``inv [J]``
    mapping jobs back (exactly :func:`dedupe_data`).  Scalar level:
    ``w_l(u, v) = d_l * inv_rate(u, v) + wait(u, v)`` depends on the data
    size *scalar* d_l only, so two (row, layer) slots sharing a d value
    have bitwise-identical weight matrices — and hence bitwise-identical
    closures — under **every** queue state.  ``d_vals [D]`` are the unique
    scalars and ``d_idx [U, Lmax+1]`` gathers them back; the fused solver
    closes [D, V, V] matrices per round instead of [U, Lmax+1, V, V]
    (model-serving batches share layer widths, so D is typically an order
    of magnitude below U * (Lmax+1)).  Queue-state independent, so solvers
    hoist one plan out of the round loop.
    """

    uniq: jax.Array    # [U, Lmax+1] unique data rows
    inv: jax.Array     # [J] int32: job -> row in uniq
    d_vals: jax.Array  # [D] unique data-size scalars
    d_idx: jax.Array   # [U, Lmax+1] int32: (row, layer) -> slot in d_vals


def dedupe_plan(batch) -> DedupePlan:
    """Build the two-level :class:`DedupePlan` for a job batch (host-level)."""
    return telemetry.to_device(dedupe_plan_host(batch))


def dedupe_plan_host(batch) -> DedupePlan:
    """:func:`dedupe_plan` with numpy leaves, for staging that pads it on
    the host before the one transfer (from a host batch, with no fetch)."""
    uniq, inv = _dedupe_rows(batch)
    d_vals, d_idx = np.unique(uniq, return_inverse=True)
    return DedupePlan(uniq=uniq, inv=inv, d_vals=d_vals,
                      d_idx=d_idx.reshape(uniq.shape).astype(np.int32))


def closures_for_dedup(net: ComputeNetwork, plan: DedupePlan,
                       *, use_pallas: bool | None = None) -> Closures:
    """Uncounted batch-stacked closure build through a :class:`DedupePlan`.

    jit/scan-safe (the fused solver's round body calls it with traced
    queues).  Closes the [D, V, V] unique-scalar stack and gathers back to
    [J, Lmax+1, V, V]; the closure of each weight matrix is computed
    independently, so the gathered stack is bitwise identical to
    ``build_closures_batch``'s.  ``w`` is dropped as usual (cheap to
    recompute per job).
    """
    t_d = ops.minplus_closure(layer_edge_weights(net, plan.d_vals),
                              use_pallas=use_pallas)      # [D, V, V]
    t_u = t_d[plan.d_idx]                                 # [U, Lmax+1, V, V]
    return Closures(w=None, t=t_u[plan.inv])              # [J, ...]


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _closures_gathered(net: ComputeNetwork, uniq: jax.Array, inv: jax.Array,
                       *, use_pallas: bool | None = None) -> Closures:
    """One fused program: close the unique stack, gather back to [J, ...].

    Only ``t`` is gathered; ``w`` is dropped (cheap to recompute per job,
    and gathering it J-fold would double the artifact's footprint).
    """
    cl = closures_for(net, uniq, use_pallas=use_pallas)
    return Closures(w=None, t=cl.t[inv])


def build_closures_batch(net: ComputeNetwork, batch,
                         *, use_pallas: bool | None = None,
                         dedupe: tuple[jax.Array, jax.Array] | None = None,
                         ) -> Closures:
    """[J, Lmax+1, V, V] stacked :class:`Closures` for a job batch.

    Jobs sharing a data-size vector dedupe to a single closure computation:
    the [U, Lmax+1, V, V] unique stack is closed in one batched kernel call
    and gathered back to [J, ...].  ``dedupe`` takes a precomputed
    :func:`dedupe_data` result (it is queue-state independent, so round
    loops hoist it).  Counted as one build.
    """
    telemetry.count("closure_builds")
    uniq, inv = dedupe_data(batch) if dedupe is None else dedupe
    return _closures_gathered(net, uniq, inv, use_pallas=use_pallas)


def transfer_closure(net: ComputeNetwork, data_sizes: jax.Array,
                     *, use_pallas: bool | None = None) -> jax.Array:
    """[L+1, V, V] min-cost transfer tensor T_l = closure(w_l)."""
    return closures_for(net, data_sizes, use_pallas=use_pallas).t


def reconstruct_path(w: jax.Array, t: jax.Array, src: jax.Array, dst: jax.Array,
                     max_hops: int) -> jax.Array:
    """Explicit path from src to dst under edge weights w and closure t.

    Returns hops [max_hops, 2] int32 (u, v) pairs, padded with (-1, -1) once
    dst is reached. jit/vmap friendly (fixed max_hops).

    A fixed-length ``scan`` with ``unroll=4``: the fused solver walks every
    layer of every round on device (its commit charges the walked hops and
    emits them as ``plan.paths``), so per-step loop overhead — not the
    few-hop arithmetic — is the cost, and unrolling beats both the plain
    scan and a ``while_loop`` early exit (whose batched ``cond`` pays its
    own per-iteration carry).  Unrolling is contraction-safe here: the body is
    gathers, adds, and an argmin — no multiply feeding an add, so there is
    no FMA for LLVM to contract differently across unroll factors.
    Post-arrival steps emit exactly the (-1, -1) padding, so the output is
    bit-identical regardless of loop form.  Lint rule RL002
    (unsafe-unroll) admits ``unroll > 1`` only for contraction-free
    bodies like this one.
    """

    def step(state, _):
        cur, done = state
        # next hop minimizing edge + remaining distance; exclude the zero-cost
        # self-loop (diagonal) so ties never stall the walk
        cand = (w[cur] + t[:, dst]).at[cur].set(INF)
        nxt = jnp.argmin(cand).astype(jnp.int32)
        arrived = cur == dst
        dead = done | arrived
        hop = jnp.stack([jnp.where(dead, -1, cur), jnp.where(dead, -1, nxt)])
        return (jnp.where(dead, cur, nxt), dead), hop

    (_, _), hops = jax.lax.scan(
        step, (src.astype(jnp.int32), jnp.asarray(False)),
        None, length=max_hops, unroll=4)
    return hops
