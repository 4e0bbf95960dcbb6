"""Time-aware network state: immutable :class:`Topology` + fluid :class:`QueueState`.

The paper charges waiting time against queue backlogs Q but says nothing
about *time passing*: a one-shot batch evaluation only ever adds to the
queues.  For online serving the state must also **drain** — every resource
works through its backlog at its service rate mu while the clock runs.
This module is the split the rest of the stack builds on:

  * :class:`Topology` — what the network *is*: compute capacities
    ``mu_node`` [V] and link capacities ``mu_link`` [V, V].  Immutable for
    the lifetime of a deployment (straggler events scale a *view* of it,
    never mutate it).
  * :class:`QueueState` — what the network is *doing*: backlogs ``q_node``
    [V] / ``q_link`` [V, V] plus a scalar ``clock``.  :func:`advance`
    implements the fluid drain  q <- max(q - mu * dt, 0),  clock <- clock
    + dt: each resource serves its backlog at full rate (work-conserving,
    the same service model the fictitious bound charges waiting against).

Both are registered JAX pytrees, so jitted paths take them explicitly and a
:class:`~repro.core.network.ComputeNetwork` is just the zero-copy composed
view ``topology.view(state)`` — no arrays are rebuilt anywhere.

The fluid drain is exact for the bound's purposes: the waiting term
Q_u / mu_u of a backlog drained for dt seconds is exactly ``max(Q_u -
mu_u * dt, 0) / mu_u`` — the residual wait a new arrival at ``clock + dt``
would experience.  It also composes: ``advance(s, a).advance(b) ==
advance(s, a + b)`` (clipping at zero commutes with further draining),
which the property tests assert.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from . import telemetry


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Topology:
    """Immutable capacities of the physical network (a JAX pytree)."""

    mu_node: jax.Array  # [V] FLOP/s (0 = no compute resources at node)
    mu_link: jax.Array  # [V, V] bytes/s (0 = no link)

    @property
    def num_nodes(self) -> int:
        return self.mu_node.shape[0]

    def empty_state(self, clock: float = 0.0) -> "QueueState":
        """All-zero backlogs at the given clock."""
        return QueueState(
            q_node=jnp.zeros_like(self.mu_node),
            q_link=jnp.zeros_like(self.mu_link),
            clock=jnp.float32(clock),
        )

    def view(self, state: "QueueState | None" = None):
        """Compose with a queue state into a :class:`ComputeNetwork` view."""
        from .network import ComputeNetwork
        return ComputeNetwork(topology=self,
                              state=self.empty_state() if state is None
                              else state)


def effective_topology(topo: Topology, slowdown,
                       avail_node=None, link_up=None) -> Topology:
    """Health-scaled *view* of a topology: the one rate computation shared
    by the online scheduler's drains/solves and the piecewise ground-truth
    replay, so both always see bit-identical effective rates.

    ``slowdown`` [V] follows the "factor=2 means half speed" convention
    (float32 in both callers).  ``avail_node`` [V] bool zeroes failed
    nodes' compute *and* every incident link (a dead node cannot relay);
    ``link_up`` [V, V] bool zeroes individually failed directed links.
    With both masks omitted this is exactly ``mu_node * (1 / slowdown)``
    — the pre-fault expression, preserved bit-for-bit.
    """
    if avail_node is None and link_up is None:
        return Topology(mu_node=_scale_by_inverse(
            topo.mu_node, telemetry.to_device(np.asarray(slowdown))),
            mu_link=topo.mu_link)
    avail = (np.ones((topo.num_nodes,), bool) if avail_node is None
             else np.asarray(avail_node, bool))
    scale = jnp.where(jnp.asarray(avail),
                      1.0 / jnp.asarray(slowdown), 0.0)
    mask = avail[:, None] & avail[None, :]
    if link_up is not None:
        mask = mask & np.asarray(link_up, bool)
    return Topology(mu_node=topo.mu_node * scale,
                    mu_link=topo.mu_link * jnp.asarray(mask,
                                                       topo.mu_link.dtype))


@jax.jit
def _scale_by_inverse(mu_node: jax.Array, slowdown: jax.Array) -> jax.Array:
    """``mu_node * (1 / slowdown)`` in one program: jitted so the constant
    is baked at trace time (the eager form staged it per call).  No
    multiply feeds an add, so there is nothing to contract, and it matches
    the two eager ops bit for bit (``tests/test_state.py``)."""
    return mu_node * (1.0 / slowdown)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QueueState:
    """Backlogs + clock: the only mutable part of the network (a pytree)."""

    q_node: jax.Array  # [V] FLOPs queued
    q_link: jax.Array  # [V, V] bytes queued
    clock: jax.Array   # scalar f32 seconds

    def advance(self, topo: Topology, dt) -> "QueueState":
        """Fluid drain for ``dt`` seconds (see :func:`advance`)."""
        return advance(topo, self, dt)

    def with_queues(self, q_node: jax.Array, q_link: jax.Array) -> "QueueState":
        """Same clock, new backlogs."""
        return dataclasses.replace(self, q_node=q_node, q_link=q_link)


@jax.jit
def advance(topo: Topology, state: QueueState, dt) -> QueueState:
    """Drain every resource at its service rate for ``dt`` seconds.

    q <- max(q - mu * dt, 0) on nodes and links; clock <- clock + dt.
    Resources with mu == 0 hold no backlog by construction and stay at 0.

    ``clock`` is float32 (a pytree leaf under 32-bit JAX), so *accumulating*
    it here loses sub-second ticks once it exceeds ~2^24 s; long-lived
    drivers (the serving schedulers) keep an authoritative float64 clock
    host-side and stamp ``state.clock`` from it instead of summing.
    """
    dt = jnp.asarray(dt, jnp.float32)
    return QueueState(
        # repro-lint: disable=RL001 -- fluid drain IS q - mu*dt; sim state,
        q_node=jnp.maximum(state.q_node - topo.mu_node * dt, 0.0),
        # repro-lint: disable=RL001 -- not the parity-gated solver closures
        q_link=jnp.maximum(state.q_link - topo.mu_link * dt, 0.0),
        # repro-lint: disable=RL005 -- single-step add; drivers re-stamp f64
        clock=state.clock + dt,
    )


def backlog_seconds(topo: Topology, state: QueueState) -> float:
    """Worst-resource residual wait: max over nodes/links of Q / mu (host).

    This is the quantity a new top-priority arrival would wait behind at the
    most backed-up resource — the scalar the online benchmarks and the
    stability tests track over time.  Fetches the four arrays in one wait;
    :func:`host_backlog_seconds` is the same formula over host copies.
    """
    return host_backlog_seconds(*telemetry.to_host(
        (topo.mu_node, topo.mu_link, state.q_node, state.q_link)))


def host_backlog_seconds(mu_node, mu_link, q_node, q_link) -> float:
    """:func:`backlog_seconds` from host arrays, computed in float64."""
    mu_n, mu_l, q_n, q_l = (np.asarray(x, np.float64)
                            for x in (mu_node, mu_link, q_node, q_link))
    node_wait = np.where(mu_n > 0, q_n / np.maximum(mu_n, 1e-30), 0.0)
    link_wait = np.where(mu_l > 0, q_l / np.maximum(mu_l, 1e-30), 0.0)
    return float(max(node_wait.max(initial=0.0), link_wait.max(initial=0.0)))


def total_backlog(state: QueueState) -> tuple[float, float]:
    """(sum of node backlogs in FLOPs, sum of link backlogs in bytes)."""
    return (float(np.asarray(state.q_node, np.float64).sum()),
            float(np.asarray(state.q_link, np.float64).sum()))
