"""Routing-integrated serving scheduler — the paper's technique, deployed.

A serving cluster (TPU slices + edge ingress points + interconnect) is
modeled as the paper's computing network: slice i becomes node i with
``mu_u`` = achievable FLOP/s, interconnect hops become links with ``mu_uv``
bytes/s, and the per-slice backlog of already-scheduled work is exactly the
queue vector Q the formulation charges waiting time against.

Since the time-aware state split the scheduler holds the two parts
explicitly: one immutable :class:`~repro.core.state.Topology` for the life
of the deployment and a :class:`~repro.core.state.QueueState` that evolves
— ``commit`` grows it, :meth:`RoutedScheduler.advance` drains it while the
clock runs.  Solvers see the zero-copy composed view ``topo.view(state)``;
nothing rebuilds arrays.

Two drain models are threaded through (``drain="fluid" | "exact"``):

  * ``"fluid"`` (default, bit-identical to the pre-ledger behaviour):
    every resource drains independently at full rate, q <- max(q - mu dt,
    0).  Fast, optimistic — it serves link bytes whose producing compute
    hasn't finished and node FLOPs out of priority order.
  * ``"exact"``: a :class:`~repro.core.completions.CommittedWork` ledger
    records every committed plan's work items (priority + precedence), and
    time passing drains *exactly those jobs* through the preempt-resume
    event loop the simulator uses.  The solver-visible ``QueueState`` is
    materialized from the ledger's residual work, so every bound is charged
    against committed work, not rate-capacity fluid.

``track_commits=True`` additionally keeps a never-drained commit *log* (a
second ledger) regardless of drain mode — the full-horizon ground-truth
replay record the fidelity benchmark compares both models against.

Every batch of inference requests is turned into InferenceJobs via the
architecture cost profiles (configs/<arch>.cost_profile) and placed through
the unified solver entry point (``solvers.solve`` — greedy by default, any
registered method by name): each request gets (a) the nodes computing each
layer range — i.e. a layer-wise model split when transfers are cheap
relative to queueing, or a single fast node when they are not — and (b) a
priority.  The solver's :class:`~repro.core.plan.Plan` is stored whole;
:class:`Placement` objects are per-job *views* over it, so the full plan
(including its queue state and provenance) can be serialized, shipped, or
re-planned without reassembling anything.

Straggler mitigation falls out of the formulation: a slow or overloaded
slice has a long queue (or degraded mu_u after ``report_slowdown``), so its
waiting term grows and new jobs route around it — tests/test_serving.py
asserts this end-to-end.  ``replan_last`` re-places the most recent batch
against the updated cluster health (incremental re-plan: the pre-batch
queue state is restored, the stored jobs re-solved, and the new plan
committed in place of the old one).
"""
from __future__ import annotations

import dataclasses
import itertools
import time

import numpy as np

from repro.core import (completions as C, jobs as J, network as N, solvers,
                        telemetry)
from repro.core.state import (QueueState, Topology, backlog_seconds,
                              effective_topology, host_backlog_seconds)
from repro.core.plan import Plan
from repro.configs import registry


@dataclasses.dataclass(frozen=True)
class Placement:
    """View over one job of a stored :class:`Plan`."""

    plan: Plan
    job: int                    # row in the plan
    job_name: str
    num_layers: int

    @property
    def priority(self) -> int:
        return int(self.plan.priority[self.job])

    @property
    def assign(self) -> np.ndarray:
        """[L] node per (real) layer."""
        return self.plan.job_assign(self.job, self.num_layers)

    @property
    def bound_s(self) -> float:
        """Completion-time upper bound."""
        return float(self.plan.bounds[self.job])

    @property
    def nodes_used(self) -> list[int]:
        seen = []
        for n in self.assign:
            if not seen or seen[-1] != n:
                seen.append(int(n))
        return seen


@dataclasses.dataclass
class Request:
    arch: str
    src: int
    dst: int
    seq_len: int = 2048
    batch: int = 1
    name: str = ""


def requests_to_jobs(requests: list[Request]) -> list[J.InferenceJob]:
    """Cost-profile each request into an :class:`InferenceJob`."""
    infer_jobs = []
    for i, r in enumerate(requests):
        comp, data = registry.cost_profile(r.arch, seq_len=r.seq_len,
                                           batch=r.batch)
        infer_jobs.append(J.InferenceJob(
            r.name or f"req{i}", r.src, r.dst,
            comp.astype(np.float32), data.astype(np.float32)))
    return infer_jobs


class RoutedScheduler:
    drain_queues: bool = True  # OnlineScheduler's no-drain baseline flips this

    def __init__(self, net: N.ComputeNetwork | Topology, *,
                 method: str = "greedy", drain: str = "fluid",
                 track_commits: bool = False, sim_engine: str = "indexed",
                 **solver_opts):
        if isinstance(net, Topology):
            self.topology = net
            self.state = net.empty_state()
        else:
            self.topology = net.topology
            self.state = net.state
        if drain not in ("fluid", "exact"):
            raise ValueError(
                f"drain must be 'fluid' or 'exact', got {drain!r}")
        if sim_engine not in ("indexed", "ref"):
            raise ValueError(
                f"sim_engine must be 'indexed' or 'ref', got {sim_engine!r}")
        self.method = method
        # Exact-drain event engine: "indexed" (persistent O(log)-per-event
        # index threaded through drains/commits/replans) or "ref" (the seed
        # linear-scan loop — benchmarks/drain_bench.py races the two).
        self.sim_engine = sim_engine
        self.solver_opts = solver_opts
        # Authoritative clock, host-side float64: ``state.clock`` (f32, so it
        # loses sub-second ticks past ~2^24 s if accumulated) is only ever
        # *stamped* from this, never summed.
        self._now = float(np.asarray(self.state.clock))
        self._slowdown = np.ones((self.topology.num_nodes,), np.float32)
        # Availability masks (the fault layer's state): failed nodes lose
        # compute *and* every incident link; links can also fail alone.
        self._avail_node = np.ones((self.topology.num_nodes,), bool)
        self._link_up = np.ones((self.topology.num_nodes,) * 2, bool)
        # The health-scaled topology, built on first use after a health
        # event, and float64 host copies of its rates: the solver, the
        # drain and the backlog all read the device's own bits.
        self._eff: Topology | None = None
        self._eff_rates: tuple[np.ndarray, np.ndarray] | None = None
        # Exact mode: float32 host copies of the ledger queues last
        # uploaded into ``self.state`` (None while they are not current).
        self._queues: tuple[np.ndarray, np.ndarray] | None = None
        self.drain_mode = drain
        # Live registry of committed InferenceJobs (exact mode): the fault
        # policies reconstruct residual jobs from it when a resource fails.
        self.inflight_jobs: dict[str, J.InferenceJob] = {}
        # Exact mode: the committed-work ledger is the source of truth for
        # backlogs; the solver-visible QueueState is materialized from it.
        self.ledger: C.CommittedWork | None = (
            C.CommittedWork.empty(self.topology.num_nodes, clock=self._now)
            if drain == "exact" else None)
        # Optional never-drained commit log (ground-truth replay record).
        self.commit_log: C.CommittedWork | None = (
            C.CommittedWork.empty(self.topology.num_nodes, clock=self._now)
            if track_commits else None)
        # (batch, jobs, pre-batch state, health + clock + ledgers at snapshot)
        self._last: tuple[J.JobBatch, list[J.InferenceJob], QueueState,
                          Topology, float, C.CommittedWork | None,
                          C.CommittedWork | None] | None = None
        self.last_plan: Plan | None = None
        # Why the most recent replan_last() call did / did not commit:
        # None (never called) | "replanned" | "no_batch" | "no_improvement".
        self.last_replan_reason: str | None = None
        # Solver wall-time telemetry: per-call and cumulative.  The
        # streaming pipeline's "measured" latency model reads these to put
        # real solve latency on the simulated clock.
        self.last_solve_s: float = 0.0
        self.total_solve_s: float = 0.0

    # -- cluster health / time ---------------------------------------------
    def _check_slowdown(self, node: int, factor: float) -> float:
        """Validate a slowdown event's arguments (the "factor=2 means half
        speed" convention): the factor must be finite and > 0, since the
        effective topology divides by it, and the node in range."""
        factor = float(factor)
        if not np.isfinite(factor) or factor <= 0:
            raise ValueError(
                f"slowdown factor must be finite and > 0 (factor=2 means "
                f"half speed, factor=1 restores full health), got {factor}")
        self._check_node(node)
        return factor

    def report_slowdown(self, node: int, factor: float) -> None:
        """Straggling slice: effective mu_u /= factor from now on.

        ``factor`` follows the "factor=2 means half speed" convention: the
        node's effective capacity becomes mu_u / factor (it serves *and
        drains* slower), ``factor=1`` restores full health.  Raises
        ``ValueError`` for factor <= 0 or non-finite factors, and for a
        node outside the topology.  When a commit log is kept the event is
        recorded there too, so ``replay_piecewise`` can reconstruct the
        true segment-by-segment health history.
        """
        self._slowdown[node] = self._check_slowdown(node, factor)
        self._eff = None
        if self.commit_log is not None:
            self.commit_log = self.commit_log.record_slowdown(
                self._now, node, self._slowdown[node])

    def report_recovery(self, node: int) -> None:
        """Straggler cleared: restore the node's effective rate to full
        health — the inverse of :meth:`report_slowdown`, i.e. factor back
        to 1.0.  Raises ``ValueError`` for a node outside the topology.
        Recorded in the commit log's health history (when kept), so
        ``replay_piecewise`` sees the recovery window instead of treating
        the last reported slowdown as permanent.
        """
        self.report_slowdown(self._check_node(node), 1.0)

    def _check_node(self, node: int) -> int:
        node = int(node)
        if not (0 <= node < self.topology.num_nodes):
            raise ValueError(f"node {node} out of range "
                             f"[0, {self.topology.num_nodes})")
        return node

    @property
    def degraded(self) -> bool:
        """Any node or link currently failed?"""
        return not (self._avail_node.all() and self._link_up.all())

    def set_node_availability(self, node: int, up: bool) -> None:
        """Infrastructure event: the node (and implicitly every incident
        link — a dead node cannot relay) fails or recovers from now on.

        Recovery restores *full* health: the node's slowdown factor resets
        to 1.0 (rejoining capacity is assumed re-provisioned, and a
        recovery record of the stale factor would misstate the replay).
        Recorded in the commit log's health history as ``factor=inf``
        (down) / ``1.0`` (up), the encoding ``replay_piecewise`` consumes.
        """
        node = self._check_node(node)
        self._avail_node[node] = bool(up)
        if up:
            self._slowdown[node] = 1.0
        self._eff = None
        if self.commit_log is not None:
            self.commit_log = self.commit_log.record_health(
                self._now, node, 1.0 if up else np.inf)

    def set_link_availability(self, u: int, v: int, up: bool) -> None:
        """Infrastructure event on one *directed* link (u -> v); callers
        modeling a bidirectional cut flip both directions.  Raises for a
        link that does not exist in the base topology (mu_uv == 0) — its
        failure could never matter, so reporting one is a caller bug.
        """
        u, v = self._check_node(u), self._check_node(v)
        if float(np.asarray(self.topology.mu_link)[u, v]) <= 0:
            raise ValueError(
                f"link ({u}, {v}) does not exist in the topology "
                f"(mu_link[{u}, {v}] == 0); availability events apply "
                f"to real links only")
        self._link_up[u, v] = bool(up)
        self._eff = None
        if self.commit_log is not None:
            self.commit_log = self.commit_log.record_health(
                self._now, ("link", u, v), 1.0 if up else np.inf)

    def _down_keys(self) -> tuple:
        """Engine-facing resource keys currently failed (() when healthy)."""
        if not self.degraded:
            return ()
        return C.down_keys(self.topology, self._avail_node, self._link_up)

    def _drain_state(self, dt: float) -> None:
        """Advance backlogs ``dt`` seconds at effective (health-aware) rates
        under the configured drain model.  Does not move the clock."""
        eff = self._effective_topology()
        if self.drain_mode == "exact":
            self.ledger = C.drain_exact(eff, self.ledger, dt,
                                        engine=self.sim_engine,
                                        down=self._down_keys(),
                                        rates=self._eff_rates)
            self._sync_ledger_queues()
        else:
            self.state = self.state.advance(eff, dt)

    def _sync_ledger_queues(self) -> None:
        """Materialize the ledger's residual work into the QueueState."""
        self._queues = self.ledger.queue_arrays()
        self.state = self.state.with_queues(
            *telemetry.to_device(self._queues))

    def advance(self, dt: float) -> None:
        """Let ``dt`` seconds pass: the backlog drains at effective rates
        (fluid or exact per ``drain_mode``) and the clock moves forward."""
        if dt < 0:
            raise ValueError(f"dt must be >= 0, got {dt}")
        self._drain_state(dt)
        self._now += float(dt)
        self._stamp_clock()

    def _stamp_clock(self) -> None:
        self.state = dataclasses.replace(
            self.state, clock=telemetry.to_device(np.float32(self._now)))

    @property
    def clock(self) -> float:
        return self._now

    def drain(self) -> None:
        """All scheduled work finished: reset queues (clock preserved).

        In exact mode the ledger's live jobs are dropped without recording
        completions; ``commit_log`` (a pure record of what was committed)
        is left untouched.
        """
        import jax.numpy as jnp
        self.state = self.state.with_queues(
            jnp.zeros_like(self.state.q_node),
            jnp.zeros_like(self.state.q_link))
        if self.ledger is not None:
            self.ledger = self.ledger.cleared()
        self._queues = None
        self._last = None
        self.last_plan = None

    @telemetry.spanned("sched.topology")
    def _effective_topology(self) -> Topology:
        """The health-scaled topology, rebuilt only after a health event
        (``report_slowdown``, ``set_node_availability``,
        ``set_link_availability``), each counted as ``topology_builds``."""
        if self._eff is None:
            telemetry.count("topology_builds")
            if not self.degraded:
                # bit-identical to the pre-fault expression (and rates)
                eff = effective_topology(self.topology, self._slowdown)
            else:
                eff = effective_topology(self.topology, self._slowdown,
                                         self._avail_node, self._link_up)
            self._eff_rates = C.host_rates(eff)
            self._eff = eff
        return self._eff

    @telemetry.spanned("sched.backlog")
    def _backlog(self, state: QueueState | None = None,
                 queues: tuple[np.ndarray, np.ndarray] | None = None
                 ) -> float:
        """``backlog_seconds`` of ``state`` (default: the live state and
        its ledger queues) at the effective rates.  Computed from the host
        copies where the queues have one, else fetched from the device."""
        eff = self._effective_topology()
        if state is None:
            state, queues = self.state, self._queues
        if queues is None:
            return backlog_seconds(eff, state)
        return host_backlog_seconds(*self._eff_rates, *queues)

    # -- placement ----------------------------------------------------------
    def _placements(self, plan: Plan,
                    infer_jobs: list[J.InferenceJob]) -> list[Placement]:
        # Walk priority slots directly, so the list is born sorted.
        out = [Placement(plan=plan, job=int(j),
                         job_name=infer_jobs[j].name,
                         num_layers=infer_jobs[j].num_layers)
               for j in plan.order]
        assert [p.priority for p in out] == list(range(len(out)))
        return out

    # Solvers that can fill plan.paths during the solve, reusing each
    # round's closures (greedy.greedy_route(extract_paths=True)).  For any
    # other method _commit_plan falls back to a full replay_solution.
    _PATH_SOLVERS = ("greedy", "greedy_ref", "lazy")

    def _solve_opts(self, method: str) -> dict:
        """Solver options, asking for paths where a ledger will need them."""
        if ((self.ledger is not None or self.commit_log is not None)
                and method in self._PATH_SOLVERS):
            return {"extract_paths": True, **self.solver_opts}
        return self.solver_opts

    @telemetry.spanned("sched.commit")
    def _commit_plan(self, topo: Topology, batch: J.JobBatch, plan: Plan,
                     pre_state: QueueState,
                     names: list[str] | None) -> Plan:
        """Commit one solved plan: queue state, ledger/commit-log, telemetry.

        Every commit goes through here, one window at a time, from
        :meth:`_commit_windows` (``pre_state`` = the queue state that
        window was solved against) or from :meth:`replan_last`.
        """
        logged = self.ledger is not None or self.commit_log is not None
        if plan.net is None or (logged and plan.paths is None):
            # The exact solver reports no queue state, and only the
            # _PATH_SOLVERS fill paths: one replay against the solve-time
            # queues gives both, the hops the plan's bounds charged
            # (Alg. 1 / Alg. 2 semantics).
            from repro.core import schedule
            _, paths, net = schedule.replay_solution(
                topo.view(pre_state), batch, plan.assign, plan.order)
            plan = dataclasses.replace(
                plan, net=net if plan.net is None else plan.net,
                paths=paths if logged else plan.paths)
        if self.ledger is None:
            # Committed backlogs come from the plan; the clock is ours to
            # keep.  (In exact mode the ledger sync below is authoritative,
            # so the fluid commit would be a dead store.)
            self.state = self.state.with_queues(plan.net.q_node,
                                                plan.net.q_link)
        if logged:
            self._ledger_commit(topo, batch, plan, names)
        self.last_plan = plan
        # Fused multi-window plans carry the shared dispatch's wall in
        # solve_s and their per-window share in solve_share_s; accumulate
        # the share so total_solve_s sums to real wall, not W * wall.
        self.last_solve_s = float(plan.meta.get(
            "solve_share_s", plan.meta.get("solve_s", 0.0)))
        self.total_solve_s += self.last_solve_s
        return plan

    def _ledger_commit(self, topo: Topology, batch: J.JobBatch, plan: Plan,
                       names: list[str] | None) -> None:
        """Record the committed plan's work items (exact ledger and/or the
        ground-truth commit log); ``plan`` carries its paths."""
        if self.ledger is not None:
            self.ledger = self.ledger.commit(batch, plan, names=names,
                                             at=self._now)
            if self.sim_engine == "indexed":
                # First commit births the persistent index; later commits
                # extend it in place inside CommittedWork.commit.
                self.ledger = C.warm_engine(topo, self.ledger)
            # Ledger is the source of truth in exact mode: rounding of the
            # committed queues must match what later drains will report.
            self._sync_ledger_queues()
        if self.commit_log is not None:
            self.commit_log = self.commit_log.commit(batch, plan,
                                                     names=names,
                                                     at=self._now)

    def _solve(self, windows: list[list[J.InferenceJob]], *,
               pad_to: int | None, method: str
               ) -> tuple[list[J.JobBatch], list[Plan]]:
        """Pure solve of W >= 1 windows, each against the previous one's
        committed queues: one window through ``solvers.solve`` (the
        registry is read at call time), several in one fused dispatch.
        The batches stay on the host for the whole decision: the solver
        uploads what it runs, and the ledger reads them with no fetch."""
        with telemetry.span("sched.batch"):
            batches = [J.batch_jobs_host(jobs, pad_to=pad_to)
                       for jobs in windows]
        opts, topo = self._solve_opts(method), self._effective_topology()
        if len(batches) == 1:
            return batches, [solvers.solve(topo, batches[0], method=method,
                                           state=self.state, **opts)]
        return batches, solvers.solve_fused(topo, batches, state=self.state,
                                            pad_to=pad_to, **opts)

    def _commit_windows(self, windows: list[list[J.InferenceJob]],
                        batches: list[J.JobBatch], plans: list[Plan]
                        ) -> tuple[list[list[Placement]], list[tuple]]:
        """The one commit loop: commit solved windows in order.  Returns
        the per-window placements and post-commit ``(state, host queues)``
        — what W one-window commits would leave, read by the backlog."""
        topo = self._effective_topology()
        out, states = [], []
        for jobs, batch, plan in zip(windows, batches, plans):
            pre_state, pre_ledger, pre_log = (self.state, self.ledger,
                                              self.commit_log)
            plan = self._commit_plan(topo, batch, plan, pre_state,
                                     [j.name for j in jobs])
            # Record only after the commit succeeds, so a raising solver
            # can't poison replan_last() with a batch never scheduled.
            self._last = (batch, jobs, pre_state, topo, self._now,
                          pre_ledger, pre_log)
            if self.ledger is not None:
                # Fault policies rebuild residual jobs from this registry;
                # prune lazily once dead entries dominate (mirrors the
                # engine cache's bloat rule — amortized O(1) per job).
                self.inflight_jobs.update((j.name, j) for j in jobs)
                n = len(self.inflight_jobs)
                if n >= 2048 and n > 2 * len(self.ledger.jobs):
                    live = {j.name for j in self.ledger.jobs}
                    self.inflight_jobs = {k: j for k, j in
                                          self.inflight_jobs.items()
                                          if k in live}
            out.append(self._placements(plan, jobs))
            states.append((self.state, self._queues))
        return out, states

    def presolve(self, infer_jobs: list[J.InferenceJob],
                 *, pad_to: int | None = None,
                 method: str | None = None) -> tuple[J.JobBatch, Plan]:
        """Pure candidate solve against the current state: no commit, no
        queue/ledger/telemetry mutation.  The admission controller scores
        the returned plan with ``completions.predict_completions`` before
        deciding whether to commit it (:meth:`commit_presolved`)."""
        (batch,), (plan,) = self._solve(
            [infer_jobs], pad_to=pad_to,
            method=self.method if method is None else method)
        return batch, plan

    def commit_presolved(self, infer_jobs: list[J.InferenceJob],
                         batch: J.JobBatch, plan: Plan) -> list[Placement]:
        """Commit a plan solved by :meth:`presolve` against the *unchanged*
        current state."""
        return self._commit_windows([infer_jobs], [batch], [plan])[0][0]

    def schedule_jobs(self, infer_jobs: list[J.InferenceJob],
                      *, pad_to: int | None = None,
                      method: str | None = None) -> list[Placement]:
        """Place one window: :meth:`schedule_windows` with W = 1."""
        return self.schedule_windows([infer_jobs], pad_to=pad_to,
                                     method=method)[0][0]

    def schedule_windows(self, windows: list[list[J.InferenceJob]],
                         *, pad_to: int | None = None,
                         method: str | None = None
                         ) -> tuple[list[list[Placement]], list[tuple]]:
        """Place W queued arrival windows in order, each against the
        previous one's committed queues; returns the per-window placements
        and post-commit ``(state, host queues)``.

        Several windows under the fused greedy share **one** dispatch;
        any other method has no multi-window program and places them one
        after another (same results, W dispatches).  ``method`` overrides
        the configured solver for this call only — the fault layer's
        migrate policy re-places residual jobs with ``"migrate"``.
        """
        method = self.method if method is None else method
        if not windows:
            return [], []
        if len(windows) > 1 and method != "greedy":
            per = [self.schedule_windows([jobs], pad_to=pad_to, method=method)
                   for jobs in windows]
            return [o for (o,), _ in per], [st for _, (st,) in per]
        return self._commit_windows(
            windows, *self._solve(windows, pad_to=pad_to, method=method))

    def withdraw(self, names, *, at: float) -> None:
        """Take live committed jobs back at ``at`` (``drain="exact"``):
        served work stays served, the commit log (if kept) records the
        removal, the queues are re-materialized from the ledger, and the
        replan snapshot goes — its rollback would resurrect the jobs."""
        names = list(names)
        self.ledger = self.ledger.remove_jobs(names, at=at)
        if self.commit_log is not None:
            self.commit_log = self.commit_log.record_removal(at, names)
        self._sync_ledger_queues()
        self._last = None

    def warmup(self, sample_jobs: list[J.InferenceJob],
               *, pad_to: int | None = None, max_jobs: int | None = None,
               window_counts: tuple[int, ...] = ()) -> dict:
        """Pre-compile the fused solve at this deployment's serving shapes.

        Runs throwaway solves (pure — no queue state, ledger, clock, or
        telemetry mutation) so that steady-state arrivals never pay a jit
        compile wall: one per power-of-two job-count bucket up to
        ``max_jobs`` (default: ``len(sample_jobs)``), plus one fused
        multi-window program per entry of ``window_counts``.  The
        streaming pipeline's "measured" latency model assumes warmed
        shapes; re-compiles that still slip through (an unseen model mix,
        a new window count) are flagged by ``meta["jit_compiled"]`` and
        excluded from its EMA.  Returns ``{"compiles": n, "wall_s": w,
        "warm_solve_s": s}`` — ``warm_solve_s`` times one *post-compile*
        solve at the largest warmed size, the seed the pipeline's
        "measured" latency EMA starts from (stream.py's cold-start fix:
        before the first real observation the model returned 0.0, so the
        first window's admission predictions were systematically
        optimistic).
        """
        if self.method != "greedy" or not sample_jobs:
            return {"compiles": 0, "wall_s": 0.0, "warm_solve_s": 0.0}
        t0 = time.perf_counter()
        topo = self._effective_topology()
        opts = self._solve_opts(self.method)
        top = max_jobs if max_jobs is not None else len(sample_jobs)
        sizes, s = [], 1
        while s < top:
            sizes.append(s)
            s *= 2
        sizes.append(s)
        cyc = list(itertools.islice(itertools.cycle(sample_jobs), sizes[-1]))
        compiles = 0
        for size in sizes:
            plan = solvers.solve(topo,
                                 J.batch_jobs_host(cyc[:size], pad_to=pad_to),
                                 method=self.method, state=self.state, **opts)
            compiles += int(plan.meta.get("jit_compiled", False))
        for w in window_counts:
            if w < 2:
                continue
            batches = [J.batch_jobs_host(cyc[: sizes[-1]], pad_to=pad_to)
                       for _ in range(w)]
            plans = solvers.solve_fused(topo, batches, state=self.state,
                                        pad_to=pad_to, **opts)
            compiles += int(plans[0].meta.get("jit_compiled", False))
        wall = time.perf_counter() - t0
        # One more solve at the largest (already-compiled) size: a clean
        # compile-excluded wall measurement for the latency-model seed,
        # taken again if an unseen shape slipped in.
        for _ in range(2):
            t1 = time.perf_counter()
            plan = solvers.solve(topo, J.batch_jobs_host(cyc, pad_to=pad_to),
                                 method=self.method, state=self.state, **opts)
            warm = time.perf_counter() - t1
            if not plan.meta.get("jit_compiled", False):
                break
        return {"compiles": compiles, "wall_s": wall + warm,
                "warm_solve_s": warm}

    def replan_last(self, *, min_improvement: float | None = None
                    ) -> list[Placement] | None:
        """Re-place the most recent batch against updated cluster health.

        Rolls the queue state back to just before that batch was committed,
        re-solves with the current slowdown factors, and commits the new
        plan — incremental re-planning after ``report_slowdown`` without the
        caller resubmitting requests.  Returns None if nothing to re-plan;
        :attr:`last_replan_reason` records why (``no_batch`` — nothing was
        scheduled, or ``no_improvement``) so monitor decisions are
        auditable.

        ``min_improvement`` (default None = always commit, the manual-call
        semantics) gates the commit on the re-solve actually helping: the
        old assignment is re-scored under *current* health and the
        rolled-back queues, and the new plan commits only if its worst
        bound beats that by the given relative margin (0.0 = any strict
        improvement).  On decline nothing is mutated — the auto-replan
        monitor uses this so hysteresis never pays for a no-op re-commit.
        """
        self.last_replan_reason = "no_batch"
        if self._last is None:
            return None
        import jax.numpy as jnp
        (batch, infer_jobs, pre_state, pre_topo, pre_now,
         pre_ledger, pre_log) = self._last
        # Pre-batch backlogs, drained over the time elapsed since they were
        # captured (work that was genuinely served must not resurrect) at the
        # *snapshot-time* health — the rates that actually applied until the
        # event that triggered this replan (exact for the canonical
        # report_slowdown-then-replan flow; piecewise health histories are
        # approximated by their first segment).  The clock never rolls back.
        # Everything is computed locally first: a declined replan (the
        # min_improvement gate) must leave the scheduler untouched.
        elapsed = self._now - pre_now
        ledger = queues = None
        if self.drain_mode == "exact":
            ledger = pre_ledger
            if elapsed > 0 and self.drain_queues:
                # The snapshot's engine slot went stale the moment the live
                # chain drained past it, so this rollback drain rebuilds the
                # index lazily from the snapshot's immutable job records.
                ledger = C.drain_exact(pre_topo, ledger, elapsed,
                                       engine=self.sim_engine)
            queues = ledger.queue_arrays()
            state = pre_state.with_queues(*map(jnp.asarray, queues))
        else:
            state = pre_state
            if elapsed > 0 and self.drain_queues:
                state = state.advance(pre_topo, elapsed)
        state = dataclasses.replace(state, clock=jnp.float32(self._now))
        # Candidate re-solve at current health against the rolled-back
        # queues (pure — nothing committed yet).
        topo = self._effective_topology()
        plan = solvers.solve(topo, batch, method=self.method, state=state,
                             **self._solve_opts(self.method))
        if min_improvement is not None:
            from repro.core import schedule
            old = self.last_plan
            new_cost = float(np.asarray(plan.bounds, np.float64).max())
            if old is None:
                improved = True
            else:
                old_bounds, _, _ = schedule.replay_solution(
                    topo.view(state), batch, old.assign, old.order)
                old_cost = float(old_bounds.max())
                improved = (new_cost < old_cost * (1.0 - min_improvement)
                            - schedule.time_eps(old_cost))
            if not improved:
                self.last_replan_reason = "no_improvement"
                return None
        # Committing: apply the rollback, then the new plan.
        self.ledger = ledger if self.drain_mode == "exact" else self.ledger
        self.state, self._queues = state, queues
        # The superseded batch never ran to completion: drop it from the
        # ground-truth record too (same approximation as the state rollback)
        # — but keep the full health history, which rollback cannot undo.
        if pre_log is not None and self.commit_log is not None:
            pre_log = dataclasses.replace(pre_log,
                                          health=self.commit_log.health)
        self.commit_log = pre_log
        plan = self._commit_plan(topo, batch, plan, self.state,
                                 [j.name for j in infer_jobs])
        self.last_replan_reason = "replanned"
        return self._placements(plan, infer_jobs)
