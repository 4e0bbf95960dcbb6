"""The *actual system*: an event-driven, preemptive-priority simulator.

The routing formulation minimizes an upper bound on completion time (the
fictitious system of §III-B).  This module measures what actually happens
when the routed jobs run: every resource (compute node, directed link)
serves the highest-priority arrived task, preempting lower-priority work on
arrival (preempt-resume, work-conserving) — exactly the paper's scheduling
model.  Tests assert bound >= simulated completion on every instance.

``replay_solution`` reconstructs, for any (assignment, priority) solution —
raw arrays or a :class:`~repro.core.plan.Plan` — the per-job fictitious
bounds, the explicit per-layer transfer paths (chosen against the queue
state seen at that job's priority level, as both Alg. 1 and Alg. 2 do), and
the final queue state.  ``Plan.replay``/``Plan.simulate`` are the
plan-first entry points.

The inner event loop is shared machinery: :func:`run_event_loop` advances a
set of :class:`TaskRun` records (per-job stage pointers + residual work)
from ``t`` to ``t_end`` under preempt-resume priority service.  One-shot
:func:`simulate` runs it to completion from time 0; the committed-work
ledger (:mod:`repro.core.completions`) runs it *incrementally* — a ``dt``
window at a time between online arrivals — which is what makes the exact
queue drain a first-class alternative to the fluid model.

Two interchangeable engines implement the loop's semantics:

  * ``engine="ref"`` — :func:`run_event_loop_ref`, the seed's linear-scan
    loop: every event rescans every task to rebuild the per-resource
    serving heads (O(events x tasks)).  It is the semantic reference the
    indexed engine is gated against, and stays the default for the
    one-shot :func:`simulate` so its results are unchanged bit-for-bit.
  * ``engine="indexed"`` — :mod:`repro.core.eventsim`, a priority-indexed
    event engine (per-resource heaps, a global event heap, virtual-time
    residuals) that costs O(log) per event and persists across drain
    windows.  The serving hot path (:mod:`repro.core.completions`) runs on
    it; ``benchmarks/drain_bench.py`` measures the speedup and gates
    parity.

Event-time comparisons share one tolerance discipline: :func:`time_eps`
(relative to the clock — an absolute epsilon like ``t + 1e-18`` silently
degrades to exact comparison once ``t`` exceeds ~1e-2 in float64) and
:func:`work_eps` (relative to a stage's work) are used by both engines and
by :func:`repro.core.completions.exact_backlog_trace`.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .network import ComputeNetwork
from . import jobs as J
from .jobs import JobBatch
from . import routing


@dataclasses.dataclass(frozen=True)
class SimResult:
    completion: np.ndarray  # [J] actual completion time of each job
    makespan: float


def _as_assign_order(assign, order):
    """Accept either (assign, order) arrays or a Plan in the first slot."""
    from .plan import Plan
    if isinstance(assign, Plan):
        if order is not None:
            raise ValueError("pass either a Plan or (assign, order), not both")
        return assign.assign, assign.order
    if order is None:
        raise ValueError("order is required when assign is an array")
    return assign, order


def replay_solution(net: ComputeNetwork, batch: JobBatch, assign, order=None):
    """Replay jobs in priority order, committing loads; return bounds+paths.

    Each priority step builds the job's closure stack once
    (``shortest_path.build_closures``) and shares it across the bound
    evaluation, the path extraction, and the queue commit (3 closure builds
    per job in the seed -> 1).
    """
    import jax.numpy as jnp

    from . import shortest_path as SP

    assign, order = _as_assign_order(assign, order)
    assign = jnp.asarray(assign, jnp.int32)
    batch = J.device_batch(batch)
    n = batch.num_jobs
    bounds = np.zeros((n,), np.float64)
    paths: dict[int, list[list[tuple[int, int]]]] = {}
    cur = net
    for p in range(n):
        j = int(order[p])
        args = (batch.comp[j], batch.data[j], batch.src[j], batch.dst[j],
                batch.num_layers[j])
        cl = SP.build_closures(cur, batch.data[j])
        bounds[j] = float(routing.cost_given_assignment(cur, *args, assign[j],
                                                        closures=cl))
        paths[j] = routing.extract_paths(cur, *args, assign[j], closures=cl)
        cur = routing.commit_assignment(cur, *args, assign[j], closures=cl)
    return bounds, paths, cur


# A work stage: (resource key, amount of work).  Resource keys are
# ("node", u) for compute (work in FLOPs) and ("link", u, v) for a directed
# transfer hop (work in bytes).
Stage = tuple[tuple, float]


def job_stages(batch: JobBatch, assign,
               paths: dict[int, list[list[tuple[int, int]]]]
               ) -> dict[int, list[Stage]]:
    """Per-job (resource, work) stage lists, in precedence order.

    Layer l's output transfer hops come before layer l+1's compute, which
    comes before layer l+1's output hops — so layer k's transfer cannot
    start (and its bytes cannot occupy a link) before layer k's compute
    completes.  This is the precedence structure both the one-shot
    simulator and the incremental committed-work drain honour.  Reads the
    host copy of a host batch (the serving scheduler's) and fetches a
    device batch once (``batch_fetches``).
    """
    host = J.host_batch(batch)
    comp = np.asarray(host.comp, np.float64)
    data = np.asarray(host.data, np.float64)
    nl = host.num_layers
    a = np.asarray(assign)
    stages: dict[int, list[Stage]] = {}
    for j in range(batch.num_jobs):
        L = int(nl[j])
        st: list[Stage] = []
        for l in range(L + 1):
            for (u, v) in paths[j][l]:
                st.append((("link", u, v), float(data[j, l])))
            if l < L:
                st.append((("node", int(a[j, l])), float(comp[j, l])))
        stages[j] = st
    return stages


@dataclasses.dataclass
class TaskRun:
    """Mutable run-state of one job inside the shared event loop."""

    stages: list[Stage]        # (resource, work) in precedence order
    prio: int                  # global priority (0 = served first)
    ptr: int = 0               # completed-stage count
    remaining: float | None = None  # residual work of the current stage
    arrived: float = 0.0       # instant the job became ready at this stage
    done: bool = False
    completion: float = 0.0    # valid once done


def time_eps(t: float) -> float:
    """Tolerance for event-time comparisons at clock ``t``.

    Relative to the clock magnitude: an absolute epsilon (the seed used
    ``t + 1e-18``) is below one ulp of ``t`` whenever ``t`` exceeds ~1e-2,
    so the arrival guard silently degraded to exact comparison at any
    nonzero clock.  Shared by both event-loop engines and the ledger's
    backlog trace so window boundaries and arrival cutoffs agree.
    """
    return 1e-12 * max(1.0, abs(t))


def work_eps(work: float) -> float:
    """Completion threshold for a stage of ``work`` units (relative)."""
    return 1e-12 * max(1.0, work)


def _resource_rate(res: tuple, mu_node: np.ndarray,
                   mu_link: np.ndarray) -> float:
    return float(mu_node[res[1]] if res[0] == "node"
                 else mu_link[res[1], res[2]])


def run_event_loop_ref(tasks: list[TaskRun], mu_node: np.ndarray,
                       mu_link: np.ndarray, *, t: float = 0.0,
                       t_end: float = np.inf, guard: int = 1_000_000,
                       down: frozenset | tuple = ()) -> float:
    """Preempt-resume priority service of ``tasks`` over ``[t, t_end]``.

    Every resource serves the highest-priority arrived task (strict
    priority, preempting on arrival, work-conserving).  Mutates the tasks
    in place and returns the stop time: ``t_end`` if work remains beyond
    it, else the instant the last event fired.  With the default
    ``t_end=inf`` this is exactly the one-shot simulator's loop; a finite
    ``t_end`` is the incremental drain window used by the committed-work
    ledger.

    ``down`` lists resource keys failed for the whole window: tasks whose
    current stage targets one wait (no service, no dead-resource error).
    Work stuck behind an outage at an infinite ``t_end`` raises — the
    caller must restore the resource or clear the work (recovery
    policies requeue / migrate / shed it) before running to completion.

    This is the seed's linear-scan loop (the semantic reference for
    :mod:`repro.core.eventsim`): each event rescans every task.  Service
    rates are hoisted into per-stage arrays up front — the rate of a
    (task, stage) pair never changes within a run, so the scan does one
    list index instead of two dict lookups per serving resource per event.
    """
    # Hoisted per-stage service rates, indexed [task][stage].
    stage_rates = [[_resource_rate(res, mu_node, mu_link)
                    for res, _ in task.stages] for task in tasks]
    down = frozenset(down)
    for task in tasks:
        if not task.done and task.ptr >= len(task.stages):
            task.done = True
            task.completion = task.arrived
    steps = 0
    while not all(task.done for task in tasks):
        steps += 1
        if steps > guard:
            raise RuntimeError("simulator did not converge")
        # Highest-priority arrived task per resource.
        serving: dict[tuple, tuple[TaskRun, float]] = {}
        eps = time_eps(t)
        for task, rates in zip(tasks, stage_rates):
            if task.done or task.arrived > t + eps:
                continue
            res, work = task.stages[task.ptr]
            if task.remaining is None:
                task.remaining = work
            if res in down:
                continue              # blocked on a failed resource
            cur = serving.get(res)
            if cur is None or task.prio < cur[0].prio:
                serving[res] = (task, rates[task.ptr])
        if not serving:
            # advance to the next stage-arrival (nothing serveable now).
            # With failed resources, live tasks may be *stuck* with
            # arrived <= t — jumping to min(arrived) would freeze the
            # clock and spin the guard out; only future arrivals advance.
            nxt = min((task.arrived for task in tasks
                       if not task.done and task.arrived > t + eps),
                      default=np.inf)
            if nxt >= t_end:
                if not np.isfinite(t_end) and not np.isfinite(nxt):
                    raise RuntimeError(
                        f"event loop stalled: live tasks blocked on "
                        f"failed resources {sorted(down)} — restore them "
                        f"or clear the work before running to completion")
                return t_end if np.isfinite(t_end) else t
            t = nxt
            continue
        # Next completion event.
        dt = np.inf
        for res, (task, rate) in serving.items():
            if rate <= 0:
                raise RuntimeError(
                    f"job with priority {task.prio} scheduled on dead "
                    f"resource {res}")
            dt = min(dt, task.remaining / rate)
        nxt_arr = min((task.arrived for task in tasks
                       if not task.done and task.arrived > t + eps),
                      default=np.inf)
        dt = min(dt, nxt_arr - t)
        clipped = t + dt >= t_end
        if clipped:
            dt = t_end - t  # serve the final partial slice, then stop
        t += dt
        for res, (task, rate) in serving.items():
            task.remaining -= rate * dt
            if task.remaining <= work_eps(task.stages[task.ptr][1]):
                task.remaining = None
                task.ptr += 1
                task.arrived = t
                if task.ptr >= len(task.stages):
                    task.done = True
                    task.completion = t
        if clipped:
            return t_end
    return t


def run_event_loop(tasks: list[TaskRun], mu_node: np.ndarray,
                   mu_link: np.ndarray, *, t: float = 0.0,
                   t_end: float = np.inf, guard: int = 1_000_000,
                   engine: str = "ref", down: frozenset | tuple = ()) -> float:
    """Run the preempt-resume loop with the chosen engine.

    ``engine="ref"`` (default) is the seed linear-scan loop;
    ``engine="indexed"`` routes through the O(log)-per-event engine of
    :mod:`repro.core.eventsim` — same semantics, same tolerance
    discipline, event times equal up to float accumulation order (gated by
    the parity tests and ``benchmarks/drain_bench.py``).  ``down`` lists
    resource keys failed for the whole window (both engines honour it).
    """
    if engine == "indexed":
        from . import eventsim
        return eventsim.run_event_loop_indexed(
            tasks, mu_node, mu_link, t=t, t_end=t_end, guard=guard,
            down=tuple(down))
    if engine != "ref":
        raise ValueError(f"engine must be 'ref' or 'indexed', got {engine!r}")
    return run_event_loop_ref(tasks, mu_node, mu_link, t=t, t_end=t_end,
                              guard=guard, down=down)


def simulate(net: ComputeNetwork, batch: JobBatch, assign, order=None,
             paths: dict[int, list[list[tuple[int, int]]]] | None = None,
             engine: str = "ref") -> SimResult:
    """Event-driven simulation of the routed jobs in the actual system.

    ``assign`` may be a :class:`~repro.core.plan.Plan` (then ``order`` must
    be omitted and the plan's stored paths, if any, are used).  ``engine``
    picks the event-loop implementation; the default ``"ref"`` keeps
    one-shot results bit-identical to the seed loop (``"indexed"`` agrees
    up to float accumulation order — see ``benchmarks/drain_bench.py``).
    """
    from .plan import Plan
    if isinstance(assign, Plan) and paths is None:
        paths = assign.paths
    assign, order = _as_assign_order(assign, order)
    if paths is None:
        _, paths, _ = replay_solution(net.reset_queues(), batch, assign, order)

    mu_node = np.asarray(net.mu_node, np.float64)
    mu_link = np.asarray(net.mu_link, np.float64)
    prio_of = {int(order[p]): p for p in range(len(order))}
    stages = job_stages(batch, assign, paths)
    tasks = [TaskRun(stages=stages[j], prio=prio_of[j])
             for j in range(batch.num_jobs)]
    run_event_loop(tasks, mu_node, mu_link, engine=engine)
    completion = np.array([task.completion for task in tasks], np.float64)
    return SimResult(completion=completion, makespan=float(np.max(completion)))
