"""CommittedWork ledger + exact drain: equivalence with the event
simulator, fluid-as-optimistic-bound, drain composition, and the online
fidelity invariants the benchmark gates on."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import completions as C, jobs as J, schedule, solve
from repro.scenarios import make_scenario
from repro.serving.online import OnlineScheduler, run_online
from util import random_instance


def _committed_ledger(rng, num_jobs=3):
    """(net, batch, plan-with-paths, ledger committed at t=0)."""
    net, jobs = random_instance(rng, num_jobs=num_jobs)
    batch = J.batch_jobs(jobs)
    plan = solve(net, batch, method="greedy")
    if plan.makespan_bound >= 1e29:
        return None  # disconnected/dead instance; skip
    plan = plan.replay(net, batch)  # fill explicit paths
    ledger = C.CommittedWork.empty(net.num_nodes).commit(
        batch, plan, names=[j.name for j in jobs])
    return net, batch, plan, ledger


# -- ledger structure ---------------------------------------------------------

def test_commit_requires_paths_and_monotone_time():
    rng = np.random.default_rng(0)
    net, jobs = random_instance(rng, num_jobs=2)
    batch = J.batch_jobs(jobs)
    plan = solve(net, batch, method="greedy")
    led = C.CommittedWork.empty(net.num_nodes, clock=5.0)
    with pytest.raises(ValueError, match="paths"):
        led.commit(batch, plan)
    plan = plan.replay(net, batch)
    with pytest.raises(ValueError, match="behind the ledger clock"):
        led.commit(batch, plan, at=1.0)
    led2 = led.commit(batch, plan, at=5.0, names=[j.name for j in jobs])
    assert len(led2.jobs) == 2 and led2.next_prio == 2
    assert [j.prio for j in led2.jobs] == [0, 1]
    # priority order == plan order; clock unmoved by commits
    assert led2.jobs[0].name == jobs[int(plan.order[0])].name
    assert led2.clock == 5.0


def test_queue_arrays_match_fluid_commit_at_commit_instant():
    """Before any draining, the ledger's residual work equals the fluid
    committed queues (same loads on the same resources)."""
    rng = np.random.default_rng(1)
    out = _committed_ledger(rng)
    assert out is not None
    net, batch, plan, ledger = out
    qn, ql = ledger.queue_arrays()
    np.testing.assert_allclose(qn, np.asarray(plan.net.q_node), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(ql, np.asarray(plan.net.q_link), rtol=1e-5,
                               atol=1e-5)


# -- exact drain vs the one-shot simulator -----------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_run_to_completion_matches_simulate(seed):
    """Draining a freshly committed ledger to completion reproduces the
    event simulator's per-job completion times (same machinery, same
    numbers)."""
    rng = np.random.default_rng(seed)
    out = _committed_ledger(rng)
    if out is None:
        return
    net, batch, plan, ledger = out
    sim = schedule.simulate(net.reset_queues(), batch, plan)
    comps, drained = C.run_to_completion(net.topology, ledger)
    assert not drained.jobs
    for j in range(batch.num_jobs):
        name = f"job{j}"
        np.testing.assert_allclose(comps[name], sim.completion[j],
                                   rtol=1e-9, atol=1e-12)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_drain_exact_composes(seed):
    """Chunked draining is exact: drain(a) then drain(b) == drain(a+b) in
    residual work, progress, and recorded completions."""
    rng = np.random.default_rng(seed)
    out = _committed_ledger(rng)
    if out is None:
        return
    net, batch, plan, ledger = out
    a, b = rng.uniform(0, 2, size=2)
    two = C.drain_exact(net.topology,
                        C.drain_exact(net.topology, ledger, a), b)
    one = C.drain_exact(net.topology, ledger, a + b)
    assert dict(two.completed).keys() == dict(one.completed).keys()
    for name, when in one.completed:
        np.testing.assert_allclose(dict(two.completed)[name], when,
                                   rtol=1e-9, atol=1e-12)
    qn2, ql2 = two.queue_arrays()
    qn1, ql1 = one.queue_arrays()
    np.testing.assert_allclose(qn2, qn1, atol=1e-5)
    np.testing.assert_allclose(ql2, ql1, atol=1e-5)
    assert two.clock == pytest.approx(one.clock)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_drain_exact_never_under_drains_vs_fluid(seed):
    """Fluid is the optimistic bound: per resource, the exact residual is
    >= the fluid residual after any dt (fluid serves each resource at the
    maximum possible rate, ignoring precedence and priority)."""
    rng = np.random.default_rng(seed)
    out = _committed_ledger(rng)
    if out is None:
        return
    net, batch, plan, ledger = out
    dt = float(rng.uniform(0, 3))
    led = C.drain_exact(net.topology, ledger, dt)
    qn_e, ql_e = led.queue_arrays()
    fluid = net.state.with_queues(plan.net.q_node,
                                  plan.net.q_link).advance(net.topology, dt)
    assert (qn_e >= np.asarray(fluid.q_node) - 1e-4).all()
    assert (ql_e >= np.asarray(fluid.q_link) - 1e-4).all()


def test_drain_exact_respects_precedence():
    """A layer's transfer bytes must not drain before its compute does:
    with compute far from finished after dt, the output link's queued bytes
    are untouched under exact drain (the fluid model drains them)."""
    import jax.numpy as jnp
    from repro.core import network as N
    from repro.core.plan import Plan

    net = N.make_network(3, [(0, 1, 1.0), (1, 2, 1.0)], [0.0, 1.0, 0.0])
    job = J.InferenceJob("j", 0, 2, np.asarray([10.0], np.float32),
                         np.asarray([1.0, 4.0], np.float32))
    batch = J.batch_jobs([job])
    plan = Plan(assign=np.asarray([[1]]), priority=np.asarray([0]),
                bounds=np.asarray([0.0]))
    plan = plan.replay(net, batch)
    ledger = C.CommittedWork.empty(3).commit(batch, plan, names=["j"])
    # After 2s: input transfer (1 byte @ 1 B/s) done, compute has 9 FLOPs
    # left, so the 4-byte output transfer has not started.
    led = C.drain_exact(net.topology, ledger, 2.0)
    qn, ql = led.queue_arrays()
    assert ql[1, 2] == pytest.approx(4.0)       # untouched: precedence
    assert qn[1] == pytest.approx(9.0)          # compute drained 1s worth
    fluid = net.state.with_queues(plan.net.q_node,
                                  plan.net.q_link).advance(net.topology, 2.0)
    assert float(np.asarray(fluid.q_link)[1, 2]) == pytest.approx(2.0)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_bound_dominates_simulation_on_ledger_drained_state(seed):
    """bound >= simulated completion still holds when the queue state a new
    batch is solved against came from an exact ledger drain."""
    rng = np.random.default_rng(seed)
    out = _committed_ledger(rng, num_jobs=2)
    if out is None:
        return
    ledger_net, batch1, plan1, ledger = out
    led = C.drain_exact(ledger_net.topology, ledger, float(rng.uniform(0, 3)))
    state = led.queue_state()
    net = ledger_net.topology.view(state)
    _, jobs2 = random_instance(rng, num_jobs=3)
    batch2 = J.batch_jobs(jobs2)
    plan2 = solve(net, batch2, method="greedy")
    if plan2.makespan_bound >= 1e29:
        return
    sim = schedule.simulate(net, batch2, plan2.assign, plan2.order)
    assert sim.makespan <= plan2.makespan_bound * (1 + 1e-5)


# -- online integration -------------------------------------------------------

def _star_run(drain, *, load=0.7, arrivals=25, **kw):
    sc = make_scenario("star", seed=0)
    rate = sc.nominal_rate(load)
    return sc, run_online(sc, horizon=arrivals / rate, seed=3, rate=rate,
                          drain=drain, **kw)


def test_online_exact_backlog_bounded_and_bounds_hold():
    """Exact drain keeps backlog bounded under sub-capacity load, and its
    per-arrival bounds dominate the actual (event-simulated) completions —
    the property the fluid drain loses."""
    sc, tr = _star_run("exact", track_commits=True, finish=True)
    assert len(tr.records) >= 15
    assert tr.backlog_growth() <= 1.5, tr.summary()
    act, bound = tr.actual_latencies(), tr.latencies
    assert act.size == bound.size == len(tr.completions)
    assert (act <= bound * (1 + 1e-6) + 1e-9).all()


def test_online_exact_incremental_matches_one_shot_replay():
    """Completion times recorded by the chunked online drain equal the
    one-shot full-horizon replay of the same commit log."""
    _, tr = _star_run("exact", track_commits=True, finish=True)
    assert tr.completions.keys() == tr.replay_completions.keys()
    for name, when in tr.completions.items():
        np.testing.assert_allclose(when, tr.replay_completions[name],
                                   rtol=1e-9, atol=1e-9)


def test_online_exact_backlog_trace_dominates_fluid():
    """Replaying the fluid policy's own commits under exact accounting
    never reports less backlog than the fluid model claimed."""
    sc, tr = _star_run("fluid", track_commits=True, finish=True)
    exb = C.exact_backlog_trace(sc.topology, tr.commit_log, tr.times)
    flb = np.array([r.backlog_before for r in tr.records])
    assert exb.shape == flb.shape
    assert (exb >= flb - 1e-6).all()


def test_exact_backlog_trace_rejects_drained_ledger():
    sc = make_scenario("star", seed=0)
    rng = np.random.default_rng(0)
    sched = OnlineScheduler(sc.topology, drain="exact")
    sched.submit_jobs(0.0, sc.sample_jobs(rng, 1), pad_to=sc.max_layers)
    sched.advance_to(1e-3)
    with pytest.raises(ValueError, match="undrained"):
        C.exact_backlog_trace(sc.topology, sched.ledger, [1.0])


def test_scheduler_drain_mode_validation_and_reset():
    sc = make_scenario("star", seed=0)
    with pytest.raises(ValueError, match="drain must be"):
        OnlineScheduler(sc.topology, drain="magic")
    sched = OnlineScheduler(sc.topology, drain="exact")
    rng = np.random.default_rng(1)
    sched.submit_jobs(0.0, sc.sample_jobs(rng, 2), pad_to=sc.max_layers)
    assert sched.ledger is not None and len(sched.ledger.jobs) == 2
    # state queues were materialized from the ledger
    qn, _ = sched.ledger.queue_arrays()
    np.testing.assert_allclose(np.asarray(sched.state.q_node), qn)
    sched.drain()
    assert not sched.ledger.jobs
    assert float(np.asarray(sched.state.q_node).max()) == 0.0


def test_exact_replan_rolls_ledger_back():
    """replan_last in exact mode restores the pre-batch ledger, drains it
    over the elapsed window, and commits the re-solved batch — the ledger
    never double-counts the superseded plan."""
    sc = make_scenario("edge-cloud", traffic="synthetic", seed=0)
    sched = OnlineScheduler(sc.topology, drain="exact")
    rng = np.random.default_rng(3)
    sched.submit_jobs(0.0, sc.sample_jobs(rng, 2), pad_to=sc.max_layers)
    sched.submit_jobs(0.0, sc.sample_jobs(rng, 2), pad_to=sc.max_layers)
    assert len(sched.ledger.jobs) == 4
    bound0 = sched.last_plan.bound()
    sched.advance_to(1e9)  # everything committed has long been served
    assert not sched.ledger.jobs  # all completed by the drain
    sched.replan_last()
    # rollback + elapsed drain: batch 1 completed, batch 2 re-committed
    assert len(sched.ledger.jobs) == 2
    assert sched.last_plan.bound() < bound0


def test_ledger_rejects_duplicate_job_names():
    """Completion records key on job names; a repeat would silently
    overwrite an earlier job's completion, so commit() rejects it."""
    from repro.serving.scheduler import Request, RoutedScheduler
    from repro.core import network as N

    G, GB = 1e12, 1e9
    net = N.make_network(3, [(0, 1, 10 * GB), (1, 2, 10 * GB)],
                         [0, 50 * G, 0])
    sched = RoutedScheduler(net, drain="exact")
    sched.schedule([Request("smollm_135m", 0, 2)])  # defaults to name req0
    with pytest.raises(ValueError, match="duplicate job name 'req0'"):
        sched.schedule([Request("smollm_135m", 0, 2)])
    # distinct names are fine across batches
    sched.schedule([Request("smollm_135m", 0, 2, name="r1")])
    assert len(sched.ledger.jobs) == 2


def test_online_slowdown_invalid_node_does_not_move_clock():
    """An out-of-range node is rejected before the clock advances, like an
    invalid factor."""
    sc = make_scenario("star", seed=0)
    sched = OnlineScheduler(sc.topology)
    sched.advance_to(1.0)
    with pytest.raises(ValueError, match="out of range"):
        sched.report_slowdown(sc.num_nodes + 5, 2.0, at=9.0)
    assert sched.now == pytest.approx(1.0)
    assert sched.trace.events == []


def test_exact_bounds_hold_through_replan():
    """replan_last refreshes the superseded arrival record (the new bound,
    measured from the replan instant, plus the wait already incurred), so
    bound >= actual survives straggler replans in exact mode."""
    sc = make_scenario("edge-cloud", traffic="synthetic", seed=0)
    sched = OnlineScheduler(sc.topology, drain="exact")
    rng = np.random.default_rng(11)
    sched.submit_jobs(0.0, sc.sample_jobs(rng, 1), pad_to=sc.max_layers)
    sched.submit_jobs(0.5, sc.sample_jobs(rng, 2), pad_to=sc.max_layers)
    victim = int(sched.last_plan.assign[int(sched.last_plan.order[0]), 0])
    sched.report_slowdown(victim, 50.0, at=1.0)
    sched.replan_last()
    sched.finish()
    actual, bounds = sched.trace.actual_latencies(), sched.trace.latencies
    assert actual.size == bounds.size == 3
    assert (actual <= bounds * (1 + 1e-6) + 1e-9).all(), (actual, bounds)


def test_scenario_job_names_unique_across_batches():
    """Completion tracking keys on names; sample_jobs must never repeat one
    even across many calls on the same scenario instance."""
    sc = make_scenario("star", seed=0)
    rng = np.random.default_rng(0)
    names = [j.name for _ in range(50) for j in sc.sample_jobs(rng, 2)]
    assert len(set(names)) == len(names)


def test_online_scheduler_finish_requires_exact():
    sc = make_scenario("star", seed=0)
    sched = OnlineScheduler(sc.topology)
    with pytest.raises(ValueError, match="exact"):
        sched.finish()
    with pytest.raises(ValueError, match="track_commits"):
        sched.replay_ground_truth()
