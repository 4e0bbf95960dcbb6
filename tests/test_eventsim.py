"""Indexed event engine vs the reference loop: parity properties.

The engine (`repro.core.eventsim`) must reproduce the reference linear-scan
loop's trajectories — same preempt-resume priority semantics, same
tolerance discipline — up to float accumulation order (the reference
decrements every serving residual at every global event, the engine once
per head change).  Random systems exercise shared resources, random
priorities, staggered stage arrivals, finite drain-window splits, and the
dead-resource error path; the scheduler-level tests pin the persistent
engine's behaviour through commits, drains, rollbacks, and replays.
"""
import copy

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import completions as C, eventsim, jobs as J, schedule
from repro.scenarios import make_scenario
from repro.serving.online import OnlineScheduler, run_online


def _random_system(rng, *, staggered=False, V=5, max_tasks=6,
                   dead_node=None, t0=0.0):
    """Random rates + task stage lists (no solver involved: pure loop test)."""
    mu_node = rng.uniform(0.5, 3.0, V)
    mu_link = rng.uniform(0.5, 3.0, (V, V))
    if dead_node is not None:
        mu_node[dead_node] = 0.0
    n = int(rng.integers(1, max_tasks + 1))
    prios = rng.permutation(n)
    tasks = []
    for i in range(n):
        stages = []
        for _ in range(int(rng.integers(1, 7))):
            if rng.random() < 0.5:
                stages.append((("node", int(rng.integers(V))),
                               float(rng.uniform(0.2, 3.0))))
            else:
                u, v = rng.choice(V, 2, replace=False)
                stages.append((("link", int(u), int(v)),
                               float(rng.uniform(0.2, 3.0))))
        arrived = t0 + (float(rng.uniform(0, 3.0)) if staggered else 0.0)
        tasks.append(schedule.TaskRun(stages=stages, prio=int(prios[i]),
                                      arrived=arrived))
    return mu_node, mu_link, tasks


def _residual(task):
    """Total unfinished work of a task (current-stage residual included)."""
    out = 0.0
    for k in range(task.ptr, len(task.stages)):
        w = task.stages[k][1]
        if k == task.ptr and task.remaining is not None:
            w = task.remaining
        out += w
    return out


def _assert_same_outcome(ref, idx, *, rtol=1e-9, atol=1e-9):
    for a, b in zip(ref, idx):
        assert a.done == b.done
        if a.done:
            np.testing.assert_allclose(b.completion, a.completion,
                                       rtol=rtol, atol=atol)
        else:
            np.testing.assert_allclose(_residual(b), _residual(a),
                                       rtol=1e-7, atol=1e-7)
            np.testing.assert_allclose(b.arrived, a.arrived,
                                       rtol=rtol, atol=atol)


# -- to-completion parity -----------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2**31 - 1), st.booleans())
def test_indexed_matches_ref_to_completion(seed, staggered):
    """Random priorities, shared resources, optional staggered arrivals:
    identical completion trajectories up to float accumulation order."""
    rng = np.random.default_rng(seed)
    mu_node, mu_link, tasks = _random_system(rng, staggered=staggered)
    ref = copy.deepcopy(tasks)
    idx = copy.deepcopy(tasks)
    t_ref = schedule.run_event_loop_ref(ref, mu_node, mu_link)
    t_idx = eventsim.run_event_loop_indexed(idx, mu_node, mu_link)
    _assert_same_outcome(ref, idx)
    np.testing.assert_allclose(t_idx, t_ref, rtol=1e-9, atol=1e-9)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_window_splits_compose_and_match_ref(seed):
    """Finite t_end windows: the persistent engine advanced window by
    window matches the reference loop run over the same windows *and* its
    own one-shot run (drain composition across arbitrary cuts)."""
    rng = np.random.default_rng(seed)
    mu_node, mu_link, tasks = _random_system(rng, staggered=True)
    ref = copy.deepcopy(tasks)
    idx = copy.deepcopy(tasks)
    one = copy.deepcopy(tasks)
    cuts = np.sort(rng.uniform(0.0, 12.0, 3))
    eng = eventsim.EventEngine(mu_node, mu_link)
    eng.add_tasks(idx)
    t = 0.0
    for c in cuts:
        schedule.run_event_loop_ref(ref, mu_node, mu_link, t=t, t_end=float(c))
        eng.advance(float(c))
        _assert_same_outcome(ref, idx, rtol=1e-7, atol=1e-7)
        t = float(c)
    schedule.run_event_loop_ref(ref, mu_node, mu_link, t=t)
    eng.advance()
    eventsim.run_event_loop_indexed(one, mu_node, mu_link)
    _assert_same_outcome(ref, idx)
    _assert_same_outcome(one, idx)   # windowing is invisible


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_zero_rate_resource_error_parity(seed):
    """A job routed over a dead resource raises the same error from both
    engines (and neither silently serves at rate 0)."""
    rng = np.random.default_rng(seed)
    V = 5
    dead = int(rng.integers(V))
    mu_node, mu_link, tasks = _random_system(rng, V=V, dead_node=dead)
    # force at least one stage onto the dead node
    victim = tasks[int(rng.integers(len(tasks)))]
    victim.stages[int(rng.integers(len(victim.stages)))] = (
        ("node", dead), 1.0)
    with pytest.raises(RuntimeError, match="dead resource"):
        schedule.run_event_loop_ref(copy.deepcopy(tasks), mu_node, mu_link)
    with pytest.raises(RuntimeError, match="dead resource"):
        eventsim.run_event_loop_indexed(copy.deepcopy(tasks), mu_node,
                                        mu_link)


# -- tolerance discipline -----------------------------------------------------

def test_time_eps_is_relative():
    """The arrival guard must not degrade to exact comparison at nonzero
    clock: eps scales with |t| (the seed's absolute 1e-18 was below one
    ulp for any t >~ 1e-2)."""
    assert schedule.time_eps(0.0) == 1e-12
    assert schedule.time_eps(1.0) == 1e-12
    t = 2.0**26
    assert t + schedule.time_eps(t) > t          # representable nudge
    assert t + 1e-18 == t                        # the seed guard was not
    assert schedule.time_eps(-t) == schedule.time_eps(t)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_large_clock_drain_matches_time_shifted_run(seed):
    """Regression for the absolute-epsilon guard: the same system released
    at clock 2^26 must reproduce the t=0 trajectory shifted, for both
    engines — event-time comparisons are relative, not absolute."""
    t0 = float(2**26)
    rng = np.random.default_rng(seed)
    mu_node, mu_link, base = _random_system(rng, staggered=True)
    shifted = copy.deepcopy(base)
    for task in shifted:
        task.arrived += t0
    schedule.run_event_loop_ref(base, mu_node, mu_link)
    for eng_tasks, runner in ((copy.deepcopy(shifted),
                               schedule.run_event_loop_ref),
                              (copy.deepcopy(shifted),
                               eventsim.run_event_loop_indexed)):
        runner(eng_tasks, mu_node, mu_link, t=t0)
        for a, b in zip(base, eng_tasks):
            assert b.done
            np.testing.assert_allclose(b.completion - t0, a.completion,
                                       rtol=1e-9, atol=1e-4)


# -- persistent engine through the serving stack ------------------------------

def _lockstep_schedulers(sc, seeds=(0,), arrivals=6, **kw):
    """Two exact-mode schedulers fed identical jobs, one per engine."""
    scheds = {eng: OnlineScheduler(sc.topology, drain="exact",
                                   sim_engine=eng, **kw)
              for eng in ("indexed", "ref")}
    rng = np.random.default_rng(11)
    t = 0.0
    for _ in range(arrivals):
        jobs = sc.sample_jobs(rng, 1)
        for sched in scheds.values():
            sched.submit_jobs(t, list(jobs), pad_to=sc.max_layers)
        t += float(rng.uniform(0.05, 0.4))
    return scheds


def test_scheduler_engines_agree_end_to_end():
    """The full online loop — drains, commits, ledger-materialized queue
    states, final completions — agrees between the persistent indexed
    engine and the per-window reference loop."""
    sc = make_scenario("star", seed=0)
    scheds = _lockstep_schedulers(sc)
    a, b = scheds["indexed"], scheds["ref"]
    # the solver saw the same ledger-materialized queues at every arrival
    la = np.array([r.latencies for r in a.trace.records], np.float64)
    lb = np.array([r.latencies for r in b.trace.records], np.float64)
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)
    ca, cb = a.finish(), b.finish()
    assert ca.keys() == cb.keys()
    for name in ca:
        np.testing.assert_allclose(ca[name], cb[name], rtol=1e-7, atol=1e-7)


def test_persistent_engine_is_threaded_not_rebuilt():
    """Sequential drains/commits reuse one live index: the ledger returned
    by each step carries the same engine object, while a stale snapshot
    (rollback semantics) loses the slot and rebuilds lazily."""
    sc = make_scenario("star", seed=0)
    sched = OnlineScheduler(sc.topology, drain="exact")
    rng = np.random.default_rng(3)
    sched.submit_jobs(0.0, sc.sample_jobs(rng, 2), pad_to=sc.max_layers)
    eng0 = C._engine_of(sched.ledger)
    assert eng0 is not None
    snapshot = sched.ledger
    sched.advance_to(0.05)
    sched.submit_jobs(0.1, sc.sample_jobs(rng, 1), pad_to=sc.max_layers)
    assert C._engine_of(sched.ledger) is eng0        # same index, threaded
    assert C._engine_of(snapshot) is None            # snapshot went stale
    # and the stale snapshot still drains correctly (lazy rebuild)
    re = C.drain_exact(sc.topology, snapshot, 0.05)
    ref = C.drain_exact(sc.topology, snapshot, 0.05, engine="ref")
    np.testing.assert_allclose(re.queue_arrays()[0], ref.queue_arrays()[0],
                               rtol=1e-6, atol=1e-6)


def test_replan_rollback_with_indexed_engine():
    """replan_last's ledger rollback works on the indexed engine: the
    pre-batch snapshot rebuilds, drains the elapsed window, and the chain
    continues without double counting."""
    sc = make_scenario("edge-cloud", traffic="synthetic", seed=0)
    for eng in ("indexed", "ref"):
        sched = OnlineScheduler(sc.topology, drain="exact", sim_engine=eng)
        rng = np.random.default_rng(3)
        sched.submit_jobs(0.0, sc.sample_jobs(rng, 2), pad_to=sc.max_layers)
        sched.submit_jobs(0.0, sc.sample_jobs(rng, 2), pad_to=sc.max_layers)
        assert len(sched.ledger.jobs) == 4
        sched.advance_to(1e9)
        assert not sched.ledger.jobs
        sched.replan_last()
        assert len(sched.ledger.jobs) == 2


def test_exact_backlog_trace_single_pass_matches_ref():
    """The one-forward-pass engine trace equals the seed per-sample
    rebuild, on a commit log from a real fluid run."""
    sc = make_scenario("star", seed=0)
    rate = sc.nominal_rate(0.7)
    tr = run_online(sc, horizon=20 / rate, seed=3, rate=rate,
                    track_commits=True)
    fast = C.exact_backlog_trace(sc.topology, tr.commit_log, tr.times)
    ref = C.exact_backlog_trace(sc.topology, tr.commit_log, tr.times,
                                engine="ref")
    np.testing.assert_allclose(fast, ref, rtol=1e-5, atol=1e-6)


# -- piecewise-health ground truth -------------------------------------------

def test_piecewise_replay_matches_incremental_through_slowdown():
    """With a mid-run straggler, the incremental exact drain served each
    window at the health then in force; the ground-truth replay now does
    too (the seed replayed one end-state topology for the whole horizon,
    so completion times disagreed whenever health changed mid-run)."""
    sc = make_scenario("star", seed=0)
    sched = OnlineScheduler(sc.topology, drain="exact", track_commits=True)
    rng = np.random.default_rng(5)
    sched.submit_jobs(0.0, sc.sample_jobs(rng, 2), pad_to=sc.max_layers)
    victim = int(sched.last_plan.assign[int(sched.last_plan.order[0]), 0])
    sched.report_slowdown(victim, 6.0, at=0.02)
    sched.submit_jobs(0.05, sc.sample_jobs(rng, 1), pad_to=sc.max_layers)
    incremental = sched.finish()
    assert sched.commit_log.health == ((0.02, victim, 6.0),)
    replay = sched.replay_ground_truth()
    assert incremental.keys() == replay.keys()
    for name in incremental:
        np.testing.assert_allclose(replay[name], incremental[name],
                                   rtol=1e-6, atol=1e-6)
    # the end-state-topology replay is *not* the truth here
    end_state, _ = C.run_to_completion(sched._effective_topology(),
                                       sched.commit_log)
    worst = max(abs(end_state[n] - incremental[n]) for n in incremental)
    assert worst > 1e-4


def test_replan_keeps_health_history_in_commit_log():
    """Rolling back the superseded batch must not erase straggler records:
    the health history survives replan_last."""
    sc = make_scenario("edge-cloud", traffic="synthetic", seed=0)
    sched = OnlineScheduler(sc.topology, drain="exact", track_commits=True)
    rng = np.random.default_rng(7)
    sched.submit_jobs(0.0, sc.sample_jobs(rng, 1), pad_to=sc.max_layers)
    sched.submit_jobs(0.2, sc.sample_jobs(rng, 1), pad_to=sc.max_layers)
    sched.report_slowdown(0, 2.0, at=0.3)
    sched.replan_last()
    assert len(sched.commit_log.health) == 1
    at, node, factor = sched.commit_log.health[0]
    assert (at, node, factor) == (0.3, 0, 2.0)


def test_solver_extracted_paths_match_replay():
    """greedy/lazy extract_paths=True fills plan.paths during the solve
    (one extraction per round, reusing the round's closures) with exactly
    the hops replay_solution derives — and leaves bounds untouched."""
    from repro.core import schedule as S, solve

    sc = make_scenario("edge-cloud", traffic="synthetic", seed=0)
    rng = np.random.default_rng(9)
    batch = J.batch_jobs(sc.sample_jobs(rng, 4), pad_to=sc.max_layers)
    net = sc.topology.view()
    for method in ("greedy", "lazy"):
        plan = solve(net, batch, method=method, extract_paths=True)
        assert plan.paths is not None and set(plan.paths) == set(range(4))
        _, paths, _ = S.replay_solution(net, batch, plan.assign, plan.order)
        assert plan.paths == paths
        base = solve(net, batch, method=method)
        assert base.paths is None
        np.testing.assert_array_equal(np.asarray(base.assign),
                                      np.asarray(plan.assign))
        assert base.bounds.tolist() == plan.bounds.tolist()


# -- engine selection ---------------------------------------------------------

def test_engine_validation():
    with pytest.raises(ValueError, match="engine must be"):
        schedule.run_event_loop([], np.ones(1), np.ones((1, 1)),
                                engine="magic")
    sc = make_scenario("star", seed=0)
    with pytest.raises(ValueError, match="sim_engine must be"):
        OnlineScheduler(sc.topology, drain="exact", sim_engine="magic")
    led = C.CommittedWork.empty(3)
    with pytest.raises(ValueError, match="engine must be"):
        C.drain_exact(None, led, 1.0, engine="magic")


def test_simulate_engine_param_agrees():
    """One-shot simulate: default (ref) and indexed engines agree on a
    solved instance; the default path is the reference loop, so seed
    results are unchanged bit-for-bit."""
    from repro.core import solve
    from util import random_instance

    rng = np.random.default_rng(2)
    net, jobs = random_instance(rng, num_jobs=3)
    batch = J.batch_jobs(jobs)
    plan = solve(net, batch, method="greedy")
    if plan.makespan_bound >= 1e29:
        pytest.skip("disconnected instance")
    ref = schedule.simulate(net, batch, plan)
    idx = schedule.simulate(net, batch, plan, engine="indexed")
    np.testing.assert_allclose(idx.completion, ref.completion,
                               rtol=1e-9, atol=1e-9)
    again = schedule.simulate(net, batch, plan)   # default == ref, bitwise
    assert again.completion.tolist() == ref.completion.tolist()
