"""What-if fork & predicted completions: the tentpole invariants of the
admission layer.  ``EventEngine.fork()`` + ``predict_completions`` must be
(a) exact — predictions made at any instant equal the completions the live
system later realizes, to rtol 1e-9, at fresh and queued states across the
scenario catalog; (b) free of side effects — serving a fork to quiescence
(and mutating it arbitrarily) never perturbs the live engine; and (c)
exact through fault outages — a prediction made after a fail/recover
sequence still matches the realized completions."""

import dataclasses

import numpy as np
import pytest

from repro.core import completions as C, jobs as J, schedule, solve
from repro.core.eventsim import EventEngine
from repro.scenarios import FAMILIES, make_scenario
from repro.serving.faults import FaultEvent
from repro.serving.online import OnlineScheduler
from util import random_instance

RTOL = 1e-9


def _drive(sched, sc, rng, windows, batch=2, dt=0.05):
    t = 0.0
    for _ in range(windows):
        sched.submit_window(t, sc.sample_jobs(rng, batch),
                          pad_to=sc.max_layers)
        t += dt
    return t


# -- (a) exactness across the catalog, fresh and queued ----------------------

@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_predictions_match_realized_completions(family):
    """At a fresh commit and again at a queued mid-run state, the forked
    prediction equals what finish() later realizes — rtol 1e-9."""
    sc = make_scenario(family, seed=0)
    rng = np.random.default_rng(7)
    sched = OnlineScheduler(sc.topology, drain="exact")
    _drive(sched, sc, rng, windows=1)
    fresh = C.predict_completions(sched._effective_topology(), sched.ledger)
    _drive(sched, sc, rng, windows=2)
    queued = C.predict_completions(sched._effective_topology(),
                                   sched.ledger)
    realized = sched.finish()
    # Jobs committed after the fresh prediction exist only in the queued
    # one; every predicted job must match its realized completion.
    assert set(queued) >= set(realized)
    for name, t_done in realized.items():
        np.testing.assert_allclose(queued[name], t_done, rtol=RTOL)
        if name in fresh:
            np.testing.assert_allclose(fresh[name], t_done, rtol=RTOL)


def test_prediction_with_extra_plan_matches_commit_then_finish():
    """Scoring an uncommitted candidate window through ``extra_plans``
    predicts exactly the completions realized when that window is then
    committed at the same instant."""
    sc = make_scenario("paper-small", seed=0)
    rng = np.random.default_rng(3)
    sched = OnlineScheduler(sc.topology, drain="exact")
    t = _drive(sched, sc, rng, windows=2)
    jobs = sc.sample_jobs(rng, 3)
    names = [j.name for j in jobs]
    batch, plan = sched.presolve(jobs, pad_to=sc.max_layers)
    if plan.paths is None:
        eff = sched._effective_topology()
        _, paths, _ = schedule.replay_solution(eff.view(sched.state), batch,
                                               plan.assign, plan.order)
        plan = dataclasses.replace(plan, paths=paths)
    preds = C.predict_completions(
        sched._effective_topology(), sched.ledger,
        extra_plans=[(batch, plan, names)], at=t)
    sched.advance_to(t)
    sched.commit_presolved(jobs, batch, plan)
    realized = sched.finish()
    for name in names:
        np.testing.assert_allclose(preds[name], realized[name], rtol=RTOL)


def test_indexed_and_ref_prediction_engines_agree():
    sc = make_scenario("star", seed=0)
    rng = np.random.default_rng(11)
    sched = OnlineScheduler(sc.topology, drain="exact")
    _drive(sched, sc, rng, windows=2)
    topo = sched._effective_topology()
    fast = C.predict_completions(topo, sched.ledger, engine="indexed")
    ref = C.predict_completions(topo, sched.ledger, engine="ref")
    assert set(fast) == set(ref)
    for name in fast:
        np.testing.assert_allclose(fast[name], ref[name], rtol=RTOL)


# -- (b) the fork is side-effect free -----------------------------------------

def test_fork_mutation_never_perturbs_live_engine():
    """Lockstep parity: two identical ledgers, one repeatedly forked and
    mutated between drains, must realize bit-identical completions."""
    rng = np.random.default_rng(5)
    net, jobs = random_instance(rng, num_jobs=4)
    batch = J.batch_jobs(jobs)
    plan = solve(net, batch, method="greedy").replay(net, batch)
    names = [j.name for j in jobs]

    def fresh():
        led = C.CommittedWork.empty(net.num_nodes).commit(batch, plan,
                                                          names=names)
        return C.warm_engine(net.topology, led)

    control, probed = fresh(), fresh()
    t = 0.0
    for _ in range(4):
        # Abuse the probed ledger's fork between drains: predict (which
        # serves a fork to quiescence) and separately mutate a raw fork.
        C.predict_completions(net.topology, probed)
        eng = C._engine_of(probed).eng
        fk = eng.fork()
        fk.advance(fk.now + 0.7)
        fk.add_tasks([C._task_of(j) for j in probed.jobs[:1]])
        t += 0.2
        control = C.drain_exact(net.topology, control, 0.2)
        probed = C.drain_exact(net.topology, probed, 0.2)
        assert control.completed == probed.completed  # bit-identical
    done_c, _ = C.run_to_completion(net.topology, control)
    done_p, _ = C.run_to_completion(net.topology, probed)
    assert done_c == done_p


def test_fork_is_independent_copy():
    """Mutating every forked structure leaves the original's behaviour
    untouched (heaps, events, tasks, rates, down-set are all copied)."""
    rng = np.random.default_rng(9)
    net, jobs = random_instance(rng, num_jobs=3)
    batch = J.batch_jobs(jobs)
    plan = solve(net, batch, method="greedy").replay(net, batch)
    led = C.CommittedWork.empty(net.num_nodes).commit(
        batch, plan, names=[j.name for j in jobs])
    led = C.warm_engine(net.topology, led)
    eng: EventEngine = C._engine_of(led).eng
    before = (eng.now, len(eng.completions), eng.events_processed,
              [(t.ptr, t.remaining, t.done) for t in eng.tasks])
    fk = eng.fork()
    fk.advance(np.inf)
    assert fk.live == 0 and len(fk.completions) == len(eng.tasks)
    after = (eng.now, len(eng.completions), eng.events_processed,
             [(t.ptr, t.remaining, t.done) for t in eng.tasks])
    assert before == after
    # the original still drains to the same completions the fork predicted
    eng.advance(np.inf)
    assert eng.completions == fk.completions


# -- (c) exact through a fault outage -----------------------------------------

def test_predictions_exact_through_outage_segment():
    """A prediction made after a node fail/recover cycle (requeue policy)
    matches the realized completions exactly."""
    from repro.serving.online import run_online
    sc = make_scenario("paper-small", seed=0)
    rate = sc.nominal_rate(0.8)
    horizon = 10 / rate
    faults = [FaultEvent(0.3 * horizon, "node_fail", node=1),
              FaultEvent(0.6 * horizon, "node_recover", node=1)]
    rng = np.random.default_rng(2)
    sched = OnlineScheduler(sc.topology, drain="exact")
    from repro.serving.faults import FaultInjector
    injector = FaultInjector(sched, policy="requeue", pad_to=sc.max_layers)
    times = np.linspace(0, horizon, 8)
    fi = 0
    for t in times:
        while fi < len(faults) and faults[fi].time <= float(t):
            injector.apply(faults[fi])
            fi += 1
        jobs = sc.sample_jobs(rng, 1)
        if sched.degraded:
            jobs = injector.filter_arrivals(float(t), jobs)
            if not jobs:
                continue
        sched.submit_window(float(t), jobs, pad_to=sc.max_layers)
    while fi < len(faults):
        injector.apply(faults[fi])
        fi += 1
    preds = C.predict_completions(sched._effective_topology(), sched.ledger,
                                  down=sched._down_keys())
    realized = sched.finish()
    for name, t_done in realized.items():
        np.testing.assert_allclose(preds[name], t_done, rtol=RTOL)
