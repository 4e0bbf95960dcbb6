"""Fault events, recovery policies, and ground truth through outages.

The tentpole contract under test: a run threaded through ANY fault
schedule (node/link failures and recoveries, joins, lagged rescales)
under ANY recovery policy stays exactly replayable — the commit log's
health + removal history drives ``replay_piecewise`` to the same
completion times the incremental exact drain produced.  The engine-level
half of the same contract: ``remove_resource`` / ``restore_resource`` on
a persistent :class:`~repro.core.eventsim.EventEngine` agree with a
fresh engine rebuilt at every availability edge.  Unit tests pin the
event/schedule validation surface, victim selection, the ``migrate``
solver's one-node placement, and each recovery policy's handling of
stranded work (including bounded retry and solver-exception shedding).
"""
import copy

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from test_eventsim import _assert_same_outcome, _random_system

from repro.core import eventsim, jobs as J, solvers
from repro.scenarios import make_scenario
from repro.serving import faults as F
from repro.serving.online import OnlineScheduler, run_online
from repro.serving.stream import run_stream

FAMILIES = tuple(sorted(F.FAULT_FAMILIES))
REPLAY_EPS_S = 1e-6


# -- replay parity through fault sequences (satellite 3, end to end) ----------

@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_replay_matches_exact_drain_through_faults(seed):
    """Any fault family x any recovery policy: the piecewise commit-log
    replay reproduces the incremental exact drain's completion times."""
    family = FAMILIES[seed % len(FAMILIES)]
    policy = F.POLICIES[(seed // len(FAMILIES)) % len(F.POLICIES)]
    sc = make_scenario("edge-cloud", seed=0)
    rate = sc.nominal_rate(0.9)
    horizon = 12 / rate
    faults = F.make_fault_schedule(family, sc, horizon, seed=seed % 1000)
    tr = run_online(sc, horizon=horizon, rate=rate, seed=seed % 100,
                    drain="exact", track_commits=True, finish=True,
                    fault_schedule=faults, recovery=policy)
    cc, rr = tr.completions, tr.replay_completions
    assert set(cc) == set(rr)
    for name, t in cc.items():
        assert abs(rr[name] - t) <= REPLAY_EPS_S, (family, policy, name)


# -- engine remove/restore vs fresh rebuild (satellite 3, engine level) -------

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.booleans())
def test_engine_remove_restore_matches_fresh_rebuild(seed, link_victim):
    """A persistent engine through an outage window [t1, t2) on one
    resource matches three fresh engines — one per availability segment,
    the middle one built with ``down=`` — over the same task state."""
    rng = np.random.default_rng(seed)
    mu_node, mu_link, tasks = _random_system(rng, staggered=True)
    V = mu_node.shape[0]
    if link_victim:
        u, v = rng.choice(V, 2, replace=False)
        res = ("link", int(u), int(v))
    else:
        res = ("node", int(rng.integers(V)))
    t1, t2 = np.sort(rng.uniform(0.0, 8.0, 2))

    live = copy.deepcopy(tasks)
    eng = eventsim.EventEngine(mu_node, mu_link)
    eng.add_tasks(live)
    eng.advance(float(t1))
    eng.remove_resource(res)
    eng.advance(float(t2))
    eng.restore_resource(res)
    eng.advance()

    ref = copy.deepcopy(tasks)
    eventsim.run_event_loop_indexed(ref, mu_node, mu_link, t=0.0,
                                    t_end=float(t1))
    eventsim.run_event_loop_indexed(ref, mu_node, mu_link, t=float(t1),
                                    t_end=float(t2), down=(res,))
    eventsim.run_event_loop_indexed(ref, mu_node, mu_link, t=float(t2))
    _assert_same_outcome(ref, live, rtol=1e-7, atol=1e-7)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_engine_sync_is_remove_then_restore(seed):
    """sync(mu, mu, down=) reaches the same trajectories as explicit
    remove/restore calls — the scheduler's one-call path is no different
    from the injector's granular one."""
    rng = np.random.default_rng(seed)
    mu_node, mu_link, tasks = _random_system(rng, staggered=True)
    res = ("node", int(rng.integers(mu_node.shape[0])))
    t1, t2 = np.sort(rng.uniform(0.0, 8.0, 2))

    a, b = copy.deepcopy(tasks), copy.deepcopy(tasks)
    ea = eventsim.EventEngine(mu_node, mu_link)
    eb = eventsim.EventEngine(mu_node, mu_link)
    ea.add_tasks(a), eb.add_tasks(b)
    ea.advance(float(t1)), eb.advance(float(t1))
    ea.remove_resource(res)
    eb.sync(mu_node, mu_link, down=(res,))
    ea.advance(float(t2)), eb.advance(float(t2))
    ea.restore_resource(res)
    eb.sync(mu_node, mu_link, down=())
    ea.advance(), eb.advance()
    _assert_same_outcome(a, b)


# -- recovery event on the scheduler (satellite 1) ----------------------------

def test_report_recovery_restores_full_health():
    sc = make_scenario("edge-cloud", seed=0)
    sched = OnlineScheduler(sc.topology, drain="exact", track_commits=True)
    sched.report_slowdown(8, 2.0)
    assert sched._slowdown[8] == 2.0
    sched.report_recovery(8, at=1.0)
    assert sched._slowdown[8] == 1.0
    assert sched.now == 1.0
    # recorded in the health history (replay_piecewise's contract) ...
    assert sched.commit_log.health[-1] == (1.0, 8, 1.0)
    # ... and on the trace
    assert sched.trace.events[-1]["event"] == "recovery"


def test_report_recovery_validates_node():
    sc = make_scenario("edge-cloud", seed=0)
    sched = OnlineScheduler(sc.topology, drain="exact")
    with pytest.raises(ValueError, match="out of range"):
        sched.report_recovery(sc.num_nodes)
    with pytest.raises(ValueError, match="out of range"):
        sched.report_recovery(-1)


def test_availability_setters_validate():
    sc = make_scenario("edge-cloud", seed=0)
    sched = OnlineScheduler(sc.topology, drain="exact")
    with pytest.raises(ValueError, match="out of range"):
        sched.set_node_availability(sc.num_nodes, False)
    u, v = map(int, np.argwhere(
        np.asarray(sc.topology.mu_link) == 0)[0])
    with pytest.raises(ValueError, match="does not exist"):
        sched.set_link_availability(u, v, False)


# -- event / schedule validation ----------------------------------------------

def test_fault_event_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        F.FaultEvent(1.0, "meteor")
    with pytest.raises(ValueError, match="needs link"):
        F.FaultEvent(1.0, "link_fail")
    with pytest.raises(ValueError, match="needs node"):
        F.FaultEvent(1.0, "node_fail")
    with pytest.raises(ValueError, match="finite and > 0"):
        F.FaultEvent(1.0, "rescale", node=0, factor=0.0)
    with pytest.raises(ValueError, match="finite and > 0"):
        F.FaultEvent(1.0, "rescale", node=0, factor=np.inf)
    with pytest.raises(ValueError, match="time must be finite"):
        F.node_fail(np.inf, 0)


def test_fault_schedule_sorts_and_validates():
    sched = F.schedule_from([F.node_recover(5.0, 1), F.node_fail(2.0, 1)])
    assert [ev.kind for ev in sched] == ["node_fail", "node_recover"]
    assert len(sched) == 2
    with pytest.raises(ValueError, match="outside"):
        F.FaultSchedule((F.node_fail(1.0, 99),)).validate(4)
    with pytest.raises(ValueError, match="outside"):
        F.FaultSchedule((F.link_fail(1.0, 0, 99),)).validate(4)


def test_capacity_rescale_lag():
    ev = F.capacity_rescale(2.0, 3, 0.5, lag=0.25)
    assert ev.time == 2.25 and ev.kind == "rescale" and ev.factor == 0.5


def test_make_fault_schedule_families():
    sc = make_scenario("edge-cloud", seed=0)
    with pytest.raises(ValueError, match="unknown fault family"):
        F.make_fault_schedule("volcano", sc, 10.0)
    for family in FAMILIES:
        sched = F.make_fault_schedule(family, sc, 10.0, seed=3)
        assert len(sched) >= 2
        assert all(0.0 <= ev.time <= 10.0 for ev in sched)
        times = [ev.time for ev in sched]
        assert times == sorted(times)


def test_pick_victim_prefers_interior_compute():
    sc = make_scenario("edge-cloud", seed=0)
    # the cloud node: highest-capacity compute that is not ingress/egress
    assert F.pick_victim(sc) == 8
    u, _ = F.pick_victim_link(sc)
    assert u == 8


# -- the migrate solver -------------------------------------------------------

def test_migrate_solver_places_each_job_on_one_node():
    sc = make_scenario("edge-cloud", seed=0)
    jobs = sc.sample_jobs(np.random.default_rng(0), 3)
    plan = solvers.solve(sc.topology, J.batch_jobs(jobs), method="migrate")
    for j, job in enumerate(jobs):
        row = plan.assign[j, :job.num_layers]
        assert len(set(row.tolist())) == 1
        assert sc.topology.mu_node[row[0]] > 0
    assert plan.solver == "migrate"


# -- the injector: construction + policies ------------------------------------

def _stranded_setup(policy, **kw):
    """Two jobs committed at t=0 (greedy puts work on the cloud node 8),
    then node 8 fails at t=0.1 — returns (sched, injector, outage rec)."""
    sc = make_scenario("edge-cloud", seed=0)
    sched = OnlineScheduler(sc.topology, drain="exact", track_commits=True)
    sched.submit_jobs(0.0, sc.sample_jobs(np.random.default_rng(0), 2))
    inj = F.FaultInjector(sched, policy=policy, **kw)
    rec = inj.apply(F.node_fail(0.1, 8))
    assert rec["affected"], "setup: no work landed on the victim node"
    return sched, inj, rec


def test_injector_requires_exact_drain():
    sc = make_scenario("edge-cloud", seed=0)
    with pytest.raises(ValueError, match="drain='exact'"):
        F.FaultInjector(OnlineScheduler(sc.topology))  # fluid: no ledger


def test_injector_validates_args():
    sc = make_scenario("edge-cloud", seed=0)
    sched = OnlineScheduler(sc.topology, drain="exact")
    with pytest.raises(ValueError, match="policy"):
        F.FaultInjector(sched, policy="pray")
    with pytest.raises(ValueError, match="max_retries"):
        F.FaultInjector(sched, max_retries=-1)


def test_policy_lost_sheds_and_accounts():
    sched, _, rec = _stranded_setup("lost")
    assert rec["lost"] and not rec["requeued"]
    assert {why for _, why in rec["lost"]} == {"failed_resource"}
    assert set(rec["lost"]) == set(sched.trace.lost)
    downs = set(sched._down_keys())
    assert all(job.stages[k][0] not in downs
               for job in sched.ledger.jobs
               for k in range(job.ptr, len(job.stages)))


def test_policy_requeue_replans_with_retry_suffix():
    sched, _, rec = _stranded_setup("requeue")
    assert rec["requeued"]
    # a job whose last finished layer's output sat ON the victim loses its
    # intermediate data with the node — shed, not requeued
    assert {why for _, why in rec["lost"]} <= {"data_lost"}
    assert all(n.endswith("#r1") for n in rec["requeued"])
    live = {j.name for j in sched.ledger.jobs}
    assert set(rec["requeued"]) <= live
    # the originals were withdrawn from the live ledger
    assert not any(F._parse_retry(n)[1] == 0 for n in live)
    # requeued latency is charged from the ORIGINAL arrival instant
    for n in rec["requeued"]:
        base, _ = F._parse_retry(n)
        assert sched.trace.arrivals_by_name[n] == \
            sched.trace.arrivals_by_name[base]


def test_policy_requeue_avoids_dead_resources():
    sched, _, rec = _stranded_setup("requeue")
    downs = set(sched._down_keys())
    for job in sched.ledger.jobs:
        assert all(res not in downs for res, _ in job.stages)


def test_policy_migrate_places_residual_on_one_node():
    sched, _, rec = _stranded_setup("migrate")
    assert rec["requeued"]
    requeued = [j for j in sched.ledger.jobs if j.name in set(rec["requeued"])]
    assert requeued
    for job in requeued:
        nodes = {res[1] for res, _ in job.stages if res[0] == "node"}
        assert len(nodes) == 1 and 8 not in nodes


def test_retries_exhausted_bounds_the_loop():
    sched, _, rec = _stranded_setup("requeue", max_retries=0)
    assert not rec["requeued"]
    assert {why for _, why in rec["lost"]} == {"retries_exhausted"}


def test_recover_event_restores_routability():
    sched, inj, _ = _stranded_setup("lost")
    assert sched.degraded
    inj.apply(F.node_recover(0.5, 8))
    assert not sched.degraded
    assert sched._slowdown[8] == 1.0


def test_rescale_event_is_absolute_slowdown():
    sc = make_scenario("edge-cloud", seed=0)
    sched = OnlineScheduler(sc.topology, drain="exact")
    inj = F.FaultInjector(sched)
    inj.apply(F.capacity_rescale(0.0, 8, 0.5))   # half capacity
    assert sched._slowdown[8] == 2.0
    inj.apply(F.capacity_rescale(1.0, 8, 1.0))   # back to nominal
    assert sched._slowdown[8] == 1.0


# -- routability + arrival filtering ------------------------------------------

def test_filter_arrivals_sheds_unroutable():
    sc = make_scenario("edge-cloud", seed=0)
    sched = OnlineScheduler(sc.topology, drain="exact")
    inj = F.FaultInjector(sched, policy="lost")
    sched.set_node_availability(0, False)
    assert not inj.routable(0, 3)       # dead source
    assert not inj.routable(3, 0)       # dead destination
    assert inj.routable(1, 3)
    jobs = [J.synthetic_job("dead-src", 0, 3, 4, seed=1),
            J.synthetic_job("alive", 1, 3, 4, seed=2)]
    kept = inj.filter_arrivals(0.0, jobs)
    assert [j.name for j in kept] == ["alive"]
    assert ("dead-src", "arrival_unroutable") in sched.trace.lost


# -- solver exceptions must not kill the pipeline (satellite 2) ---------------

def test_stream_survives_solver_exception():
    @solvers.register("test-bomb")
    def _bomb(net, batch, **opts):
        raise RuntimeError("solver exploded")

    sc = make_scenario("star", seed=0)
    rate = sc.nominal_rate(0.5)
    tr = run_stream(sc, horizon=8 / rate, rate=rate, seed=1,
                    drain="exact", method="test-bomb")
    s = tr.summary()
    assert s["requests"] == 0
    assert s["shed"] > 0
    assert s["shed_by_reason"] == {"solver_error": s["shed"]}


def test_stream_retries_transient_solver_failure_once():
    calls = {"n": 0}

    @solvers.register("test-flaky")
    def _flaky(net, batch, **opts):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient")
        return solvers.get("greedy")(net, batch, **opts)

    sc = make_scenario("star", seed=0)
    rate = sc.nominal_rate(0.5)
    tr = run_stream(sc, horizon=8 / rate, rate=rate, seed=1,
                    drain="exact", method="test-flaky", finish=True)
    s = tr.summary()
    assert s.get("shed", 0) == 0
    assert s["requests"] == s["arrivals"] > 0
    assert calls["n"] >= 2


# -- faults through the streaming pipeline ------------------------------------

def test_stream_fault_schedule_matches_serial_loop():
    """window_s=0, max_batch=1, zero solver latency: the faulted streaming
    run must reproduce the faulted serial loop bit for bit."""
    sc = make_scenario("edge-cloud", seed=0)
    rate = sc.nominal_rate(0.85)
    horizon = 10 / rate
    faults = F.make_fault_schedule("transient-node", sc, horizon, seed=5)
    kw = dict(horizon=horizon, rate=rate, seed=2, drain="exact",
              track_commits=True, finish=True,
              fault_schedule=faults, recovery="requeue")
    serial = run_online(make_scenario("edge-cloud", seed=0), **kw)
    pipe = run_stream(make_scenario("edge-cloud", seed=0), window_s=0.0,
                      max_batch=1, **kw)
    assert set(pipe.completions) == set(serial.completions)
    for n, t in serial.completions.items():
        assert abs(pipe.completions[n] - t) <= REPLAY_EPS_S
    assert sorted(n for n, _ in pipe.lost) == sorted(n for n, _ in serial.lost)
