"""Online serving loop: arrival processes, queue draining keeps backlog
bounded under sub-capacity load (while the legacy no-drain loop diverges),
and straggler/replan events on the clock."""
import dataclasses

import numpy as np
import pytest

from repro.core import arrivals as A
from repro.scenarios import make_scenario
from repro.serving.online import OnlineScheduler, run_online


# -- arrival processes -------------------------------------------------------

def test_poisson_times_rate_and_sorted():
    rng = np.random.default_rng(0)
    t = A.poisson_times(rng, rate=5.0, horizon=200.0)
    assert (np.diff(t) >= 0).all() and (t >= 0).all() and (t < 200.0).all()
    assert 700 <= t.size <= 1300  # ~1000 expected, generous tolerance


def test_bursty_times_long_run_rate():
    rng = np.random.default_rng(1)
    t = A.bursty_times(rng, rate=8.0, horizon=100.0, burst_size=4)
    assert (np.diff(t) >= 0).all()
    assert 550 <= t.size <= 1050  # ~800 expected
    # bursts: many tiny gaps
    assert (np.diff(t) < 1e-3).sum() > t.size / 3


def test_diurnal_times_peak_heavier_than_base():
    rng = np.random.default_rng(2)
    t = A.diurnal_times(rng, base_rate=0.5, peak_rate=8.0, horizon=100.0,
                        period=100.0)
    mid = ((t > 35) & (t < 65)).sum()     # around the peak
    edges = ((t < 15) | (t > 85)).sum()   # around the base
    assert mid > 2 * max(edges, 1)


def test_make_process_registry():
    assert set(A.available()) >= {"poisson", "bursty", "diurnal"}
    fn = A.make_process("poisson", rate=2.0)
    assert fn(np.random.default_rng(0), 10.0).size > 0
    with pytest.raises(ValueError, match="unknown arrival process"):
        A.make_process("nope")


# -- the headline regression: drain bounded, no-drain diverges ---------------

def test_online_backlog_bounded_iff_draining():
    """Sub-capacity Poisson load: the draining scheduler's backlog stays
    bounded (flat second half) while the no-drain commit loop grows without
    bound — the reason the time-aware state split exists."""
    sc = make_scenario("star", seed=0)
    rate = sc.nominal_rate(0.5)
    horizon = 80 / rate
    drain = run_online(sc, horizon=horizon, seed=1, rate=rate,
                       drain_queues=True)
    nodrain = run_online(sc, horizon=horizon, seed=1, rate=rate,
                         drain_queues=False)
    assert len(drain.records) == len(nodrain.records) >= 40
    # bounded: the second half's peak backlog does not keep climbing
    assert drain.backlog_growth() <= 1.3, drain.summary()
    # divergent: backlog is (weakly) monotone and roughly doubles
    nb = nodrain.backlogs
    assert (np.diff(nb) >= -1e-6).all()
    assert nodrain.backlog_growth() >= 1.7, nodrain.summary()
    assert nodrain.percentile(99) > drain.percentile(99)


def test_online_drained_latency_matches_fresh_solve_at_low_rate():
    """Arrivals far apart: queues fully drain, so every request sees an
    empty network — latency equals the scenario's empty-network service."""
    sc = make_scenario("star", seed=0)
    rate = sc.nominal_rate(0.01)  # gaps ~100x the service time
    tr = run_online(sc, horizon=20 / rate, seed=3, rate=rate)
    assert tr.records, "no arrivals sampled"
    # an exponential gap is occasionally shorter than the service time, so
    # ask for "almost always fully drained", not "always"
    empty = [r.backlog_before == 0.0 for r in tr.records[1:]]
    assert np.mean(empty) >= 0.7, tr.summary()


# -- events on the clock -----------------------------------------------------

def _edge_cloud_sched(**kw):
    sc = make_scenario("edge-cloud", traffic="synthetic", seed=0)
    return sc, OnlineScheduler(sc.topology, **kw)


def test_slowdown_and_replan_are_clock_events():
    sc, sched = _edge_cloud_sched()
    rng = np.random.default_rng(0)
    sched.submit_window(1.0, sc.sample_jobs(rng, 2), pad_to=sc.max_layers)
    before = sched.last_plan
    victim = int(before.assign[int(before.order[0]), 0])
    sched.report_slowdown(victim, 100.0, at=2.5)
    assert sched.now == 2.5 and sched.clock == pytest.approx(2.5)
    replans = sched.replan_last()
    assert replans is not None
    for p in replans:
        assert victim not in p.nodes_used
    kinds = [e["event"] for e in sched.trace.events]
    assert kinds == ["slowdown", "replan"]
    assert sched.trace.events[0]["time"] == 2.5


def test_nodrain_clock_still_advances():
    """Time passing and queue draining are independent: the no-drain
    baseline freezes backlogs but not the clock."""
    sc, sched = _edge_cloud_sched(drain_queues=False)
    rng = np.random.default_rng(2)
    sched.submit_window(0.0, sc.sample_jobs(rng, 1), pad_to=sc.max_layers)
    q0 = np.asarray(sched.state.q_node).copy()
    sched.advance_to(5.0)
    assert sched.clock == pytest.approx(5.0)
    np.testing.assert_array_equal(np.asarray(sched.state.q_node), q0)


def test_replan_drains_elapsed_time_from_rollback():
    """replan_last after time has passed must not resurrect already-served
    backlog: the pre-batch snapshot is drained over the elapsed window."""
    sc, sched = _edge_cloud_sched()
    rng = np.random.default_rng(3)
    # batch 1 builds backlog; batch 2's pre-state snapshot carries it
    sched.submit_window(0.0, sc.sample_jobs(rng, 2), pad_to=sc.max_layers)
    sched.submit_window(0.0, sc.sample_jobs(rng, 2), pad_to=sc.max_layers)
    assert float(np.asarray(sched._last[2].q_node).sum()) > 0
    bound0 = sched.last_plan.bound()  # scored against batch-1 backlog
    sched.advance_to(1e9)  # everything committed has long been served
    sched.replan_last()
    # the rollback snapshot was drained before re-solving, so batch 2 now
    # sees an empty network and its bound strictly improves
    assert sched.last_plan.bound() < bound0
    assert sched.clock == pytest.approx(1e9)


def test_inherited_advance_shares_the_one_clock():
    """RoutedScheduler.advance and OnlineScheduler.advance_to move the same
    clock: mixing them must not drain the same interval twice."""
    sc, sched = _edge_cloud_sched()
    rng = np.random.default_rng(5)
    sched.submit_window(0.0, sc.sample_jobs(rng, 1), pad_to=sc.max_layers)
    sched.advance(5.0)                 # inherited explicit-drain call
    assert sched.now == pytest.approx(5.0)
    q_after_advance = np.asarray(sched.state.q_node).copy()
    sched.advance_to(5.0)              # same instant: dt == 0, no extra drain
    np.testing.assert_array_equal(np.asarray(sched.state.q_node),
                                  q_after_advance)
    assert sched.clock == pytest.approx(5.0)


def test_time_cannot_go_backwards():
    _, sched = _edge_cloud_sched()
    sched.advance_to(5.0)
    with pytest.raises(ValueError, match="backwards"):
        sched.advance_to(4.0)


def test_slowdown_slows_draining():
    """A degraded node drains its backlog at the degraded rate."""
    sc, fast = _edge_cloud_sched()
    _, slow = _edge_cloud_sched()
    rng = np.random.default_rng(1)
    jobs = sc.sample_jobs(rng, 2)
    for s in (fast, slow):
        s.submit_window(0.0, list(jobs), pad_to=sc.max_layers)
    q = np.asarray(fast.state.q_node, np.float64)
    mu = np.asarray(sc.topology.mu_node, np.float64)
    waits = np.where(mu > 0, q / np.maximum(mu, 1e-30), 0.0)
    hot = int(np.argmax(waits))
    slow.report_slowdown(hot, 10.0)
    dt = 0.25 * waits[hot]  # partial drain even at the healthy rate
    assert dt > 0
    fast.advance_to(dt)
    slow.advance_to(dt)
    q_fast = float(np.asarray(fast.state.q_node)[hot])
    q_slow = float(np.asarray(slow.state.q_node)[hot])
    assert q_slow > q_fast  # drained at mu/10 instead of mu


# -- bugfix regressions ------------------------------------------------------

def test_backlog_growth_flat_zero_run_is_one():
    """Low-load runs whose backlog is all ~zero must report growth 1.0, not
    the ~1e12 artifact of dividing by the 1e-12 floor."""
    from repro.serving.online import ArrivalRecord, OnlineTrace
    tr = OnlineTrace(records=[
        ArrivalRecord(time=float(i), names=(f"r{i}",), latencies=(0.1,),
                      backlog_before=0.0, backlog_after=0.0, solve_s=0.0)
        for i in range(8)])
    assert tr.backlog_growth() == 1.0
    # ...but genuine growth from a ~zero first half still reads as huge
    tr.records[-1] = dataclasses.replace(tr.records[-1], backlog_after=5.0)
    assert tr.backlog_growth() > 1e6


def test_run_online_rate_scales_diurnal():
    """run_online(rate=) must drive the diurnal process (peak_rate=rate,
    base_rate=rate/5), not be silently dropped."""
    sc = make_scenario("star", seed=0)
    rate = sc.nominal_rate(0.4)
    lo = run_online(sc, horizon=10 / rate, seed=5, process="diurnal",
                    rate=rate)
    hi = run_online(sc, horizon=10 / rate, seed=5, process="diurnal",
                    rate=4 * rate)
    assert len(hi.records) > len(lo.records) >= 1
    # explicit process_params always win over the shorthand
    explicit = run_online(sc, horizon=10 / rate, seed=5, process="diurnal",
                          rate=4 * rate,
                          process_params={"peak_rate": rate,
                                          "base_rate": rate / 5})
    assert len(explicit.records) == len(lo.records)


def test_run_online_rate_rejected_for_unknown_mapping():
    """A registered process with no defined rate mapping must reject the
    shorthand instead of silently ignoring it."""
    from repro.core import arrivals as A

    @A.register_process("every-second")
    def _every_second(gap: float = 1.0):
        return lambda rng, horizon: np.arange(0.0, horizon, gap)

    sc = make_scenario("star", seed=0)
    try:
        with pytest.raises(ValueError, match="no defined mapping"):
            run_online(sc, horizon=3.0, process="every-second", rate=2.0)
        tr = run_online(sc, horizon=3.0, process="every-second",
                        process_params={"gap": 1.0})
        assert len(tr.records) == 3
    finally:
        A._PROCESSES.pop("every-second", None)


def test_report_slowdown_rejects_nonpositive_factor():
    """factor <= 0 or non-finite would flip 1/factor into negative or
    infinite effective capacity; the convention is factor=2 == half speed."""
    _, sched = _edge_cloud_sched()
    sched.advance_to(1.0)
    for bad in (0.0, -2.0, float("nan"), float("inf")):
        with pytest.raises(ValueError, match="slowdown factor"):
            sched.report_slowdown(0, bad, at=5.0)
    # the invalid event must not have moved the clock or logged an event
    assert sched.now == pytest.approx(1.0)
    assert sched.trace.events == []
    sched.report_slowdown(0, 2.0, at=5.0)  # valid: half speed
    assert sched.now == pytest.approx(5.0)


def test_trace_to_dict_roundtrips_json():
    import json
    sc = make_scenario("random-geometric", seed=2)
    rate = sc.nominal_rate(0.3)
    tr = run_online(sc, horizon=10 / rate, seed=4, rate=rate)
    blob = json.loads(json.dumps(tr.to_dict()))
    assert blob["arrivals"] == len(tr.records)
    assert len(blob["backlogs"]) == len(tr.records)


def test_trace_to_dict_keeps_exact_drain_results():
    """to_dict must not drop completions/replay_completions or the
    actual-latency percentiles — the exact-drain results PR 4/5 compute."""
    import json
    sc = make_scenario("paper-small", seed=0)
    rate = sc.nominal_rate(0.6)
    tr = run_online(sc, horizon=6 / rate, seed=7, rate=rate, drain="exact",
                    track_commits=True, finish=True)
    assert tr.completions and tr.replay_completions
    blob = json.loads(json.dumps(tr.to_dict()))
    assert blob["completions"] == tr.completions
    assert blob["replay_completions"] == tr.replay_completions
    assert len(blob["actual_latencies"]) == len(tr.actual_latencies())
    assert "p99_actual_s" in blob and "p50_actual_s" in blob
    # names serialize alongside, so actuals stay alignable after a reload
    assert blob["names"] == [list(r.names) for r in tr.records]


def test_advance_to_guard_is_relative_at_large_clocks():
    """The backwards-clock guard must scale with the clock (time_eps): at
    t ~ 1e12 an absolute 1e-9 slack is below one ulp, so float-accumulation
    jitter on a legitimate same-instant event would be rejected."""
    from repro.core import schedule

    _, sched = _edge_cloud_sched()
    big = 1e12
    sched.advance_to(big)
    # within tolerance: one ulp of slack at this magnitude is ~0.000122 s,
    # far above the old absolute 1e-9 guard
    jitter = big - 0.25 * schedule.time_eps(big)
    assert jitter < big  # representable below the clock
    sched.advance_to(jitter)           # must not raise
    assert sched.now == big            # ...and the clock never rolls back
    with pytest.raises(ValueError, match="backwards"):
        sched.advance_to(big - 10 * schedule.time_eps(big))
