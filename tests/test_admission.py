"""Deadline-aware admission control & SLO-guarded auto re-planning:
policy validation, predicted-miss gating (reject/defer), original-arrival
expiry accounting, the measured-EMA cold-start seed, replan reasons, and
the monitor's hysteresis (cooldown + exponential backoff + budget)."""

import dataclasses
import types

import numpy as np
import pytest

from repro.core import jobs as J
from repro.scenarios import make_scenario
from repro.serving.admission import (AdmissionController, AdmissionPolicy,
                                     ReplanMonitor, ReplanPolicy)
from repro.serving.faults import FaultEvent
from repro.serving.online import OnlineScheduler, run_online
from repro.serving.stream import StreamConfig, StreamingPipeline, run_stream


@pytest.fixture(scope="module")
def scenario():
    return make_scenario("paper-small", seed=0)


# -- validation ---------------------------------------------------------------

def test_admission_policy_validation():
    with pytest.raises(ValueError, match="admission policy"):
        AdmissionPolicy(policy="bogus")
    with pytest.raises(ValueError, match="margin_s"):
        AdmissionPolicy(policy="reject", margin_s=-1.0)
    ctl = AdmissionController("defer")
    assert ctl.policy.policy == "defer" and ctl.gating
    assert not AdmissionController().gating       # admit_all default


def test_replan_policy_validation():
    for bad in (dict(threshold=-0.1), dict(cooldown_s=-1.0),
                dict(backoff=0.5), dict(budget=-1),
                dict(min_improvement=1.0),
                dict(cooldown_s=10.0, max_cooldown_s=1.0)):
        with pytest.raises(ValueError):
            ReplanPolicy(**bad)


def test_job_deadline_field():
    job = J.synthetic_job("d0", 0, 1, 3)
    assert job.deadline_s == float("inf")         # default: no SLO
    tight = job.with_deadline(0.25)
    assert tight.deadline_s == 0.25 and job.deadline_s == float("inf")
    with pytest.raises(ValueError, match="deadline_s"):
        J.InferenceJob("d1", 0, 1, job.comp, job.data, deadline_s=0.0)
    with pytest.raises(ValueError, match="deadline_s"):
        job.with_deadline(float("nan"))


# -- predicted-miss gating ----------------------------------------------------

def test_reject_policy_beats_admit_all_under_overload(scenario):
    """The acceptance contrast: under overload, deadline-aware admission
    has a strictly lower SLO-miss rate at equal-or-better goodput."""
    rate = scenario.nominal_rate(2.5)
    kw = dict(horizon=12 / rate, seed=3, rate=rate, batch_size=2,
              drain="exact", finish=True,
              deadline_s=1.2 * scenario.mean_service_s)
    base = run_online(scenario, admission="admit_all", **kw).summary()
    gated = run_online(scenario, admission="reject", **kw).summary()
    assert gated["slo"]["slo_miss_rate"] < base["slo"]["slo_miss_rate"]
    assert gated["slo"]["goodput"] >= base["slo"]["goodput"]
    assert gated["shed_by_reason"].get("admission_reject", 0) > 0
    assert gated["admission"]["rejected"] == \
        gated["shed_by_reason"]["admission_reject"]
    # exact predictions: every admitted request actually met its SLO
    assert gated["slo"]["late"] == 0


def test_defer_then_expire_charged_from_original_arrival(scenario):
    """A deferred request that can no longer make its deadline is shed as
    ``deadline_miss`` with its ORIGINAL arrival instant in the record."""
    sched = OnlineScheduler(scenario.topology, drain="exact",
                            admission="defer")
    rng = np.random.default_rng(4)
    filler = scenario.sample_jobs(rng, 3)
    (victim,) = scenario.sample_jobs(rng, 1)
    victim = victim.with_deadline(1e-3)   # can never be met once queued
    sched.submit_window(0.0, filler + [victim], pad_to=scenario.max_layers)
    assert [j.name for j, _ in sched.admission.deferred] == [victim.name]
    # next window, past the deadline: the deferral expires
    later = scenario.sample_jobs(rng, 1)
    sched.submit_window(0.5, later, pad_to=scenario.max_layers)
    (rec,) = [s for s in sched.trace.shed if s["name"] == victim.name]
    assert rec["reason"] == "deadline_miss"
    assert rec["arrival"] == 0.0 and rec["time"] == 0.5
    assert sched.trace.arrivals_by_name[victim.name] == 0.0
    assert sched.admission.counters["expired"] == 1


def test_flush_deferred_drains_out(scenario):
    sched = OnlineScheduler(scenario.topology, drain="exact",
                            admission="defer")
    rng = np.random.default_rng(6)
    jobs = [j.with_deadline(1e-3) for j in scenario.sample_jobs(rng, 2)]
    filler = scenario.sample_jobs(rng, 2)
    sched.submit_window(0.0, filler + jobs, pad_to=scenario.max_layers)
    assert len(sched.admission.deferred) == 2
    placed = sched.flush_deferred(at=0.25, pad_to=scenario.max_layers)
    assert placed == [] and not sched.admission.deferred
    assert not sched.admission.final          # reset even on the shed path
    by = sched.trace.shed_by_reason()
    assert by.get("deadline_miss", 0) == 2


def test_submit_windows_rejects_gating_admission(scenario):
    sched = OnlineScheduler(scenario.topology, drain="exact",
                            admission="reject")
    rng = np.random.default_rng(8)
    with pytest.raises(ValueError, match="one at a time"):
        sched.submit_windows(0.0, [scenario.sample_jobs(rng, 1)])


def test_streaming_defer_preserves_original_arrival(scenario):
    """Through the pipeline, admission-deferred requests re-enter with
    their original arrival and a later expiry is charged from it."""
    rate = scenario.nominal_rate(2.5)
    tr = run_stream(scenario, horizon=8 / rate, seed=3, rate=rate,
                    batch_size=2, window_s=0.5 / rate, max_batch=4,
                    drain="exact", finish=True,
                    deadline_s=1.2 * scenario.mean_service_s,
                    admission="defer")
    misses = [s for s in tr.shed if s["reason"] == "deadline_miss"]
    assert misses, "overloaded defer run must eventually shed"
    for s in misses:
        assert s["time"] >= s["arrival"]
        assert tr.arrivals_by_name[s["name"]] == s["arrival"]
    s = tr.summary()
    assert s["slo"]["pending"] == 0          # finish + drain-out decide all
    assert s["slo"]["offered"] == (s["slo"]["met"] + s["slo"]["late"]
                                   + s["slo"]["shed"])


# -- measured-EMA cold start --------------------------------------------------

def test_seed_latency_fixes_ema_cold_start(scenario):
    cfg = StreamConfig(solver_latency="measured")
    pipe = StreamingPipeline(scenario.topology, cfg, drain="exact")
    assert pipe._model_latency() == 0.0           # the old cold-start hole
    pipe.seed_latency(0.02)
    assert pipe._model_latency() == 0.02
    pipe.seed_latency(0.5)                        # no-op once seeded
    assert pipe._model_latency() == 0.02
    pipe._observe_solve(0.04)                     # EMA folds real walls in
    assert pipe._model_latency() == pytest.approx(0.03)


def test_warmup_seeds_measured_latency_model(scenario):
    """Regression: with warmup, the *first* window's commit already models
    a positive solver latency instead of riding free."""
    rate = scenario.nominal_rate(0.5)
    tr = run_stream(scenario, horizon=4 / rate, seed=3, rate=rate,
                    solver_latency="measured", warmup=True, drain="exact")
    assert tr.windows[0].solve_model_s > 0.0
    assert tr.windows[0].commit_s > tr.windows[0].close_s


def test_warmup_reports_compile_free_solve_wall(scenario):
    sched = OnlineScheduler(scenario.topology, drain="exact")
    rng = np.random.default_rng(5)
    info = sched.warmup(scenario.sample_jobs(rng, 2),
                        pad_to=scenario.max_layers)
    assert info["warm_solve_s"] > 0.0
    assert info["warm_solve_s"] < info["wall_s"]  # excludes compile walls


# -- replan reasons & monitor hysteresis -------------------------------------

def test_replan_reasons_recorded(scenario):
    sched = OnlineScheduler(scenario.topology, drain="exact")
    assert sched.replan_last() is None
    assert sched.last_replan_reason == "no_batch"
    rng = np.random.default_rng(12)
    sched.submit_window(0.0, scenario.sample_jobs(rng, 2),
                      pad_to=scenario.max_layers)
    # steady health: a re-solve ties, so any positive margin declines it
    assert sched.replan_last(min_improvement=0.25) is None
    assert sched.last_replan_reason == "no_improvement"
    assert sched.replan_last() is not None        # manual = always commit
    assert sched.last_replan_reason == "replanned"
    events = [e["event"] for e in sched.trace.events]
    assert events.count("replan_skipped") == 2
    assert events.count("replan") == 1
    s = sched.trace.summary()
    assert s["replans"] == 1
    assert s["replans_skipped"] == {"no_batch": 1, "no_improvement": 1}


def _fake_sched(divergences):
    """Minimal stand-in for the monitor's scheduler surface."""
    sched = types.SimpleNamespace(
        now=0.0, trace=types.SimpleNamespace(events=[]), committed=0)
    seq = iter(divergences)

    def plan_divergence():
        return next(seq)

    def replan_last(*, min_improvement=None):
        sched.committed += 1
        return ["placement"]

    sched.plan_divergence = plan_divergence
    sched.replan_last = replan_last
    return sched


def test_monitor_threshold_and_calm_reset():
    mon = ReplanMonitor(ReplanPolicy(threshold=0.5, cooldown_s=1.0,
                                     backoff=2.0, max_cooldown_s=8.0))
    sched = _fake_sched([0.2, None, 0.8])
    assert not mon.check(sched)                   # under threshold
    assert not mon.check(sched)                   # no data
    assert mon.check(sched)                       # crossed: triggers
    assert mon.triggers == 1 and sched.committed == 1


def test_monitor_cooldown_and_exponential_backoff():
    mon = ReplanMonitor(ReplanPolicy(threshold=0.1, cooldown_s=1.0,
                                     backoff=2.0, max_cooldown_s=8.0))
    sched = _fake_sched([1.0, 1.0, 1.0, 1.0, 1.0, 1.0])
    sched.now = 0.0
    assert mon.check(sched)                       # t=0: quiet until 1.0
    sched.now = 0.5
    assert not mon.check(sched)                   # cooling down — no call
    sched.now = 1.0
    assert mon.check(sched)                       # t=1: quiet until 3.0 (2x)
    sched.now = 2.5
    assert not mon.check(sched)
    sched.now = 3.0
    assert mon.check(sched)                       # quiet until 7.0 (4x)
    assert mon.triggers == 3 and sched.committed == 3
    # a calm observation resets the backoff to the base cooldown
    calm = _fake_sched([0.0, 1.0])
    calm.now = 10.0
    mon2 = ReplanMonitor(ReplanPolicy(threshold=0.1, cooldown_s=1.0,
                                      backoff=4.0, max_cooldown_s=64.0))
    mon2._cool = 16.0                             # as if after 2 triggers
    assert not mon2.check(calm)
    assert mon2._cool == 1.0
    assert mon2.check(calm)                       # next storm: base cooldown


def test_monitor_budget_bounds_replans():
    mon = ReplanMonitor(ReplanPolicy(threshold=0.1, cooldown_s=0.0,
                                     budget=2))
    sched = _fake_sched([1.0] * 5)
    fired = sum(mon.check(sched) for _ in range(5))
    assert fired == 2 and mon.triggers == 2 and sched.committed == 2


def test_auto_replan_under_fault(scenario):
    """Integration: a capacity rescale mid-run arms the monitor; triggers
    stay within budget and are visible in the summary."""
    rate = scenario.nominal_rate(2.0)   # overload: backlog persists
    horizon = 10 / rate
    faults = [FaultEvent(0.4 * horizon, "rescale", node=0, factor=0.2)]
    tr = run_online(scenario, horizon=horizon, seed=3, rate=rate,
                    batch_size=2, drain="exact", finish=True,
                    fault_schedule=faults,
                    auto_replan=ReplanPolicy(threshold=0.1,
                                             cooldown_s=horizon / 20,
                                             budget=3))
    s = tr.summary()
    assert s.get("auto_replan_triggers", 0) >= 1
    assert s.get("auto_replan_triggers", 0) <= 3      # budget respected
    # every trigger resolved into a commit or an audited decline
    resolved = s.get("replans", 0) + sum(
        s.get("replans_skipped", {}).values())
    assert resolved >= s.get("auto_replan_triggers", 0)


def test_admission_counters_live_on_trace(scenario):
    sched = OnlineScheduler(scenario.topology, drain="exact",
                            admission="reject")
    rng = np.random.default_rng(21)
    jobs = [j.with_deadline(1e-3) for j in scenario.sample_jobs(rng, 2)]
    sched.submit_window(0.0, jobs, pad_to=scenario.max_layers)
    s = sched.trace.summary()
    assert s["admission"]["assessed"] == 2
    assert s["admission"]["rejected"] + s["admission"]["expired"] == 2
    assert s["shed"] == 2


def test_admit_all_matches_no_admission_trajectory(scenario):
    """admit_all gates nothing: identical trace to a run with admission
    disabled (one code path for the A/B baseline)."""
    rate = scenario.nominal_rate(1.0)
    kw = dict(horizon=6 / rate, seed=9, rate=rate, drain="exact",
              finish=True, deadline_s=2 * scenario.mean_service_s)
    a = run_online(scenario, admission=None, **kw)
    b = run_online(scenario, admission="admit_all", **kw)
    # job names carry a process-global counter; compare trajectories
    assert sorted(a.completions.values()) == sorted(b.completions.values())
    assert a.latencies.tolist() == b.latencies.tolist()
    assert not b.shed and b.admission["rejected"] == 0
