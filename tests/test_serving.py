"""Serving: routed scheduler behaviour (straggler avoidance, queue-aware
spreading) and the decode engine end-to-end."""
import dataclasses

import numpy as np
import pytest

from repro.core import network as N
from repro.scenarios import make_scenario
from repro.serving.scheduler import (Request, RoutedScheduler,
                                     requests_to_jobs)


def _cluster():
    """4 TPU slices in a line + 2 edge ingress nodes."""
    G = 1e12
    GB = 1e9
    #   0 (edge) - 1 - 2 - 3 - 4 (slices) - 5 (edge)
    edges = [(0, 1, 10 * GB), (1, 2, 40 * GB), (2, 3, 40 * GB),
             (3, 4, 40 * GB), (4, 5, 10 * GB), (1, 3, 40 * GB),
             (2, 4, 40 * GB)]
    caps = [0, 50 * G, 50 * G, 50 * G, 50 * G, 0]
    return N.make_network(6, edges, caps)


def test_placements_valid_and_prioritized():
    sched = RoutedScheduler(_cluster())
    reqs = [Request("smollm_135m", src=0, dst=5, seq_len=1024, name=f"r{i}")
            for i in range(4)]
    plans = sched.schedule_jobs(requests_to_jobs(reqs))
    # regression: the list is built in priority order (no re-sort needed)
    assert [p.priority for p in plans] == [0, 1, 2, 3]
    for p in plans:
        assert all(n in (1, 2, 3, 4) for n in p.nodes_used)
        assert p.bound_s > 0


def test_placements_are_views_over_stored_plan():
    """Placements share the scheduler's stored Plan; bounds agree and the
    plan round-trips through JSON with the placements' data intact."""
    import json
    from repro.core.plan import Plan

    sched = RoutedScheduler(_cluster())
    plans = sched.schedule_jobs(requests_to_jobs(
        [Request("smollm_135m", 0, 5, name=f"r{i}") for i in range(3)]))
    stored = sched.last_plan
    assert stored is not None and stored.solver == "greedy"
    for p in plans:
        assert p.plan is stored
        assert p.bound_s == float(stored.bounds[p.job])
    rt = Plan.from_dict(json.loads(json.dumps(stored.to_dict())))
    np.testing.assert_array_equal(rt.assign, stored.assign)
    np.testing.assert_array_equal(rt.priority, stored.priority)


def test_scheduler_method_flag():
    """Solver choice is a string flag; lazy greedy places identically."""
    reqs = [Request("smollm_135m", 0, 5, name=f"r{i}") for i in range(3)]
    by_method = {}
    for method in ("greedy", "lazy"):
        sched = RoutedScheduler(_cluster(), method=method)
        sched.schedule_jobs(requests_to_jobs(reqs))
        by_method[method] = sched.last_plan
    np.testing.assert_allclose(by_method["greedy"].bounds,
                               by_method["lazy"].bounds, rtol=1e-6)


def test_replan_last_routes_around_straggler():
    """report_slowdown + replan_last re-places the same batch."""
    sched = RoutedScheduler(_cluster())
    plans = sched.schedule_jobs(requests_to_jobs(
        [Request("olmo_1b", 0, 5, name=f"r{i}") for i in range(2)]))
    victim = plans[0].nodes_used[0]
    sched.report_slowdown(victim, 50.0)
    replans = sched.replan_last()
    assert replans is not None and len(replans) == 2
    for p in replans:
        assert victim not in p.nodes_used, (victim, p.nodes_used)


def test_queue_aware_spreading():
    """Many identical jobs: the waiting term must spread them over slices
    rather than piling all on one (the paper's Fig. 1 argument)."""
    sched = RoutedScheduler(_cluster())
    reqs = [Request("olmo_1b", src=0, dst=5, seq_len=2048, name=f"r{i}")
            for i in range(8)]
    plans = sched.schedule_jobs(requests_to_jobs(reqs))
    used = {n for p in plans for n in p.nodes_used}
    assert len(used) >= 2, f"all jobs piled on {used}"


def test_straggler_avoidance():
    """A slice reported 10x slow receives no new placements."""
    sched = RoutedScheduler(_cluster())
    plans0 = sched.schedule_jobs(
        requests_to_jobs([Request("olmo_1b", 0, 5, name="warm")]))
    hot = plans0[0].nodes_used[0]
    sched.drain()
    sched.report_slowdown(hot, 10.0)
    plans = sched.schedule_jobs(requests_to_jobs(
        [Request("olmo_1b", 0, 5, name=f"r{i}") for i in range(4)]))
    for p in plans:
        assert hot not in p.nodes_used, (hot, p.nodes_used)


def test_engine_generates():
    import jax
    from repro.configs import registry
    from repro.models import model as M
    from repro.serving.engine import DecodeEngine

    cfg = registry.smoke_config("smollm_135m")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = DecodeEngine(cfg, params, max_len=64)
    prompts = np.full((3, 4), 7, np.int32)
    res = eng.generate(prompts, gen_len=8)
    assert res.tokens.shape == (3, 8)
    assert (res.tokens >= 0).all() and (res.tokens < cfg.padded_vocab).all()
    # determinism
    res2 = eng.generate(prompts, gen_len=8)
    np.testing.assert_array_equal(res.tokens, res2.tokens)
    # fused single-call prefill == per-token reference loop
    ref = eng.generate(prompts, gen_len=8, prefill_mode="per_token")
    np.testing.assert_array_equal(res.tokens, ref.tokens)
    import pytest
    with pytest.raises(ValueError, match="prefill_mode"):
        eng.generate(prompts, gen_len=8, prefill_mode="bogus")


def test_report_slowdown_validates_inputs():
    """factor must be finite and > 0 (factor=2 == half speed); node must be
    in range.  Invalid reports leave health untouched."""
    import pytest

    sched = RoutedScheduler(_cluster())
    for bad in (0.0, -1.0, float("nan"), float("inf")):
        with pytest.raises(ValueError, match="slowdown factor"):
            sched.report_slowdown(1, bad)
    with pytest.raises(ValueError, match="out of range"):
        sched.report_slowdown(99, 2.0)
    assert (sched._slowdown == 1.0).all()
    sched.report_slowdown(1, 2.0)
    assert sched._slowdown[1] == 2.0


def test_scheduler_exact_drain_end_to_end():
    """drain='exact' on the request path: placements come out the same shape,
    advance() drains the ledger, and full drain empties the queues."""
    sched = RoutedScheduler(_cluster(), drain="exact")
    plans = sched.schedule_jobs(requests_to_jobs(
        [Request("smollm_135m", 0, 5, name=f"r{i}") for i in range(3)]))
    assert [p.priority for p in plans] == [0, 1, 2]
    assert len(sched.ledger.jobs) == 3
    q0 = float(np.asarray(sched.state.q_node).sum())
    assert q0 > 0
    sched.advance(1e-3)
    assert float(np.asarray(sched.state.q_node).sum()) < q0
    sched.advance(1e9)  # plenty of time: everything completes
    assert not sched.ledger.jobs and len(sched.ledger.completed) == 3
    assert float(np.asarray(sched.state.q_node).max()) == 0.0
    assert float(np.asarray(sched.state.q_link).max()) == 0.0


def test_scheduler_advance_drains_queues():
    """Time passing drains the committed backlog at effective rates."""
    sched = RoutedScheduler(_cluster())
    sched.schedule_jobs(
        requests_to_jobs([Request("olmo_1b", 0, 5, name="r0")]))
    q0 = float(np.asarray(sched.state.q_node).sum())
    assert q0 > 0
    sched.advance(1e-3)
    q1 = float(np.asarray(sched.state.q_node).sum())
    assert q1 < q0
    sched.advance(1e9)  # plenty of time: everything drains
    assert float(np.asarray(sched.state.q_node).max()) == 0.0
    assert float(np.asarray(sched.state.q_link).max()) == 0.0
    assert sched.clock > 0


# -- the cached effective topology and the host copies ----------------------

def _bits(*arrays):
    return [np.asarray(a).tobytes() for a in arrays]


def _fresh_topology(sched):
    """The effective topology built anew from the scheduler's health."""
    from repro.core.state import effective_topology
    masks = (sched._avail_node, sched._link_up) if sched.degraded else ()
    return effective_topology(sched.topology, sched._slowdown, *masks)


# (the state before the event, the event): node 2 and link 1->2 of _cluster
HEALTH_EVENTS = {
    "slowdown": ((), ("report_slowdown", 2, 2.5)),
    "recovery": (("report_slowdown", 2, 2.5), ("report_recovery", 2)),
    "node-down": ((), ("set_node_availability", 2, False)),
    "node-up": (("set_node_availability", 2, False),
                ("set_node_availability", 2, True)),
    "link-down": ((), ("set_link_availability", 1, 2, False)),
    "link-up": (("set_link_availability", 1, 2, False),
                ("set_link_availability", 1, 2, True)),
}


@pytest.mark.parametrize("track_commits", [False, True],
                         ids=["no-log", "commit-log"])
@pytest.mark.parametrize("event", list(HEALTH_EVENTS))
def test_health_event_rebuilds_the_effective_topology(event, track_commits,
                                                      monkeypatch):
    """Each health mutator drops the cached topology; the next use builds
    it once, bit-identical to a fresh build, with host rates equal to its
    fetch, and the very next drain reads them."""
    from repro.core import completions as C, telemetry
    sched = RoutedScheduler(_cluster(), drain="exact",
                            track_commits=track_commits)
    sched.schedule_jobs(requests_to_jobs(
        [Request("smollm_135m", 0, 5, name=f"r{i}") for i in range(3)]))
    before, (name, *args) = HEALTH_EVENTS[event]
    if before:
        getattr(sched, before[0])(*before[1:])
    cached = sched._effective_topology()
    built = telemetry.counter("topology_builds")
    assert sched._effective_topology() is cached
    getattr(sched, name)(*args)
    eff = sched._effective_topology()
    assert eff is not cached
    assert sched._effective_topology() is eff
    assert telemetry.counter("topology_builds") == built + 1
    fresh = _fresh_topology(sched)
    assert (_bits(eff.mu_node, eff.mu_link)
            == _bits(fresh.mu_node, fresh.mu_link))
    assert _bits(*sched._eff_rates) == _bits(*C.host_rates(fresh))
    seen = []
    drain = C.drain_exact

    def spy(*args, rates, **kwargs):
        seen.append(rates)
        return drain(*args, rates=rates, **kwargs)

    monkeypatch.setattr(C, "drain_exact", spy)
    sched.advance(1e-3)
    assert len(seen) == 1 and _bits(*seen[0]) == _bits(*C.host_rates(fresh))


def test_a_healthy_stream_builds_the_topology_once():
    from repro.core import telemetry
    from repro.serving.online import OnlineScheduler
    sched = OnlineScheduler(_cluster().topology, drain="exact")
    built = telemetry.counter("topology_builds")
    for w in range(5):
        sched.submit_window(0.01 * (w + 1),
                            requests_to_jobs([Request("smollm_135m", 0, 5,
                                                      name=f"r{w}")]))
    assert telemetry.counter("topology_builds") == built + 1


def _serve(cls, per_window: int, drain: str, events):
    """Eight served windows on USNET, ``events(sched, w, t)`` before each;
    a window every mean gap of a single request, so backlogs carry over."""
    sc = make_scenario("us-backbone:paper", seed=0)
    rng = np.random.default_rng(5)
    sched = cls(sc.topology, method="greedy", drain=drain,
                sim_engine="indexed" if per_window == 1 else "ref")
    gap = 1 / sc.nominal_rate(0.8)
    for w in range(8):
        t = (w + 1) * gap
        events(sched, w, t - gap / 2)
        sched.submit_window(t, sc.sample_jobs(rng, per_window),
                            pad_to=sc.max_layers)
    comps = sched.finish() if drain == "exact" else {}
    return sched, comps


def _health(sched, w, t):
    """A slowdown, a node down and up, a link down and up, a recovery."""
    link = (1, 2)     # USNET link between two nodes no request ends at
    if w == 2:
        sched.report_slowdown(7, 3.0, at=t)
    elif w == 3:
        sched.set_node_availability(12, False, at=t)
    elif w in (4, 6):
        for u, v in (link, link[::-1]):
            sched.set_link_availability(u, v, w == 6, at=t)
        if w == 6:
            sched.report_recovery(7, at=t)
    elif w == 5:
        sched.set_node_availability(12, True, at=t)


def _replan(sched, w, t):
    if w == 3:
        sched.report_slowdown(7, 4.0, at=t)
        assert sched.replan_last() is not None


STREAMS = {
    "b1-exact-health": (1, "exact", _health),
    "b32-exact-health": (32, "exact", _health),
    "b1-exact-replan": (1, "exact", _replan),
    "b32-fluid-health": (32, "fluid", _health),
}


@pytest.mark.parametrize("stream", list(STREAMS))
def test_backlog_from_host_copies_matches_the_fetch(stream):
    """Every backlog the scheduler reads from its host copies equals the
    fetching ``backlog_seconds`` bit for bit, and the stream's records and
    completions equal those of a scheduler that rebuilds the effective
    topology at every use and fetches every backlog (as it did before the
    copies were kept).  Fluid mode holds no queue copy and fetches."""
    from repro.core.state import backlog_seconds
    from repro.serving.online import OnlineScheduler

    class Checked(OnlineScheduler):
        reads = mirrored = 0

        def _backlog(self, state=None, queues=None):
            got = super()._backlog(state, queues)
            if state is None:
                state, queues = self.state, self._queues
            assert got == backlog_seconds(_fresh_topology(self), state)
            self.reads += 1
            self.mirrored += queues is not None
            return got

    class Rebuilding(OnlineScheduler):
        def _effective_topology(self):
            self._eff = None
            return super()._effective_topology()

        def _backlog(self, state=None, queues=None):
            return backlog_seconds(self._effective_topology(),
                                   self.state if state is None else state)

    per_window, drain, events = STREAMS[stream]
    got, got_done = _serve(Checked, per_window, drain, events)
    want, want_done = _serve(Rebuilding, per_window, drain, events)
    assert got.reads >= 16
    assert got.mirrored == (got.reads if drain == "exact" else 0)
    strip = [dataclasses.replace(r, solve_s=0.0) for r in got.trace.records]
    assert strip == [dataclasses.replace(r, solve_s=0.0)
                     for r in want.trace.records]
    assert any(r.backlog_before > 0 for r in strip)
    assert got_done == want_done


@pytest.mark.parametrize("engine", ["indexed", "ref"])
def test_withdraw_leaves_the_state_of_never_committing(engine):
    """``withdraw`` takes a committed window back: ledger, host and device
    queues equal those of a scheduler that never committed it, the commit
    log records the removal, and the replan snapshot is gone."""
    from repro.serving.online import OnlineScheduler
    sc = make_scenario("paper-small", seed=0)
    rng = np.random.default_rng(3)
    kept, taken = sc.sample_jobs(rng, 3), sc.sample_jobs(rng, 2)

    def sched_with(*windows):
        s = OnlineScheduler(sc.topology, drain="exact", track_commits=True,
                            sim_engine=engine)
        for jobs in windows:
            s.submit_window(0.5, jobs, pad_to=sc.max_layers)
        return s

    s, ref = sched_with(kept, taken), sched_with(kept)
    names = [j.name for j in taken]
    s.withdraw(names, at=0.5)
    assert [j.name for j in s.ledger.jobs] == [j.name for j in ref.ledger.jobs]
    for got, want in zip((*s._queues, *s.ledger.queue_arrays()),
                         (*ref._queues, *ref.ledger.queue_arrays())):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(s.state.q_node), ref._queues[0])
    np.testing.assert_array_equal(np.asarray(s.state.q_link), ref._queues[1])
    assert s.commit_log.removed[-len(names):] == tuple(
        (0.5, n) for n in names)
    assert s.replan_last() is None
    assert s.last_replan_reason == "no_batch"


def test_pipeline_reaches_each_patched_hook_once_per_window():
    """The benchmark's hooks: with ``submit_window`` and ``advance_to``
    replaced on the scheduler instance and a counting ``"greedy"`` in the
    solver registry, a batched pipeline (windows of up to 4, one window per
    dispatch) calls each exactly once per window."""
    from repro.core import solvers
    from repro.serving.online import OnlineScheduler
    from repro.serving.stream import StreamConfig, StreamingPipeline
    sc = make_scenario("paper-small", seed=0)
    rng = np.random.default_rng(4)
    sched = OnlineScheduler(sc.topology, method="greedy", drain="exact")
    pipe = StreamingPipeline(sched, StreamConfig(
        window_s=0.05, max_batch=4, solve_mode="batched", solver_latency=0.0,
        fuse_windows=1))
    calls = {"submit_window": 0, "advance_to": 0, "greedy": 0}
    greedy = solvers.get("greedy")
    submit_window, advance_to = sched.submit_window, sched.advance_to

    def counted(name, fn):
        def call(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return call

    solvers.register("greedy")(counted("greedy", greedy))
    sched.submit_window = counted("submit_window", submit_window)
    sched.advance_to = counted("advance_to", advance_to)
    try:
        stream = [(0.02 * i, sc.sample_jobs(rng, 1)) for i in range(12)]
        trace = pipe.run(stream, pad_to=sc.max_layers)
    finally:
        solvers.register("greedy")(greedy)
        del sched.submit_window, sched.advance_to
    windows = len(trace.windows)
    assert windows >= 3 and max(w.size for w in trace.windows) > 1
    assert calls == dict.fromkeys(calls, windows)
